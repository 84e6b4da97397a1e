"""The readers that split the step's device time by the program's own
table of scopes, on the recorded trace with a table made here from the
trace's own instruction names, and through the tiny benchmark's line."""

import gzip
import os

import pytest

import benchmark_tiny
from benchmark.harness import manifest, peaks, readers, report, scope_readers
from benchmark.harness import trace_reduce as tr
from deeplearning4j_tpu.observability import runtime

FIXTURE = os.path.join(manifest.BENCH_DIR, "fixtures",
                       "gpt2_1layer_4steps.xplane.pb.gz")
MODULE = "jit_train_step"
SCOPE_METRICS = ["attn_ms_per_step", "mlp_ms_per_step", "head_ms_per_step",
                 "optimizer_ms_per_step", "other_ms_per_step"]
KERNEL_METRICS = ["flash_fwd_ms_per_step", "flash_bwd_dkv_ms_per_step",
                  "flash_bwd_dq_ms_per_step"]


class Cell:
    chips = 1
    workload = {"step_module": MODULE}


def scope_by_name(ins):
    """A table for the recorded program, which had no scopes: by the look
    of each instruction's name (the kernels were ``jvp()`` then)."""
    if "jvp__" in ins:
        return "attn"
    if ins.startswith("divide_add_fusion"):
        return "optimizer"
    if ins.startswith("convolution"):
        return "mlp"
    if ins in ("fusion.7", "fusion.242"):
        return "head"
    if ins.startswith("subtract_bitcast"):
        return "embed"
    return None


@pytest.fixture(scope="module")
def sliced():
    return tr.reduce_trace(FIXTURE, step_module=MODULE)


@pytest.fixture()
def table(sliced, monkeypatch):
    """The whole table of the recorded program, published as the program
    would, in a table of this test's own."""
    monkeypatch.setattr(runtime, "_PROGRAMS", {})
    names = {scope_readers.instruction(n) for n, _, _ in sliced.devices[0].ops}
    scopes = {n: scope_by_name(n) for n in names}
    runtime.publish_program(MODULE, flops=3e11, scopes=scopes)
    return scopes


def context(trace, cell=Cell):
    return readers.Context(trace=trace, counters={}, cell=cell,
                           peaks=peaks.peaks_for("TPU v5 lite"))


def test_the_components_sum_to_the_busy_time(sliced, table):
    ctx = context(sliced)
    parts = {s: scope_readers.scope_ms_per_step(ctx, scopes=[s])
             for s in scope_readers.COMPONENTS + (scope_readers.OTHER,)}
    assert all(v > 0 for v in parts.values()), parts
    assert sum(parts.values()) == pytest.approx(
        readers.busy_ms_per_step(ctx), rel=1e-6)
    # two scopes asked for at once are their sum
    both = scope_readers.scope_ms_per_step(ctx, scopes=["attn", "mlp"])
    assert both == pytest.approx(parts["attn"] + parts["mlp"])


def test_other_is_embed_and_the_unscoped(sliced, table):
    ctx = context(sliced)
    other = scope_readers.scope_ms_per_step(ctx, scopes=["other"])
    ms = ctx.notes["scopes"]["ms_per_step"]
    assert other == pytest.approx(ms["embed"] + ms["unscoped"])
    assert set(ms) == {"attn", "mlp", "head", "optimizer", "embed",
                       "unscoped"}


def test_notes_name_the_longest_unscoped_and_the_unknown_share(sliced, table):
    ctx = context(sliced)
    scope_readers.scope_ms_per_step(ctx, scopes=["attn"])
    notes = ctx.notes["scopes"]
    assert notes["unknown_share"] == 0.0
    assert notes["stale_metadata"] is False
    longest = notes["longest_unscoped"]
    assert len(longest) == 5
    assert [ms for _, ms in longest] == sorted(
        (ms for _, ms in longest), reverse=True)
    assert all(table[name] is None for name, _ in longest)
    assert longest[0][0] == "convert_element_type.84"
    # the unscoped by kind of instruction: what ``other`` is made of
    kinds = notes["unscoped_by_kind"]
    assert list(kinds)[0] == "copy-done" and len(kinds) == 8
    assert list(kinds.values()) == sorted(kinds.values(), reverse=True)
    assert sum(kinds.values()) <= notes["ms_per_step"]["unscoped"]
    # and the ten longest of all, each with the scope the table gives it
    top = notes["longest"]
    assert len(top) == 10
    assert [ms for _, _, ms in top] == sorted(
        (ms for _, _, ms in top), reverse=True)
    assert top[0][:2] == ["divide_add_fusion", "optimizer"]
    assert all(scope == (table[name] or "unscoped") for name, scope, _ in top)
    assert [name for name, _, _ in top] == [
        name.split("[")[0] for name, _ in sliced.device_ops(10)]


def test_time_on_instructions_the_table_lacks_is_the_unknown_share(
        sliced, table):
    kept = dict(table)
    gone = [n for n in kept if n.startswith("divide_add_fusion")]
    for n in gone:
        del kept[n]
    runtime.publish_program(MODULE, flops=None, scopes=kept)
    ctx = context(sliced)
    assert scope_readers.scope_ms_per_step(ctx, scopes=["optimizer"]) == 0
    full = context(sliced)
    runtime.publish_program(MODULE, flops=None, scopes=table)
    optimizer = scope_readers.scope_ms_per_step(full, scopes=["optimizer"])
    share = optimizer / readers.busy_ms_per_step(full)
    assert ctx.notes["scopes"]["unknown_share"] == pytest.approx(share)
    assert 0.2 < share < 0.5
    # what the table does not know is counted with the unscoped
    assert (scope_readers.scope_ms_per_step(ctx, scopes=["other"])
            == pytest.approx(
                scope_readers.scope_ms_per_step(full, scopes=["other"])
                + optimizer))


def test_only_the_step_programs_operations_count():
    """Two programs in the window, the same instruction names in both."""
    ops = [("%fusion.1 = f32[] fusion()", 0.0, 4e6),
           ("%fusion.2 = f32[] fusion()", 4e6, 6e6),
           ("%fusion.1 = f32[] fusion()", 10e6, 13e6)]
    modules = [("jit_train_step(1)", 0.0, 7e6), ("jit_other(2)", 9e6, 14e6)]
    device = tr.DeviceSlice("/device:TPU:0", ops, modules, (0.0, 20e6), 2)
    runtime.publish_program("jit_train_step", flops=None,
                            scopes={"fusion.1": "attn", "fusion.2": "mlp"})
    try:
        ctx = context(tr.TraceSlice([device], []))
        assert scope_readers.scope_ms_per_step(ctx, scopes=["attn"]) == 2.0
        assert scope_readers.scope_ms_per_step(ctx, scopes=["mlp"]) == 1.0
        assert scope_readers.scope_ms_per_step(ctx, scopes=["other"]) == 0.0
    finally:
        runtime._PROGRAMS.pop("jit_train_step", None)


@pytest.mark.parametrize("why", ["no table", "no trace", "no step_module"])
def test_nothing_to_read_gives_nothing(sliced, monkeypatch, why):
    monkeypatch.setattr(runtime, "_PROGRAMS", {})
    cell = Cell
    if why != "no table":
        runtime.publish_program(MODULE, flops=None, scopes={"x": "attn"})
    if why == "no step_module":
        cell = type("Serving", (), {"chips": 1, "workload": {}})
    ctx = context(None if why == "no trace" else sliced, cell)
    assert scope_readers.scope_ms_per_step(ctx, scopes=["attn"]) is None
    assert "scopes" not in ctx.notes


def test_a_program_without_the_table_gives_nothing(sliced, monkeypatch):
    """The parent of the PR that brought the table has no such function."""
    monkeypatch.delattr(runtime, "program_table")
    assert scope_readers.published(MODULE) is None
    assert scope_readers.scope_ms_per_step(
        context(sliced), scopes=["attn"]) is None


@pytest.mark.parametrize("pattern,events_a_step", [
    (r"^%?jvp__[.0-9]* = ", 1),             # the forward kernel, unnamed then
    (r"^%?transpose_jvp___[.0-9]* = ", 2),  # both backward kernels
    ('custom_call_target="tpu_custom_call"', 3),
])
def test_op_ms_per_step_is_the_matching_operations_time(
        sliced, pattern, events_a_step):
    ctx = context(sliced)
    seconds, count = sliced.matching_s(pattern)
    assert count == events_a_step * sliced.steps
    assert scope_readers.op_ms_per_step(ctx, pattern=pattern) == \
        pytest.approx(1e3 * seconds / sliced.steps)


def test_the_kernels_sum_to_what_the_roofline_divides_by(sliced):
    ctx = context(sliced)
    parts = [scope_readers.op_ms_per_step(ctx, pattern=p) for p in
             (r"^%?jvp__[.0-9]* = ", r"^%?transpose_jvp___[.0-9]* = ")]
    mosaic, _ = sliced.matching_s('custom_call_target="tpu_custom_call"')
    assert sum(parts) == pytest.approx(1e3 * mosaic / sliced.steps)


@pytest.mark.parametrize("trace", ["none", "no match"])
def test_op_ms_per_step_with_nothing_to_read(sliced, trace):
    ctx = context(None if trace == "none" else sliced)
    assert scope_readers.op_ms_per_step(ctx, pattern="^%?flash_fwd") is None


def test_the_instruction_of_an_event():
    assert scope_readers.instruction(
        "%fusion.7 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop") \
        == "fusion.7"
    assert scope_readers.instruction(
        '%flash_fwd.2 = (bf16[8]) custom-call(%x), '
        'custom_call_target="tpu_custom_call"') == "flash_fwd.2"


# -- through the tiny benchmark ------------------------------------------------

@pytest.mark.parametrize("metric", SCOPE_METRICS + KERNEL_METRICS)
def test_the_manifest_names_the_metric_and_its_file_names_a_reader(metric):
    doc = manifest.load_json(manifest.MANIFEST)
    entry, = [m for m in doc["per_layer"] if m["name"] == metric]
    assert entry["source"] == "device_trace" and entry["unit"] == "ms"
    assert entry["better"] == "lower"
    assert entry["moves"] == "train_tok_s_chip"
    kernels = metric in KERNEL_METRICS
    assert entry["layer"] == ("kernels" if kernels else "model step")
    assert entry["workloads"] == (
        ["gpt2_small.train_s1024"] if kernels
        else ["bert_base.train_s128", "gpt2_small.train_s1024"])
    loaded = manifest.load_metric(
        entry, os.path.join(manifest.BENCH_DIR, "metrics"))
    assert loaded.reader.__module__ == "benchmark.harness.scope_readers"


@pytest.mark.parametrize("cell_name,kernels", [
    ("tiny_bert.tiny_mlm", False), ("tiny_gpt.tiny_clm", True)])
def test_the_tiny_cells_line_reports_the_split(tmp_path, table, cell_name,
                                               kernels):
    """The traced line of a tiny cell, over the recorded trace laid out as
    the profiler would have left it."""
    cell = benchmark_tiny.load(str(tmp_path), cell_name)
    listed = [m.name for m in cell.per_layer]
    assert set(SCOPE_METRICS) <= set(listed)
    assert set(KERNEL_METRICS) <= set(listed) if kernels \
        else not set(KERNEL_METRICS) & set(listed)
    where = tmp_path / "trace" / "plugins" / "profile" / "one"
    where.mkdir(parents=True)
    with gzip.open(FIXTURE, "rb") as f:
        (where / "host.xplane.pb").write_bytes(f.read())
    counters = {"flops_per_step": 3e11, "rows": 1, "heads": 12,
                "seq_len": 1024, "head_dim": 64, "layers": 1}
    line = report.result_line(
        cell, correct=True, attempted=3, failed=0,
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        values={}, counters=counters, compared=[],
        trace_dir=str(tmp_path / "trace"),
        step_module=cell.workload["step_module"])
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(SCOPE_METRICS) <= set(metrics)
    assert sum(metrics[m] for m in SCOPE_METRICS) == pytest.approx(
        metrics["busy_ms_per_step"], rel=1e-6)
    assert all(line["metrics"][m]["unit"] == "ms" for m in SCOPE_METRICS)
    # the recorded program's kernels had no names yet: nothing to read
    assert not set(KERNEL_METRICS) & set(metrics)
    assert line["notes"]["scopes"]["unknown_share"] == 0.0
    assert len(line["notes"]["scopes"]["longest_unscoped"]) == 5


def test_an_executable_with_an_older_programs_metadata_is_said_so(
        sliced, table, monkeypatch):
    """What a compile-cache entry written before the scopes gives: the
    instructions are the program's, no scope is, and the line says why."""
    text = "HloModule jit_train_step\n\nENTRY %main () -> f32[] {\n" + "".join(
        f"  %{name} = f32[] constant(0)\n" for name in table) + "}\n"
    runtime.publish_program(MODULE, flops=3e11, text=lambda: text,
                            carries="optimizer")
    ctx = context(sliced)
    assert scope_readers.scope_ms_per_step(ctx, scopes=["attn"]) == 0
    assert scope_readers.scope_ms_per_step(ctx, scopes=["other"]) == \
        pytest.approx(readers.busy_ms_per_step(ctx), rel=1e-6)
    assert ctx.notes["scopes"]["stale_metadata"] is True
    assert ctx.notes["scopes"]["unknown_share"] == 0.0
