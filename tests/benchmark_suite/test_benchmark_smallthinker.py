"""The cell ``smallthinker_21b_a3b.train_s16384``: its files through the
manifest, the reference's count of operations against one written out by
hand, the configuration's stated cuts and assumptions, and the readers it
brought (``benchmark/harness/swa_readers.py``) on a synthetic trace."""

import ast
import inspect
import json
import os

import pytest

from benchmark.configs import smallthinker_reference as ref
from benchmark.harness import manifest, moe_readers, peaks, readers
from benchmark.harness import swa_readers
from benchmark.harness import trace_reduce as tr
from deeplearning4j_tpu.observability import runtime

CELL = "smallthinker_21b_a3b.train_s16384"
CONFIG = "smallthinker_21b_a3b"
MODULE = "jit_train_step"
NEW_METRICS = ["attn_window_ms_per_step", "attn_global_ms_per_step",
               "swa_attend_roofline_train", "swa_pairs_touched_over_required"]
APPENDED_TO = {"attn_ms_per_step", "mlp_ms_per_step", "head_ms_per_step",
               "optimizer_ms_per_step", "other_ms_per_step",
               "flash_fwd_ms_per_step", "flash_bwd_dkv_ms_per_step",
               "flash_bwd_dq_ms_per_step", "moe_experts_ms_per_step",
               "moe_route_ms_per_step", "expert_roofline_train",
               "expert_load_max_over_mean"}
STANDING = ["bert_base.train_s128", "gpt2_small.train_s1024",
            "zaya1_8b.train_s4096", "keye_vl2_30b_a3b.train_s8192"]
REDUCED = ["num_hidden_layers", "moe_num_primary_experts", "vocab_size"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
FULL, WINDOWED = 134_225_920, 58_722_304  # pairs of a head, by layer kind
REQUIRED = FULL + 3 * WINDOWED


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


def test_the_cell_loads_with_its_metrics(cell):
    assert cell.chips == 1 and cell.reference is ref
    assert (cell.traffic["rows"], cell.traffic["seq_len"]) == (1, 16384)
    assert cell.traffic["family"].endswith(":fixed_batches")
    assert (cell.traffic["distinct_batches"],
            cell.traffic["max_in_flight"]) == (8, 2)
    assert cell.workload["kind"] == "train"
    assert cell.workload["step_module"] == MODULE
    assert cell.workload["check"] == {"steps": 3, "reference_row_block": 1}
    # expert_roofline reads the last two; the configuration's own keys for
    # them have other names
    assert cell.workload["counters"] == {
        "heads": 28, "head_dim": 128, "layers": 4,
        "moe_intermediate_size": 768, "num_experts": 8}
    assert set(cell.workload["limits"]) <= {
        "loss_gap", "grad_norm_gap", "grad_share_gap", "change_norm_gap",
        "change_median_gap"}
    assert "grad_share_gap" in cell.workload["limits"]
    assert "fp8" in cell.workload["limits_from"]
    names = {m.name for m in cell.per_layer}
    assert set(NEW_METRICS) | APPENDED_TO <= names
    assert {"mfu_train", "busy_ms_per_step", "idle_train"} <= names
    # flash_roofline_train's pattern matches the grouped product's calls,
    # and flash_roofline_named counts every causal pair, 1.73 x what three
    # windowed layers in four require: neither is the cell's
    assert not {"flash_roofline_train", "flash_roofline_named",
                "cca_mix_ms_per_step", "dsa_index_ms_per_step",
                "dsa_attend_roofline_train"} & names
    assert [m["name"] for m in cell.end_to_end] == ["train_tok_s_chip",
                                                    "setup_s"]


def test_the_new_metrics_are_the_new_cells_alone():
    doc = manifest.load_json(manifest.MANIFEST)
    found = []
    for m in doc["per_layer"]:
        if m["name"] in NEW_METRICS:
            found.append(m["name"])
            assert m["workloads"] == [CELL]
            assert m["moves"] == "train_tok_s_chip"
            assert set(m) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
    assert found == NEW_METRICS  # in this order, side by side
    for other in STANDING:
        assert not set(NEW_METRICS) & {
            m.name for m in manifest.load_cell(other).per_layer}
    by_name = {m["name"]: m for m in doc["per_layer"]}
    roofline = by_name["swa_attend_roofline_train"]
    assert (roofline["layer"], roofline["unit"], roofline["better"],
            roofline["source"]) == ("kernels", "%", "higher", "device_trace")
    touched = by_name["swa_pairs_touched_over_required"]
    assert (touched["layer"], touched["unit"], touched["better"],
            touched["source"]) == ("kernels", "ratio", "lower",
                                   "program_counter")
    for name in NEW_METRICS[:2]:
        assert (by_name[name]["layer"], by_name[name]["unit"],
                by_name[name]["source"]) == ("model step", "ms",
                                             "device_trace")


@pytest.mark.parametrize("metric", sorted(APPENDED_TO) + ["train_tok_s_chip"])
def test_an_accepted_metric_gains_this_cell_after_the_standing_ones(metric):
    """The cell is in each list it joined, once, and the cells that stood
    before it keep their order in front of it; whatever a later PR
    appends comes after."""
    doc = manifest.load_json(manifest.MANIFEST)
    entry, = [m for m in doc["per_layer"] + doc["end_to_end"]
              if m["name"] == metric]
    cells = entry["workloads"]
    assert cells.count(CELL) == 1
    before = cells[:cells.index(CELL)]
    assert before and before == [w for w in STANDING if w in before]
    order = [w["name"] for w in doc["workloads"]]
    assert cells == [w for w in order if w in cells]  # the manifest's order


def test_the_entries_are_in_their_lists_after_the_standing_ones():
    doc = manifest.load_json(manifest.MANIFEST)
    configs = [c["name"] for c in doc["configs"]]
    assert configs[:4] == ["bert_base", "gpt2_small", "zaya1_8b",
                           "keye_vl2_30b_a3b"]
    assert configs.index(CONFIG) >= 4
    entry = doc["configs"][configs.index(CONFIG)]
    assert entry["reduced"] == REDUCED
    assert entry["file"] == "benchmark/configs/smallthinker_21b_a3b.json"
    cells = [w["name"] for w in doc["workloads"]]
    assert cells[:4] == STANDING and cells.index(CELL) >= 4
    assert doc["workloads"][cells.index(CELL)] == {
        "name": CELL, "config": CONFIG, "traffic": "train_s16384",
        "chips": 1, "why": doc["workloads"][cells.index(CELL)]["why"]}
    metrics = [m["name"] for m in doc["per_layer"]]
    assert metrics.index("attn_window_ms_per_step") > metrics.index(
        "dsa_keys_selected_mean")
    for e in (entry, doc["workloads"][cells.index(CELL)]):
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    assert doc["run_seconds"] == 51
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 0


def test_every_number_of_the_catalogs_config_is_in_the_file(cell):
    """Every key of the catalog row's ``config`` under the same key with
    the same value, but for the three that ``reduced`` lists; the two
    lists of the layers' kinds whole."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    assert cell.config["source"] == row["source_url"]
    assert len(row["config"]) >= 20
    for key, value in row["config"].items():
        if key in REDUCED:
            assert cell.config["published"][key] == value
            assert cell.config[key] != value
        else:
            assert cell.config[key] == value, key


def test_the_file_states_every_cut_and_every_assumption(cell):
    cfg = cell.config
    # widths as published
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["moe_ffn_hidden_size"],
            cfg["moe_num_active_primary_experts"],
            cfg["sliding_window_size"]) == (2560, 128, 28, 4, 768, 6, 4096)
    assert cfg["rope_theta"] == 1500000 and cfg["norm_topk_prob"] is True
    assert cfg["moe_primary_router_apply_softmax"] is True
    assert cfg["tie_word_embeddings"] is False
    assert cfg["max_position_embeddings"] == cell.traffic["seq_len"]
    assert cfg["rope_layout"] == cfg["sliding_window_layout"] == [
        0, 1, 1, 1] * 13
    assert ref.layer_kinds(cfg) == [(False, None)] + [(True, 4096)] * 3
    # the cuts, each beside the published number
    assert set(cfg["changed"]) == set(REDUCED)
    assert cfg["published"] == {"num_hidden_layers": 52,
                                "moe_num_primary_experts": 64,
                                "vocab_size": 151936}
    assert (cfg["num_hidden_layers"], cfg["moe_num_primary_experts"],
            cfg["vocab_size"]) == (4, 8, 18992)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["moe_num_primary_experts_total"] == 64  # the router's width
    assert cfg["experts_held"] == list(range(8))
    for word in ("4 of the 52 layers", "eight-way expert-parallel",
                 "vocabulary-parallel eight ways", "experts 0-7",
                 "one whole period"):
        assert word in cfg["deployment"], word
    # every (A) of ISSUE 35, in the words the reference's docstring uses
    assumed = cfg["assumed"]
    document = " ".join(ref.__doc__.split())
    for key in ("residual", "router", "attention", "rotary", "window",
                "experts", "head", "secondary_experts", "sparsity_predictor",
                "residual_projection_init"):
        assert key in assumed, key
        assert " ".join(assumed[key].split()[:8]) in document, key
    assert "before the input RMSNorm and before attention" in assumed["router"]
    assert "no positions" in assumed["rotary"]
    assert "counts the query's own position" in assumed["window"]
    assert assumed["secondary_experts"].startswith("left out")
    assert assumed["sparsity_predictor"].startswith("left out")
    assert "relu" in assumed["experts"]
    assert "float32" in cfg["precision"] and "highest" in cfg["precision"]
    assert cfg["program"] == {
        "factory": "deeplearning4j_tpu.models.smallthinker:"
                   "smallthinker_21b_a3b",
        "kwargs": {"num_layers": 4, "experts_held": list(range(8)),
                   "vocab_size": 18992,
                   "residual_init_scale": cfg["residual_init_scale"]}}
    assert cfg["residual_init_scale"] == pytest.approx(
        1 / (2 * cfg["published"]["num_hidden_layers"]), rel=1e-12)
    keye = manifest.load_cell("keye_vl2_30b_a3b.train_s8192").config
    assert cfg["training"] == keye["training"]
    assert "7.3e-6" in assumed["learning_rate"]
    assert "not SmallThinker's own" in assumed["learning_rate"]


def test_the_program_builds_what_the_file_names(cell):
    """The factory resolves, and its model's leaves are the reference's,
    leaf for leaf, at the cell's size (shapes only: nothing is drawn)."""
    import jax

    from benchmark.configs import reference_common as rc

    program = cell.config["program"]
    model = manifest.resolve(program["factory"])(**program["kwargs"])
    c = model.config
    assert (c.rope_layout, c.sliding_window_layout) == ((0, 1, 1, 1),) * 2
    assert (c.sliding_window, c.rope_theta) == (4096, 1.5e6)
    shapes = jax.eval_shape(model.init, 0)["params"]
    got = {jax.tree_util.keystr(p): leaf.size for p, leaf in
           jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert got == rc.leaf_sizes(ref.param_shapes(cell.config))


def test_the_reference_imports_nothing_of_the_program():
    tree = ast.parse(inspect.getsource(ref))
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module)
    assert imported == {"__future__", "math", "typing", "jax", "jax.numpy",
                        "numpy", "benchmark.configs"}
    assert "deeplearning4j_tpu" not in inspect.getsource(ref)


def test_train_flops_is_the_count_written_out_by_hand(cell):
    """Section 4 of ISSUE 35: attention 13.35 TFLOP over 310,392,832 pairs
    x 28 heads, the layers' matrices 10.05, the head 4.78: 28.2."""
    assert ref.attended_pairs(16384, None) == 16384 * 16385 // 2 == FULL
    assert ref.attended_pairs(16384, 4096) == (
        4096 * 4097 // 2 + (16384 - 4096) * 4096) == WINDOWED
    assert ref.attended_pairs(16384, 16384) == FULL
    assert ref.attended_pairs(1024, 4096) == 1024 * 1025 // 2
    assert REQUIRED == 310_392_832
    attention = 3 * 2 * 2 * 28 * 128 * REQUIRED
    projections = 2 * (2560 * 3584 + 2 * 2560 * 512 + 3584 * 2560)
    router = 2 * 2560 * 64
    experts = 3 * 2 * 2560 * 768 * 6 * 8 / 64
    matrices = 3 * 4 * 16384 * (projections + router + experts)
    head = 3 * 2 * 16383 * 2560 * 18992
    assert projections == pytest.approx(41.9e6, rel=2e-3)
    assert experts == pytest.approx(8.85e6, rel=2e-3)
    parts = ref.train_flops_parts(cell.config, cell.traffic)
    assert parts == {"attention": attention, "matrices": matrices,
                     "head": head}
    assert attention == pytest.approx(13.35e12, rel=1e-3)
    assert matrices == pytest.approx(10.05e12, rel=1e-3)
    assert head == pytest.approx(4.78e12, rel=1e-3)
    assert ref.train_flops(cell.config, cell.traffic) == (
        attention + matrices + head)
    assert ref.train_flops(cell.config, cell.traffic) == pytest.approx(
        28.2e12, rel=2e-3)
    assert ref.tokens_per_step(cell.traffic) == 16384
    # a dense kernel under a mask would be credited 1.73 x this
    assert 4 * FULL / REQUIRED == pytest.approx(1.73, abs=0.005)


def test_the_leaves_add_up_to_370_547_200(cell):
    from benchmark.configs import reference_common as rc

    sizes = rc.leaf_sizes(ref.param_shapes(cell.config))
    assert sum(sizes.values()) == 370_547_200
    assert sizes["['embeddings']['word']"] == 18992 * 2560 == 48_619_520
    assert sizes["['head']['out']"] == 48_619_520
    assert sizes["['final']['norm']"] == 2560
    for i in range(4):
        layer = {k: v for k, v in sizes.items()
                 if k.startswith(f"['layer_{i}']")}
        assert sum(layer.values()) == 68_326_400
        assert len(layer) == 10  # no q-k norm, no indexer, no bias
        assert sizes[f"['layer_{i}']['attn']['Wq']"] == 9_175_040
        assert sizes[f"['layer_{i}']['attn']['Wo']"] == 9_175_040
        assert sizes[f"['layer_{i}']['attn']['Wk']"] == 1_310_720
        assert sizes[f"['layer_{i}']['moe']['Wg']"] == 163_840
        assert sizes[f"['layer_{i}']['moe']['gate']"] == 8 * 2560 * 768
    # 12 resident bytes a parameter, 18 at the step's peak
    assert 12 * 370_547_200 == pytest.approx(4.45e9, rel=2e-3)
    assert 18 * 370_547_200 == pytest.approx(6.67e9, rel=2e-3)


def test_the_traffic_draws_its_ids_from_the_slice_held(cell):
    import numpy as np

    batch = ref.make_batch(cell.config, np.random.default_rng(2**31 + 11),
                           cell.traffic)
    ids = batch["features"]["token_ids"]
    assert ids.shape == (1, 16384) and ids.dtype == np.int32
    assert 0 <= ids.min() and ids.max() < 18992
    assert ref.part_weights(batch) == {"lm": 16383.0}
    assert ref.vocab_size(cell.config) == 18992


# -- the readers --------------------------------------------------------------

class Cell:
    chips = 1
    workload = {"step_module": MODULE}
    config = {"sliding_window_size": 4096, "num_hidden_layers": 4,
              "sliding_window_layout": [0, 1, 1, 1] * 13}


COUNTERS = {"rows": 1, "seq_len": 16384, "heads": 28, "head_dim": 128,
            "layers": 4}


def context(trace, counters=None, cell=Cell):
    return readers.Context(trace=trace, counters=counters or {}, cell=cell,
                           peaks=peaks.peaks_for("TPU v5 lite"))


def device_of(ops, modules=None):
    ms = 1e6
    return tr.DeviceSlice(
        "/device:TPU:0", [(n, s * ms, e * ms) for n, s, e in ops],
        modules or [("jit_train_step(1)", 0.0, 430 * ms),
                    ("jit_other(2)", 490 * ms, 540 * ms)],
        (0.0, 600 * ms), 2)


@pytest.fixture()
def sliced(monkeypatch):
    """Two steps of a program with the two kinds of attention under their
    sub-scopes and the three kernels by name, beside another program."""
    monkeypatch.setattr(runtime, "_PROGRAMS", {})
    ops = [("%flash_fwd.1 = bf16[] custom-call()", 0, 30),       # global
           ("%fusion.2 = bf16[] fusion()", 30, 40),              # its repeat
           ("%flash_fwd.3 = bf16[] custom-call()", 40, 80),      # windowed
           ("%fusion.4 = f32[] fusion()", 80, 100),              # rotary
           ("%flash_bwd_dkv.5 = bf16[] custom-call()", 100, 220),
           ("%flash_bwd_dq.6 = bf16[] custom-call()", 220, 320),
           ("%fusion.7 = bf16[] fusion()", 320, 410),  # attn alone: Wq, Wo
           ("%jvp_jit_gmm__.8 = bf16[] custom-call()", 410, 420),
           ("%fusion.2 = bf16[] fusion()", 500, 530)]            # another's
    scopes = {name: "attn" for name in (
        "flash_fwd.1", "fusion.2", "flash_fwd.3", "fusion.4",
        "flash_bwd_dkv.5", "flash_bwd_dq.6", "fusion.7")}
    scopes["jvp_jit_gmm__.8"] = "mlp"
    runtime.publish_program(
        MODULE, flops=None, scopes=scopes,
        subscopes={"flash_fwd.1": "attn_global", "fusion.2": "attn_global",
                   "flash_fwd.3": "attn_window", "fusion.4": "attn_window",
                   "flash_bwd_dkv.5": "attn_window",
                   "flash_bwd_dq.6": "attn_window",
                   "jvp_jit_gmm__.8": "moe_experts"})
    return tr.TraceSlice([device_of(ops)], [])


def test_the_sub_scopes_split_attention_by_the_layers_kind(sliced):
    ctx = context(sliced)
    assert moe_readers.subscope_ms_per_step(ctx, scope="attn_global") == 20.0
    assert moe_readers.subscope_ms_per_step(ctx, scope="attn_window") == 140.0
    assert moe_readers.subscope_ms_per_step(ctx, scope="moe_experts") == 5.0
    # norm, projections, Wo and the residual add stay in attn outside them
    assert moe_readers.subscope_ms_per_step(ctx, scope="dsa_index") is None


def test_the_pairs_required_come_from_the_configurations_lists(cell):
    assert swa_readers.attended_pairs(16384, None) == FULL
    assert swa_readers.attended_pairs(16384, 4096) == WINDOWED
    assert swa_readers.attended_pairs(4096, 4096) == 4096 * 4097 // 2
    assert swa_readers.pairs_by_layer(cell.config, 16384) == [
        FULL, WINDOWED, WINDOWED, WINDOWED]
    assert sum(swa_readers.pairs_by_layer(cell.config, 16384)) == REQUIRED
    # at a sequence no longer than the window every layer is causal
    assert swa_readers.pairs_by_layer(cell.config, 4096) == [
        4096 * 4097 // 2] * 4
    # the benchmark's count and the reference's are written apart and agree
    assert swa_readers.pairs_by_layer(cell.config, 16384) == [
        ref.attended_pairs(16384, w) for _, w in ref.layer_kinds(cell.config)]


def test_the_attention_roofline_counts_the_pairs_required(sliced):
    need = swa_readers.attend_train_cost(
        pairs_by_layer=[FULL, WINDOWED, WINDOWED, WINDOWED], rows=1,
        heads=28, seq_len=16384, head_dim=128)
    # six products over the pairs required, all 28 heads
    assert need["flops"] == 6 * 2 * 28 * 128 * 310_392_832
    assert need["flops"] == pytest.approx(13.35e12, rel=1e-3)
    tensor = 28 * 16384 * 128 * 2
    assert need["bytes"] == 4 * 12 * tensor
    assert need["bytes"] / 819e9 < need["flops"] / 197e12  # compute-bound
    ctx = context(sliced, COUNTERS)
    got = swa_readers.attend_roofline(ctx)
    least_ms = 1e3 * need["flops"] / 197e12
    # the program's own kernels: 30 + 40 + 120 + 100 ms over two steps
    assert got == pytest.approx(100 * least_ms / 145.0)
    assert 0 < got < 100
    note = ctx.notes["swa_attend_roofline"]
    assert note["bound"] == "compute" and note["events"] == 4
    assert note["device_ms_per_step"] == pytest.approx(145.0)
    assert sum(note["pairs_by_layer"]) == 310_392_832
    # a dense kernel under a mask, at the causal roofline's 100%, reads
    # at most 310 / 537 of it here
    assert REQUIRED / (4 * FULL) == pytest.approx(0.578, abs=0.001)


def test_the_roofline_stays_under_100_where_a_kernel_takes_its_least_time(
        monkeypatch):
    """Kernels that ran at the chip's peak over exactly the pairs required
    would read 100; the count is the requirement, so nothing can pass it.
    At the kernels' best reading so far (52% of the causal roofline) over
    the tile plans' 1.042 x pairs the share reads 50."""
    monkeypatch.setattr(runtime, "_PROGRAMS", {})
    least_ms = 1e3 * 6 * 2 * 28 * 128 * REQUIRED / 197e12
    for slowdown, want in ((1.0, 100.0), (1.0422 / 0.52, 49.9)):
        per_step = least_ms * slowdown
        ops = [("%flash_fwd.1 = bf16[] custom-call()", 0, per_step),
               ("%flash_bwd_dkv.2 = bf16[] custom-call()", 200,
                200 + per_step)]
        trace = tr.TraceSlice([device_of(ops)], [])
        got = swa_readers.attend_roofline(context(trace, COUNTERS))
        assert got == pytest.approx(want, abs=0.1) and got <= 100.0 + 1e-9


@pytest.mark.parametrize("why", ["no trace", "no kernel", "no steps"])
def test_nothing_to_read_gives_nothing(sliced, why):
    trace = sliced
    if why == "no trace":
        trace = None
    if why == "no kernel":
        device = sliced.devices[0]
        trace = tr.TraceSlice([tr.DeviceSlice(
            device.name, [op for op in device.ops if "flash_" not in op[0]],
            device.modules, device.window, 2)], [])
    if why == "no steps":
        device = sliced.devices[0]
        trace = tr.TraceSlice([tr.DeviceSlice(
            device.name, device.ops, device.modules, device.window, 0)], [])
    assert swa_readers.attend_roofline(context(trace, COUNTERS)) is None


def test_the_pairs_touched_are_the_programs_counters(monkeypatch):
    monkeypatch.setattr(runtime, "_STEP_COUNTERS", {})
    ctx = context(None)
    assert swa_readers.pairs_touched_over_required(ctx) is None
    runtime.publish_step_counters(
        {"swa.pairs_required": [FULL] + [WINDOWED] * 3})
    assert swa_readers.pairs_touched_over_required(ctx) is None  # one of two
    # the plans at 1024 x 1024: 130 tile-equivalents in the full layer,
    # 59.5 in a windowed one
    touched = [130 * 2 ** 20] + [int(59.5 * 2 ** 20)] * 3
    runtime.publish_step_counters({"swa.pairs_touched": touched})
    got = swa_readers.pairs_touched_over_required(ctx)
    assert got == pytest.approx(323_485_696 / 310_392_832)
    assert got == pytest.approx(1.0422, abs=1e-4) and got < 1.25
    assert ctx.notes["swa_pairs"] == {
        "touched": touched, "required": [FULL] + [WINDOWED] * 3}
    # a dense kernel under a mask touches every tile under the diagonal
    runtime.publish_step_counters({"swa.pairs_touched": [130 * 2 ** 20] * 4})
    assert swa_readers.pairs_touched_over_required(ctx) > 1.7


def test_the_programs_counters_at_the_cells_shape_are_the_plans(monkeypatch):
    """What ``grouped_query_attention`` counts on the chip's path at
    ``[1, 28, 16384, 128]``: the figures above, from the program."""
    from deeplearning4j_tpu.kernels import flash_attention as fa

    monkeypatch.setattr(fa, "_use_pallas", lambda: True)
    assert fa.pairs_of_call(16384, 16384, 128, causal=True) == (
        FULL, 130 * 2 ** 20)
    assert fa.pairs_of_call(16384, 16384, 128, causal=True, window=4096) == (
        WINDOWED, int(59.5 * 2 ** 20))
    # a window of no whole blocks: the tiles its edge crosses run whole
    assert fa.pairs_of_call(16384, 16384, 128, causal=True, window=4000)[1] \
        > 62 * 2 ** 20


def test_a_parent_without_the_counters_table_gives_nothing(sliced,
                                                           monkeypatch):
    monkeypatch.delattr(runtime, "step_counters")
    ctx = context(sliced, COUNTERS)
    assert swa_readers.pairs_touched_over_required(ctx) is None
    # the roofline takes nothing from the program but its kernels' names
    assert swa_readers.attend_roofline(ctx) is not None


def test_a_parent_without_the_sub_scopes_gives_nothing(monkeypatch):
    """A program whose table has no ``attn_window``: the metric is left
    out, and nothing raises."""
    monkeypatch.setattr(runtime, "_PROGRAMS", {})
    ops = [("%flash_fwd.1 = bf16[] custom-call()", 0, 30)]
    runtime.publish_program(MODULE, flops=None,
                            scopes={"flash_fwd.1": "attn"}, subscopes={})
    ctx = context(tr.TraceSlice([device_of(ops)], []))
    assert moe_readers.subscope_ms_per_step(ctx, scope="attn_window") is None
    assert moe_readers.subscope_ms_per_step(ctx, scope="attn_global") is None


@pytest.mark.parametrize("name,reader,args", [
    ("attn_window_ms_per_step",
     "benchmark.harness.moe_readers:subscope_ms_per_step",
     {"scope": "attn_window"}),
    ("attn_global_ms_per_step",
     "benchmark.harness.moe_readers:subscope_ms_per_step",
     {"scope": "attn_global"}),
    ("swa_attend_roofline_train",
     "benchmark.harness.swa_readers:attend_roofline", {}),
    ("swa_pairs_touched_over_required",
     "benchmark.harness.swa_readers:pairs_touched_over_required", {})])
def test_every_new_metrics_file_names_its_reader(name, reader, args):
    spec = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "metrics", name + ".json"))
    assert (spec["reader"], spec["args"]) == (reader, args)
    assert callable(manifest.resolve(reader))


def test_the_programs_vocabulary_has_what_the_readers_read():
    from deeplearning4j_tpu.observability import vocab

    assert {"attn_window", "attn_global"} <= set(vocab.SUB_SCOPES)
    assert {"swa.pairs_required", "swa.pairs_touched"} <= set(
        vocab.STEP_COUNTERS)
    name = "jit(train_step)/jit(main)/transpose(jvp(attn))/attn_window/mul"
    assert vocab.scope_of(name) == "attn"
    assert vocab.subscope_of(name) == "attn_window"
    assert {"flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"} <= set(
        vocab.KERNEL_NAMES)
