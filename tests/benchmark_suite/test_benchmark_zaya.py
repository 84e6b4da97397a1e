"""The cell ``zaya1_8b.train_s4096``: its files through the manifest, the
reference's count of operations against one written out by hand, the
configuration's stated cuts and assumptions, and the readers it brought
(``benchmark/harness/moe_readers.py``) on a synthetic trace."""

import ast
import inspect
import os
import re

import pytest

from benchmark.configs import zaya1_reference as ref
from benchmark.harness import manifest, moe_readers, peaks, readers
from benchmark.harness import trace_reduce as tr
from deeplearning4j_tpu.observability import runtime

CELL = "zaya1_8b.train_s4096"
MODULE = "jit_train_step"
NEW_METRICS = {"moe_experts_ms_per_step", "moe_route_ms_per_step",
               "cca_mix_ms_per_step", "expert_roofline_train",
               "expert_load_max_over_mean", "flash_roofline_named"}


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


def test_the_cell_loads_with_its_metrics(cell):
    assert cell.chips == 1 and cell.reference is ref
    assert (cell.traffic["rows"], cell.traffic["seq_len"]) == (2, 4096)
    assert cell.workload["check"] == {"steps": 3, "reference_row_block": 1}
    assert cell.workload["counters"] == {"heads": 8, "head_dim": 128,
                                         "layers": 4}
    assert set(cell.workload["limits"]) == {
        "grad_norm_gap", "grad_share_gap", "change_norm_gap",
        "change_median_gap"}
    names = {m.name for m in cell.per_layer}
    assert NEW_METRICS <= names
    assert {"mfu_train", "busy_ms_per_step", "idle_train", "attn_ms_per_step",
            "mlp_ms_per_step", "head_ms_per_step", "optimizer_ms_per_step",
            "other_ms_per_step", "flash_fwd_ms_per_step",
            "flash_bwd_dkv_ms_per_step", "flash_bwd_dq_ms_per_step"} <= names
    # every custom call matches flash_roofline_train's pattern, and the
    # grouped product is one: the cell reads the kernels by name instead
    assert "flash_roofline_train" not in names
    assert [m["name"] for m in cell.end_to_end] == ["train_tok_s_chip",
                                                    "setup_s"]


def test_the_new_metrics_are_the_new_cells_alone():
    doc = manifest.load_json(manifest.MANIFEST)
    for m in doc["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "train_tok_s_chip"
    other = manifest.load_cell("gpt2_small.train_s1024")
    assert not NEW_METRICS & {m.name for m in other.per_layer}


def test_the_file_states_every_cut_and_every_assumption(cell):
    cfg = cell.config
    # widths as published
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["moe_intermediate_size"],
            cfg["router_hidden_size"], cfg["num_experts_per_tok"]) == (
                2048, 128, 8, 2, 2048, 256, 1)
    assert (cfg["cca_time0"], cfg["cca_time1"]) == (2, 2)
    assert cfg["partial_rotary_factor"] == 0.5
    assert cfg["rope_parameters"]["hybrid"]["rope_theta"] == 5000000
    assert cfg["tie_word_embeddings"] and cfg["sliding_window"] is None
    assert len(cfg["layer_types"]) == 40  # the nested group, copied whole
    # the cuts, each beside the published number
    assert set(cfg["changed"]) == {"num_hidden_layers", "num_experts",
                                   "vocab_size"}
    assert cfg["published"] == {"num_hidden_layers": 40, "num_experts": 16,
                                "vocab_size": 262272}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 8, 32784)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["num_experts_total"] == 16  # the router's width stays
    assert cfg["experts_held"] == list(range(8))
    for word in ("4 of the 40 layers", "two-way expert-parallel",
                 "vocabulary-parallel eight ways"):
        assert word in cfg["deployment"], word
    # every (A) of ISSUE 28, in the words the reference's docstring uses
    assumed = cfg["assumed"]
    for key in ("residual", "value_shift", "convolutions", "qk_mean",
                "qk_norm", "router", "experts", "sublayer_order",
                "initializer_range"):
        assert key in assumed, key
        if key != "initializer_range":
            words = " ".join(assumed[key].split()[:6])
            assert words in " ".join(ref.__doc__.split()), key
    kwargs = cfg["program"]["kwargs"]
    assert kwargs == {"num_layers": 4, "experts_held": list(range(8)),
                      "vocab_size": 32784}


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


def test_train_flops_is_the_count_written_out_by_hand(cell):
    """Section 3 of ISSUE 28, a token forward: projections and router
    2 x 5.9 M, convolutions 0.66 M, experts 2 x 12.58 M x 8/16, attention
    8.4 M at T = 4096 causal; the head 134 M over T - 1 positions."""
    projections = 2 * (2048 * 1024 + 2048 * 256 + 2 * 2048 * 128
                       + 1024 * 2048)
    router = 2 * (2048 * 256 + 2 * 256 * 256 + 256 * 16)
    convolutions = 2 * (2 * 1280 + 2 * 128 * 1280)  # depthwise, by head
    experts = 3 * 2 * 2048 * 2048 * 8 / 16
    attention = 2 * 2 * 1024 * (4096 + 1) / 2
    layer = projections + router + convolutions + experts + attention
    assert projections + router == 2 * 5_902_336
    assert ref.layer_forward_flops_per_token(cell.config, 4096) == layer
    head = 2 * 2048 * 32784
    want = 3 * (4 * 2 * 4096 * layer + 2 * 4095 * head)
    assert ref.train_flops(cell.config, cell.traffic) == want
    assert want == pytest.approx(6.586e12, rel=1e-3)
    assert ref.tokens_per_step(cell.traffic) == 8192


def test_the_leaves_add_up_to_the_issues_half_a_billion(cell):
    from benchmark.configs import reference_common as rc

    sizes = rc.leaf_sizes(ref.param_shapes(cell.config))
    assert sum(sizes.values()) == pytest.approx(495e6, rel=0.01)
    assert sizes["['embeddings']['word']"] == 32784 * 2048
    layer = sum(v for k, v in sizes.items() if k.startswith("['layer_1']"))
    assert layer == pytest.approx(107e6, rel=0.01)
    assert "['layer_0']['moe']['gamma']" not in sizes
    assert sizes["['layer_1']['moe']['gamma']"] == 1
    assert sizes["['layer_1']['moe']['Wc']"] == 256 * 16


# -- the readers ---------------------------------------------------------------

class Cell:
    chips = 1
    workload = {"step_module": MODULE}


def context(trace, counters=None):
    return readers.Context(trace=trace, counters=counters or {}, cell=Cell,
                           peaks=peaks.peaks_for("TPU v5 lite"))


@pytest.fixture()
def sliced(monkeypatch):
    """Two steps of a program with sub-scopes, beside another program."""
    monkeypatch.setattr(runtime, "_PROGRAMS", {})
    ops = [("%fusion.1 = bf16[] fusion()", 0.0, 4e6),       # cca_mix
           ("%flash_fwd.2 = bf16[] custom-call()", 4e6, 5e6),  # attn alone
           ("%jvp_jit_gmm__.3 = bf16[] custom-call()", 5e6, 11e6),
           ("%sort.4 = s32[] sort()", 11e6, 12e6),          # moe_route
           ("%fusion.1 = bf16[] fusion()", 20e6, 23e6)]     # another program
    modules = [("jit_train_step(1)", 0.0, 13e6), ("jit_other(2)", 19e6, 24e6)]
    device = tr.DeviceSlice("/device:TPU:0", ops, modules, (0.0, 30e6), 2)
    scopes = {"fusion.1": "attn", "flash_fwd.2": "attn",
              "jvp_jit_gmm__.3": "mlp", "sort.4": "mlp"}
    runtime.publish_program(
        MODULE, flops=None, scopes=scopes,
        subscopes={"fusion.1": "cca_mix", "jvp_jit_gmm__.3": "moe_experts",
                   "sort.4": "moe_route"})
    return tr.TraceSlice([device], [])


def test_subscopes_split_the_step_and_leave_the_rest_to_the_component(sliced):
    ctx = context(sliced)
    assert moe_readers.subscope_ms_per_step(ctx, scope="cca_mix") == 2.0
    assert moe_readers.subscope_ms_per_step(ctx, scope="moe_experts") == 3.0
    assert moe_readers.subscope_ms_per_step(ctx, scope="moe_route") == 0.5
    assert moe_readers.subscope_ms_per_step(ctx, scope="no_such") is None
    assert ctx.notes["subscopes"] == {"cca_mix": 2.0, "moe_experts": 3.0,
                                      "moe_route": 0.5}


COUNTERS = {"hidden_size": 2048, "moe_intermediate_size": 2048,
            "num_experts": 8}


@pytest.mark.parametrize("here,tokens", [
    # balanced, 512 an expert; and what training makes of it: every token
    # of the step on a few of the experts held
    ([[512] * 8] * 4, 4 * 4096),
    ([[1970, 1766, 2493, 0, 0, 1894, 68, 1]] * 2
     + [[1853, 2432, 0, 3142, 0, 1, 764, 0]] * 2, 4 * 8192)])
def test_the_experts_roofline_counts_the_tokens_that_landed(
        sliced, monkeypatch, here, tokens):
    monkeypatch.setattr(runtime, "_STEP_COUNTERS", {})
    runtime.publish_step_counters({"moe.tokens_here": here})
    ctx = context(sliced, COUNTERS)
    need = moe_readers.expert_train_cost(
        tokens_by_layer=[sum(layer) for layer in here], hidden=2048,
        inner=2048, experts_held=8)
    # nine products a layer over the tokens that landed on it
    assert need["flops"] == 9 * 2 * tokens * 2048 * 2048
    assert need["bytes"] == 9 * 2 * (tokens * 4096 + 4 * 8 * 2048 * 2048)
    least_ms = 1e3 * need["flops"] / 197e12  # compute-bound on a v5e
    assert need["bytes"] / 819e9 < need["flops"] / 197e12
    got = moe_readers.expert_roofline(ctx)
    assert got == pytest.approx(100 * least_ms / 3.0)
    assert ctx.notes["expert_roofline"]["bound"] == "compute"
    assert sum(ctx.notes["expert_roofline"]["tokens_by_layer"]) == tokens


def test_the_experts_roofline_without_the_counter_is_nothing(
        sliced, monkeypatch):
    monkeypatch.setattr(runtime, "_STEP_COUNTERS", {})
    assert moe_readers.expert_roofline(context(sliced, COUNTERS)) is None


@pytest.mark.parametrize("why", ["no table", "table without subscopes",
                                 "no trace"])
def test_nothing_to_read_gives_nothing(sliced, monkeypatch, why):
    if why == "no table":
        monkeypatch.setattr(runtime, "_PROGRAMS", {})
    if why == "table without subscopes":  # the parent's table
        runtime.publish_program(MODULE, flops=None, scopes={"fusion.1": "attn"})
    monkeypatch.setattr(runtime, "_STEP_COUNTERS", {})
    runtime.publish_step_counters({"moe.tokens_here": [[512] * 8] * 4})
    ctx = context(None if why == "no trace" else sliced, COUNTERS)
    assert moe_readers.subscope_ms_per_step(ctx, scope="cca_mix") is None
    assert moe_readers.expert_roofline(ctx) is None
    assert "subscopes" not in ctx.notes


def test_the_load_is_the_programs_counter(monkeypatch):
    monkeypatch.setattr(runtime, "_STEP_COUNTERS", {})
    ctx = context(None)
    name = "moe.load_max_over_mean"
    assert moe_readers.step_counter(ctx, name=name) is None
    runtime.publish_step_counters({name: 1.25,
                                   "moe.tokens_here": [[5, 3], [4, 4]]})
    assert moe_readers.step_counter(ctx, name=name) == 1.25
    assert ctx.notes["step_counters"]["moe.tokens_here"] == [[5, 3], [4, 4]]
    # a parent has no such table
    monkeypatch.delattr(runtime, "step_counters")
    assert moe_readers.step_counter(context(None), name=name) is None


def test_every_new_metrics_file_names_a_reader_of_the_new_module():
    for name in sorted(NEW_METRICS - {"flash_roofline_named"}):
        spec = manifest.load_json(os.path.join(
            manifest.BENCH_DIR, "metrics", name + ".json"))
        assert spec["reader"].startswith("benchmark.harness.moe_readers:")


# -- what two tests of PR 25 and PR 26 guard, for every cell that stands -------
# (they fail since this cell: PERF.md section 7, edits 4 and 5)

WIDTH = re.compile(r"(^|_)(hidden|intermediate|latent|state|proj\w*)_"
                   r"(size|dim|rank|width)$|_dim$|_rank$|head_size|^n_embd$|"
                   r"^n_inner$|expansion|per_tok")


@pytest.mark.parametrize("key,is_width", [
    ("hidden_size", True), ("moe_intermediate_size", True),
    ("router_hidden_size", True), ("kv_latent_dim", True), ("q_lora_rank", True),
    ("head_dim", True), ("state_size", True), ("num_experts_per_tok", True),
    ("n_embd", True), ("num_hidden_layers", False), ("num_experts", False),
    ("vocab_size", False), ("dropout", False)])
def test_the_pattern_for_widths_tells_a_width_from_a_depth(key, is_width):
    assert bool(WIDTH.search(key)) == is_width


def test_no_configuration_reduces_a_width():
    doc = manifest.load_json(manifest.MANIFEST)
    assert len(doc["configs"]) >= 3
    for c in doc["configs"]:
        assert not [k for k in c["reduced"] if WIDTH.search(k)], c["name"]


COMPONENTS = ["attn_ms_per_step", "mlp_ms_per_step", "head_ms_per_step",
              "optimizer_ms_per_step", "other_ms_per_step"]
KERNELS = ["flash_fwd_ms_per_step", "flash_bwd_dkv_ms_per_step",
           "flash_bwd_dq_ms_per_step"]


@pytest.mark.parametrize("metric", COMPONENTS + KERNELS)
def test_the_split_keeps_its_entries_and_gains_this_cell(metric):
    """Every field of PR 26's entries as it was, the list of cells with
    this one appended and nothing before it moved."""
    doc = manifest.load_json(manifest.MANIFEST)
    entry, = [m for m in doc["per_layer"] if m["name"] == metric]
    assert (entry["source"], entry["unit"], entry["better"],
            entry["moves"]) == ("device_trace", "ms", "lower",
                                "train_tok_s_chip")
    kernels = metric in KERNELS
    assert entry["layer"] == ("kernels" if kernels else "model step")
    before = (["gpt2_small.train_s1024"] if kernels
              else ["bert_base.train_s128", "gpt2_small.train_s1024"])
    assert entry["workloads"] == before + [CELL]
    loaded = manifest.load_metric(
        entry, os.path.join(manifest.BENCH_DIR, "metrics"))
    assert loaded.reader.__module__ == "benchmark.harness.scope_readers"


# -- tools/host_stalls.py --------------------------------------------------------

def test_host_stalls_names_the_span_that_owns_a_slow_iteration(
        tmp_path, monkeypatch):
    """The tool over a tiny fit whose listener sleeps once: that iteration
    is the slow one, its time lies in ``train.listeners`` with no CPU
    beside it, and the trainer's spans are the profiler's again after."""
    import json
    import time

    import numpy as np

    from benchmark import run as bench_run
    from benchmark.tools import host_stalls
    from deeplearning4j_tpu.models.zaya import zaya_tiny
    from deeplearning4j_tpu.train import trainer as trainer_mod
    from deeplearning4j_tpu.train.listeners import TrainingListener

    tiny = {"experts_held": [0, 1], "vocab_size": 96}
    rows = ref.make_batch(tiny, np.random.default_rng(3),
                          {"rows": 2, "seq_len": 32})

    class Sleeps(TrainingListener):
        def on_iteration(self, epoch, step, ts, metrics):
            if step == 3 + 20:
                time.sleep(0.3)
            return False

    def fit(argv):
        trainer = trainer_mod.Trainer(zaya_tiny(experts_held=(0, 1)))
        ts = trainer.fit(trainer.init_state(), [rows] * 3)  # set-up's
        trainer.fit(ts, [rows] * 30, listeners=[Sleeps()])
        return 0

    monkeypatch.setattr(bench_run, "main", fit)
    monkeypatch.setattr(host_stalls, "ROOT", str(tmp_path))
    monkeypatch.setattr(trainer_mod, "_annotate", trainer_mod._annotate)
    assert host_stalls.main(["--workload", "tiny", "--seed", "5",
                             "--seconds", "1"]) == 0
    with open(tmp_path / "chiprun_out" / "host_stalls_tiny_5.json") as f:
        out = json.load(f)
    assert out["steps"] == 30 and len(out["intervals_ms"]) == 29
    worst = max(out["slow"], key=lambda row: row["interval_ms"])
    assert worst["step"] == 19 and worst["interval_ms"] >= 300
    span = worst["spans"]["train.listeners"]
    assert span["wall_ms"] >= 300 and span["cpu_ms"] < 50
    assert out["lost_ms"] >= 250
    assert set(worst["host"]) == set(out["host"])
