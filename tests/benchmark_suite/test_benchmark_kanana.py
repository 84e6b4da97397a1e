"""The cell ``kanana2_30b_a3b.train_s8192``: its files through the manifest,
the reference's count of operations against one written out by hand, the
configuration's stated cuts and assumptions, and the reader it brought
(``benchmark/harness/mla_readers.py``) on a synthetic trace."""

import ast
import inspect
import json
import os

import pytest

from benchmark.configs import kanana2_reference as ref
from benchmark.harness import flops, manifest, mla_readers, moe_readers
from benchmark.harness import peaks, readers
from benchmark.harness import trace_reduce as tr
from deeplearning4j_tpu.observability import runtime

CELL = "kanana2_30b_a3b.train_s8192"
CONFIG = "kanana2_30b_a3b"
MODULE = "jit_train_step"
NEW_METRICS = ["mla_latent_ms_per_step", "moe_shared_ms_per_step",
               "mla_attend_roofline_train"]
APPENDED_TO = {"attn_ms_per_step", "mlp_ms_per_step", "head_ms_per_step",
               "optimizer_ms_per_step", "other_ms_per_step",
               "flash_fwd_ms_per_step", "flash_bwd_dkv_ms_per_step",
               "flash_bwd_dq_ms_per_step", "moe_experts_ms_per_step",
               "moe_route_ms_per_step", "expert_roofline_train",
               "expert_load_max_over_mean", "host_ms_per_step",
               "host_stall_share_window", "compiles_in_fit"}
STANDING = ["bert_base.train_s128", "gpt2_small.train_s1024",
            "zaya1_8b.train_s4096", "keye_vl2_30b_a3b.train_s8192",
            "smallthinker_21b_a3b.train_s16384"]
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PAIRS = 8192 * 8193 // 2  # causal pairs of one sequence and one head


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


def test_the_cell_loads_with_its_metrics(cell):
    assert cell.chips == 1 and cell.reference is ref
    assert (cell.traffic["rows"], cell.traffic["seq_len"]) == (2, 8192)
    assert cell.traffic["family"].endswith(":fixed_batches")
    assert (cell.traffic["distinct_batches"],
            cell.traffic["max_in_flight"]) == (8, 2)
    assert cell.workload["kind"] == "train"
    assert cell.workload["step_module"] == MODULE
    assert cell.workload["check"] == {"steps": 3, "reference_row_block": 1}
    # expert_roofline reads num_experts (the configuration's own key for
    # the experts held has another name); the MLA roofline reads layers
    assert cell.workload["counters"] == {"layers": 5, "heads": 32,
                                         "num_experts": 16}
    assert set(cell.workload["limits"]) <= {
        "loss_gap", "grad_norm_gap", "grad_share_gap", "change_norm_gap",
        "change_median_gap"}
    assert "grad_share_gap" in cell.workload["limits"]
    assert "fp8" in cell.workload["limits_from"]
    names = {m.name for m in cell.per_layer}
    assert set(NEW_METRICS) | APPENDED_TO <= names
    assert {"mfu_train", "busy_ms_per_step", "idle_train"} <= names
    # both flash rooflines count one width: neither is the cell's
    assert not {"flash_roofline_train", "flash_roofline_named",
                "cca_mix_ms_per_step", "dsa_attend_roofline_train",
                "swa_attend_roofline_train"} & names
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
            assert m["source"] == "device_trace"
            assert set(m) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
    assert found == NEW_METRICS  # in this order, side by side
    for other in STANDING:
        assert not set(NEW_METRICS) & {
            m.name for m in manifest.load_cell(other).per_layer}
    by_name = {m["name"]: m for m in doc["per_layer"]}
    roofline = by_name["mla_attend_roofline_train"]
    assert (roofline["layer"], roofline["unit"],
            roofline["better"]) == ("kernels", "%", "higher")
    for name in NEW_METRICS[:2]:
        assert (by_name[name]["layer"], by_name[name]["unit"],
                by_name[name]["better"]) == ("model step", "ms", "lower")


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
    assert configs[:5] == ["bert_base", "gpt2_small", "zaya1_8b",
                           "keye_vl2_30b_a3b", "smallthinker_21b_a3b"]
    assert configs.count(CONFIG) == 1
    entry = doc["configs"][configs.index(CONFIG)]
    assert entry["reduced"] == REDUCED
    assert entry["file"] == "benchmark/configs/kanana2_30b_a3b.json"
    cells = [w["name"] for w in doc["workloads"]]
    assert cells[:5] == STANDING and cells.count(CELL) == 1
    assert doc["workloads"][cells.index(CELL)] == {
        "name": CELL, "config": CONFIG, "traffic": "train_s8192",
        "chips": 1, "why": doc["workloads"][cells.index(CELL)]["why"]}
    metrics = [m["name"] for m in doc["per_layer"]]
    assert metrics.index("mla_latent_ms_per_step") > metrics.index(
        "compiles_in_fit")
    for e in (entry, doc["workloads"][cells.index(CELL)]):
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    # flash_roofline_train and _named count one width; left as they were
    by_name = {m["name"]: m for m in doc["per_layer"]}
    assert CELL not in by_name["flash_roofline_train"]["workloads"]
    assert CELL not in by_name["flash_roofline_named"]["workloads"]
    assert doc["run_seconds"] == 51
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 0


def test_every_number_of_the_catalogs_config_is_in_the_file(cell):
    """Every key of the catalog row's ``config`` under the same key with
    the same value, but for the three that ``reduced`` lists."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "kanana-2-30b-a3b-instruct-2601")
    assert cell.config["source"] == row["source_url"]
    assert len(row["config"]) >= 30
    for key, value in row["config"].items():
        if key in REDUCED:
            assert cell.config["published"][key] == value
            assert cell.config[key] != value
        else:
            assert cell.config[key] == value, key


def test_the_file_states_every_cut_and_every_assumption(cell):
    cfg = cell.config
    # widths as published
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["qk_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["n_shared_experts"]) == (
        2048, 32, 128, 64, 192, 128, 512, 6144, 768, 6, 2)
    assert cfg["q_lora_rank"] is None and cfg["rope_scaling"] is None
    assert cfg["rope_interleave"] is True and cfg["rope_theta"] == 1000000
    assert (cfg["scoring_func"], cfg["topk_method"], cfg["n_group"],
            cfg["topk_group"]) == ("sigmoid", "noaux_tc", 1, 1)
    assert cfg["routed_scaling_factor"] == 2.448
    assert cfg["norm_topk_prob"] is True
    assert cfg["first_k_dense_replace"] == 1 and cfg["moe_layer_freq"] == 1
    assert cfg["tie_word_embeddings"] is False
    # the cuts, each beside the published number
    assert set(cfg["changed"]) == set(REDUCED)
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 128,
                                "vocab_size": 128256}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 16, 16032)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["n_routed_experts_total"] == 128  # the router's width
    assert cfg["experts_held"] == list(range(16))
    assert "correction_bias" not in cfg  # zeros: the bias as it starts
    for word in ("5 of the 48 layers", "eight-way expert-parallel",
                 "vocabulary-parallel eight ways", "experts 0-15",
                 "the shared experts"):
        assert word in cfg["deployment"], word
    # every (A) of ISSUE 39, in the words the reference's docstring uses
    assumed = cfg["assumed"]
    document = " ".join(ref.__doc__.split())
    for key in ("residual", "attention", "rotary", "router",
                "shared_experts", "correction_bias", "aux_loss", "head",
                "residual_projection_init"):
        assert key in assumed, key
        assert " ".join(assumed[key].split()[:8]) in document, key
    for key in ("dense_mlp", "experts", "multi_token_prediction",
                "initializer_range", "weights", "learning_rate"):
        assert key in assumed, key
    assert "interleaved pairs" in assumed["rotary"]
    assert "p and not p + b" in assumed["router"]
    assert "held frozen" in assumed["correction_bias"]
    assert "left out" in assumed["aux_loss"]
    assert "2 x 768 = 1536" in assumed["shared_experts"]
    assert "float32" in cfg["precision"] and "highest" in cfg["precision"]
    assert cfg["program"] == {
        "factory": "deeplearning4j_tpu.models.kanana:kanana2_30b_a3b",
        "kwargs": {"num_layers": 5, "experts_held": list(range(16)),
                   "vocab_size": 16032,
                   "residual_init_scale": cfg["residual_init_scale"]}}
    assert cfg["residual_init_scale"] == pytest.approx(
        1 / (2 * cfg["published"]["num_hidden_layers"]), rel=1e-12)
    keye = manifest.load_cell("keye_vl2_30b_a3b.train_s8192").config
    assert cfg["training"] == keye["training"]
    assert "7.3e-6" in assumed["learning_rate"]


def test_the_program_builds_what_the_file_names(cell):
    """The factory resolves, and its model's leaves are the reference's,
    leaf for leaf, at the cell's size (shapes only: nothing is drawn)."""
    import jax

    from benchmark.configs import reference_common as rc

    program = cell.config["program"]
    model = manifest.resolve(program["factory"])(**program["kwargs"])
    c = model.config
    assert (c.num_layers, c.first_dense, c.experts_total,
            c.correction_bias) == (5, 1, 128, None)
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
    """ISSUE 39: per token and layer forward, the attention core 83.9
    MFLOP (causal pairs at 192 + 128 over 32 heads) and the four MLA
    projections 52.7; an expert layer's router, shared experts (18.9) and
    routed experts at balance (7.1); the head 65.7: 45.7 TFLOP a step."""
    tokens = 2 * 8192
    attention = 3 * 5 * 2 * 32 * (192 + 128) * 2 * PAIRS
    projections = 2 * (2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048)
    assert projections == 52_690_944
    latent = 3 * 5 * tokens * projections
    dense = 3 * 2 * 2048 * 6144
    router, shared = 2 * 2048 * 128, 3 * 2 * 2048 * 1536
    routed = 3 * 2 * 2048 * 768 * 6 * 16 / 128
    assert shared == pytest.approx(18.9e6, rel=2e-3)
    assert routed == pytest.approx(7.08e6, rel=2e-3)
    feed_forward = 3 * tokens * (dense + 4 * (router + shared + routed))
    head = 3 * 2 * 2 * 8191 * 2048 * 16032
    assert head / (3 * tokens) == pytest.approx(65.7e6, rel=1e-3)
    assert attention / (3 * 5 * tokens) == pytest.approx(83.9e6, rel=2e-3)
    parts = ref.train_flops_parts(cell.config, cell.traffic)
    assert parts == {"attention": attention, "latent": latent,
                     "feed_forward": feed_forward, "head": head}
    total = ref.train_flops(cell.config, cell.traffic)
    assert total == attention + latent + feed_forward + head
    assert total == pytest.approx(45.7e12, rel=2e-3)
    # MLA, core and projections, is about 73% of the step
    assert (attention + latent) / total == pytest.approx(0.73, abs=0.01)
    assert ref.tokens_per_step(cell.traffic) == tokens


def test_the_leaves_add_up_to_575_955_456(cell):
    from benchmark.configs import reference_common as rc

    sizes = rc.leaf_sizes(ref.param_shapes(cell.config))
    assert sum(sizes.values()) == 575_955_456
    assert sizes["['embeddings']['word']"] == 16032 * 2048 == 32_833_536
    assert sizes["['head']['out']"] == 32_833_536
    assert sizes["['final']['norm']"] == 2048
    mla = 12_582_912 + 1_179_648 + 512 + 4_194_304 + 8_388_608
    for i in range(5):
        layer = {k: v for k, v in sizes.items()
                 if k.startswith(f"['layer_{i}']")}
        attn = {k: v for k, v in layer.items() if "['attn']" in k}
        assert sum(attn.values()) - 2048 == mla == 26_345_984
        assert sizes[f"['layer_{i}']['attn']['Wq']"] == 2048 * 32 * 192
        assert sizes[f"['layer_{i}']['attn']['Wkvb']"] == 512 * 32 * 256
        if i == 0:
            assert sum(layer.values()) == 64_098_816
            assert sizes["['layer_0']['mlp']['gate']"] == 2048 * 6144
        else:
            assert sum(layer.values()) == 111_546_880
            assert sizes[f"['layer_{i}']['moe']['Wg']"] == 262_144
            assert sizes[f"['layer_{i}']['moe']['gate']"] == 16 * 2048 * 768
            assert sizes[f"['layer_{i}']['moe']['shared_up']"] == 2048 * 1536
    # 12 resident bytes a parameter, 18 at the step's peak
    assert 12 * 575_955_456 == pytest.approx(6.91e9, rel=2e-3)
    assert 18 * 575_955_456 == pytest.approx(10.37e9, rel=2e-3)


def test_the_traffic_draws_its_ids_from_the_slice_held(cell):
    import numpy as np

    batch = ref.make_batch(cell.config, np.random.default_rng(2**31 + 11),
                           cell.traffic)
    ids = batch["features"]["token_ids"]
    assert ids.shape == (2, 8192) and ids.dtype == np.int32
    assert 0 <= ids.min() and ids.max() < 16032
    assert ref.part_weights(batch) == {"lm": 2 * 8191.0}
    assert ref.vocab_size(cell.config) == 16032


# -- the readers --------------------------------------------------------------

class Cell:
    chips = 1
    workload = {"step_module": MODULE}
    config = {}


COUNTERS = {"rows": 2, "seq_len": 8192, "num_attention_heads": 32,
            "qk_head_dim": 192, "v_head_dim": 128, "layers": 5}


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
    """Two steps of a program with the latent's work and the shared
    experts under their sub-scopes and the three kernels by name, beside
    another program."""
    monkeypatch.setattr(runtime, "_PROGRAMS", {})
    ops = [("%fusion.1 = bf16[] fusion()", 0, 30),                # latent
           ("%flash_fwd.2 = bf16[] custom-call()", 30, 80),
           ("%flash_bwd_dkv.3 = bf16[] custom-call()", 80, 180),
           ("%flash_bwd_dq.4 = bf16[] custom-call()", 180, 260),
           ("%convolution.5 = bf16[] convolution()", 260, 300),   # Wq, Wo
           ("%fusion.6 = bf16[] fusion()", 300, 340),             # shared
           ("%jvp_jit_gmm__.7 = bf16[] custom-call()", 340, 360),
           ("%fusion.1 = bf16[] fusion()", 500, 530)]             # another's
    scopes = {name: "attn" for name in (
        "fusion.1", "flash_fwd.2", "flash_bwd_dkv.3", "flash_bwd_dq.4",
        "convolution.5")}
    scopes.update({"fusion.6": "mlp", "jvp_jit_gmm__.7": "mlp"})
    runtime.publish_program(
        MODULE, flops=None, scopes=scopes,
        subscopes={"fusion.1": "mla_latent", "fusion.6": "moe_shared",
                   "jvp_jit_gmm__.7": "moe_experts"})
    return tr.TraceSlice([device_of(ops)], [])


def test_the_sub_scopes_read_the_latent_and_the_shared_experts(sliced):
    ctx = context(sliced)
    assert moe_readers.subscope_ms_per_step(ctx, scope="mla_latent") == 15.0
    assert moe_readers.subscope_ms_per_step(ctx, scope="moe_shared") == 20.0
    assert moe_readers.subscope_ms_per_step(ctx, scope="moe_experts") == 10.0


def test_the_roofline_counts_each_product_at_its_width():
    """Six products over the causal pairs, three at 192 and three at 128,
    and each tensor at its own width."""
    need = mla_readers.attend_train_cost(rows=2, heads=32, seq_len=8192,
                                         qk_dim=192, v_dim=128, layers=5)
    pairs = 2 * 32 * PAIRS
    qk = 2 * pairs * 192   # QK^T, dQ, dK
    pv = 2 * pairs * 128   # PV, dV, dP
    assert need["flops"] == 5 * 3 * (qk + pv)
    assert need["flops"] == pytest.approx(20.62e12, rel=1e-3)
    element = 2 * 32 * 8192 * 2  # a row of one width, bf16
    forward = element * (192 + 192 + 128 + 128)          # q, k, v; o
    backward = element * (192 + 192 + 128 + 128 + 128    # q, k, v, o, do
                          + 192 + 192 + 128)             # dq, dk, dv
    assert need["bytes"] == 5 * (forward + backward)
    assert need["bytes"] / 819e9 < need["flops"] / 197e12  # compute-bound
    # a kernel that computes at one width of 192 does 1.2 x the work
    one_width = flops.flash_train_cost(rows=2, heads=32, seq_len=8192,
                                       head_dim=192, layers=5)
    assert one_width["flops"] / need["flops"] == pytest.approx(1.2)


def test_the_roofline_divides_by_the_three_kernels_by_name(sliced):
    need = mla_readers.attend_train_cost(rows=2, heads=32, seq_len=8192,
                                         qk_dim=192, v_dim=128, layers=5)
    ctx = context(sliced, COUNTERS)
    got = mla_readers.attend_roofline(ctx)
    least_ms = 1e3 * need["flops"] / 197e12
    # the program's own kernels: 50 + 100 + 80 ms over two steps
    assert got == pytest.approx(100 * least_ms / 115.0)
    note = ctx.notes["mla_attend_roofline"]
    assert note["bound"] == "compute" and note["events"] == 3
    assert note["device_ms_per_step"] == pytest.approx(115.0)


def test_the_roofline_stays_under_100_where_a_kernel_takes_its_least_time(
        monkeypatch):
    """Kernels that ran at the chip's peak over exactly the products
    required would read 100; the count is the requirement, so nothing can
    pass it. Padded to one width of 192 at the same rate, they read 83."""
    monkeypatch.setattr(runtime, "_PROGRAMS", {})
    need = mla_readers.attend_train_cost(rows=2, heads=32, seq_len=8192,
                                         qk_dim=192, v_dim=128, layers=5)
    least_ms = 1e3 * need["flops"] / 197e12
    for slowdown, want in ((1.0, 100.0), (1.2, 100 / 1.2)):
        per_step = least_ms * slowdown
        # two steps: half of each step's least time in each kernel
        ops = [("%flash_fwd.1 = bf16[] custom-call()", 0, per_step),
               ("%flash_bwd_dq.2 = bf16[] custom-call()", 300,
                300 + per_step)]
        trace = tr.TraceSlice([device_of(ops)], [])
        got = mla_readers.attend_roofline(context(trace, COUNTERS))
        assert got == pytest.approx(want, abs=0.01) and got <= 100.0 + 1e-9


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
    assert mla_readers.attend_roofline(context(trace, COUNTERS)) is None


def test_a_parent_without_the_sub_scopes_gives_nothing(monkeypatch):
    """A program whose table has neither ``mla_latent`` nor ``moe_shared``,
    as the parent's has not: the metrics are left out, and nothing
    raises."""
    monkeypatch.setattr(runtime, "_PROGRAMS", {})
    ops = [("%fusion.1 = bf16[] fusion()", 0, 30),
           ("%fusion.6 = bf16[] fusion()", 30, 60)]
    runtime.publish_program(MODULE, flops=None,
                            scopes={"fusion.1": "attn", "fusion.6": "mlp"},
                            subscopes={"fusion.6": "moe_route"})
    ctx = context(tr.TraceSlice([device_of(ops)], []))
    assert moe_readers.subscope_ms_per_step(ctx, scope="mla_latent") is None
    assert moe_readers.subscope_ms_per_step(ctx, scope="moe_shared") is None


@pytest.mark.parametrize("name,reader,args", [
    ("mla_latent_ms_per_step",
     "benchmark.harness.moe_readers:subscope_ms_per_step",
     {"scope": "mla_latent"}),
    ("moe_shared_ms_per_step",
     "benchmark.harness.moe_readers:subscope_ms_per_step",
     {"scope": "moe_shared"}),
    ("mla_attend_roofline_train",
     "benchmark.harness.mla_readers:attend_roofline", {})])
def test_every_new_metrics_file_names_its_reader(name, reader, args):
    spec = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "metrics", name + ".json"))
    assert (spec["reader"], spec["args"]) == (reader, args)
    assert callable(manifest.resolve(reader))


def test_the_programs_vocabulary_has_what_the_readers_read():
    from deeplearning4j_tpu.observability import vocab

    assert {"mla_latent", "moe_shared"} <= set(vocab.SUB_SCOPES)
    name = "jit(train_step)/jit(main)/transpose(jvp(attn))/mla_latent/dot"
    assert vocab.scope_of(name) == "attn"
    assert vocab.subscope_of(name) == "mla_latent"
    assert "attention.mla" in vocab.KERNEL_KINDS
    assert {"flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"} <= set(
        vocab.KERNEL_NAMES)
