"""The cell ``keye_vl2_30b_a3b.train_s8192``: its files through the
manifest, the reference's count of operations against one written out by
hand, the configuration's stated cuts and assumptions, and the readers it
brought (``benchmark/harness/dsa_readers.py``) on a synthetic trace."""

import ast
import inspect
import json
import os

import pytest

from benchmark.configs import keye_vl2_reference as ref
from benchmark.harness import dsa_readers, manifest, moe_readers, peaks, readers
from benchmark.harness import trace_reduce as tr
from deeplearning4j_tpu.observability import runtime

CELL = "keye_vl2_30b_a3b.train_s8192"
MODULE = "jit_train_step"
NEW_METRICS = {"dsa_index_ms_per_step", "dsa_select_ms_per_step",
               "dsa_attend_roofline_train", "dsa_keys_selected_mean"}
APPENDED_TO = {"attn_ms_per_step", "mlp_ms_per_step", "head_ms_per_step",
               "optimizer_ms_per_step", "other_ms_per_step",
               "flash_fwd_ms_per_step", "flash_bwd_dkv_ms_per_step",
               "flash_bwd_dq_ms_per_step", "moe_experts_ms_per_step",
               "moe_route_ms_per_step", "expert_roofline_train",
               "expert_load_max_over_mean"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


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
    assert cell.workload["counters"] == {"heads": 32, "head_dim": 128,
                                         "layers": 4}
    assert set(cell.workload["limits"]) <= {
        "loss_gap", "grad_norm_gap", "grad_share_gap", "change_norm_gap",
        "change_median_gap"}
    assert "grad_share_gap" in cell.workload["limits"]
    names = {m.name for m in cell.per_layer}
    assert NEW_METRICS | APPENDED_TO <= names
    assert {"mfu_train", "busy_ms_per_step", "idle_train"} <= names
    # every custom call matches flash_roofline_train's pattern, and the
    # grouped product is one; flash_roofline_named counts every causal
    # pair, which this model does not require: neither is the cell's
    assert not {"flash_roofline_train", "flash_roofline_named",
                "cca_mix_ms_per_step"} & names
    assert [m["name"] for m in cell.end_to_end] == ["train_tok_s_chip",
                                                    "setup_s"]


def test_the_new_metrics_are_the_new_cells_alone():
    doc = manifest.load_json(manifest.MANIFEST)
    found = set()
    for m in doc["per_layer"]:
        if m["name"] in NEW_METRICS:
            found.add(m["name"])
            assert m["workloads"] == [CELL]
            assert m["moves"] == "train_tok_s_chip"
            assert set(m) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
    assert found == NEW_METRICS
    for other in ("gpt2_small.train_s1024", "zaya1_8b.train_s4096",
                  "bert_base.train_s128"):
        assert not NEW_METRICS & {m.name for m in
                                  manifest.load_cell(other).per_layer}
    by_name = {m["name"]: m for m in doc["per_layer"]}
    assert by_name["dsa_attend_roofline_train"]["layer"] == "kernels"
    assert by_name["dsa_attend_roofline_train"]["unit"] == "%"
    assert by_name["dsa_keys_selected_mean"]["source"] == "program_counter"


@pytest.mark.parametrize("metric", sorted(APPENDED_TO) + ["train_tok_s_chip"])
def test_an_accepted_metric_gains_this_cell_last_and_nothing_else(metric):
    """The lists of cells of the accepted metrics, each with this cell
    appended and nothing before it moved or changed."""
    doc = manifest.load_json(manifest.MANIFEST)
    entry, = [m for m in doc["per_layer"] + doc["end_to_end"]
              if m["name"] == metric]
    assert entry["workloads"][-1] == CELL
    before = entry["workloads"][:-1]
    assert before and CELL not in before
    standing = [w["name"] for w in doc["workloads"]][:3]
    assert before == [w for w in standing if w in before]  # their order


def test_the_entries_come_last_in_their_lists():
    doc = manifest.load_json(manifest.MANIFEST)
    assert doc["configs"][-1]["name"] == "keye_vl2_30b_a3b"
    assert doc["configs"][-1]["reduced"] == ["num_hidden_layers",
                                             "num_experts", "vocab_size"]
    assert doc["workloads"][-1] == {
        "name": CELL, "config": "keye_vl2_30b_a3b", "traffic": "train_s8192",
        "chips": 1, "why": doc["workloads"][-1]["why"]}
    assert [m["name"] for m in doc["per_layer"][-4:]] == [
        "dsa_index_ms_per_step", "dsa_select_ms_per_step",
        "dsa_attend_roofline_train", "dsa_keys_selected_mean"]
    for entry in (doc["configs"][-1], doc["workloads"][-1]):
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    assert doc["run_seconds"] == 51


def test_every_number_of_the_catalogs_config_is_in_the_file(cell):
    """Every key of the catalog row's ``config`` under the same key with
    the same value, but for the three that ``reduced`` lists."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Keye-VL-2.0-30B-A3B")
    assert cell.config["source"] == row["source_url"]
    reduced = {"num_hidden_layers", "num_experts", "vocab_size"}
    for key, value in row["config"].items():
        if key in reduced:
            assert cell.config["published"][key] == value
        else:
            assert cell.config[key] == value, key


def test_the_file_states_every_cut_and_every_assumption(cell):
    cfg = cell.config
    # widths as published
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"]) == (2048, 128, 32, 4, 768, 8)
    assert cfg["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    assert cfg["rope_theta"] == 10000000 and cfg["norm_topk_prob"] is True
    assert cfg["tie_word_embeddings"] is False
    assert cfg["rope_scaling"]["mrope_section"] == [16, 24, 24]
    # the cuts, each beside the published number
    assert set(cfg["changed"]) == {"num_hidden_layers", "num_experts",
                                   "vocab_size"}
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "vocab_size": 151936}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 16, 18992)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["num_experts_total"] == 128  # the router's width stays
    assert cfg["experts_held"] == list(range(16))
    for word in ("4 of the 48 layers", "eight-way expert-parallel",
                 "vocabulary-parallel eight ways", "experts 0-15"):
        assert word in cfg["deployment"], word
    # every (A) of ISSUE 33, in the words the reference's docstring uses
    assumed = cfg["assumed"]
    document = " ".join(ref.__doc__.split())
    for key in ("residual", "qk_norm", "rotary", "indexer", "selection",
                "router", "experts", "head", "indexer_training",
                "vision_tower", "residual_projection_init"):
        assert key in assumed, key
        assert " ".join(assumed[key].split()[:8]) in document, key
    assert "the vision tower is left out" in assumed["vision_tower"]
    assert ("the indexer's KL term is left out: its leaves get no gradient"
            in assumed["indexer_training"])
    assert "three position streams" in assumed["rotary"]
    assert "float32" in cfg["precision"] and "highest" in cfg["precision"]
    assert cfg["program"] == {
        "factory": "deeplearning4j_tpu.models.keye:keye_vl2_30b_a3b",
        "kwargs": {"num_layers": 4, "experts_held": list(range(16)),
                   "vocab_size": 18992,
                   "residual_init_scale": cfg["residual_init_scale"]}}
    # the two projections into the residual stream start at the spread over
    # 2 x the published 48 layers; program and reference read one number
    assert cfg["residual_init_scale"] == pytest.approx(
        1 / (2 * cfg["published"]["num_hidden_layers"]), rel=1e-12)
    # Adam and the generator as zaya1_8b's, at the rate of the one public
    # recipe for continuing a model's training under this indexer
    zaya = manifest.load_cell("zaya1_8b.train_s4096").config["training"]
    assert cfg["training"] == dict(zaya, adam=dict(zaya["adam"], lr=7.3e-6))
    assert "7.3e-6" in assumed["learning_rate"]
    assert "DeepSeek-V3.2-Exp" in assumed["learning_rate"]


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
    assert "jax.lax.top_k" not in inspect.getsource(ref)
    assert "approx" not in inspect.getsource(ref)


def test_train_flops_is_the_count_written_out_by_hand(cell):
    """Section 3 of ISSUE 33, a token forward, a layer: projections 37.7 M,
    attention over 1,792.1 selected pairs 29.4 M, router 0.5 M, experts
    (one pair a token here at balance) 9.4 M; the indexer, forward only,
    4.5 M of projections and 8.4 M of scores; the head 77.8 M over T - 1
    positions."""
    projections = 2 * (2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048)
    pairs = (2048 * 2049 / 2 + (8192 - 2048) * 2048) / 8192
    attention = 2 * 2 * 32 * 128 * pairs
    router = 2 * 2048 * 128
    experts = 3 * 2 * 2048 * 768 * 8 * 16 / 128
    index_projections = 2 * 2048 * (16 * 64 + 64 + 16)
    index_scores = 2 * 16 * 64 * (8192 + 1) / 2
    assert pairs == pytest.approx(1792.1, abs=0.1)
    assert ref.selected_pairs_per_query(8192, 2048) == pairs
    assert ref.selected_pairs_per_query(1024, 2048) == (1024 + 1) / 2
    assert projections == pytest.approx(37.7e6, rel=2e-3)
    assert attention == pytest.approx(29.4e6, rel=2e-3)
    assert experts == pytest.approx(9.4e6, rel=5e-3)
    assert index_projections == pytest.approx(4.5e6, rel=5e-3)
    assert index_scores == pytest.approx(8.4e6, rel=2e-3)
    layer = ref.layer_forward_flops_per_token(cell.config, 8192)
    assert layer == {"trained": projections + attention + router + experts,
                     "indexer": index_projections + index_scores}
    assert layer["trained"] + layer["indexer"] == pytest.approx(89.9e6,
                                                                rel=2e-3)
    head = 2 * 2048 * 18992
    want = (4 * 2 * 8192 * (3 * layer["trained"] + layer["indexer"])
            + 3 * 2 * 8191 * head)
    assert ref.train_flops(cell.config, cell.traffic) == want
    assert want == pytest.approx(19.8e12, rel=5e-3)
    assert ref.tokens_per_step(cell.traffic) == 16384


def test_the_leaves_add_up_to_the_issues_465_million(cell):
    from benchmark.configs import reference_common as rc

    sizes = rc.leaf_sizes(ref.param_shapes(cell.config))
    assert sum(sizes.values()) == pytest.approx(465e6, rel=0.005)
    assert sizes["['embeddings']['word']"] == 18992 * 2048
    assert sizes["['head']['out']"] == 18992 * 2048
    # a tree a layer: the comparison has every layer's leaves apart
    for i in range(4):
        layer = sum(v for k, v in sizes.items()
                    if k.startswith(f"['layer_{i}']"))
        assert layer == pytest.approx(96.9e6, rel=0.005)
        index = sum(v for k, v in sizes.items()
                    if k.startswith(f"['layer_{i}']['attn']['index']"))
        assert index == pytest.approx(2.26e6, rel=0.005)
        assert sizes[f"['layer_{i}']['moe']['Wg']"] == 2048 * 128
        assert sizes[f"['layer_{i}']['moe']['gate']"] == 16 * 2048 * 768


# -- the readers ---------------------------------------------------------------

class Cell:
    chips = 1
    workload = {"step_module": MODULE}


COUNTERS = {"rows": 2, "seq_len": 8192, "heads": 32, "head_dim": 128,
            "layers": 4}
# 1,792.1 keys a query, 16,384 queries a layer
SELECTED = [29_361_152] * 4


def context(trace, counters=None):
    return readers.Context(trace=trace, counters=counters or {}, cell=Cell,
                           peaks=peaks.peaks_for("TPU v5 lite"))


@pytest.fixture()
def sliced(monkeypatch):
    """Two steps of a program with the new sub-scopes and the three
    kernels by name, beside another program."""
    monkeypatch.setattr(runtime, "_PROGRAMS", {})
    ms = 1e6
    ops = [("%fusion.1 = f32[] fusion()", 0.0, 40 * ms),        # dsa_index
           ("%while.2 = u32[] while()", 40 * ms, 100 * ms),     # dsa_select
           ("%flash_fwd.3 = bf16[] custom-call()", 100 * ms, 180 * ms),
           ("%flash_bwd_dkv.4 = bf16[] custom-call()", 180 * ms, 300 * ms),
           ("%flash_bwd_dq.5 = bf16[] custom-call()", 300 * ms, 400 * ms),
           ("%fusion.6 = bf16[] fusion()", 400 * ms, 410 * ms),  # attn alone
           ("%jvp_jit_gmm__.7 = bf16[] custom-call()", 410 * ms, 420 * ms),
           ("%fusion.1 = bf16[] fusion()", 500 * ms, 530 * ms)]  # another's
    modules = [("jit_train_step(1)", 0.0, 430 * ms),
               ("jit_other(2)", 490 * ms, 540 * ms)]
    device = tr.DeviceSlice("/device:TPU:0", ops, modules, (0.0, 600 * ms), 2)
    scopes = {"fusion.1": "attn", "while.2": "attn", "flash_fwd.3": "attn",
              "flash_bwd_dkv.4": "attn", "flash_bwd_dq.5": "attn",
              "fusion.6": "attn", "jvp_jit_gmm__.7": "mlp"}
    runtime.publish_program(
        MODULE, flops=None, scopes=scopes,
        subscopes={"fusion.1": "dsa_index", "while.2": "dsa_select",
                   "jvp_jit_gmm__.7": "moe_experts"})
    return tr.TraceSlice([device], [])


def test_the_new_subscopes_split_the_attention_sublayer(sliced):
    ctx = context(sliced)
    assert moe_readers.subscope_ms_per_step(ctx, scope="dsa_index") == 20.0
    assert moe_readers.subscope_ms_per_step(ctx, scope="dsa_select") == 30.0
    assert moe_readers.subscope_ms_per_step(ctx, scope="moe_experts") == 5.0
    assert moe_readers.subscope_ms_per_step(ctx, scope="cca_mix") is None


def test_the_attention_roofline_counts_the_selected_pairs(sliced,
                                                          monkeypatch):
    monkeypatch.setattr(runtime, "_STEP_COUNTERS", {})
    runtime.publish_step_counters({"dsa.pairs_selected": SELECTED})
    need = dsa_readers.attend_train_cost(
        pairs_by_layer=SELECTED, rows=2, heads=32, seq_len=8192,
        head_dim=128)
    # six products a layer over the selected pairs, all 32 heads
    assert need["flops"] == 4 * 6 * 2 * 32 * 128 * 29_361_152
    tensor = 2 * 32 * 8192 * 128 * 2
    assert need["bytes"] == 4 * (12 * tensor + 3 * 2 * 8192 * 8192)
    # compute-bound on a v5e; a masked dense kernel runs 4,096.5 pairs a
    # query for these 1,792.1
    assert need["bytes"] / 819e9 < need["flops"] / 197e12
    ctx = context(sliced, COUNTERS)
    got = dsa_readers.attend_roofline(ctx)
    least_ms = 1e3 * need["flops"] / 197e12
    assert got == pytest.approx(100 * least_ms / 150.0)  # 300 ms, 2 steps
    assert 0 < got < 100
    note = ctx.notes["dsa_attend_roofline"]
    assert note["bound"] == "compute" and note["events"] == 3
    assert note["device_ms_per_step"] == pytest.approx(150.0)
    # against the count of every causal pair, which is what
    # flash_roofline_named would divide the same time into
    causal = 4 * 6 * 2 * 2 * 32 * 128 * (8192 * 8193 / 2)
    assert need["flops"] / causal == pytest.approx(1792.1 / 4096.5, rel=1e-4)


def test_the_attention_roofline_stays_under_100_where_all_is_selected(
        sliced, monkeypatch):
    """Ties can only add pairs up to the causal count; at that count and
    the kernels' best reading so far (52% of the causal roofline at this
    head width) the share is still under 100."""
    monkeypatch.setattr(runtime, "_STEP_COUNTERS", {})
    causal = 2 * 8192 * 8193 // 2
    runtime.publish_step_counters({"dsa.pairs_selected": [causal] * 4})
    got = dsa_readers.attend_roofline(context(sliced, COUNTERS))
    assert 0 < got < 100


@pytest.mark.parametrize("why", ["no counter", "no trace", "no kernel"])
def test_nothing_to_read_gives_nothing(sliced, monkeypatch, why):
    monkeypatch.setattr(runtime, "_STEP_COUNTERS", {})
    if why != "no counter":
        runtime.publish_step_counters({"dsa.pairs_selected": SELECTED})
    trace = sliced
    if why == "no trace":
        trace = None
    if why == "no kernel":
        device = sliced.devices[0]
        trace = tr.TraceSlice([tr.DeviceSlice(
            device.name, [op for op in device.ops if "flash_" not in op[0]],
            device.modules, device.window, 2)], [])
    assert dsa_readers.attend_roofline(context(trace, COUNTERS)) is None


def test_a_parent_without_the_counters_table_gives_nothing(sliced,
                                                           monkeypatch):
    monkeypatch.delattr(runtime, "step_counters")
    ctx = context(sliced, COUNTERS)
    assert dsa_readers.attend_roofline(ctx) is None
    assert moe_readers.step_counter(
        ctx, name="dsa.keys_selected_mean") is None


def test_the_keys_selected_are_the_programs_counter(monkeypatch):
    monkeypatch.setattr(runtime, "_STEP_COUNTERS", {})
    ctx = context(None)
    name = "dsa.keys_selected_mean"
    assert moe_readers.step_counter(ctx, name=name) is None
    runtime.publish_step_counters({name: 1792.1, "dsa.pairs_selected":
                                   SELECTED, "dsa.tiles_empty_share": 0.0})
    assert moe_readers.step_counter(ctx, name=name) == 1792.1
    assert ctx.notes["step_counters"]["dsa.tiles_empty_share"] == 0.0


def test_every_new_metrics_file_names_its_reader():
    want = {"dsa_index_ms_per_step":
            ("benchmark.harness.moe_readers:subscope_ms_per_step",
             {"scope": "dsa_index"}),
            "dsa_select_ms_per_step":
            ("benchmark.harness.moe_readers:subscope_ms_per_step",
             {"scope": "dsa_select"}),
            "dsa_attend_roofline_train":
            ("benchmark.harness.dsa_readers:attend_roofline", {}),
            "dsa_keys_selected_mean":
            ("benchmark.harness.moe_readers:step_counter",
             {"name": "dsa.keys_selected_mean"})}
    for name, (reader, args) in want.items():
        spec = manifest.load_json(os.path.join(
            manifest.BENCH_DIR, "metrics", name + ".json"))
        assert (spec["reader"], spec["args"]) == (reader, args)
        assert callable(manifest.resolve(reader))


def test_the_programs_vocabulary_has_what_the_readers_read():
    from deeplearning4j_tpu.observability import vocab

    assert {"dsa_index", "dsa_select"} <= set(vocab.SUB_SCOPES)
    assert {"dsa.pairs_selected", "dsa.keys_selected_mean",
            "dsa.tiles_empty_share"} <= set(vocab.STEP_COUNTERS)
    name = "jit(train_step)/jit(main)/attn/jit(_selected_pairs)/dsa_select/ge"
    assert vocab.scope_of(name) == "attn"
    assert vocab.subscope_of(name) == "dsa_select"
