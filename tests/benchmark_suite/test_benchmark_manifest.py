"""``BENCHMARK.json`` against the contract's limits, and the harness
against new files: a configuration, a cell and a per-layer metric are each
added by files and manifest entries alone."""

import json
import os
import re
import textwrap

import pytest

import benchmark_tiny
from benchmark.harness import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def doc():
    return manifest.load_json(manifest.MANIFEST)


def line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_keys_and_sizes(doc):
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(manifest.MANIFEST) <= 64 * 1024
    assert 1 <= len(doc["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in doc["paths"])
    assert len(doc["command"]) <= 32 and all(line(w) for w in doc["command"])
    assert isinstance(doc["run_seconds"], int) and 1 <= doc["run_seconds"] <= 51
    # a full check of 24 cells has to fit into 43200 seconds
    runs = 2 + 14 * 24
    assert runs * (doc["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_lines(doc):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in doc[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
    assert len(set(names)) == len(names)
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert line(c["source"]) and line(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert line(w["why"])
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in doc["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in doc["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert line(m["layer"])


def test_cells_configs_and_metrics_hang_together(doc):
    configs = {c["name"] for c in doc["configs"]}
    cells = {w["name"] for w in doc["workloads"]}
    end = {m["name"] for m in doc["end_to_end"]}
    assert "setup_s" in end
    assert {w["config"] for w in doc["workloads"]} == configs
    pairs = [(w["config"], w["traffic"]) for w in doc["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in doc["workloads"]) <= max(
        1, len(cells) // 4)
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert set(m.get("workloads", [])) <= cells
    for m in doc["per_layer"]:
        assert m["moves"] in end and m["moves"] != "setup_s"
    for name in cells:
        cell = manifest.load_cell(name)
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer, name
    assert len({c["file"] for c in doc["configs"]}) == len(configs)


def test_every_file_named_is_there_and_named_within_the_characters(doc):
    for c in doc["configs"]:
        assert os.path.isfile(os.path.join(manifest.ROOT, c["file"]))
        assert any(c["file"].startswith(p + "/") for p in doc["paths"])
        config = manifest.load_json(os.path.join(manifest.ROOT, c["file"]))
        assert config["source"] == c["source"]
        assert set(c["reduced"]) == set(config["changed"])
        assert callable(manifest.resolve(config["reference"]).loss_parts)
    for w in doc["workloads"]:
        for kind, name in (("workloads", w["name"]), ("traffic", w["traffic"])):
            assert os.path.isfile(os.path.join(
                manifest.BENCH_DIR, kind, name + ".json"))
    for m in doc["per_layer"]:
        assert callable(manifest.load_metric(
            m, os.path.join(manifest.BENCH_DIR, "metrics")).reader)
    for path in doc["paths"]:
        for folder, _, files in os.walk(os.path.join(manifest.ROOT, path)):
            if "__pycache__" in folder:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(folder, f), manifest.ROOT)
                assert PATH.match(rel), rel
    assert doc["command"][1].startswith(doc["paths"][0] + "/")


def test_every_data_file_is_named_by_the_manifest(doc):
    """No cell's, mix's or metric's file lies about without an entry."""
    named = {"workloads": {w["name"] for w in doc["workloads"]},
             "traffic": {w["traffic"] for w in doc["workloads"]},
             "metrics": {m["name"] for m in doc["per_layer"]},
             "configs": {os.path.basename(c["file"])[:-len(".json")]
                         for c in doc["configs"]}}
    for folder, names in named.items():
        files = {f[:-len(".json")] for f in os.listdir(
            os.path.join(manifest.BENCH_DIR, folder)) if f.endswith(".json")}
        assert files == names, folder


def test_no_width_is_reduced(doc):
    width = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$|"
                       r"head_size|n_embd|n_inner|expansion|per_tok)")
    for c in doc["configs"]:
        assert not [k for k in c["reduced"] if width.search(k)]


def test_a_cell_a_configuration_and_a_metric_are_added_by_files_alone(tmp_path):
    """The tiny benchmark is made of new files only; a per-layer metric
    with a reader of its own joins it by one more file and one entry."""
    path = benchmark_tiny.build(str(tmp_path))
    bench = os.path.join(str(tmp_path), "bench")
    (tmp_path / "later_pr_readers.py").write_text(textwrap.dedent("""
        def steps_per_second(ctx, *, scale):
            return scale * ctx.counters["steps"] / ctx.counters["window_s"]
        def nothing_to_read(ctx):
            return None
    """))
    doc = manifest.load_json(path)
    for name, reader, args in (
            ("steps_per_s", "later_pr_readers:steps_per_second", {"scale": 2}),
            ("silent", "later_pr_readers:nothing_to_read", {})):
        with open(os.path.join(bench, "metrics", name + ".json"), "w") as f:
            json.dump({"reader": reader, "args": args}, f)
        doc["per_layer"].append({
            "name": name, "unit": "steps/s", "better": "higher",
            "source": "program_counter", "layer": "model step",
            "moves": "train_tok_s_chip", "workloads": ["tiny_gpt.tiny_clm"]})
    with open(path, "w") as f:
        json.dump(doc, f)
    import sys
    sys.path.insert(0, str(tmp_path))
    try:
        cell = manifest.load_cell("tiny_gpt.tiny_clm", manifest_path=path,
                                  bench_dir=bench)
        other = manifest.load_cell("tiny_bert.tiny_mlm", manifest_path=path,
                                   bench_dir=bench)
    finally:
        sys.path.remove(str(tmp_path))
    assert cell.config["n_embd"] == 64 and cell.traffic["rows"] == 8
    added = {m.name: m for m in cell.per_layer}
    assert "steps_per_s" not in {m.name for m in other.per_layer}

    class Ctx:
        counters = {"steps": 30, "window_s": 2.0}

    assert added["steps_per_s"].reader(Ctx(), **added["steps_per_s"].args) == 30
    assert added["silent"].reader(Ctx()) is None


def test_an_unknown_cell_is_an_error():
    with pytest.raises(KeyError, match="no workload"):
        manifest.load_cell("no_such.cell")
