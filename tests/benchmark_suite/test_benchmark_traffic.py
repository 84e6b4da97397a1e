"""The traffic generator: the same seed gives the same inputs, another
seed other inputs of the same sizes, and all rows differ."""

import textwrap

import jax
import numpy as np
import pytest

import benchmark_tiny
from benchmark.harness import manifest, traffic


@pytest.mark.parametrize("cell_name", ["bert_base.train_s128",
                                       "gpt2_small.train_s1024"])
def test_same_seed_same_batches_other_seed_other_batches(cell_name):
    cell = manifest.load_cell(cell_name)
    big = 2**31 + 12345  # the driver's seeds pass 32 signed bits
    a, b, c = (traffic.generate(cell, s, 1.0) for s in (big, big, big + 1))
    assert len(a) == cell.traffic["distinct_batches"]
    leaves = jax.tree_util.tree_leaves
    assert all(np.array_equal(x, y) for x, y in zip(leaves(a), leaves(b)))
    assert [x.shape for x in leaves(a)] == [x.shape for x in leaves(c)]
    assert not all(np.array_equal(x, y)
                   for x, y in zip(leaves(a), leaves(c)))


@pytest.mark.parametrize("cell_name", ["bert_base.train_s128",
                                       "gpt2_small.train_s1024"])
def test_rows_all_differ_and_ids_are_in_the_vocabulary(cell_name):
    cell = manifest.load_cell(cell_name)
    batches = traffic.generate(cell, 7, 1.0)
    ids = np.concatenate([b["features"]["token_ids"] for b in batches])
    rows, seq = cell.traffic["rows"], cell.traffic["seq_len"]
    assert ids.shape == (rows * len(batches), seq)
    assert len({row.tobytes() for row in ids}) == ids.shape[0]
    assert ids.min() >= 0 and ids.max() < cell.config["vocab_size"]


def test_mlm_batch_layout():
    cell = manifest.load_cell("bert_base.train_s128")
    batch = traffic.generate(cell, 3, 1.0)[0]
    labels, feats = batch["labels"], batch["features"]
    p = cell.traffic["max_predictions"]
    assert labels["mlm_positions"].shape == (64, p)
    weights = labels["mlm_weights"]
    assert set(np.unique(weights)) <= {0.0, 1.0} and weights.sum() > 64
    # a weighted slot points at a masked token and keeps its original id
    n, k = np.nonzero(weights)
    at = labels["mlm_positions"][n, k]
    assert np.all(feats["token_ids"][n, at] == cell.traffic["mask_id"])
    assert np.all(labels["mlm_labels"][n, k] >= 5)
    assert cell.reference.part_weights(batch) == {
        "mlm": float(weights.sum()), "nsp": 64.0}


def test_an_unknown_family_is_an_error(tmp_path):
    cell = benchmark_tiny.load(str(tmp_path), "tiny_gpt.tiny_clm")
    cell.traffic["family"] = "benchmark.harness.traffic:no_such_family"
    with pytest.raises(AttributeError, match="no_such_family"):
        traffic.generate(cell, 1, 1.0)


def test_a_family_is_added_by_a_module_of_the_later_prs_own(
        tmp_path, monkeypatch):
    """A traffic mix names its family as a metric names its reader, so a
    new family edits no file that is there."""
    (tmp_path / "later_pr_traffic.py").write_text(textwrap.dedent("""
        from benchmark.harness import traffic
        def doubled(cell, seed, seconds):
            return traffic.fixed_batches(cell, seed, seconds) * 2
    """))
    monkeypatch.syspath_prepend(str(tmp_path))
    cell = benchmark_tiny.load(str(tmp_path), "tiny_gpt.tiny_clm")
    cell.traffic["family"] = "later_pr_traffic:doubled"
    assert len(traffic.generate(cell, 1, 1.0)) == 2 * len(
        traffic.fixed_batches(cell, 1, 1.0))


def chat(tmp_path):
    return benchmark_tiny.load(str(tmp_path), benchmark_tiny.CHAT)


def test_a_replayed_schedule_is_the_files_and_the_seed_draws_only_the_ids(
        tmp_path):
    """The same seed gives the same requests; another seed requests of
    the same lengths at the same times, with other token ids. The lead-in
    comes before the window, and what is due past its end is left out."""
    cell = chat(tmp_path)
    big = 2**31 + 777
    a, b, c = (traffic.generate(cell, s, 2.0) for s in (big, big, big + 1))
    rows = cell.traffic["requests"]
    assert len(a) == len(rows) and sum(r.due_s >= 0 for r in a) == 20
    assert [r.index for r in a] == list(range(len(a)))
    assert [(r.due_s, r.prompt, r.max_new_tokens) for r in a] == [
        (r.due_s, r.prompt, r.max_new_tokens) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in c]
    assert [[r.due_s, len(r.prompt), r.max_new_tokens] for r in c] == rows
    assert all(x.due_s < y.due_s for x, y in zip(a, a[1:]))
    assert a[0].due_s == -0.5
    short = traffic.generate(cell, big, 1.0)
    assert [r.prompt for r in short] == [r.prompt for r in a[:len(short)]]
    assert len(short) == sum(r[0] < 1.0 for r in rows) < len(a)


def test_a_replayed_schedule_fits_the_engine_and_the_reference(tmp_path):
    cell = chat(tmp_path)
    requests = traffic.generate(cell, 5, 2.0)
    ids = np.concatenate([r.prompt for r in requests])
    assert ids.min() >= 0 and ids.max() < cell.config["vocab_size"]
    engine, check = cell.workload["engine"], cell.workload["check"]
    longest = max(len(r.prompt) + r.max_new_tokens for r in requests)
    assert longest <= engine["max_len"] and longest - 1 <= check["pad_to"]
    assert max(r.max_new_tokens for r in requests) <= engine["max_new_tokens"]
