"""The operation and byte counts against numbers worked by hand."""

import pytest

import benchmark_tiny  # noqa: F401
from benchmark.harness import flops, peaks


def test_causal_pairs():
    assert flops.causal_pairs(1) == 1
    assert flops.causal_pairs(4) == 10
    assert flops.causal_pairs(1024) == 524800


def test_block_forward_by_hand():
    # 2 tokens, hidden 4, intermediate 8, 3 attended pairs:
    # projections 4*2*2*16 = 256, ffn 2*2*2*4*8 = 256, attention 2*2*3*4 = 48
    assert flops.block_forward_flops(2, 3, 4, 8) == 560


def test_gpt_counts_half_the_attention_of_a_dense_count():
    kw = dict(rows=1, seq_len=8, hidden=4, intermediate=8, layers=1, vocab=10)
    got = flops.gpt_train_flops(**kw)
    # block: projections 4*2*8*16 = 1024, ffn 2*2*8*4*8 = 1024,
    # attention 2*2*36*4 = 576 (36 causal pairs, not 64); head 2*7*4*10 = 560
    assert got == 3 * (1024 + 1024 + 576 + 560)
    dense_attention = 2 * 2 * 64 * 4
    assert got < 3 * (1024 + 1024 + dense_attention + 560)


def test_bert_by_hand():
    got = flops.bert_train_flops(rows=2, seq_len=4, hidden=4, intermediate=8,
                                 layers=2, vocab=10, predictions=1)
    block = 4 * 2 * 8 * 16 + 2 * 2 * 8 * 4 * 8 + 2 * 2 * (2 * 16) * 4
    mlm = 2 * 2 * 16 + 2 * 2 * 4 * 10
    nsp = 2 * 2 * 16 + 2 * 2 * 4 * 2
    assert got == 3 * (2 * block + mlm + nsp)


def test_cells_required_flops():
    # the numbers PERF.md quotes for the two cells
    bert = flops.bert_train_flops(rows=64, seq_len=128, hidden=768,
                                  intermediate=3072, layers=12, vocab=30522,
                                  predictions=20)
    gpt = flops.gpt_train_flops(rows=16, seq_len=1024, hidden=768,
                                intermediate=3072, layers=12, vocab=50257)
    assert bert == pytest.approx(4.48e12, rel=0.01)
    assert gpt == pytest.approx(1.31e13, rel=0.01)


def test_flash_cost_and_its_bound():
    cost = flops.flash_train_cost(rows=16, heads=12, seq_len=1024,
                                  head_dim=64, layers=12)
    per_product = 2 * 16 * 12 * 524800 * 64
    assert cost["flops"] == 12 * 6 * per_product
    assert cost["bytes"] == 12 * 12 * (16 * 12 * 1024 * 64 * 2)
    least = flops.roofline_seconds(cost["flops"], cost["bytes"], 197e12, 819e9)
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(cost["flops"] / 197e12)
    tiny = flops.roofline_seconds(1e6, 1e9, 197e12, 819e9)
    assert tiny["bound"] == "memory"
    assert tiny["seconds"] == pytest.approx(1e9 / 819e9)


def test_peaks_table_refuses_an_unknown_chip():
    row = peaks.peaks_for("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in row["source"]
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("cpu")
