"""The flash kernels' tile plan (kernels/flash_attention.py::TilePlan): the
one function that says which score tiles are dead, which are live, and
which of the live ones the causal diagonal crosses corner to corner and so
are computed in row sub-blocks; checked here against the dense mask it
stands for; and the flight event that reports it at trace time."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.kernels import flash_attention as fa
from deeplearning4j_tpu.kernels._dispatch import flash_block_sizes
from deeplearning4j_tpu.observability import vocab
from deeplearning4j_tpu.observability.flightrecorder import (
    FlightRecorder,
    get_flight_recorder,
    set_flight_recorder,
)

_SEQS = (8, 24, 100, 128, 256)
_BLOCKS = (8, 32, 128)


def _dense_mask(plan):
    i = np.arange(plan.seq_q)[:, None]
    j = np.arange(plan.seq_k)[None, :]
    return (j <= i + plan.offset) if plan.causal else np.ones(
        (plan.seq_q, plan.seq_k), bool)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("seq_q,seq_k", itertools.product(_SEQS, _SEQS))
def test_the_plan_is_the_dense_mask(seq_q, seq_k, causal):
    for block_q, block_k in itertools.product(_BLOCKS, _BLOCKS):
        plan = fa.TilePlan(seq_q, seq_k, block_q, block_k, causal)
        dense = _dense_mask(plan)
        live = plan.live_tiles()
        assert live.shape == (plan.n_q, plan.n_k)
        counts = plan.counts()
        assert (counts["dead"], counts["live"]) == (int((~live).sum()),
                                                    int(live.sum()))
        rebuilt = np.zeros_like(dense)
        for qi, ki in np.ndindex(*live.shape):
            rows = slice(qi * block_q, (qi + 1) * block_q)
            cols = slice(ki * block_k, (ki + 1) * block_k)
            if live[qi, ki]:  # what it contributes once its mask is applied
                rebuilt[rows, cols] = dense[rows, cols]
            else:
                assert not dense[rows, cols].any()
        np.testing.assert_array_equal(rebuilt, dense)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("seq_q,seq_k", itertools.product(_SEQS, _SEQS))
def test_a_dead_step_fetches_nothing_new(seq_q, seq_k, causal):
    for block_q, block_k in itertools.product(_BLOCKS, _BLOCKS):
        plan = fa.TilePlan(seq_q, seq_k, block_q, block_k, causal)
        live = plan.live_tiles()
        qi = np.arange(plan.n_q)[:, None]
        ki = np.arange(plan.n_k)[None, :]
        fetch_k = np.broadcast_to(plan.fetch_k(qi, ki), live.shape)
        fetch_q = np.broadcast_to(plan.fetch_q(qi, ki), live.shape)
        for fetched, n in ((fetch_k, plan.n_k), (fetch_q, plan.n_q)):
            assert fetched.min() >= 0 and fetched.max() < n
        np.testing.assert_array_equal(fetch_k[live], np.broadcast_to(
            ki, live.shape)[live])
        np.testing.assert_array_equal(fetch_q[live], np.broadcast_to(
            qi, live.shape)[live])
        # query-major sweeps (flash_fwd, flash_bwd_dq): dead steps follow
        # the row's live ones and keep pointing at the last block fetched
        dead_after = ~live[:, 1:] & live[:, :1].repeat(plan.n_k - 1, 1)
        np.testing.assert_array_equal(fetch_k[:, 1:][dead_after],
                                      fetch_k[:, :-1][dead_after])
        # key-major sweep (flash_bwd_dkv): dead steps come first and point
        # at the block the column's first live step will want
        dead_before = ~live[:-1] & live[-1:].repeat(plan.n_q - 1, 0)
        np.testing.assert_array_equal(fetch_q[:-1][dead_before],
                                      fetch_q[1:][dead_before])


# name: (seq_q, seq_k, block_q, block_k, causal), then what the plan has to
# say: sub_blocks, diagonal tiles, live tiles. The blocks are as
# ``flash_attention`` hands them over, clamped to the sequence.
_SUB = fa._SUB_BLOCK
_SPLIT_CASES = {
    "gpt2_small_one_tile_of_1024": ((1024, 1024, 1024, 1024, True),
                                    1024 // _SUB, 1, 1),
    "zaya_4096_at_1024_tiles": ((4096, 4096, 1024, 1024, True),
                                1024 // _SUB, 4, 10),
    "1024_at_512_tiles": ((1024, 1024, 512, 512, True), 512 // _SUB, 2, 3),
    "a_block_of_one_sub_block": ((4096, 4096, _SUB, _SUB, True), 1, 0, 136),
    "a_block_that_is_no_multiple_of_it": ((768, 768, 384, 384, True),
                                          1, 0, 3),
    "offset_of_one_tile": ((1024, 2048, 1024, 1024, True),
                           1024 // _SUB, 1, 2),
    "fewer_keys_than_queries_by_two_tiles": ((2048, 1024, 512, 512, True),
                                             512 // _SUB, 2, 3),
    "offset_that_no_tile_edge_meets": ((1024, 1536, 1024, 1024, True),
                                       1, 0, 2),
    "cross_512_by_2048_clamped": ((512, 2048, 512, 1024, True), 1, 0, 2),
    "unequal_blocks": ((1024, 1024, 512, 1024, True), 1, 0, 2),
    "not_causal": ((1024, 1024, 1024, 1024, False), 1, 0, 1),
    "ragged_1000_padded_to_one_tile": ((1000, 1000, 1000, 1024, True),
                                       1, 0, 1),
    "ragged_1000_at_512_tiles": ((1000, 1000, 512, 512, True),
                                 512 // _SUB, 2, 3),
}


def _touched(plan):
    """``[n_q * block_q, n_k * block_k]`` of bool: the pairs a kernel
    computes, tile by tile, from ``diagonal`` and ``parts`` alone."""
    touched = np.zeros((plan.n_q * plan.block_q, plan.n_k * plan.block_k),
                       bool)
    for qi, ki in np.ndindex(plan.n_q, plan.n_k):
        if not plan.live(qi, ki):
            assert not plan.diagonal(qi, ki) and not plan.whole(qi, ki)
            continue
        diagonal = bool(plan.diagonal(qi, ki))
        assert bool(plan.whole(qi, ki)) is not diagonal
        for part in plan.parts("diagonal" if diagonal else "whole"):
            rows, keys = part.rows, part.keys
            r = qi * plan.block_q + part.row
            c = ki * plan.block_k + part.key
            assert part.key == 0 and not part.window  # no window: a prefix
            assert not touched[r:r + rows, c:c + keys].any()  # once each
            touched[r:r + rows, c:c + keys] = True
            if not part.causal:  # then the causal term kills no pair of it
                i = np.arange(r, r + rows)[:, None]
                j = np.arange(c, c + keys)[None, :]
                assert not plan.causal or (j <= i + plan.offset).all()
    return touched


@pytest.mark.parametrize("case", sorted(_SPLIT_CASES))
def test_which_tiles_are_diagonal_and_how_they_are_split(case):
    args, sub_blocks, diagonal, live = _SPLIT_CASES[case]
    plan = fa.TilePlan(*args)
    assert plan.sub_blocks == sub_blocks
    assert int(plan.diagonal_tiles().sum()) == diagonal
    counts = plan.counts()
    assert (counts["sub_blocks"], counts["diagonal"], counts["live"]) == (
        sub_blocks, diagonal, live)
    if sub_blocks == 1:
        assert plan.parts("whole") == [(0, plan.block_q, 0, plan.block_k,
                                        plan.causal, False)]
    else:
        assert len(plan.parts("diagonal")) == sub_blocks
    # every pair the mask leaves is computed, and counted as the event says
    dense = _dense_mask(plan)
    touched = _touched(plan)
    assert touched[:plan.seq_q, :plan.seq_k][dense].all()
    assert counts["pairs_touched_over_required"] == pytest.approx(
        touched.sum() / dense.sum(), abs=1e-4)


def test_the_pairs_touched_at_the_benchmarks_shapes():
    """The figures ISSUE 32 and PERF.md quote."""
    def ratio(*args):
        return fa.TilePlan(*args).counts()["pairs_touched_over_required"]

    square = 1024 * 1024 / (1024 * 1025 / 2)  # 1.998: one tile, whole
    n = 1024 // _SUB
    assert ratio(1024, 1024, 1024, 1024, True) == pytest.approx(
        square * (n + 1) / (2 * n), abs=1e-4)
    assert ratio(1024, 1024, 1024, 1024, False) == 1.0
    # 4 diagonal tiles of 10 live, each cut to (n + 1) / 2n of itself
    assert ratio(4096, 4096, 1024, 1024, True) == pytest.approx(
        (6 + 4 * (n + 1) / (2 * n)) * 1024 ** 2 / (4096 * 4097 / 2),
        abs=1e-4)


def test_the_plan_answers_traced_indices_like_numbers():
    for plan in (fa.TilePlan(100, 128, 32, 32, True),
                 fa.TilePlan(3 * _SUB, 4 * _SUB, 2 * _SUB, 2 * _SUB, True)):
        _traced_like_numbers(plan)


def _traced_like_numbers(plan):
    qi, ki = np.meshgrid(np.arange(plan.n_q), np.arange(plan.n_k),
                         indexing="ij")

    def answers(qi, ki):
        return (plan.live(qi, ki), plan.fetch_k(qi, ki),
                plan.fetch_q(qi, ki), plan.diagonal(qi, ki),
                plan.whole(qi, ki))

    for got, want in zip(jax.jit(answers)(jnp.asarray(qi), jnp.asarray(ki)),
                         answers(qi, ki)):
        np.testing.assert_array_equal(
            np.asarray(got), np.broadcast_to(want, qi.shape))


@pytest.fixture
def flight(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_FORCE_PALLAS", "1")
    before = get_flight_recorder()
    yield set_flight_recorder(FlightRecorder())
    set_flight_recorder(before)


def test_the_plan_is_a_flight_event_of_every_traced_call(flight):
    assert "kernel.flash_plan" in vocab.known_event_kinds()
    q = jnp.ones((1, 2, 128, 16), jnp.float32)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True, block_q=32,
                                 block_k=64)
        return jnp.sum(fa.flash_attention(out, k, v, causal=False,
                                          block_q=64, block_k=128))

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    step.lower(q, q, q)  # trace only
    first, second = flight.events(kinds=["kernel.flash_plan"])  # two layers
    data = first["data"]
    assert (data["seq_q"], data["seq_k"], data["head_dim"]) == (128, 128, 16)
    assert data["causal"] is True and data["key_mask"] is False
    want = {"block_q": 32, "block_k": 64, "dead": 2, "dead_steps": 2,
            "live": 6,
            "diagonal": 0, "edge": 0, "sub_blocks": 1,
            "pairs_touched_over_required": round(
                6 * 32 * 64 / (128 * 129 / 2), 4)}
    assert data["fwd"] == data["dkv"] == data["dq"] == want
    assert second["data"]["causal"] is False
    assert second["data"]["dq"] == {"block_q": 64, "block_k": 128,
                                    "dead": 0, "dead_steps": 0, "live": 2,
                                    "diagonal": 0,
                                    "edge": 0, "sub_blocks": 1,
                                    "pairs_touched_over_required": 1.0}
    # a recorder armed later sees the plans of the next trace as well
    later = set_flight_recorder(FlightRecorder())
    jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q)
    assert len(later.events(kinds=["kernel.flash_plan"])) == 2


def test_the_default_plan_at_the_benchmarks_shape(flight):
    """The counts PERF.md quotes for ``gpt2_small.train_s1024``, from the
    geometry ``flash_block_sizes`` chose on the chip."""
    blocks = flash_block_sizes(1024, 1024, 64, True)
    q = jax.ShapeDtypeStruct((16, 12, 1024, 64), jnp.bfloat16)
    jax.eval_shape(lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
                   q, q, q)
    data = flight.events(kinds=["kernel.flash_plan"])[0]["data"]
    for name, (block_q, block_k) in blocks._asdict().items():
        counts = fa.TilePlan(1024, 1024, block_q, block_k, True).counts()
        assert data[name] == {"block_q": block_q, "block_k": block_k,
                              **counts}
    assert _QUOTED == {name: data[name] for name in ("fwd", "dkv", "dq")}


_ONE_TILE_IN_FOUR = {"block_q": 1024, "block_k": 1024, "dead": 0,
                    "dead_steps": 0, "live": 1,
                    "diagonal": 1, "edge": 0, "sub_blocks": 4,
                    "pairs_touched_over_required": 1.2488}
_QUOTED = {"fwd": _ONE_TILE_IN_FOUR, "dkv": _ONE_TILE_IN_FOUR,
           "dq": _ONE_TILE_IN_FOUR}
