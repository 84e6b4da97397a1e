"""The three flash kernels with a sliding window (a query attends to its
own position and the ``window - 1`` before it), interpreted on the CPU,
against ``reference_attention`` with the same window: the forward and all
three gradients, and what the tile plan says of a windowed call."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.kernels import flash_attention as fa
from deeplearning4j_tpu.observability.flightrecorder import (
    FlightRecorder,
    get_flight_recorder,
    set_flight_recorder,
)


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_FORCE_PALLAS", "1")


def inputs(b, h, t, s, d, seed=0):
    kq, kk, kv, kg = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(kq, (b, h, t, d)),
            jax.random.normal(kk, (b, h, s, d)),
            jax.random.normal(kv, (b, h, s, d)),
            jax.random.normal(kg, (b, h, t, d)))


def both(q, k, v, g, *, window, ref_window="same", **kw):
    def run(fn):
        out, pull = jax.vjp(lambda q, k, v: fn(q, k, v), q, k, v)
        return (out,) + pull(g)

    got = run(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, window=window, backend="pallas", **kw))
    want = run(lambda q, k, v: fa.reference_attention(
        q, k, v, causal=True,
        window=window if ref_window == "same" else ref_window))
    return got, want


def assert_close(got, want):
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5, err_msg=name)


def dense(t, s, window):
    behind = np.arange(t)[:, None] + (s - t) - np.arange(s)[None, :]
    return (behind >= 0) & (behind < window)


def test_the_reference_keeps_window_keys_a_query_itself_included():
    q, k, v, _ = inputs(1, 1, 12, 12, 4)
    got = fa.reference_attention(q, k, v, causal=True, window=3)
    s = jnp.einsum("bhtd,bhsd->bhts", q, k) * 4 ** -0.5
    s = jnp.where(jnp.asarray(dense(12, 12, 3)), s, -1e30)
    want = jnp.einsum("bhts,bhsd->bhtd", jax.nn.softmax(s, axis=-1), v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-6)
    assert dense(12, 12, 3).sum(axis=1).tolist() == [1, 2] + [3] * 10
    # one key: every query sees itself alone
    alone = fa.reference_attention(q, k, v, causal=True, window=1)
    np.testing.assert_allclose(np.asarray(alone), np.asarray(v), rtol=1e-5,
                               atol=1e-6)


# tiles of 128 x 128 over 512 positions (no tile is split): a window
# smaller than a block, one less and one more than it, equal to it, a
# multiple of it, and none of these
@pytest.mark.parametrize("window", [1, 37, 127, 128, 129, 256, 300, 511])
def test_windows_against_whole_tiles(interpreted, window):
    q, k, v, g = inputs(1, 2, 512, 512, 16, seed=window)
    got, want = both(q, k, v, g, window=window, block_q=128, block_k=128)
    assert_close(got, want)


@pytest.mark.parametrize("blocks", [(32, 128), (64, 256), (256, 128)])
def test_windows_against_unequal_tiles(interpreted, blocks):
    q, k, v, g = inputs(2, 2, 512, 512, 8, seed=7)
    got, want = both(q, k, v, g, window=200, block_q=blocks[0],
                     block_k=blocks[1])
    assert_close(got, want)


# tiles of 512 x 512 in two row sub-blocks of 256: the diagonal tile is
# split, and the tile the window's far edge crosses corner to corner (a
# window of whole blocks) is split as its mirror image
@pytest.mark.parametrize("window", [100, 256, 512, 700, 1024, 1535])
def test_windows_against_split_tiles(interpreted, window):
    plan = fa.TilePlan(1536, 1536, 512, 512, True, window=window)
    assert plan.sub_blocks == 2
    assert plan.splits_edge == (window % 512 == 0)
    q, k, v, g = inputs(1, 2, 1536, 1536, 8, seed=window)
    got, want = both(q, k, v, g, window=window, block_q=512, block_k=512)
    assert_close(got, want)


@pytest.mark.parametrize("window", [512, 513, 4096])
def test_a_window_no_shorter_than_the_keys_is_causal(interpreted, window):
    q, k, v, g = inputs(1, 2, 512, 512, 8, seed=3)
    got, want = both(q, k, v, g, window=window, ref_window=None,
                     block_q=128, block_k=128)
    assert_close(got, want)
    text = [jax.jit(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, window=w, block_q=128, block_k=128,
        backend="pallas")).lower(q, k, v).as_text() for w in (window, None)]
    assert text[0] == text[1]


def test_ragged_lengths_more_keys_than_queries_and_a_key_mask(interpreted):
    """T = 100 queries against the last of S = 300 keys (padded to whole
    tiles), a key mask beside the window."""
    q, k, v, g = inputs(2, 2, 100, 300, 16, seed=5)
    key_mask = np.ones((2, 300), np.float32)
    key_mask[1, 250:280] = 0

    def run(fn):
        out, pull = jax.vjp(fn, q, k, v)
        return (out,) + pull(g)

    got = run(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, window=90, key_mask=jnp.asarray(key_mask),
        block_q=64, block_k=128, backend="pallas"))
    want = run(lambda q, k, v: fa.reference_attention(
        q, k, v, causal=True, window=90, key_mask=jnp.asarray(key_mask)))
    assert_close(got, want)


def test_a_window_wants_a_causal_call_and_a_positive_count():
    q, k, v, _ = inputs(1, 1, 16, 16, 4)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, k, v, causal=False, window=4)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, k, v, causal=True, window=0)
    with pytest.raises(ValueError, match="window"):
        fa.TilePlan(16, 16, 8, 8, False, window=4)


# -- the plan -----------------------------------------------------------------

def test_the_plan_at_the_cells_geometry():
    """``smallthinker_21b_a3b.train_s16384``: 16 x 16 tiles of 1024 x 1024,
    a window of 4,096 keys."""
    plan = fa.TilePlan(16384, 16384, 1024, 1024, True, window=4096)
    counts = plan.counts()
    assert (counts["diagonal"], counts["edge"], counts["dead"],
            counts["live"]) == (16, 12, 186, 70)
    # a sweep is 5 blocks long: 80 grid steps a head, 10 of them dead
    assert (plan.steps_k, plan.steps_q, counts["dead_steps"]) == (5, 5, 10)
    whole = counts["live"] - counts["diagonal"] - counts["edge"]
    assert whole == 42
    assert plan.pairs_required() == 58_722_304
    assert fa.TilePlan(16384, 16384, 1024, 1024, True).pairs_required() == (
        134_225_920)
    # 42 tiles whole, 28 in four sub-blocks that run 10 sixteenths of them
    assert plan.pairs_touched() == (42 + 28 * 10 / 16) * 1024 * 1024
    assert counts["pairs_touched_over_required"] == pytest.approx(
        1.0625, abs=1e-4)
    assert counts["pairs_touched_over_required"] < 1.25
    # the far edge's tile is the diagonal's mirror image
    assert [(p.row, p.key, p.keys) for p in plan.parts("edge")] == [
        (0, 0, 1024), (256, 256, 768), (512, 512, 512), (768, 768, 256)]
    assert all(p.window and not p.causal for p in plan.parts("edge"))
    assert all(p.causal and not p.window for p in plan.parts("diagonal"))
    assert plan.parts("whole") == [fa.Part(0, 1024, 0, 1024, False, False)]


_PLANS = [(512, 512, 128, 128, w) for w in (1, 37, 128, 129, 256, 300)] + [
    (1536, 1536, 512, 512, w) for w in (100, 256, 512, 700, 1024)] + [
    (100, 300, 64, 128, 90), (512, 512, 32, 128, 200),
    (2048, 2048, 1024, 1024, 1024), (1024, 2048, 512, 512, 512)]


@pytest.mark.parametrize("args", _PLANS, ids=lambda a: "x".join(map(str, a)))
def test_the_plan_is_the_dense_mask(args):
    """Every pair the window leaves is computed, once; a dead tile holds
    none; a rectangle without a term needs none; the counts are those of
    the rectangles."""
    seq_q, seq_k, block_q, block_k, window = args
    plan = fa.TilePlan(seq_q, seq_k, block_q, block_k, True, window=window)
    mask = dense(seq_q, seq_k, window)
    assert plan.pairs_required() == mask.sum()
    padded = np.zeros((plan.n_q * block_q, plan.n_k * block_k), bool)
    padded[:seq_q, :seq_k] = mask
    touched = np.zeros_like(padded)
    i = np.arange(padded.shape[0])[:, None] + plan.offset
    j = np.arange(padded.shape[1])[None, :]
    for qi, ki in np.ndindex(plan.n_q, plan.n_k):
        kinds = [kind for kind in ("diagonal", "edge", "whole")
                 if getattr(plan, kind)(qi, ki)]
        assert len(kinds) == (1 if plan.live(qi, ki) else 0)
        for kind in kinds:
            for part in plan.parts(kind):
                rows = slice(qi * block_q + part.row,
                             qi * block_q + part.row + part.rows)
                cols = slice(ki * block_k + part.key,
                             ki * block_k + part.key + part.keys)
                assert not touched[rows, cols].any()
                touched[rows, cols] = True
                if not part.causal:
                    assert (i[rows] >= j[:, cols]).all()
                if not part.window:
                    assert (i[rows] - j[:, cols] < window).all()
    assert touched[padded].all()
    assert touched.sum() == plan.pairs_touched()


@pytest.mark.parametrize("args", _PLANS, ids=lambda a: "x".join(map(str, a)))
def test_a_dead_step_fetches_nothing_new(args):
    seq_q, seq_k, block_q, block_k, window = args
    plan = fa.TilePlan(seq_q, seq_k, block_q, block_k, True, window=window)
    live = plan.live_tiles()
    qi = np.arange(plan.n_q)[:, None]
    ki = np.arange(plan.n_k)[None, :]
    fetch_k = np.broadcast_to(plan.fetch_k(qi, ki), live.shape)
    fetch_q = np.broadcast_to(plan.fetch_q(qi, ki), live.shape)
    np.testing.assert_array_equal(fetch_k[live],
                                  np.broadcast_to(ki, live.shape)[live])
    np.testing.assert_array_equal(fetch_q[live],
                                  np.broadcast_to(qi, live.shape)[live])
    for row in range(plan.n_q):  # query-major: flash_fwd, flash_bwd_dq
        if live[row].any():
            assert set(fetch_k[row]) == set(np.flatnonzero(live[row]))
            assert (np.diff(fetch_k[row]) >= 0).all()
    for col in range(plan.n_k):  # key-major: flash_bwd_dkv
        if live[:, col].any():
            assert set(fetch_q[:, col]) == set(np.flatnonzero(live[:, col]))
            assert (np.diff(fetch_q[:, col]) >= 0).all()


@pytest.mark.parametrize("args", _PLANS, ids=lambda a: "x".join(map(str, a)))
def test_a_sweep_starts_at_its_first_live_block_and_misses_none(args):
    """Under a window the grid's inner axis is as long as the widest run
    of live blocks, and step ``j`` of a block's sweep is its first live
    block plus ``j``: every live tile is a step of exactly one sweep, a
    step past the arrays is dead, and what a sweep fetches moves forward
    over live blocks only."""
    seq_q, seq_k, block_q, block_k, window = args
    plan = fa.TilePlan(seq_q, seq_k, block_q, block_k, True, window=window)
    live = plan.live_tiles()
    assert 1 <= plan.steps_k <= plan.n_k and 1 <= plan.steps_q <= plan.n_q
    assert plan.steps_k == max(1, live.sum(axis=1).max())
    assert plan.steps_q == max(1, live.sum(axis=0).max())
    seen = np.zeros_like(live)
    for qi in range(plan.n_q):  # flash_fwd, flash_bwd_dq
        swept = [int(plan.key_of_step(qi, j)) for j in range(plan.steps_k)]
        for ki in swept:
            if plan.live(qi, ki):
                assert ki < plan.n_k and not seen[qi, ki]
                seen[qi, ki] = True
        fetched = [int(plan.fetch_k(qi, ki)) for ki in swept]
        assert fetched == sorted(fetched)
        assert set(fetched) <= (set(np.flatnonzero(live[qi]))
                                or set(fetched))
    np.testing.assert_array_equal(seen, live)
    seen = np.zeros_like(live)
    for ki in range(plan.n_k):  # flash_bwd_dkv
        swept = [int(plan.query_of_step(ki, j)) for j in range(plan.steps_q)]
        for qi in swept:
            if plan.live(qi, ki):
                assert qi < plan.n_q and not seen[qi, ki]
                seen[qi, ki] = True
        fetched = [int(plan.fetch_q(qi, ki)) for qi in swept]
        assert fetched == sorted(fetched)
        assert set(fetched) <= (set(np.flatnonzero(live[:, ki]))
                                or set(fetched))
    np.testing.assert_array_equal(seen, live)
    assert plan.counts()["dead_steps"] == (plan.n_q * plan.steps_k
                                           - int(live.sum()))


def test_without_a_window_a_sweep_is_every_block():
    plan = fa.TilePlan(4096, 4096, 1024, 1024, True)
    assert (plan.steps_k, plan.steps_q) == (4, 4)
    assert plan.key_of_step(3, 2) == 2 and plan.query_of_step(1, 3) == 3
    assert plan.counts()["dead_steps"] == plan.counts()["dead"] == 6


def test_the_plan_answers_traced_indices_like_numbers():
    plan = fa.TilePlan(1536, 2048, 512, 512, True, window=1024)
    assert plan.splits_edge
    qi, ki = np.meshgrid(np.arange(plan.n_q), np.arange(plan.n_k),
                         indexing="ij")

    def answers(qi, ki):
        return (plan.live(qi, ki), plan.fetch_k(qi, ki),
                plan.fetch_q(qi, ki), plan.diagonal(qi, ki),
                plan.edge(qi, ki), plan.whole(qi, ki),
                plan.key_of_step(qi, ki), plan.query_of_step(ki, qi))

    for got, want in zip(jax.jit(answers)(jnp.asarray(qi), jnp.asarray(ki)),
                         answers(qi, ki)):
        np.testing.assert_array_equal(
            np.asarray(got), np.broadcast_to(want, qi.shape))


def test_the_flight_event_carries_the_window_and_the_edge(interpreted):
    before = get_flight_recorder()
    flight = set_flight_recorder(FlightRecorder())
    try:
        q = jax.ShapeDtypeStruct((1, 2, 2048, 16), jnp.float32)
        for window in (1024, None, 4096):
            jax.eval_shape(lambda q, k, v: fa.flash_attention(
                q, k, v, causal=True, window=window, block_q=512,
                block_k=512), q, q, q)
    finally:
        set_flight_recorder(before)
    windowed, bare, longer = [
        e["data"] for e in flight.events(kinds=["kernel.flash_plan"])]
    assert windowed["window"] == 1024
    assert bare["window"] is None and longer["window"] is None
    assert bare["fwd"] == longer["fwd"]
    for name in ("fwd", "dkv", "dq"):
        assert windowed[name]["edge"] == 2 and bare[name]["edge"] == 0
        assert windowed[name]["dead"] == 6 + 1 and bare[name]["dead"] == 6
