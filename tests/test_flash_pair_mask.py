"""The three flash kernels with a per-pair mask ([batch, T, S] int8, one
for all heads of a sequence), interpreted on the CPU, against
``reference_attention`` with the same mask: the forward and all three
gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.kernels import flash_attention as fa


@pytest.fixture(autouse=True)
def interpreted(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_FORCE_PALLAS", "1")


def inputs(b, h, t, s, d, seed=0):
    kq, kk, kv, kg = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(kq, (b, h, t, d)),
            jax.random.normal(kk, (b, h, s, d)),
            jax.random.normal(kv, (b, h, s, d)),
            jax.random.normal(kg, (b, h, t, d)))


def selected(b, t, keep, seed=1):
    """A causal mask of pairs in which each query keeps ``keep`` keys of
    its past drawn at random (all of it where it is no longer), and row 5
    only itself."""
    rng = np.random.default_rng(seed)
    mask = np.zeros((b, t, t), np.int8)
    for n in range(b):
        for q in range(t):
            past = np.arange(q + 1)
            mask[n, q, rng.permutation(past)[:keep]] = 1
    mask[:, 5] = 0
    mask[:, 5, 5] = 1
    return jnp.asarray(mask)


def both(q, k, v, g, mask, **kw):
    def run(fn):
        out, pull = jax.vjp(lambda q, k, v: fn(q, k, v), q, k, v)
        return (out,) + pull(g)

    got = run(lambda q, k, v: fa.flash_attention(
        q, k, v, pair_mask=mask, backend="pallas", **kw))
    kw.pop("block_q", None), kw.pop("block_k", None)
    want = run(lambda q, k, v: fa.reference_attention(
        q, k, v, pair_mask=mask, **kw))
    return got, want


@pytest.mark.parametrize("blocks", [(32, 128), (64, 128), (128, 128)])
def test_causal_with_a_pair_mask_over_several_tiles(blocks):
    """T = 256 in tiles of 32 to 128 queries by 128 keys: whole tiles of a
    query row with no selected key at all come before the tile that holds
    one (the running maximum stays at its floor meanwhile), and row 5
    attends to itself alone."""
    q, k, v, g = inputs(2, 3, 256, 256, 16)
    mask = selected(2, 256, 9)
    got, want = both(q, k, v, g, mask, causal=True, block_q=blocks[0],
                     block_k=blocks[1])
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5, err_msg=name)
    # row 5's output is its own value, whatever its score
    np.testing.assert_allclose(np.asarray(got[0][:, :, 5]),
                               np.asarray(v[:, :, 5]), rtol=1e-5, atol=1e-6)


def test_a_diagonal_tile_in_sub_blocks_reads_its_rows_of_the_mask():
    """One 512 x 512 tile, causal: swept in two row sub-blocks of 256, each
    against the key prefix it can see and its own rows of the mask."""
    plan = fa.TilePlan(512, 512, 512, 512, True, True)
    assert plan.sub_blocks == 2 and plan.pair_mask
    q, k, v, g = inputs(1, 2, 512, 512, 8, seed=3)
    mask = selected(1, 512, 40, seed=4)
    got, want = both(q, k, v, g, mask, causal=True)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5, err_msg=name)


def test_ragged_lengths_a_key_mask_and_no_causal_term():
    """T = 100 queries against S = 200 keys (padded to whole tiles, the
    padding selecting nothing), a key mask beside the pair mask, not
    causal; every row keeps at least one key."""
    q, k, v, g = inputs(2, 2, 100, 200, 16, seed=5)
    rng = np.random.default_rng(6)
    mask = (rng.random((2, 100, 200)) < 0.1).astype(np.int8)
    mask[:, :, 7] = 1
    key_mask = np.ones((2, 200), np.float32)
    key_mask[1, 150:] = 0
    got, want = both(q, k, v, g, jnp.asarray(mask), causal=False,
                     key_mask=jnp.asarray(key_mask), block_q=64, block_k=128)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5, err_msg=name)


def test_the_mask_gets_no_gradient_and_the_plan_says_it_is_there():
    from deeplearning4j_tpu.observability.flightrecorder import (
        FlightRecorder,
        get_flight_recorder,
        set_flight_recorder,
    )

    q, k, v, _ = inputs(1, 2, 128, 128, 8)
    mask = selected(1, 128, 6)
    before = get_flight_recorder()
    flight = set_flight_recorder(FlightRecorder())
    try:
        grads = jax.grad(
            lambda q, k, v, m: jnp.sum(fa.flash_attention(
                q, k, v, causal=True, pair_mask=m, backend="pallas")),
            argnums=(0, 3), allow_int=True)(q, k, v, mask)
        fa.flash_attention(q, k, v, causal=True, backend="pallas")
    finally:
        set_flight_recorder(before)
    assert grads[1].dtype == jax.dtypes.float0
    with_mask, without = flight.events(kinds=["kernel.flash_plan"])
    assert with_mask["data"]["pair_mask"] is True
    assert without["data"]["pair_mask"] is False
    assert with_mask["data"]["fwd"] == without["data"]["fwd"]  # same tiles
