"""The three flash kernels with a value narrower than the query and key
(latent attention's 192-wide q and k over 128-wide v, here tiny: 48 over
32), interpreted on the CPU, against ``reference_attention``: the forward
and all three gradients on every path a call can take (causal whole and in
split tiles, a window, a pair mask, a key mask, a mesh), and what the
geometry and the flight event say of such a call."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.kernels import flash_attention as fa
from deeplearning4j_tpu.kernels._dispatch import flash_block_sizes, kernel_mesh
from deeplearning4j_tpu.observability.flightrecorder import (
    FlightRecorder,
    get_flight_recorder,
    set_flight_recorder,
)

QK, V = 48, 32


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_FORCE_PALLAS", "1")


def inputs(b, h, t, s, seed=0, d=QK, dv=V):
    kq, kk, kv, kg = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(kq, (b, h, t, d)),
            jax.random.normal(kk, (b, h, s, d)),
            jax.random.normal(kv, (b, h, s, dv)),
            jax.random.normal(kg, (b, h, t, dv)))


def both(q, k, v, g, *, ref_kw=None, **kw):
    """Output and the three gradients of the kernels and of the reference,
    both called with ``kw`` (the reference without the geometry)."""
    def run(fn):
        out, pull = jax.vjp(fn, q, k, v)
        return (out,) + pull(g)

    if ref_kw is None:
        ref_kw = {k_: v_ for k_, v_ in kw.items()
                  if k_ not in ("block_q", "block_k")}
    got = run(lambda q, k, v: fa.flash_attention(q, k, v, backend="pallas",
                                                 **kw))
    want = run(lambda q, k, v: fa.reference_attention(q, k, v, **ref_kw))
    return got, want


def assert_close(got, want, rtol=2e-4, atol=2e-5):
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=rtol,
                                   atol=atol, err_msg=name)


def test_the_reference_takes_the_scale_from_q_and_returns_vs_width():
    q, k, v, _ = inputs(1, 2, 12, 12)
    got = fa.reference_attention(q, k, v, causal=True)
    s = jnp.einsum("bhtd,bhsd->bhts", q, k) * QK ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((12, 12), bool)), s, -1e30)
    want = jnp.einsum("bhts,bhsd->bhtd", jax.nn.softmax(s, axis=-1), v)
    assert got.shape == (1, 2, 12, V)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-6)


# tiles of 128 x 128 over 512 positions (whole tiles, several along each
# axis) and of 512 x 512 over 1024 (the diagonal tiles in two row
# sub-blocks of 256)
@pytest.mark.parametrize("t,block", [(512, 128), (1024, 512)])
def test_causal_forward_and_gradients(interpreted, t, block):
    q, k, v, g = inputs(1, 2, t, t, seed=t)
    got, want = both(q, k, v, g, causal=True, block_q=block, block_k=block)
    assert_close(got, want)


def test_latent_widths_at_the_default_tile(interpreted):
    """The cell's widths, 192 over 128, at the geometry the rule gives them:
    2,048 positions in 1024 x 1024 tiles, the two diagonal tiles in four
    row sub-blocks of 256."""
    plan = fa.forward_plan(2048, 2048, 192, causal=True)
    assert (plan.block_q, plan.block_k, plan.sub_blocks) == (1024, 1024, 4)
    q, k, v, g = inputs(1, 1, 2048, 2048, seed=40, d=192, dv=128)
    got, want = both(q, k, v, g, causal=True)
    assert_close(got, want)


@pytest.mark.parametrize("window", [100, 512])
def test_a_window(interpreted, window):
    """A window of no whole block, and one whose far edge is split."""
    q, k, v, g = inputs(1, 2, 1536, 1536, seed=window)
    got, want = both(q, k, v, g, causal=True, window=window, block_q=512,
                     block_k=512)
    assert_close(got, want)


def test_a_pair_mask(interpreted):
    q, k, v, g = inputs(2, 2, 256, 256, seed=11)
    rng = np.random.default_rng(4)
    pairs = rng.random((2, 256, 256)) < 0.3
    pairs[:, np.arange(256), np.arange(256)] = True  # a live key a row
    pairs = jnp.asarray(pairs, jnp.int8)
    got, want = both(q, k, v, g, causal=True, pair_mask=pairs, block_q=128,
                     block_k=128)
    assert_close(got, want)


def test_a_key_mask_without_causality_and_ragged_lengths(interpreted):
    """100 queries against 300 keys (padded to whole tiles), padded keys
    masked."""
    q, k, v, g = inputs(2, 2, 100, 300, seed=5)
    key_mask = np.ones((2, 300), np.float32)
    key_mask[1, 250:] = 0
    got, want = both(q, k, v, g, key_mask=jnp.asarray(key_mask), block_q=64,
                     block_k=128)
    assert_close(got, want)


def test_under_a_mesh(interpreted):
    """Batch over the data axis and heads over the model axis, inside
    ``shard_map``: the output spec is q's and v's at once."""
    from deeplearning4j_tpu.runtime.device import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(data=-1, model=2), devices_=jax.devices()[:4])
    q, k, v, g = inputs(4, 4, 32, 32, seed=2)

    def kernel(q, k, v):
        with kernel_mesh(mesh):
            return jnp.sum(fa.flash_attention(q, k, v, causal=True) * g)

    def ref(q, k, v):
        return jnp.sum(fa.reference_attention(q, k, v, causal=True) * g)

    sh = NamedSharding(mesh, P("data", "model", None, None))
    got = jax.jit(jax.value_and_grad(kernel, argnums=(0, 1, 2)),
                  in_shardings=(sh, sh, sh))(q, k, v)
    want = jax.value_and_grad(ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3,
                                   atol=5e-4)


def test_the_widest_operand_sets_the_tile(interpreted):
    for d in (64, 128, 192):
        assert set(flash_block_sizes(8192, 8192, d, True)) == {(1024, 1024)}
    assert set(flash_block_sizes(8192, 8192, 256, True)) == {(512, 512)}
    # a call keys the rule on its widest operand, q and k's or v's
    before = get_flight_recorder()
    flight = set_flight_recorder(FlightRecorder())
    try:
        for d, dv in ((192, 128), (128, 192), (128, 64), (256, 128),
                      (128, 256)):
            q = jax.ShapeDtypeStruct((1, 1, 4096, d), jnp.bfloat16)
            v = jax.ShapeDtypeStruct((1, 1, 4096, dv), jnp.bfloat16)
            jax.eval_shape(lambda q, k, v: fa.flash_attention(
                q, k, v, causal=True), q, q, v)
    finally:
        set_flight_recorder(before)
    assert [e["data"]["fwd"]["block_q"] for e in flight.events(
        kinds=["kernel.flash_plan"])] == [1024, 1024, 1024, 512, 512]


def test_the_flight_event_names_both_widths(interpreted):
    before = get_flight_recorder()
    flight = set_flight_recorder(FlightRecorder())
    try:
        q = jax.ShapeDtypeStruct((1, 2, 2048, QK), jnp.float32)
        v = jax.ShapeDtypeStruct((1, 2, 2048, V), jnp.float32)
        for value in (v, q):
            jax.eval_shape(lambda q, k, v: fa.flash_attention(
                q, k, v, causal=True), q, q, value)
    finally:
        set_flight_recorder(before)
    narrower, equal = [
        e["data"] for e in flight.events(kinds=["kernel.flash_plan"])]
    assert (narrower["head_dim"], narrower["head_dim_v"]) == (QK, V)
    assert (equal["head_dim"], equal["head_dim_v"]) == (QK, QK)


def test_the_cells_call_lowers_for_the_chip_with_each_operand_at_its_width(
        as_on_tpu):
    """``kanana2_30b_a3b``'s call, [2, 32, 8192] at 192 over 128, forward
    and backward: three kernels, the output and dv at 128, dq and dk at
    192, nothing padded to the other width."""
    q = jax.ShapeDtypeStruct((2, 32, 8192, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((2, 32, 8192, 128), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True).astype(
            jnp.float32))

    out = jax.eval_shape(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True), q, q, v)
    assert out.shape == (2, 32, 8192, 128)
    grads = jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)), q, q, v)
    assert [g.shape[-1] for g in grads] == [192, 192, 128]
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
        q, q, v).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 3
    assert "x8192x160x" not in text and "x8192x256x" not in text


def test_the_plans_a_caller_asks_for_take_both_widths(monkeypatch):
    """``forward_plan`` and ``pairs_of_call`` at the cell's call, keyed on
    its widest operand (192): 1024 x 1024 tiles as at 128, and 512 x 512
    at 256. The tile changes no work: both geometries touch the same
    pairs."""
    monkeypatch.setattr(fa, "_use_pallas", lambda: True)
    plan = fa.forward_plan(8192, 8192, 192, causal=True)
    assert (plan.block_q, plan.block_k) == (1024, 1024)
    assert plan.sub_blocks == 4
    assert fa.forward_plan(8192, 8192, 128, causal=True).block_q == 1024
    assert fa.forward_plan(8192, 8192, 256, causal=True).block_q == 512
    required, touched = fa.pairs_of_call(8192, 8192, 192, causal=True)
    assert required == 8192 * 8193 // 2
    # 36 live tiles of 1024, 8 of them diagonal in four sub-blocks of 256
    assert touched == (28 + 8 * 10 / 16) * 1024 * 1024 == 34_603_008
    # 136 live tiles of 512, 16 of them diagonal in two sub-blocks of 256
    assert fa.pairs_of_call(8192, 8192, 256, causal=True) == (
        required, (120 + 16 * 0.75) * 512 * 512)
