"""Pallas backward-kernel gradient parity vs the XLA reference.

ref pattern: oracle testing + central-difference gradcheck (SURVEY §4).
The kernels run in interpret mode on CPU (DL4J_TPU_FORCE_PALLAS=1); the
oracle is jax.grad through the O(T²) XLA reference implementation.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.kernels import flash_attention as fa
from deeplearning4j_tpu.kernels._dispatch import FlashBlocks
from deeplearning4j_tpu.kernels.flash_attention import (
    flash_attention,
    reference_attention,
)


@pytest.fixture(autouse=True)
def _force_pallas(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_FORCE_PALLAS", "1")


def _qkv(seed, b=2, h=2, t=32, s=None, d=16):
    s = t if s is None else s
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (b, h, t, d))
    k = jax.random.normal(ks[1], (b, h, s, d))
    v = jax.random.normal(ks[2], (b, h, s, d))
    return q, k, v


def _grads(fn, q, k, v, first_row=0):
    # Scalar loss with a fixed weighting so every output element matters
    # (from query row ``first_row`` on: the rows before it see no key, and
    # there the reference attends uniformly where the kernel writes 0).
    w = jnp.cos(jnp.arange(q.shape[0] * q.shape[1] * q.shape[2] * v.shape[-1],
                           dtype=jnp.float32)).reshape(
        q.shape[0], q.shape[1], q.shape[2], v.shape[-1])
    w = w * (jnp.arange(q.shape[2]) >= first_row)[:, None]

    def loss(q, k, v):
        return jnp.sum(fn(q, k, v) * w)

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _assert_grads_close(got, want, atol=5e-4):
    for g, w, name in zip(got, want, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=atol, rtol=1e-3, err_msg=name)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bwd_matches_reference(causal):
    q, k, v = _qkv(0)
    got = _grads(functools.partial(flash_attention, causal=causal), q, k, v)
    want = _grads(functools.partial(reference_attention, causal=causal),
                  q, k, v)
    _assert_grads_close(got, want)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bwd_key_mask(causal):
    q, k, v = _qkv(1)
    mask = jnp.ones((q.shape[0], k.shape[2])).at[:, 20:].set(0.0)
    got = _grads(
        functools.partial(flash_attention, causal=causal, key_mask=mask),
        q, k, v)
    want = _grads(
        functools.partial(reference_attention, causal=causal, key_mask=mask),
        q, k, v)
    # Fully-masked reference rows softmax uniformly (flash outputs 0), so
    # compare only grads flowing from valid positions: both paths zero
    # key-masked columns' dk/dv identically and dq rows match everywhere
    # queries can see ≥1 key, which is all rows here (keys 0:20 visible).
    _assert_grads_close(got, want)


def test_flash_bwd_unpadded_multi_block():
    # Sequence spanning several kv blocks with tail padding inside a block.
    q, k, v = _qkv(2, b=1, h=2, t=200, d=32)
    got = _grads(
        functools.partial(flash_attention, block_q=64, block_k=128), q, k, v)
    want = _grads(reference_attention, q, k, v)
    _assert_grads_close(got, want)


def test_flash_bwd_cross_attention_shapes():
    # seq_q != seq_k exercises the offset in the causal/bounds index math.
    q, k, v = _qkv(3, t=24, s=40)
    got = _grads(flash_attention, q, k, v)
    want = _grads(reference_attention, q, k, v)
    _assert_grads_close(got, want)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_single_kv_iteration_block(causal):
    """block_k == seq collapses the sequential kv sweep to ONE grid step,
    so _init (ki==0) and _finish (ki==n_k-1) fire on the same iteration —
    the edge path the r5 wide-block sweep geometries (bk=T) rely on."""
    q, k, v = _qkv(4, t=128, d=16)
    fn = functools.partial(flash_attention, causal=causal,
                           block_q=64, block_k=128)
    ref = functools.partial(reference_attention, causal=causal)
    out = fn(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(q, k, v)),
                               atol=5e-5, rtol=1e-4)
    _assert_grads_close(_grads(fn, q, k, v), _grads(ref, q, k, v))


# name: (t, s, d, causal, block, (dead, live, split) tiles of the plan,
# first row that sees a key, key mask or not). Blocks of 32 x 32 make a
# 4 x 4 grid of T = S = 128: 6 tiles above the diagonal, 4 on it, 6 under
# it; a block that small is computed whole. A block of two sub-blocks or
# more (``_B``) is split where the diagonal runs corner to corner.
_B = 2 * fa._SUB_BLOCK
_TILE_CASES = {
    "d64_dead_interior_diagonal": (128, 128, 64, True, 32, (6, 10, 0), 0),
    "d40_no_multiple_of_64": (128, 128, 40, True, 32, (6, 10, 0), 0),
    "ragged_queries_and_keys_causal": (100, 100, 16, True, 32, (6, 10, 0),
                                       0),
    "ragged_not_causal": (100, 72, 16, False, 32, (0, 12, 0), 0),
    "offset_more_keys_than_queries": (64, 128, 16, True, 32, (1, 7, 0), 0),
    "offset_fewer_keys_rows_without_a_key": (128, 64, 16, True, 32,
                                             (5, 3, 0), 64),
    "whole_blocks_not_causal": (64, 96, 16, False, 32, (0, 6, 0), 0),
    # the forward's lane-broadcast running state against more keys, and a
    # wider head, than its 128 lanes
    "d256_wider_than_the_state": (64, 64, 256, True, 32, (1, 3, 0), 0),
    "keys_192_no_multiple_of_the_state": (192, 192, 16, True, 192,
                                          (0, 1, 0), 0),
    "split_one_tile": (_B, _B, 16, True, _B, (0, 1, 1), 0),
    "split_one_tile_four_sub_blocks": (2 * _B, 2 * _B, 8, True, 2 * _B,
                                       (0, 1, 1), 0),
    "split_3_by_3_grid": (3 * _B, 3 * _B, 8, True, _B, (3, 6, 3), 0),
    "split_3_by_3_grid_key_mask": (3 * _B, 3 * _B, 8, True, _B, (3, 6, 3),
                                   0, True),
    "split_one_tile_key_mask": (_B, _B, 16, True, _B, (0, 1, 1), 0, True),
    "split_padded_queries_and_keys": (2 * _B - 24, 2 * _B - 24, 8, True, _B,
                                      (1, 3, 2), 0),
    "split_padded_key_mask": (2 * _B - 24, 2 * _B - 24, 8, True, _B,
                              (1, 3, 2), 0, True),
    "split_offset_of_one_tile": (_B, 2 * _B, 8, True, _B, (0, 2, 1), 0),
    "split_fewer_keys_by_one_tile": (2 * _B, _B, 8, True, _B, (1, 1, 1),
                                     _B),
    "cross_shaped_offset_meets_no_edge": (_B, _B + _B // 2, 8, True, _B,
                                          (0, 2, 0), 0),
    "unequal_blocks_computed_whole": (_B, _B, 8, True, (_B // 2, _B),
                                      (0, 2, 0), 0),
}


@pytest.mark.parametrize("case", sorted(_TILE_CASES))
def test_flash_every_tile_kind_matches_reference(case):
    """Forward and all three gradients where one call holds dead tiles,
    tiles under the diagonal and tiles the diagonal crosses (computed whole
    or split into row sub-blocks), at head widths that are never padded."""
    (t, s, d, causal, block, (dead, live, split), first_row,
     *masked) = _TILE_CASES[case]
    block_q, block_k = block if isinstance(block, tuple) else (block, block)
    counts = fa.TilePlan(t, s, block_q, block_k, causal).counts()
    assert (counts["dead"], counts["live"], counts["diagonal"]) == (
        dead, live, split)
    q, k, v = _qkv(5, b=1, t=t, s=s, d=d)
    key_mask = None
    if masked:  # a padded tail, and a hole inside the first sub-block
        # (keys 0-2 stay live, so every row still sees a key)
        key_mask = jnp.ones((1, s)).at[:, s - 37:].set(0.0).at[:, 3:9].set(0.0)
    fn = functools.partial(flash_attention, causal=causal, key_mask=key_mask,
                           block_q=block_q, block_k=block_k)
    ref = functools.partial(reference_attention, causal=causal,
                            key_mask=key_mask)
    got = fn(q, k, v)
    np.testing.assert_allclose(np.asarray(got)[:, :, first_row:],
                               np.asarray(ref(q, k, v))[:, :, first_row:],
                               atol=5e-5, rtol=1e-4)
    assert not np.asarray(got)[:, :, :first_row].any()
    _assert_grads_close(_grads(fn, q, k, v, first_row),
                        _grads(ref, q, k, v, first_row))


def test_flash_kernels_at_three_geometries():
    """Each kernel at its own blocks, none dividing T: the residuals are
    re-padded per kernel."""
    q, k, v = _qkv(6, b=1, t=100, d=16)
    blocks = FlashBlocks(fwd=(32, 64), dkv=(64, 32), dq=(16, 48))

    def fn(q, k, v):
        return fa._flash(q, k, v, None, True, 0.25, blocks, None)

    ref = functools.partial(reference_attention, causal=True)
    np.testing.assert_allclose(np.asarray(fn(q, k, v)),
                               np.asarray(ref(q, k, v)),
                               atol=5e-5, rtol=1e-4)
    _assert_grads_close(_grads(fn, q, k, v), _grads(ref, q, k, v))


class TestLstmBackward:
    """Pallas LSTM fwd+bwd vs the XLA lax.scan reference (ops/rnn.py).

    Shapes must tile (N % 8 == 0, H % 128 == 0) to take the kernel path.
    """

    N, T, I, H = 8, 5, 16, 128

    def _weights(self, seed):
        ks = jax.random.split(jax.random.key(seed), 5)
        sc = 0.1
        x = jax.random.normal(ks[0], (self.N, self.T, self.I))
        w_x = jax.random.normal(ks[1], (self.I, 4 * self.H)) * sc
        w_h = jax.random.normal(ks[2], (self.H, 4 * self.H)) * sc
        b = jax.random.normal(ks[3], (4 * self.H,)) * sc
        peep = jax.random.normal(ks[4], (3, self.H)) * sc
        return x, w_x, w_h, b, peep

    def _compare(self, seed, use_peep, forget_bias=0.0):
        from deeplearning4j_tpu.kernels import lstm_scan
        from deeplearning4j_tpu.ops import rnn as opsrnn

        x, w_x, w_h, b, peep = self._weights(seed)
        peep_t = tuple(peep) if use_peep else None

        def loss(fn, x, w_x, w_h, b, peep):
            peeps = tuple(peep) if use_peep else None
            out, final = fn(x, w_x, w_h, b, peepholes=peeps,
                            forget_bias=forget_bias)
            return (jnp.sum(out * jnp.cos(jnp.arange(out.size, dtype=jnp.float32)).reshape(out.shape))
                    + 2.0 * jnp.sum(final.h) + 3.0 * jnp.sum(final.c))

        args = (x, w_x, w_h, b, peep)
        got_out, _ = lstm_scan.lstm(x, w_x, w_h, b, peepholes=peep_t,
                                    forget_bias=forget_bias)
        want_out, _ = opsrnn.lstm(x, w_x, w_h, b, peepholes=peep_t,
                                  forget_bias=forget_bias)
        np.testing.assert_allclose(np.asarray(got_out), np.asarray(want_out),
                                   atol=1e-5, rtol=1e-4)

        got = jax.grad(functools.partial(loss, lstm_scan.lstm),
                       argnums=(0, 1, 2, 3, 4))(*args)
        want = jax.grad(functools.partial(loss, opsrnn.lstm),
                        argnums=(0, 1, 2, 3, 4))(*args)
        names = ("dx", "dw_x", "dw_h", "db", "dpeep")
        for g, w, name in zip(got, want, names):
            if name == "dpeep" and not use_peep:
                continue
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=2e-4, rtol=1e-3, err_msg=name)

    def test_kernel_path_taken(self, monkeypatch):
        # Guard against the comparison silently degenerating into
        # reference-vs-reference via the shape/dispatch fallback.
        from deeplearning4j_tpu.kernels import lstm_scan

        called = []
        orig = lstm_scan.opsrnn.lstm
        monkeypatch.setattr(
            lstm_scan.opsrnn, "lstm",
            lambda *a, **k: (called.append(1), orig(*a, **k))[1],
        )
        x, w_x, w_h, b, _ = self._weights(0)
        out, _ = lstm_scan.lstm(x, w_x, w_h, b)
        jax.block_until_ready(out)
        assert not called, "tiled shapes should take the Pallas path"

    def test_bwd_no_peepholes(self):
        self._compare(0, use_peep=False)

    def test_bwd_peepholes_graves(self):
        self._compare(1, use_peep=True)

    def test_bwd_forget_bias(self):
        self._compare(2, use_peep=False, forget_bias=1.0)


@pytest.mark.parametrize("d,causal", [(16, False), (64, True)])
def test_flash_bwd_bf16_finite(d, causal):
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(4, t=64, d=d))
    dq, dk, dv = _grads(functools.partial(
        flash_attention, causal=causal, block_q=32, block_k=32), q, k, v)
    for g in (dq, dk, dv):
        assert g.dtype == jnp.bfloat16
        assert bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))


class TestBf16KernelPath:
    """The bench's headline BERT config runs bf16 mixed precision: the
    Pallas kernels must accept bf16 q/k/v (fp32 internally, bf16 out)."""

    def test_flash_bf16_fwd_bwd(self):
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.kernels.flash_attention import (
            flash_attention,
            reference_attention,
        )

        r = np.random.default_rng(0)
        q, k, v = (jnp.asarray(r.normal(size=(2, 2, 16, 8)), jnp.bfloat16)
                   for _ in range(3))
        km = jnp.ones((2, 16), jnp.bfloat16)

        def loss(q, k, v):
            out = flash_attention(q, k, v, key_mask=km, block_q=8, block_k=8)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        val, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        ref = jnp.sum(reference_attention(q, k, v, key_mask=km)
                      .astype(jnp.float32) ** 2)
        assert float(val) == pytest.approx(float(ref), rel=0.05)
        for g in grads:
            assert g.dtype == jnp.bfloat16
            assert np.isfinite(np.asarray(g, np.float32)).all()


class TestGruBackward:
    """Pallas GRU fwd+bwd vs the XLA lax.scan reference (ops/rnn.gru).

    Same harness as TestLstmBackward; shapes tile (N % 8, H % 128) so the
    kernel path is taken (guarded by test_kernel_path_taken).
    """

    N, T, I, H = 8, 5, 16, 128

    def _weights(self, seed):
        ks = jax.random.split(jax.random.key(seed), 4)
        sc = 0.1
        x = jax.random.normal(ks[0], (self.N, self.T, self.I))
        w_x = jax.random.normal(ks[1], (self.I, 3 * self.H)) * sc
        w_h = jax.random.normal(ks[2], (self.H, 3 * self.H)) * sc
        b = jax.random.normal(ks[3], (3 * self.H,)) * sc
        return x, w_x, w_h, b

    def _compare(self, seed, use_bias=True):
        from deeplearning4j_tpu.kernels import gru_scan
        from deeplearning4j_tpu.ops import rnn as opsrnn

        x, w_x, w_h, b = self._weights(seed)
        bb = b if use_bias else None

        def loss(fn, x, w_x, w_h, b):
            out, final = fn(x, w_x, w_h, b if use_bias else None)
            return (jnp.sum(out * jnp.cos(jnp.arange(
                out.size, dtype=jnp.float32)).reshape(out.shape))
                + 2.0 * jnp.sum(final))

        got_out, got_h = gru_scan.gru(x, w_x, w_h, bb)
        want_out, want_h = opsrnn.gru(x, w_x, w_h, bb)
        np.testing.assert_allclose(np.asarray(got_out), np.asarray(want_out),
                                   atol=1e-5, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h),
                                   atol=1e-5, rtol=1e-4)

        args = (x, w_x, w_h, b)
        got = jax.grad(functools.partial(loss, gru_scan.gru),
                       argnums=(0, 1, 2, 3))(*args)
        want = jax.grad(functools.partial(loss, opsrnn.gru),
                        argnums=(0, 1, 2, 3))(*args)
        for g, w, name in zip(got, want, ("dx", "dw_x", "dw_h", "db")):
            if name == "db" and not use_bias:
                continue
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=2e-4, rtol=1e-3, err_msg=name)

    def test_kernel_path_taken(self, monkeypatch):
        from deeplearning4j_tpu.kernels import gru_scan

        called = []
        orig = gru_scan.opsrnn.gru
        monkeypatch.setattr(
            gru_scan.opsrnn, "gru",
            lambda *a, **k: (called.append(1), orig(*a, **k))[1],
        )
        x, w_x, w_h, b = self._weights(0)
        out, _ = gru_scan.gru(x, w_x, w_h, b)
        jax.block_until_ready(out)
        assert not called, "tiled shapes should take the Pallas path"

    def test_fwd_bwd_with_bias(self):
        self._compare(0, use_bias=True)

    def test_fwd_bwd_no_bias(self):
        self._compare(1, use_bias=False)

    def test_fallback_untiled_shapes(self):
        # H=64 doesn't tile; must transparently take the XLA reference.
        from deeplearning4j_tpu.kernels import gru_scan

        ks = jax.random.split(jax.random.key(2), 4)
        x = jax.random.normal(ks[0], (4, 3, 8))
        w_x = jax.random.normal(ks[1], (8, 192)) * 0.1
        w_h = jax.random.normal(ks[2], (64, 192)) * 0.1
        b = jax.random.normal(ks[3], (192,)) * 0.1
        out, h = gru_scan.gru(x, w_x, w_h, b)
        from deeplearning4j_tpu.ops import rnn as opsrnn

        want, want_h = opsrnn.gru(x, w_x, w_h, b)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=1e-6)


def test_gru_layer_pallas_backend(monkeypatch):
    """GRU(backend='pallas') layer output matches backend='xla'."""
    monkeypatch.setenv("DL4J_TPU_FORCE_PALLAS", "1")
    from deeplearning4j_tpu.nn.layers import GRU

    x = jax.random.normal(jax.random.key(0), (8, 6, 16))
    lp = GRU(units=128, backend="pallas")
    lx = GRU(units=128, backend="xla")
    params, _ = lp.init(jax.random.key(1), (6, 16), jnp.float32)
    yp, _ = lp.apply(params, {}, x)
    yx, _ = lx.apply(params, {}, x)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yx),
                               atol=1e-5, rtol=1e-4)
