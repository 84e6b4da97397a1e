"""Gradient-compression codec tests (↔ libnd4j encode/decode_threshold +
encode/decode_bitmap oracle behavior, incl. the residual rule)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops.compression import (
    bitmap_decode,
    bitmap_encode,
    threshold_decode,
    threshold_encode,
)


def _grad(shape=(33, 7), seed=0, scale=1.0):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=shape).astype(np.float32)
        * scale)


class TestThresholdCodec:
    def test_roundtrip_plus_residual_is_identity(self):
        g = _grad()
        enc, residual = threshold_encode(g, 0.5, max_elements=64)
        dec = threshold_decode(enc, g.shape)
        np.testing.assert_allclose(np.asarray(dec + residual), np.asarray(g),
                                   rtol=1e-6, atol=1e-6)

    def test_only_above_threshold_transmitted(self):
        g = _grad()
        enc, _ = threshold_encode(g, 0.5, max_elements=512)
        dec = np.asarray(threshold_decode(enc, g.shape)).reshape(-1)
        gn = np.asarray(g).reshape(-1)
        below = np.abs(gn) < 0.5
        assert np.all(dec[below] == 0)
        above = np.abs(gn) >= 0.5
        np.testing.assert_allclose(dec[above], np.sign(gn[above]) * 0.5)
        assert int(enc.count) == int(above.sum())

    def test_overflow_keeps_largest_and_residual_covers_rest(self):
        g = _grad(scale=2.0)
        enc, residual = threshold_encode(g, 0.1, max_elements=8)
        assert int(enc.count) == 8
        dec = threshold_decode(enc, g.shape)
        np.testing.assert_allclose(np.asarray(dec + residual), np.asarray(g),
                                   rtol=1e-6, atol=1e-6)
        # the 8 slots hold the 8 largest magnitudes
        sent_idx = set(int(i) for i in np.asarray(enc.indices) if i >= 0)
        top8 = set(np.argsort(-np.abs(np.asarray(g).reshape(-1)))[:8].tolist())
        assert sent_idx == top8

    def test_jit_compatible(self):
        g = _grad()
        f = jax.jit(lambda g: threshold_encode(g, 0.5, 32))
        enc, res = f(g)
        assert enc.indices.shape == (32,)

    def test_residual_accumulation_converges(self):
        """Strom-style: repeatedly sending encode(residual+grad) eventually
        delivers the full gradient (no information lost)."""
        g = _grad(seed=3)
        delivered = jnp.zeros_like(g)
        residual = jnp.zeros_like(g)
        for _ in range(40):
            enc, residual = threshold_encode(residual + g, 0.3,
                                             max_elements=32)
            delivered = delivered + threshold_decode(enc, g.shape)
        # delivered approaches sum of 40 gradient copies
        np.testing.assert_allclose(np.asarray(delivered + residual),
                                   np.asarray(g * 40), rtol=1e-4, atol=1e-4)


class TestBitmapCodec:
    def test_roundtrip_plus_residual_is_identity(self):
        g = _grad(shape=(25,))  # non-multiple of 16
        packed, residual = bitmap_encode(g, 0.4)
        assert packed.shape == (2,)  # ceil(25/16)
        dec = bitmap_decode(packed, 0.4, g.shape)
        np.testing.assert_allclose(np.asarray(dec + residual), np.asarray(g),
                                   rtol=1e-6, atol=1e-6)

    def test_codes(self):
        g = jnp.asarray([0.5, -0.5, 0.1, 0.0], jnp.float32)
        packed, _ = bitmap_encode(g, 0.4)
        dec = np.asarray(bitmap_decode(packed, 0.4, (4,)))
        np.testing.assert_allclose(dec, [0.4, -0.4, 0.0, 0.0])

    def test_jit_compatible(self):
        g = _grad(shape=(64,))
        packed, res = jax.jit(lambda g: bitmap_encode(g, 0.3))(g)
        assert packed.shape == (4,)
