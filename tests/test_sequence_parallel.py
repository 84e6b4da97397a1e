"""Sequence/context parallelism parity tests (P9 capability, SURVEY §5.7).

Pattern per SURVEY §4: the 8-virtual-CPU-device mesh is the
multi-node-without-cluster stand-in; parity is asserted against the
single-device XLA reference attention (exact math, fp32).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.kernels.flash_attention import reference_attention
from deeplearning4j_tpu.parallel.sequence import (
    ring_attention,
    sequence_sharded_spec,
    ulysses_attention,
)
from deeplearning4j_tpu.runtime.device import MeshSpec, build_mesh

B, H, T, D = 2, 4, 32, 8


@pytest.fixture(scope="module")
def seq_mesh():
    return build_mesh(MeshSpec(data=-1, seq=4))


def _qkv(seed=0, t=T):
    rs = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rs.randn(B, H, t, D).astype(np.float32) * 0.5)
    return mk(), mk(), mk()


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, seq_mesh, causal):
        q, k, v = _qkv(0)
        want = reference_attention(q, k, v, causal=causal)
        got = ring_attention(q, k, v, mesh=seq_mesh, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)

    def test_key_mask(self, seq_mesh):
        q, k, v = _qkv(1)
        rs = np.random.RandomState(2)
        km = jnp.asarray((rs.rand(B, T) > 0.3).astype(np.float32))
        want = reference_attention(q, k, v, key_mask=km)
        got = ring_attention(q, k, v, mesh=seq_mesh, key_mask=km)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)

    def test_causal_and_mask(self, seq_mesh):
        q, k, v = _qkv(3)
        rs = np.random.RandomState(4)
        km = jnp.asarray((rs.rand(B, T) > 0.2).astype(np.float32))
        want = reference_attention(q, k, v, causal=True, key_mask=km)
        got = ring_attention(q, k, v, mesh=seq_mesh, causal=True, key_mask=km)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)

    def test_gradients_match(self, seq_mesh):
        q, k, v = _qkv(5)

        def loss_ring(q, k, v):
            return jnp.sum(ring_attention(q, k, v, mesh=seq_mesh, causal=True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ring, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-5, atol=5e-6)

    def test_jit_and_sharded_inputs(self, seq_mesh):
        from jax.sharding import NamedSharding

        q, k, v = _qkv(6)
        spec = sequence_sharded_spec(seq_mesh)
        sh = NamedSharding(seq_mesh, spec)
        qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
        f = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh=seq_mesh))
        got = f(qs, ks, vs)
        assert got.sharding.spec == spec
        want = reference_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)

    def test_indivisible_seq_raises(self, seq_mesh):
        q, k, v = _qkv(7, t=30)
        with pytest.raises(ValueError, match="not divisible"):
            ring_attention(q, k, v, mesh=seq_mesh)

    def test_no_seq_axis_falls_back(self):
        mesh = build_mesh(MeshSpec(data=-1))
        q, k, v = _qkv(8)
        got = ring_attention(q, k, v, mesh=mesh)
        want = reference_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)


class TestUlyssesAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, seq_mesh, causal):
        q, k, v = _qkv(10)
        want = reference_attention(q, k, v, causal=causal)
        got = ulysses_attention(q, k, v, mesh=seq_mesh, causal=causal,
                                use_flash=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)

    def test_flash_local_path(self, seq_mesh, monkeypatch):
        # the kernel itself (interpret mode), inside ulysses' shard_map
        monkeypatch.setenv("DL4J_TPU_FORCE_PALLAS", "1")
        q, k, v = _qkv(11)
        want = reference_attention(q, k, v)
        got = ulysses_attention(q, k, v, mesh=seq_mesh, use_flash=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)

    def test_key_mask(self, seq_mesh):
        q, k, v = _qkv(12)
        rs = np.random.RandomState(13)
        km = jnp.asarray((rs.rand(B, T) > 0.3).astype(np.float32))
        want = reference_attention(q, k, v, key_mask=km)
        got = ulysses_attention(q, k, v, mesh=seq_mesh, key_mask=km,
                                use_flash=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)

    def test_gradients_match(self, seq_mesh):
        q, k, v = _qkv(14)

        def loss_u(q, k, v):
            return jnp.sum(
                ulysses_attention(q, k, v, mesh=seq_mesh, use_flash=False) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(reference_attention(q, k, v) ** 2)

        g_u = jax.grad(loss_u, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_u, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-5, atol=5e-6)

    def test_indivisible_heads_raises(self, seq_mesh):
        rs = np.random.RandomState(15)
        q = jnp.asarray(rs.randn(B, 6, T, D).astype(np.float32))
        with pytest.raises(ValueError, match="heads"):
            ulysses_attention(q, q, q, mesh=seq_mesh)


class TestLayerOptIn:
    @pytest.mark.parametrize("impl", ["ring", "ulysses"])
    def test_self_attention_layer_sp_matches_flash(self, seq_mesh, impl):
        from deeplearning4j_tpu.nn.layers.attention import SelfAttention
        from deeplearning4j_tpu.parallel.sequence import sequence_mesh

        rs = np.random.RandomState(20)
        x = jnp.asarray(rs.randn(2, T, 16).astype(np.float32))
        base = SelfAttention(num_heads=4, causal=True)
        sp = SelfAttention(num_heads=4, causal=True, sequence_parallel=impl)
        params, _ = base.init(jax.random.key(0), (T, 16), jnp.float32)
        want, _ = base.apply(params, {}, x)
        with sequence_mesh(seq_mesh):
            got, _ = sp.apply(params, {}, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)

    def test_no_mesh_falls_back(self):
        from deeplearning4j_tpu.nn.layers.attention import SelfAttention

        rs = np.random.RandomState(21)
        x = jnp.asarray(rs.randn(2, T, 16).astype(np.float32))
        sp = SelfAttention(num_heads=4, sequence_parallel="ring")
        params, _ = sp.init(jax.random.key(0), (T, 16), jnp.float32)
        out, _ = sp.apply(params, {}, x)  # no active mesh: flash path
        assert out.shape == (2, T, 16)

    def test_encoder_block_threads_sp(self, seq_mesh):
        from deeplearning4j_tpu.nn.layers.attention import TransformerEncoderBlock
        from deeplearning4j_tpu.parallel.sequence import sequence_mesh

        rs = np.random.RandomState(22)
        x = jnp.asarray(rs.randn(2, T, 16).astype(np.float32))
        base = TransformerEncoderBlock(num_heads=4)
        sp = TransformerEncoderBlock(num_heads=4, sequence_parallel="ring")
        params, _ = base.init(jax.random.key(0), (T, 16), jnp.float32)
        want, _ = base.apply(params, {}, x)
        with sequence_mesh(seq_mesh):
            got, _ = sp.apply(params, {}, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_bad_impl_rejected_at_config_time(self):
        from deeplearning4j_tpu.nn.layers.attention import (
            LearnedSelfAttention,
            SelfAttention,
        )

        with pytest.raises(ValueError, match="valid"):
            SelfAttention(num_heads=2, sequence_parallel="ulyses")
        with pytest.raises(ValueError, match="not support"):
            LearnedSelfAttention(num_heads=2, sequence_parallel="ring")
