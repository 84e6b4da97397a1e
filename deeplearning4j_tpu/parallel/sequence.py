"""Sequence/context parallelism: ring attention + Ulysses all-to-all (P9).

ref: the reference has NO sequence parallelism (SURVEY §2.6 P9 / §5.7) —
its longest-sequence story is truncated BPTT (a memory trick) and O(T²)
attention layers. These are the TPU-native capability line-items the build
adds as first-class:

- **Ring attention** (`ring_attention`): Q/K/V sharded on the sequence axis
  over a `seq` mesh axis laid on the ICI ring. Each device keeps its local
  Q shard and online-softmax state; KV (+key-mask) shards rotate around the
  ring via `lax.ppermute`, one hop per step, n_seq steps total. Peak memory
  per chip is O(T/n · D) and the ppermute of the *next* block is issued
  before the current block's compute so XLA's latency-hiding scheduler
  overlaps ICI transfer with MXU work. Causal blocks that are fully masked
  (source shard strictly in the future) skip their matmuls via lax.cond.
- **Ulysses** (`ulysses_attention`): all-to-all scatters heads / gathers
  sequence so each device runs *full-sequence* attention on H/n heads (the
  flash kernel applies locally), then the inverse all-to-all restores
  sequence sharding. Cheaper than the ring when heads ≥ seq shards; requires
  H % n == 0.

Both are pure functions of globally-shaped arrays, built on shard_map over a
Mesh — they compose with jit/pjit/grad like any other op, and the identical
program runs on the 8-virtual-CPU-device test mesh (SURVEY §4 test pattern)
and a real slice.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu.kernels._dispatch import kernel_mesh, use_pallas
from deeplearning4j_tpu.kernels.flash_attention import (
    flash_attention,
    reference_attention,
)
from deeplearning4j_tpu.runtime.device import SEQ_AXIS


def shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


_NEG_INF = -1e30


def _ring_partial(q, k, v, km, q_off, k_off, *, scale, causal, m, l, acc):
    """Online-softmax update of (m, l, acc) with one KV block.

    q [B,H,Tq,D], k/v [B,H,Tk,D], km [B,Tk] or None; q_off/k_off are the
    global sequence offsets of the blocks (for causal masking).
    """
    s = jnp.einsum("bhtd,bhsd->bhts", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    mask = jnp.ones(s.shape, bool)
    if km is not None:
        mask = mask & (km[:, None, None, :] > 0)
    if causal:
        t_idx = q_off + jnp.arange(q.shape[2])[:, None]
        s_idx = k_off + jnp.arange(k.shape[2])[None, :]
        mask = mask & (t_idx >= s_idx)[None, None]
    s = jnp.where(mask, s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None]) * mask.astype(jnp.float32)
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bhts,bhsd->bhtd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def ring_attention(
    q, k, v, *, mesh: Mesh, causal: bool = False, scale: Optional[float] = None,
    key_mask=None, seq_axis: str = SEQ_AXIS,
):
    """Ring attention over the `seq` mesh axis. q/k/v [B,H,T,D] global.

    Sequence must divide evenly over the axis. Returns [B,H,T,D] with the
    same sequence sharding as the inputs.
    """
    if seq_axis not in mesh.axis_names:
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               key_mask=key_mask)
    n = mesh.shape[seq_axis]
    b, h, t, d = q.shape
    if t % n != 0:
        raise ValueError(f"seq len {t} not divisible by seq axis size {n}")
    scale = (d ** -0.5) if scale is None else scale
    has_mask = key_mask is not None
    chunk = t // n

    # Everything not on the seq axis is replicated from shard_map's view —
    # batch/model sharding composes outside via the enclosing pjit.
    qkv_spec = P(None, None, seq_axis, None)
    km_spec = P(None, seq_axis)

    def local(q_l, k_l, v_l, km_l):
        my = lax.axis_index(seq_axis)
        m0 = jnp.full((b, h, chunk), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, chunk), jnp.float32)
        a0 = jnp.zeros((b, h, chunk, d), jnp.float32)
        perm = [(i, (i + 1) % n) for i in range(n)]
        q_off = my * chunk

        def update(k_cur, v_cur, km_cur, i, m, l, acc):
            src = (my - i) % n  # who produced the block we currently hold
            k_off = src * chunk

            def compute(m, l, acc):
                return _ring_partial(
                    q_l, k_cur, v_cur, km_cur if has_mask else None,
                    q_off, k_off, scale=scale, causal=causal, m=m, l=l, acc=acc)

            if causal:
                # A block strictly in the future is fully masked: skip it.
                return lax.cond(
                    k_off > q_off + chunk - 1,
                    lambda m, l, acc: (m, l, acc),
                    compute, m, l, acc)
            return compute(m, l, acc)

        def step(carry, i):
            k_cur, v_cur, km_cur, m, l, acc = carry
            # Issue the rotation for the NEXT step first so ICI transfer
            # overlaps this step's matmuls. Only the mask actually in use
            # rides the ring.
            rot = (k_cur, v_cur, km_cur) if has_mask else (k_cur, v_cur)
            rot = jax.tree_util.tree_map(
                lambda x: lax.ppermute(x, seq_axis, perm), rot)
            k_nxt, v_nxt = rot[0], rot[1]
            km_nxt = rot[2] if has_mask else km_cur
            m, l, acc = update(k_cur, v_cur, km_cur, i, m, l, acc)
            return (k_nxt, v_nxt, km_nxt, m, l, acc), None

        km_l0 = km_l if has_mask else jnp.ones((b, chunk), jnp.float32)
        # n-1 rotate+compute steps, then the last received block computes
        # WITHOUT a trailing ppermute (its output would be discarded, and a
        # collective in a loop body can't be DCE'd — one free ICI hop saved).
        (k_f, v_f, km_f, m, l, acc), _ = lax.scan(
            step, (k_l, v_l, km_l0, m0, l0, a0), jnp.arange(n - 1))
        m, l, acc = update(k_f, v_f, km_f, n - 1, m, l, acc)
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.astype(q.dtype)

    km_in = key_mask if has_mask else jnp.ones((b, t), jnp.float32)
    fn = shard_map(
        local, mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, km_spec),
        out_specs=qkv_spec,
    )
    return fn(q, k, v, km_in)


def ulysses_attention(
    q, k, v, *, mesh: Mesh, causal: bool = False, scale: Optional[float] = None,
    key_mask=None, seq_axis: str = SEQ_AXIS,
    use_flash: Optional[bool] = None,
    block_q: int = 256, block_k: int = 256,
):
    """Ulysses-style SP: all-to-all head-scatter/seq-gather, local full-seq
    attention, inverse all-to-all. q/k/v [B,H,T,D] global. ``use_flash``:
    the local attention is the Pallas flash kernel (True) or the XLA
    reference (False); None picks the kernel where it can run (on TPU, or
    under the tests' DL4J_TPU_FORCE_PALLAS). An explicit True that cannot
    be honoured raises."""
    if use_flash is None:
        use_flash = use_pallas()
    if seq_axis not in mesh.axis_names:
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               key_mask=key_mask)
    n = mesh.shape[seq_axis]
    b, h, t, d = q.shape
    if h % n != 0:
        raise ValueError(f"heads {h} not divisible by seq axis size {n}")
    if t % n != 0:
        raise ValueError(f"seq len {t} not divisible by seq axis size {n}")
    scale = (d ** -0.5) if scale is None else scale
    has_mask = key_mask is not None

    qkv_spec = P(None, None, seq_axis, None)
    km_spec = P(None, seq_axis)

    def local(q_l, k_l, v_l, km_l):
        # [B, H, T/n, D] -> [B, H/n, T, D]: split heads across devices,
        # gather the full sequence (one fused ICI all-to-all).
        def scatter_heads(x):
            return lax.all_to_all(x, seq_axis, split_axis=1, concat_axis=2,
                                  tiled=True)

        def gather_heads(x):
            return lax.all_to_all(x, seq_axis, split_axis=2, concat_axis=1,
                                  tiled=True)

        qh, kh, vh = scatter_heads(q_l), scatter_heads(k_l), scatter_heads(v_l)
        km_full = lax.all_gather(km_l, seq_axis, axis=1, tiled=True) \
            if has_mask else None
        if use_flash:
            # Explicit backend: use_flash=True means the Pallas kernel, not
            # the auto-dispatch (which would route short sequences to XLA
            # and make this flag a no-op). Already inside shard_map: shadow
            # any mesh the kernel would otherwise wrap itself over.
            with kernel_mesh(None):
                out = flash_attention(qh, kh, vh, causal=causal, scale=scale,
                                      key_mask=km_full, block_q=block_q,
                                      block_k=block_k, backend="pallas")
        else:
            out = reference_attention(qh, kh, vh, causal=causal, scale=scale,
                                      key_mask=km_full)
        return gather_heads(out)

    km_in = key_mask if has_mask else jnp.ones((b, t), jnp.float32)
    fn = shard_map(
        local, mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, km_spec),
        out_specs=qkv_spec,
    )
    return fn(q, k, v, km_in)


def sequence_sharded_spec(mesh: Mesh, seq_axis: str = SEQ_AXIS) -> P:
    """PartitionSpec for [B,H,T,D] activations sharded on the seq axis."""
    if seq_axis not in mesh.axis_names:
        return P()
    return P(None, None, seq_axis, None)


# --- active sequence mesh -------------------------------------------------
# Layer configs are serializable dataclasses and cannot hold a Mesh; layers
# that opt into sequence parallelism (SelfAttention.sequence_parallel) pick
# the mesh up from this context at apply time.

import contextlib  # noqa: E402

_ACTIVE_SEQ_MESH: Optional[Mesh] = None


def set_sequence_mesh(mesh: Optional[Mesh]) -> None:
    global _ACTIVE_SEQ_MESH
    _ACTIVE_SEQ_MESH = mesh


def get_sequence_mesh() -> Optional[Mesh]:
    return _ACTIVE_SEQ_MESH


@contextlib.contextmanager
def sequence_mesh(mesh: Mesh):
    global _ACTIVE_SEQ_MESH
    prev = _ACTIVE_SEQ_MESH
    _ACTIVE_SEQ_MESH = mesh
    try:
        yield mesh
    finally:
        _ACTIVE_SEQ_MESH = prev


VALID_SP_IMPLS = ("ring", "ulysses")


def sharded_attention(q, k, v, *, impl: str, causal=False, scale=None,
                      key_mask=None):
    """Dispatch helper used by nn layers: ``impl`` in {"ring", "ulysses"};
    falls back to the flash kernel when no sequence mesh is active.

    NOTE (trace-time semantics): the active mesh is captured when the
    enclosing function is *traced*. If you jit a train/apply step yourself,
    enter ``sequence_mesh(mesh)`` before the first (compiling) call and keep
    the same mesh for the jit'd function's lifetime — a cached trace will
    not notice a later mesh change (standard JAX practice: meshes are
    trace-time constants, as with flax's mesh contexts)."""
    if impl not in VALID_SP_IMPLS:
        raise ValueError(
            f"unknown sequence_parallel impl {impl!r}; valid: {VALID_SP_IMPLS}")
    mesh = get_sequence_mesh()
    if mesh is None or SEQ_AXIS not in mesh.axis_names:
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               key_mask=key_mask)
    fn = {"ring": ring_attention, "ulysses": ulysses_attention}[impl]
    return fn(q, k, v, mesh=mesh, causal=causal, scale=scale, key_mask=key_mask)
