"""Pallas TPU blockwise (flash) attention kernel.

ref: the reference's only attention is the O(T²)-memory libnd4j
``multi_head_dot_product_attention`` op behind SameDiff attention layers
(SURVEY §5.7) — it materializes the [T,S] score matrix in HBM. This kernel
is the TPU-native replacement: online-softmax tiling keeps only
[block_q, block_k] score tiles in VMEM, so memory is O(T·D) and the two
matmuls per tile run back-to-back on the MXU.

Grid: (batch*heads, q_blocks, kv_blocks), kv innermost so the running
max/denominator/accumulator for one q block live in VMEM scratch across the
kv sweep. Causal masking skips fully-masked kv blocks via ``pl.when``.
Per-example key padding masks ([B,S] 1/0 — the BERT attention-mask case)
are handled *inside* the kernel, so masked batches keep the flash path;
only arbitrary additive ``bias`` falls back to the XLA reference.

Backward: blockwise Pallas kernels (FlashAttention-2 style). The forward
saves the per-row logsumexp (lane-broadcast [BH,T,128] layout, the Mosaic
tiling-friendly shape jax's own TPU flash kernel uses); backward runs two
kernels — dk/dv with a q-block sweep per kv block, dq with a kv-block
sweep per q block — plus one XLA pass for delta = rowsum(dO*O). Scores are
recomputed on-chip, so backward memory stays O(T·D) like forward. The
same kernels run everywhere: compiled on TPU, interpret-mode in CPU tests
(via DL4J_TPU_FORCE_PALLAS=1; plain CPU callers never reach them because
flash_attention auto-dispatches to reference_attention off-TPU, and an
explicit ``backend="pallas"`` there raises).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.kernels._dispatch import (
    active_kernel_mesh as _active_kernel_mesh,
    flash_block_sizes as _flash_block_sizes,
    flash_min_seq as _flash_min_seq,
    force_pallas as _force_pallas,
    interpret as _interpret,
    on_tpu as _on_tpu,
    use_pallas as _use_pallas,
)

_NEG_INF = -1e30


def _matmul_dtype(dtype):
    """MXU input dtype for score/value matmuls.

    fp32 operands are cast to bf16 (fp32 accumulation via
    ``preferred_element_type`` is kept): a true-fp32 MXU matmul costs ~6
    passes, while XLA's einsum at its DEFAULT precision runs ONE bf16 pass —
    that asymmetry was most of the r3 kernels_ab 8x forward loss at T=512
    (the XLA reference was single-pass bf16, the kernel six-pass fp32).
    Matching XLA's default keeps the A/B apples-to-apples and the parity
    bound unchanged (both sides now carry bf16 matmul error).
    DL4J_TPU_FLASH_FP32=1 restores true-fp32 matmuls.

    Off-TPU (interpret-mode unit tests) the input dtype is kept: those
    tests pin kernel LOGIC against the fp32 XLA oracle at tight tolerance,
    and numpy emulation has no MXU whose precision policy needs matching.
    DL4J_TPU_FLASH_BF16=1 opts interpret mode into the cast path so the
    policy itself is testable on CPU.
    """
    import os

    if os.environ.get("DL4J_TPU_FLASH_FP32", "") == "1":
        return jnp.float32
    if not _on_tpu() and os.environ.get("DL4J_TPU_FLASH_BF16", "") != "1":
        return dtype
    return jnp.bfloat16 if dtype == jnp.float32 else dtype


def _compiler_params(*semantics):
    """Mosaic grid-dimension semantics (parallel dims enable multi-core
    partitioning on megacore chips and better pipelining); only meaningful
    when compiled for TPU — interpret mode ignores them."""
    if not _on_tpu():
        return None
    return pltpu.CompilerParams(dimension_semantics=tuple(semantics))


def reference_attention(q, k, v, *, causal=False, bias=None, key_mask=None,
                        scale=None):
    """XLA O(T²) attention; q [B,H,T,D], k/v [B,H,S,D]. fp32 softmax.

    ``key_mask`` [B,S] 1/0 is folded into an additive bias. Fully-masked
    rows produce uniform attention (softmax of constant) — callers never
    read those outputs.
    """
    d = q.shape[-1]
    scale = (d ** -0.5) if scale is None else scale
    s = jnp.einsum("bhtd,bhsd->bhts", q, k).astype(jnp.float32) * scale
    if bias is not None:
        s = s + bias
    if key_mask is not None:
        s = s + jnp.where(key_mask[:, None, None, :] > 0, 0.0, _NEG_INF)
    if causal:
        t_len, s_len = s.shape[-2], s.shape[-1]
        idx_t = jnp.arange(t_len)[:, None]
        idx_s = jnp.arange(s_len)[None, :]
        s = jnp.where(idx_t + (s_len - t_len) >= idx_s, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhts,bhsd->bhtd", p, v)


def _flash_kernel(q_ref, k_ref, v_ref, km_ref, o_ref, lse_ref, m_scr, l_scr,
                  acc_scr, *,
                  scale, causal, has_mask, block_q, block_k, seq_q, seq_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Causal: a kv block whose smallest key index exceeds the largest query
    # index is fully masked — skip its compute entirely.
    q_hi = (qi + 1) * block_q - 1 + (seq_k - seq_q)
    k_lo = ki * block_k
    run = (not causal) or (q_hi >= k_lo)

    @pl.when(run)
    def _compute():
        mm = _matmul_dtype(q_ref.dtype)
        q = q_ref[0].astype(mm)
        k = k_ref[0].astype(mm)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]
        # Mask key padding (seq_k tail + per-example mask) and the causal
        # triangle.
        key_idx = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = key_idx < seq_k
        if has_mask:
            mask = mask & (km_ref[0] > 0)  # [1, bk] broadcasts over rows
        if causal:
            query_idx = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = mask & (query_idx + (seq_k - seq_q) >= key_idx)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # Explicitly zero masked probabilities: in a fully-masked block
        # m_new stays _NEG_INF and exp(s - m_new) would be 1, not 0.
        p = jnp.exp(s - m_new) * mask.astype(jnp.float32)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(mm), v_ref[0].astype(mm), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == n_k - 1)
    def _finish():
        # Fully-masked rows: l == 0 → output 0 (callers never read them).
        o_ref[0] = (
            acc_scr[:] / jnp.maximum(l_scr[:, :1], 1e-30)
        ).astype(o_ref.dtype)
        if lse_ref is not None:
            # Row logsumexp, lane-broadcast — the backward residual. Fully
            # masked / padded rows get ~-1e30; backward clamps before exp.
            lse_ref[0] = m_scr[:] + jnp.log(jnp.maximum(l_scr[:], 1e-30))


def _round_up(x, m):
    return -(-x // m) * m


def _pad_to(x, axis, multiple):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _prep_blocks(q, k, v, key_mask, block_q, block_k):
    """Tile-align block sizes and pad operands — shared by fwd and bwd so
    their block geometry can never desynchronize."""
    b, h, t, d = q.shape
    s_len = k.shape[2]
    # Blocks stay (8,128)-tile-aligned even for short sequences.
    block_q = min(block_q, _round_up(t, 8))
    block_k = min(block_k, _round_up(s_len, 128))

    qp = _pad_to(_pad_to(q.reshape(b * h, t, d), 1, block_q), 2, 128)
    kp = _pad_to(_pad_to(k.reshape(b * h, s_len, d), 1, block_k), 2, 128)
    vp = _pad_to(_pad_to(v.reshape(b * h, s_len, d), 1, block_k), 2, 128)

    if key_mask is not None:
        km = _pad_to(key_mask.astype(jnp.float32), 1, block_k)  # [B, tk]
        # [B*H, 1, tk] — tiny; the unit middle dim keeps the Mosaic block
        # shape (1, 1, block_k) legal (second-minor equals the array dim).
        km = jnp.repeat(km, h, axis=0)[:, None, :]
        km_block = block_k
    else:
        km = jnp.ones((b * h, 1, 1), jnp.float32)  # placeholder operand
        km_block = 1
    return qp, kp, vp, km, km_block, block_q, block_k


def _flash_fwd(q, k, v, key_mask, *, causal, scale, block_q, block_k,
               save_lse=False):
    b, h, t, d = q.shape
    s_len = k.shape[2]
    qp, kp, vp, km, km_block, block_q, block_k = _prep_blocks(
        q, k, v, key_mask, block_q, block_k)
    dp = qp.shape[-1]
    tq, tk = qp.shape[1], kp.shape[1]
    has_mask = key_mask is not None

    params = dict(scale=scale, causal=causal, has_mask=has_mask,
                  block_q=block_q, block_k=block_k, seq_q=t, seq_k=s_len)
    if save_lse:
        kernel = functools.partial(_flash_kernel, **params)
        out_specs = [
            pl.BlockSpec((1, block_q, dp), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 128), lambda bh, qi, ki: (bh, qi, 0)),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((b * h, tq, dp), q.dtype),
            jax.ShapeDtypeStruct((b * h, tq, 128), jnp.float32),
        ]
    else:
        def kernel(q_ref, k_ref, v_ref, km_ref, o_ref, m_scr, l_scr, acc_scr):
            return _flash_kernel(q_ref, k_ref, v_ref, km_ref, o_ref, None,
                                 m_scr, l_scr, acc_scr, **params)

        out_specs = pl.BlockSpec((1, block_q, dp),
                                 lambda bh, qi, ki: (bh, qi, 0))
        out_shape = jax.ShapeDtypeStruct((b * h, tq, dp), q.dtype)

    km_index = (lambda bh, qi, ki: (bh, 0, ki)) if has_mask else (
        lambda bh, qi, ki: (bh, 0, 0)
    )
    grid = (b * h, tq // block_q, tk // block_k)
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dp), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, dp), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, dp), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, 1, km_block), km_index),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, dp), jnp.float32),
        ],
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary"),
        interpret=_interpret(),
        name="flash_fwd",
    )(qp, kp, vp, km)
    out, lse = res if save_lse else (res, None)
    return out[:, :t, :d].reshape(b, h, t, d), lse


def _bwd_recompute(q_ref, k_ref, v_ref, km_ref, g_ref, lse_ref, delta_ref,
                   qi, ki, *, scale, causal, has_mask, block_q, block_k,
                   seq_q, seq_k):
    """Recompute p and ds for one (q-block, kv-block) pair — the math both
    backward kernels share. Returns (q, k, g, p, ds); matmul inputs in the
    MXU compute dtype (see _matmul_dtype), p/ds stats in fp32."""
    mm = _matmul_dtype(q_ref.dtype)
    q = q_ref[0].astype(mm)
    k = k_ref[0].astype(mm)
    v = v_ref[0].astype(mm)
    g = g_ref[0].astype(mm)
    # Clamp: padded / fully-masked rows carry lse ≈ -1e30; after the
    # query-validity mask below their scores are -1e30 too, so the
    # clamped difference underflows exp to exactly 0 (no inf·0 NaNs).
    lse = jnp.maximum(lse_ref[0][:, :1], -1e20)
    delta = delta_ref[0][:, :1]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    k_lo = ki * block_k
    key_idx = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    query_idx = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    mask = (key_idx < seq_k) & (query_idx < seq_q)
    if has_mask:
        mask = mask & (km_ref[0] > 0)
    if causal:
        mask = mask & (query_idx + (seq_k - seq_q) >= key_idx)
    s = jnp.where(mask, s, _NEG_INF)
    p = jnp.exp(s - lse)  # [bq, bk]; exactly 0 where masked
    dp = jax.lax.dot_general(
        g, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta) * scale
    # p/ds feed straight into MXU matmuls at the call sites — hand them
    # over in the compute dtype (fp32 accumulation happens there).
    return q, k, g, p.astype(mm), ds.astype(mm)


def _causal_block_live(qi, ki, *, causal, block_q, block_k, seq_q, seq_k):
    """False only for kv blocks entirely above the causal diagonal."""
    q_hi = (qi + 1) * block_q - 1 + (seq_k - seq_q)
    return (not causal) or (q_hi >= ki * block_k)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, km_ref, g_ref, lse_ref,
                          delta_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                          **params):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(_causal_block_live(qi, ki, **{k: params[k] for k in (
        "causal", "block_q", "block_k", "seq_q", "seq_k")}))
    def _compute():
        q, k, g, p, ds = _bwd_recompute(
            q_ref, k_ref, v_ref, km_ref, g_ref, lse_ref, delta_ref,
            qi, ki, **params)
        dv_scr[:] += jax.lax.dot_general(
            p, g, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(qi == n_q - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, km_ref, g_ref, lse_ref,
                         delta_ref, dq_ref, dq_scr, **params):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(_causal_block_live(qi, ki, **{k: params[k] for k in (
        "causal", "block_q", "block_k", "seq_q", "seq_k")}))
    def _compute():
        q, k, g, p, ds = _bwd_recompute(
            q_ref, k_ref, v_ref, km_ref, g_ref, lse_ref, delta_ref,
            qi, ki, **params)
        dq_scr[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ki == n_k - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_impl(q, k, v, key_mask, out, lse, g, *, causal, scale,
                    block_q, block_k):
    """Blockwise backward; block geometry shared with fwd via _prep_blocks."""
    b, h, t, d = q.shape
    s_len = k.shape[2]
    qp, kp, vp, km, km_block, block_q, block_k = _prep_blocks(
        q, k, v, key_mask, block_q, block_k)
    gp = _pad_to(_pad_to(g.reshape(b * h, t, d), 1, block_q), 2, 128)
    dp = qp.shape[-1]
    tq, tk = qp.shape[1], kp.shape[1]
    has_mask = key_mask is not None

    delta = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32), axis=-1)
    delta = _pad_to(delta.reshape(b * h, t), 1, block_q)
    delta = jnp.broadcast_to(delta[:, :, None], (b * h, tq, 128))

    common = dict(scale=scale, causal=causal, has_mask=has_mask,
                  block_q=block_q, block_k=block_k, seq_q=t, seq_k=s_len)
    n_q, n_k = tq // block_q, tk // block_k

    km_index_kq = (lambda bh, ki, qi: (bh, 0, ki)) if has_mask else (
        lambda bh, ki, qi: (bh, 0, 0)
    )
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **common),
        grid=(b * h, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, dp), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, dp), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, dp), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, 1, km_block), km_index_kq),
            pl.BlockSpec((1, block_q, dp), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 128), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 128), lambda bh, ki, qi: (bh, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, dp), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, dp), lambda bh, ki, qi: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tk, dp), k.dtype),
            jax.ShapeDtypeStruct((b * h, tk, dp), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, dp), jnp.float32),
            pltpu.VMEM((block_k, dp), jnp.float32),
        ],
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary"),
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(qp, kp, vp, km, gp, lse, delta)

    km_index_qk = (lambda bh, qi, ki: (bh, 0, ki)) if has_mask else (
        lambda bh, qi, ki: (bh, 0, 0)
    )
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **common),
        grid=(b * h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, dp), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, dp), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, dp), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, 1, km_block), km_index_qk),
            pl.BlockSpec((1, block_q, dp), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 128), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 128), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dp), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, tq, dp), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dp), jnp.float32)],
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary"),
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(qp, kp, vp, km, gp, lse, delta)

    dq = dq[:, :t, :d].reshape(b, h, t, d)
    dk = dk[:, :s_len, :d].reshape(b, h, s_len, d)
    dv = dv[:, :s_len, :d].reshape(b, h, s_len, d)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, key_mask, causal, scale, block_q, block_k):
    out, _ = _flash_fwd(q, k, v, key_mask, causal=causal, scale=scale,
                        block_q=block_q, block_k=block_k)
    return out


def _flash_vjp_fwd(q, k, v, key_mask, causal, scale, block_q, block_k):
    out, lse = _flash_fwd(q, k, v, key_mask, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k, save_lse=True)
    return out, (q, k, v, key_mask, out, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, res, g):
    q, k, v, key_mask, out, lse = res
    dq, dk, dv = _flash_bwd_impl(
        q, k, v, key_mask, out, lse, g,
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
    )
    dkm = jnp.zeros_like(key_mask) if key_mask is not None else None
    return dq, dk, dv, dkm


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _flash_on_mesh(mesh, q, k, v, key_mask, causal, scale, block_q, block_k):
    """The kernel under a multi-device mesh: inside ``shard_map``, batch
    split over the data-like axes and heads over the model axis (attention
    is independent across both, so no collective is needed); a dimension
    the axis size does not divide stays whole and is computed redundantly.
    """
    from deeplearning4j_tpu.runtime.device import MODEL_AXIS, data_like_axes

    b, h = q.shape[:2]
    batch_axes = data_like_axes(mesh)
    if b % math.prod(mesh.shape[a] for a in batch_axes):
        batch_axes = ()
    head_axis = (MODEL_AXIS if h % mesh.shape.get(MODEL_AXIS, h + 1) == 0
                 else None)
    qkv_spec = P(batch_axes or None, head_axis, None, None)
    args, specs = [q, k, v], [qkv_spec] * 3
    if key_mask is not None:
        args.append(key_mask)
        specs.append(P(batch_axes or None, None))

    def local(q, k, v, *km):
        return _flash(q, k, v, km[0] if km else None, causal, scale,
                      block_q, block_k)

    return jax.shard_map(local, mesh=mesh, in_specs=tuple(specs),
                         out_specs=qkv_spec, check_vma=False)(*args)


def flash_attention(q, k, v, *, causal: bool = False, scale=None, bias=None,
                    key_mask=None, block_q: int = None, block_k: int = None,
                    backend: str = None):
    """Blockwise attention; q [B,H,T,D], k/v [B,H,S,D] → [B,H,T,D].

    ``key_mask`` [B,S] 1/0 (padding mask) runs inside the kernel — the
    BERT path keeps the flash fast path. Arbitrary additive ``bias``
    forces the XLA fallback.

    ``backend``: None (auto), 'pallas', or 'xla'. Auto dispatch picks XLA's
    fused attention below ``_dispatch.flash_min_seq()`` keys, off-TPU, for
    biased attention and for fewer than 8 queries, and the Pallas kernel
    at long sequences where the O(T^2) score materialization pressures
    HBM. DL4J_TPU_FORCE_PALLAS=1 (kernel unit tests) forces the kernel
    path. An explicit 'pallas' that cannot be honoured raises.
    """
    d = q.shape[-1]
    scale = (d ** -0.5) if scale is None else scale
    if backend not in (None, "pallas", "xla"):
        raise ValueError(f"backend must be None|'pallas'|'xla', got {backend!r}")
    default_bq, default_bk = _flash_block_sizes()
    block_q = default_bq if block_q is None else block_q
    block_k = default_bk if block_k is None else block_k
    can_pallas = bias is None and q.shape[2] >= 8 and _use_pallas()
    if backend == "pallas" and not can_pallas:
        # an explicit request is a contract: a caller that asked for the
        # kernel must never be handed XLA without a word
        raise ValueError(
            "backend='pallas' cannot be honoured: "
            + ("additive bias is not supported by the kernel"
               if bias is not None else
               f"query length {q.shape[2]} < 8" if q.shape[2] < 8 else
               "not on TPU and DL4J_TPU_FORCE_PALLAS is not set"))
    if backend is None:
        backend = "pallas" if can_pallas and (
            _force_pallas() or k.shape[2] >= _flash_min_seq()) else "xla"
    if backend == "xla":
        return reference_attention(q, k, v, causal=causal, bias=bias,
                                   key_mask=key_mask, scale=scale)
    mesh = _active_kernel_mesh()
    if mesh is not None:
        return _flash_on_mesh(mesh, q, k, v, key_mask, causal, scale,
                              block_q, block_k)
    return _flash(q, k, v, key_mask, causal, scale, block_q, block_k)
