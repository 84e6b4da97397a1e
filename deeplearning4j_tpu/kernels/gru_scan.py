"""Pallas TPU GRU scan kernels (forward + backward).

ref: the cuDNN RNN platform helper covers GRU alongside LSTM (libnd4j
ops/declarable/platform/cudnn + DL4J CudnnLSTMHelper family); this is the
GRU half of the 'cuDNN RNN helper → Pallas scan' role that
kernels/lstm_scan.py fills for LSTM.

Same schedule as the LSTM kernel: grid=(T,), the recurrent weights [H,3H]
resident in VMEM for the whole sequence, ONE MXU matmul (h·RW) per step +
VPU gate math; the input projection x·W for all T steps is one large MXU
GEMM outside the kernel. Cell math matches ops/rnn.gru_cell exactly (gate
order r,z,n; candidate uses r ⊙ (h·RWn) — reset applied AFTER the
recurrent projection):

    r,z = σ(xp_rz + h·RW_rz + b_rz)
    n   = tanh(xp_n + r ⊙ (h·RW_n) + b_n)
    h'  = (1−z) ⊙ n + z ⊙ h

Backward: reversed-time dgrad sweep carrying dh in VMEM scratch and
streaming out dz̃ = [dr_pre, dz_pre, dn_pre] per step; ALL weight/bias
grads are large batched GEMMs/reductions over the saved tensors outside
the kernel (the dgrad-then-wgrad schedule that fixed the LSTM backward's
0.65x — see _make_bwd_kernel in lstm_scan.py). The one GRU-specific twist:
dh−1 needs [dr_pre, dz_pre, r ⊙ dn_pre] · RWᵀ, which is still a single
MXU dot per step.

Off-TPU the public ``gru`` routes to ops/rnn.py (kernels/_dispatch.py);
shapes that don't tile (N % 8, H % 128) also fall back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.kernels._dispatch import interpret as _interpret
from deeplearning4j_tpu.kernels._dispatch import use_pallas as _use_pallas
from deeplearning4j_tpu.ops import rnn as opsrnn


def _make_fwd_kernel(save_ws: bool):
    """One timestep per grid index; h carried in VMEM scratch."""

    def kernel(*refs):
        xp_ref, rw_ref, b_ref, h0_ref = refs[0:4]
        outs = refs[4:]
        out_ref, hN_ref = outs[0:2]
        if save_ws:
            gates_ref, hpn_ref, h_scr = outs[2:]
        else:
            (h_scr,) = outs[2:]

        t = pl.program_id(0)
        n_t = pl.num_programs(0)

        @pl.when(t == 0)
        def _init():
            h_scr[:] = h0_ref[:]

        h = h_scr[:]
        H = h.shape[-1]

        hproj = jnp.dot(h, rw_ref[:], preferred_element_type=jnp.float32)
        xp = xp_ref[0]
        b = b_ref[0]  # [3H], broadcasts over the batch rows
        rz = jax.nn.sigmoid(xp[:, : 2 * H] + hproj[:, : 2 * H] + b[: 2 * H])
        r = rz[:, :H]
        z = rz[:, H:]
        hpn = hproj[:, 2 * H :]
        n = jnp.tanh(xp[:, 2 * H :] + r * hpn + b[2 * H :])
        h_new = (1.0 - z) * n + z * h

        h_scr[:] = h_new
        out_ref[0] = h_new.astype(out_ref.dtype)
        if save_ws:
            gates_ref[0] = jnp.concatenate([r, z, n], axis=1)
            hpn_ref[0] = hpn

        @pl.when(t == n_t - 1)
        def _final():
            hN_ref[:] = h_new.astype(hN_ref.dtype)

    return kernel


def _gru_pallas_fwd(x_proj_tm, rw, b, h0, save_workspace=False):
    """x_proj_tm: [T,N,3H] time-major.

    Returns (hs [T,N,H], hT) and, with ``save_workspace``, also the
    post-activation gates [T,N,3H] (r,z,n) and the candidate recurrent
    projection h·RW_n [T,N,H] (needed for dr in the backward sweep).
    """
    t_len, n, threeh = x_proj_tm.shape
    h_dim = threeh // 3
    dtype = x_proj_tm.dtype

    b2 = b.reshape(1, threeh).astype(jnp.float32)
    kernel = _make_fwd_kernel(save_workspace)

    in_specs = [
        pl.BlockSpec((1, n, threeh), lambda t: (t, 0, 0)),  # x_proj step t
        pl.BlockSpec((h_dim, threeh), lambda t: (0, 0)),    # RW resident
        pl.BlockSpec((1, threeh), lambda t: (0, 0)),        # bias
        pl.BlockSpec((n, h_dim), lambda t: (0, 0)),         # h0
    ]
    out_specs = [
        pl.BlockSpec((1, n, h_dim), lambda t: (t, 0, 0)),   # hs
        pl.BlockSpec((n, h_dim), lambda t: (0, 0)),         # hT
    ]
    out_shape = [
        jax.ShapeDtypeStruct((t_len, n, h_dim), dtype),
        jax.ShapeDtypeStruct((n, h_dim), dtype),
    ]
    if save_workspace:
        out_specs += [
            pl.BlockSpec((1, n, threeh), lambda t: (t, 0, 0)),  # gates
            pl.BlockSpec((1, n, h_dim), lambda t: (t, 0, 0)),   # h·RW_n
        ]
        out_shape += [
            jax.ShapeDtypeStruct((t_len, n, threeh), jnp.float32),
            jax.ShapeDtypeStruct((t_len, n, h_dim), jnp.float32),
        ]
    scratch = [pltpu.VMEM((n, h_dim), jnp.float32)]

    return pl.pallas_call(
        kernel,
        grid=(t_len,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=_interpret(),
        name="gru_scan_fwd",
    )(
        x_proj_tm,
        rw.astype(jnp.float32),
        b2,
        h0.astype(jnp.float32),
    )


def _make_bwd_kernel():
    """Reversed-time dgrad step (grid index i processes t = T-1-i via the
    index maps in _gru_pallas_bwd).

    Streams out dz̃_t = [dr_pre, dz_pre, dn_pre] [N,3H]; the dh carry uses
    the rotated vector [dr_pre, dz_pre, r ⊙ dn_pre] · RWᵀ — one MXU dot.
    Weight/bias grads happen outside over the full dz̃ tensor.
    """

    def kernel(gates_ref, hpn_ref, hprev_ref, gh_ref, rw_ref, dxp_ref,
               dh_scr):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            dh_scr[:] = jnp.zeros_like(dh_scr)

        gates = gates_ref[0]
        H = gates.shape[-1] // 3
        r = gates[:, 0 * H : 1 * H]
        z = gates[:, 1 * H : 2 * H]
        n = gates[:, 2 * H : 3 * H]
        hpn = hpn_ref[0]
        h_prev = hprev_ref[0]

        dh_total = gh_ref[0] + dh_scr[:]
        dn = dh_total * (1.0 - z)
        dz = dh_total * (h_prev - n)
        dn_pre = dn * (1.0 - n * n)
        dr = dn_pre * hpn
        dr_pre = dr * r * (1.0 - r)
        dz_pre = dz * z * (1.0 - z)

        dxp_ref[0] = jnp.concatenate([dr_pre, dz_pre, dn_pre], axis=1)
        # dh_{t-1}: direct path + the three recurrent-matmul paths in one
        # dot (the n-gate path carries r ⊙ dn_pre, not dn_pre).
        rot = jnp.concatenate([dr_pre, dz_pre, r * dn_pre], axis=1)
        dh_scr[:] = dh_total * z + jax.lax.dot_general(
            rot, rw_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    return kernel


def _gru_pallas_bwd(gates_tm, hpn_tm, h_prev_tm, gh_tm, rw):
    """Reversed-time dgrad sweep.

    gates_tm [T,N,3H] (r,z,n post-activation), hpn_tm [T,N,H] (h·RW_n),
    h_prev_tm [T,N,H], gh_tm [T,N,H] (upstream grad per step, final-state
    grad folded into the last step). Returns dz̃_tm [T,N,3H].
    """
    t_len, n, threeh = gates_tm.shape
    h_dim = threeh // 3

    rev = lambda i: (t_len - 1 - i, 0, 0)  # noqa: E731 - index map
    const2 = lambda i: (0, 0)  # noqa: E731

    in_specs = [
        pl.BlockSpec((1, n, threeh), rev),     # gates
        pl.BlockSpec((1, n, h_dim), rev),      # h·RW_n
        pl.BlockSpec((1, n, h_dim), rev),      # h_{t-1}
        pl.BlockSpec((1, n, h_dim), rev),      # dL/dh_t
        pl.BlockSpec((h_dim, threeh), const2),  # RW resident
    ]
    out_specs = pl.BlockSpec((1, n, threeh), rev)
    out_shape = jax.ShapeDtypeStruct((t_len, n, threeh), jnp.float32)
    scratch = [pltpu.VMEM((n, h_dim), jnp.float32)]

    return pl.pallas_call(
        _make_bwd_kernel(),
        grid=(t_len,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=_interpret(),
        name="gru_scan_bwd",
    )(gates_tm, hpn_tm, h_prev_tm, gh_tm, rw.astype(jnp.float32))


def _shapes_tile(n: int, h: int) -> bool:
    return n % 8 == 0 and h % 128 == 0


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def _gru_core(x, w_x, w_h, b):
    """Returns (outputs [N,T,H], h_T [N,H])."""
    return _gru_core_fwd_impl(x, w_x, w_h, b)[0]


def _gru_core_fwd_impl(x, w_x, w_h, b, save_workspace=False):
    n, t, _ = x.shape
    h_dim = w_h.shape[0]
    x_proj = jnp.einsum("nti,ih->nth", x, w_x)  # big MXU GEMM outside kernel
    xp_tm = jnp.swapaxes(x_proj, 0, 1).astype(jnp.float32)
    h0 = jnp.zeros((n, h_dim), jnp.float32)
    res = _gru_pallas_fwd(xp_tm, w_h, b, h0, save_workspace=save_workspace)
    hs, hT = res[0:2]
    primal = (jnp.swapaxes(hs, 0, 1).astype(x.dtype), hT)
    ws = (hs, res[2], res[3]) if save_workspace else None
    return primal, ws


def _gru_core_vjp_fwd(x, w_x, w_h, b):
    primal, ws = _gru_core_fwd_impl(x, w_x, w_h, b, save_workspace=True)
    hs_tm, gates_tm, hpn_tm = ws
    return primal, (x, w_x, w_h, b, hs_tm, gates_tm, hpn_tm)


def _gru_core_vjp_bwd(res, g):
    x, w_x, w_h, b, hs_tm, gates_tm, hpn_tm = res
    g_out, ghT = g
    t_len, n, h_dim = hs_tm.shape

    zeros_nh = jnp.zeros((1, n, h_dim), jnp.float32)
    h_prev_tm = jnp.concatenate([zeros_nh, hs_tm[:-1].astype(jnp.float32)], 0)

    gh_tm = jnp.swapaxes(g_out, 0, 1).astype(jnp.float32)
    gh_tm = gh_tm.at[-1].add(ghT.astype(jnp.float32))

    dxp_tm = _gru_pallas_bwd(gates_tm, hpn_tm, h_prev_tm, gh_tm, w_h)

    # Wgrad phase: large MXU GEMMs over the saved tensors. The recurrent
    # weight grad needs the ROTATED vector for its n-columns (the kernel
    # streams raw dn_pre; the candidate matmul consumed r ⊙ h·RW_n).
    r_tm = gates_tm[:, :, :h_dim]
    rot_tm = jnp.concatenate(
        [dxp_tm[:, :, : 2 * h_dim], r_tm * dxp_tm[:, :, 2 * h_dim :]], axis=2)
    drw = jnp.einsum("tnh,tnf->hf", h_prev_tm, rot_tm)
    db = jnp.sum(dxp_tm, axis=(0, 1))
    dx = jnp.einsum("tnh,ih->nti", dxp_tm, w_x.astype(jnp.float32))
    dw_x = jnp.einsum("nti,tnh->ih", x.astype(jnp.float32), dxp_tm)
    return (dx.astype(x.dtype), dw_x.astype(w_x.dtype),
            drw.astype(w_h.dtype), db.astype(b.dtype))


_gru_core.defvjp(_gru_core_vjp_fwd, _gru_core_vjp_bwd)


def gru(x, w_x, w_h, b=None, *, init_h=None):
    """Drop-in replacement for ops/rnn.gru using the Pallas kernels.

    Falls back to the XLA scan when shapes don't tile (N % 8, H % 128),
    when an initial state is supplied (kernel assumes zero init for the
    backward sweep), or off-TPU (kernels/_dispatch.py policy).
    """
    n, t, _ = x.shape
    h_dim = w_h.shape[0]
    if init_h is not None or not _shapes_tile(n, h_dim) or not _use_pallas():
        return opsrnn.gru(x, w_x, w_h, b, init_h=init_h)
    if b is None:
        b = jnp.zeros((3 * h_dim,), jnp.float32)
    outputs, h_t = _gru_core(x, w_x, w_h, b)
    return outputs, h_t
