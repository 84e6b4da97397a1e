"""Pallas TPU LSTM scan kernels (forward + backward).

ref: the cuDNN RNN platform helper (libnd4j
ops/declarable/platform/cudnn/lstmLayer.cu + DL4J CudnnLSTMHelper) —
benchmark config #3 'GravesLSTM cuDNN RNN helper → Pallas scan'.

Design: one `pallas_call` with grid=(T,). The recurrent weights [H,4H] and
the per-step carried state (h, c — VMEM scratch) stay resident on-chip for
the whole sequence; each grid step does ONE MXU matmul (h·RW) + VPU gate
math + a [N,4H] slice stream-in / [N,H] stream-out. The input projection
x·W for all timesteps is done OUTSIDE the kernel as one large MXU GEMM
(same schedule cuDNN uses).

Backward: a second Pallas kernel sweeping time REVERSED (index maps flip
t → T-1-t), carrying (dh, dc) in VMEM scratch and accumulating dRW/db/
dpeephole directly in constant-index output blocks that stay VMEM-resident
across the sweep — the cuDNN-style training path. The forward-under-AD
variant saves the post-activation gates and cell states ([T,N,4H]+[T,N,H],
the cuDNN training-workspace analogue) so backward needs no recompute; the
primal (inference) call skips those outputs.

Off-TPU the public ``lstm`` routes to ops/rnn.py (see kernels/_dispatch.py);
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


def _make_fwd_kernel(peep: bool, save_ws: bool, forget_bias: float):
    """One timestep per grid index; state carried in VMEM scratch."""

    def kernel(*refs):
        xp_ref, rw_ref, b_ref = refs[0:3]
        i0 = 3
        if peep:
            pI_ref, pF_ref, pO_ref = refs[3:6]
            i0 = 6
        h0_ref, c0_ref = refs[i0], refs[i0 + 1]
        outs = refs[i0 + 2:]
        out_ref, hN_ref, cN_ref = outs[0:3]
        if save_ws:
            gates_ref, cs_ref = outs[3:5]
            h_scr, c_scr = outs[5:]
        else:
            h_scr, c_scr = outs[3:]

        t = pl.program_id(0)
        n_t = pl.num_programs(0)

        @pl.when(t == 0)
        def _init():
            h_scr[:] = h0_ref[:]
            c_scr[:] = c0_ref[:]

        h = h_scr[:]
        c_prev = c_scr[:]
        H = h.shape[-1]

        z = (
            xp_ref[0]
            + jnp.dot(h, rw_ref[:], preferred_element_type=jnp.float32)
            + b_ref[0]
        )
        zi = z[:, 0 * H : 1 * H]
        zf = z[:, 1 * H : 2 * H]
        zg = z[:, 2 * H : 3 * H]
        zo = z[:, 3 * H : 4 * H]
        if peep:
            zi = zi + pI_ref[0] * c_prev
            zf = zf + pF_ref[0] * c_prev
        i = jax.nn.sigmoid(zi)
        f = jax.nn.sigmoid(zf + forget_bias)
        g = jnp.tanh(zg)
        c = f * c_prev + i * g
        if peep:
            zo = zo + pO_ref[0] * c
        o = jax.nn.sigmoid(zo)
        h_new = o * jnp.tanh(c)

        h_scr[:] = h_new
        c_scr[:] = c
        out_ref[0] = h_new.astype(out_ref.dtype)
        if save_ws:
            gates_ref[0] = jnp.concatenate([i, f, g, o], axis=1)
            cs_ref[0] = c

        @pl.when(t == n_t - 1)
        def _final():
            hN_ref[:] = h_new.astype(hN_ref.dtype)
            cN_ref[:] = c.astype(cN_ref.dtype)

    return kernel


def _lstm_pallas_fwd(x_proj_tm, rw, b, h0, c0, peepholes, forget_bias,
                     save_workspace=False):
    """x_proj_tm: [T,N,4H] time-major.

    Returns (hs [T,N,H], hT, cT) and, with ``save_workspace``, also the
    post-activation gates [T,N,4H] and cell states [T,N,H].
    """
    t_len, n, fourh = x_proj_tm.shape
    h_dim = fourh // 4
    dtype = x_proj_tm.dtype

    b2 = b.reshape(1, fourh).astype(jnp.float32)
    peep = peepholes is not None
    peep_args = ()
    peep_specs = ()
    if peep:
        peep_args = tuple(p.reshape(1, h_dim).astype(jnp.float32) for p in peepholes)
        peep_specs = tuple(
            pl.BlockSpec((1, h_dim), lambda t: (0, 0)) for _ in range(3)
        )

    kernel = _make_fwd_kernel(peep, save_workspace, float(forget_bias))

    in_specs = [
        pl.BlockSpec((1, n, fourh), lambda t: (t, 0, 0)),  # x_proj step t
        pl.BlockSpec((h_dim, fourh), lambda t: (0, 0)),    # RW resident
        pl.BlockSpec((1, fourh), lambda t: (0, 0)),        # bias
        *peep_specs,
        pl.BlockSpec((n, h_dim), lambda t: (0, 0)),        # h0
        pl.BlockSpec((n, h_dim), lambda t: (0, 0)),        # c0
    ]
    out_specs = [
        pl.BlockSpec((1, n, h_dim), lambda t: (t, 0, 0)),  # hs
        pl.BlockSpec((n, h_dim), lambda t: (0, 0)),        # hT
        pl.BlockSpec((n, h_dim), lambda t: (0, 0)),        # cT
    ]
    out_shape = [
        jax.ShapeDtypeStruct((t_len, n, h_dim), dtype),
        jax.ShapeDtypeStruct((n, h_dim), dtype),
        jax.ShapeDtypeStruct((n, h_dim), dtype),
    ]
    if save_workspace:
        out_specs += [
            pl.BlockSpec((1, n, fourh), lambda t: (t, 0, 0)),  # gates
            pl.BlockSpec((1, n, h_dim), lambda t: (t, 0, 0)),  # cs
        ]
        out_shape += [
            jax.ShapeDtypeStruct((t_len, n, fourh), jnp.float32),
            jax.ShapeDtypeStruct((t_len, n, h_dim), jnp.float32),
        ]
    scratch = [
        pltpu.VMEM((n, h_dim), jnp.float32),
        pltpu.VMEM((n, h_dim), jnp.float32),
    ]

    return pl.pallas_call(
        kernel,
        grid=(t_len,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=_interpret(),
        name="lstm_scan_fwd",
    )(
        x_proj_tm,
        rw.astype(jnp.float32),
        b2,
        *peep_args,
        h0.astype(jnp.float32),
        c0.astype(jnp.float32),
    )


def _make_bwd_kernel(peep: bool):
    """Reversed-time step: grid index i processes t = T-1-i (the index
    maps in _lstm_pallas_bwd do the flip, so refs already hold step t).

    The sweep is dgrad-only (dz per step + the dh/dc carries): weight,
    bias and peephole grads are ONE large batched GEMM / reduction over
    the saved dz tensor OUTSIDE the kernel (the cuDNN dgrad-then-wgrad
    schedule). Accumulating dRW/db per step inside the sweep would put a
    tiny [H,N]x[N,4H] matmul plus a [H,4H] VMEM read-modify-write on the
    sequential critical path of every timestep; hoisting the wgrad out
    removes that work from the recurrence entirely."""

    def kernel(*refs):
        (gates_ref, cs_ref, csp_ref, gh_ref, gcT_ref, rw_ref) = refs[0:6]
        i0 = 6
        if peep:
            pI_ref, pF_ref, pO_ref = refs[6:9]
            i0 = 9
        dxp_ref = refs[i0]
        dh_scr, dc_scr = refs[i0 + 1:]

        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            dh_scr[:] = jnp.zeros_like(dh_scr)
            dc_scr[:] = gcT_ref[:]

        gates = gates_ref[0]
        H = gates.shape[-1] // 4
        ig = gates[:, 0 * H : 1 * H]
        fg = gates[:, 1 * H : 2 * H]
        gg = gates[:, 2 * H : 3 * H]
        og = gates[:, 3 * H : 4 * H]
        c_t = cs_ref[0]
        c_prev = csp_ref[0]

        dh_total = gh_ref[0] + dh_scr[:]
        tanh_c = jnp.tanh(c_t)
        do = dh_total * tanh_c
        dzo = do * og * (1.0 - og)
        dc = dc_scr[:] + dh_total * og * (1.0 - tanh_c * tanh_c)
        if peep:
            dc = dc + dzo * pO_ref[0]
        di = dc * gg
        df = dc * c_prev
        dg = dc * ig
        dzi = di * ig * (1.0 - ig)
        dzf = df * fg * (1.0 - fg)
        dzg = dg * (1.0 - gg * gg)
        dc_next = dc * fg
        if peep:
            dc_next = dc_next + dzi * pI_ref[0] + dzf * pF_ref[0]

        dz = jnp.concatenate([dzi, dzf, dzg, dzo], axis=1)  # [N,4H]
        dxp_ref[0] = dz
        # dh_{t-1} through the recurrent matmul: dz · RWᵀ.
        dh_scr[:] = jax.lax.dot_general(
            dz, rw_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dc_scr[:] = dc_next

    return kernel


def _lstm_pallas_bwd(gates_tm, cs_tm, c_prev_tm, gh_tm, gcT, rw, peepholes):
    """Reversed-time dgrad sweep.

    gates_tm [T,N,4H], cs_tm/c_prev_tm [T,N,H], gh_tm [T,N,H] (upstream
    grad per step incl. the final-state grad folded into the last step),
    gcT [N,H]. Returns dxp_tm [T,N,4H]; weight/bias/peephole grads are
    computed from it outside (one big GEMM — see _make_bwd_kernel).
    """
    t_len, n, fourh = gates_tm.shape
    h_dim = fourh // 4
    peep = peepholes is not None

    rev = lambda i: (t_len - 1 - i, 0, 0)  # noqa: E731 - index map
    const2 = lambda i: (0, 0)  # noqa: E731

    peep_args = ()
    peep_in_specs = ()
    if peep:
        peep_args = tuple(p.reshape(1, h_dim).astype(jnp.float32) for p in peepholes)
        peep_in_specs = tuple(pl.BlockSpec((1, h_dim), const2) for _ in range(3))

    in_specs = [
        pl.BlockSpec((1, n, fourh), rev),   # gates
        pl.BlockSpec((1, n, h_dim), rev),   # c_t
        pl.BlockSpec((1, n, h_dim), rev),   # c_{t-1}
        pl.BlockSpec((1, n, h_dim), rev),   # dL/dh_t (upstream)
        pl.BlockSpec((n, h_dim), const2),   # dL/dc_T
        pl.BlockSpec((h_dim, fourh), const2),  # RW resident
        *peep_in_specs,
    ]
    out_specs = pl.BlockSpec((1, n, fourh), rev)   # dxp
    out_shape = jax.ShapeDtypeStruct((t_len, n, fourh), jnp.float32)
    scratch = [
        pltpu.VMEM((n, h_dim), jnp.float32),  # dh carry
        pltpu.VMEM((n, h_dim), jnp.float32),  # dc carry
    ]

    return pl.pallas_call(
        _make_bwd_kernel(peep),
        grid=(t_len,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=_interpret(),
        name="lstm_scan_bwd",
    )(
        gates_tm,
        cs_tm,
        c_prev_tm,
        gh_tm,
        gcT,
        rw.astype(jnp.float32),
        *peep_args,
    )


def _shapes_tile(n: int, h: int) -> bool:
    return n % 8 == 0 and (4 * h) % 128 == 0 and h % 128 == 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _lstm_core(x, w_x, w_h, b, peep_stack, forget_bias, has_peep):
    """peep_stack: [3,H] array when has_peep else zeros. Returns the triple
    (outputs [N,T,H], h_T [N,H], c_T [N,H])."""
    return _lstm_core_fwd_impl(x, w_x, w_h, b, peep_stack, forget_bias,
                               has_peep)[0]


def _lstm_core_fwd_impl(x, w_x, w_h, b, peep_stack, forget_bias, has_peep,
                        save_workspace=False):
    n, t, _ = x.shape
    h_dim = w_h.shape[0]
    x_proj = jnp.einsum("nti,ih->nth", x, w_x)  # big MXU GEMM outside kernel
    xp_tm = jnp.swapaxes(x_proj, 0, 1).astype(jnp.float32)
    h0 = jnp.zeros((n, h_dim), jnp.float32)
    c0 = jnp.zeros((n, h_dim), jnp.float32)
    peep = tuple(peep_stack) if has_peep else None
    res = _lstm_pallas_fwd(xp_tm, w_h, b, h0, c0, peep, forget_bias,
                           save_workspace=save_workspace)
    hs, hT, cT = res[0:3]
    primal = (jnp.swapaxes(hs, 0, 1).astype(x.dtype), hT, cT)
    ws = (hs, res[3], res[4]) if save_workspace else None
    return primal, ws


def _lstm_core_vjp_fwd(x, w_x, w_h, b, peep_stack, forget_bias, has_peep):
    primal, ws = _lstm_core_fwd_impl(
        x, w_x, w_h, b, peep_stack, forget_bias, has_peep,
        save_workspace=True,
    )
    hs_tm, gates_tm, cs_tm = ws
    return primal, (x, w_x, w_h, b, peep_stack, hs_tm, gates_tm, cs_tm)


def _lstm_core_vjp_bwd(forget_bias, has_peep, res, g):
    x, w_x, w_h, b, peep_stack, hs_tm, gates_tm, cs_tm = res
    g_out, ghT, gcT = g
    t_len, n, h_dim = hs_tm.shape

    zeros_nh = jnp.zeros((1, n, h_dim), jnp.float32)
    h_prev_tm = jnp.concatenate([zeros_nh, hs_tm[:-1].astype(jnp.float32)], 0)
    c_prev_tm = jnp.concatenate([zeros_nh, cs_tm[:-1]], 0)

    gh_tm = jnp.swapaxes(g_out, 0, 1).astype(jnp.float32)
    gh_tm = gh_tm.at[-1].add(ghT.astype(jnp.float32))

    peep = tuple(peep_stack) if has_peep else None
    dxp_tm = _lstm_pallas_bwd(
        gates_tm, cs_tm, c_prev_tm, gh_tm, gcT.astype(jnp.float32), w_h, peep,
    )

    # Wgrad phase: one large MXU GEMM / reduction each over the full dz
    # tensor (dgrad-then-wgrad — see _make_bwd_kernel docstring).
    drw = jnp.einsum("tnh,tnf->hf", h_prev_tm, dxp_tm)
    db = jnp.sum(dxp_tm, axis=(0, 1))
    dx = jnp.einsum("tnh,ih->nti", dxp_tm, w_x.astype(jnp.float32))
    dw_x = jnp.einsum("nti,tnh->ih", x.astype(jnp.float32), dxp_tm)
    if has_peep:
        h_dim_ = c_prev_tm.shape[-1]
        dzi = dxp_tm[:, :, 0 * h_dim_:1 * h_dim_]
        dzf = dxp_tm[:, :, 1 * h_dim_:2 * h_dim_]
        dzo = dxp_tm[:, :, 3 * h_dim_:4 * h_dim_]
        dpeep_stack = jnp.stack([
            jnp.sum(dzi * c_prev_tm, axis=(0, 1)),
            jnp.sum(dzf * c_prev_tm, axis=(0, 1)),
            jnp.sum(dzo * cs_tm, axis=(0, 1)),
        ])
    else:
        dpeep_stack = jnp.zeros_like(peep_stack)
    return (dx.astype(x.dtype), dw_x.astype(w_x.dtype), drw.astype(w_h.dtype),
            db.astype(b.dtype), dpeep_stack.astype(peep_stack.dtype))


_lstm_core.defvjp(_lstm_core_vjp_fwd, _lstm_core_vjp_bwd)


def lstm(
    x,
    w_x,
    w_h,
    b,
    *,
    peepholes=None,
    forget_bias: float = 0.0,
    init_state=None,
):
    """Drop-in replacement for ops/rnn.lstm using the Pallas kernels.

    Falls back to the XLA scan when shapes don't tile onto the TPU VPU/MXU
    (N % 8 != 0 or H % 128 != 0) or when an initial state is supplied
    (kernel currently assumes zero init for the backward sweep).
    """
    n, t, _ = x.shape
    h_dim = w_h.shape[0]
    if init_state is not None or not _shapes_tile(n, h_dim) or not _use_pallas():
        return opsrnn.lstm(
            x, w_x, w_h, b, peepholes=peepholes, forget_bias=forget_bias,
            init_state=init_state,
        )
    if peepholes is not None:
        peep_stack = jnp.stack(peepholes)
        has_peep = True
    else:
        peep_stack = jnp.zeros((3, h_dim), x.dtype)
        has_peep = False
    outputs, h_t, c_t = _lstm_core(x, w_x, w_h, b, peep_stack, float(forget_bias), has_peep)
    return outputs, opsrnn.LSTMState(h_t, c_t)
