"""On-chip Pallas-vs-XLA kernel A/B: compiled parity + speedup.

Run via ``python bench.py --kernels`` on a machine with a TPU attached.
The CPU suites check the Pallas kernels' logic in interpret mode only; this
module compiles BOTH the Pallas kernels and their XLA reference
implementations on the real chip, checks numerical parity of forward AND
backward, and A/B-times them with the same forced-host-materialization
sync that bench.py uses (see bench.py docstring).

Emits one JSON dict (bench.py --kernels prints it).
"""

from __future__ import annotations

import os
import time


def _sync_scalar(x):
    """Force completion: materialize a scalar data-dependent on x."""
    import jax

    return float(jax.device_get(x.ravel()[0] if x.ndim else x))


def _one_window(fn, args, iters):
    """One honestly-synced timing window: async dispatch, one in-window
    materialization that is data-dependent on every call."""
    t0 = time.perf_counter()
    outs = []
    for _ in range(iters):
        o = fn(*args)
        outs.append(o if not isinstance(o, tuple) else o[0])
    # One scalar per call: every dispatch must have completed.
    s = sum(o.ravel()[0] for o in outs)
    _sync_scalar(s)
    return (time.perf_counter() - t0) / iters * 1000  # ms


def _warm(fn, args, n=2):
    for _ in range(n):
        out = fn(*args)
        _sync_scalar(out if not isinstance(out, tuple) else out[0])


def _time_fn(fn, args, iters=30):
    """Best of 3 honestly-synced windows (single-sided); the two warmup
    calls keep window 1 from paying first-touch costs."""
    _warm(fn, args)
    best = None
    for _ in range(3):
        dt = _one_window(fn, args, iters)
        best = dt if best is None else min(best, dt)
    return best


def _time_pair(fn_a, fn_b, args, iters=30, rounds=3):
    """Time two implementations of the same computation INTERLEAVED:
    A,B,A,B,... window by window, min per side.

    Sequential per-side timing (all A windows, then all B windows) lets
    slow drift — host load that varies over seconds — land entirely on one
    side and flip a speedup ratio. Alternating windows gives both sides
    the same exposure to drift; min-of-rounds rejects outlier windows.
    """
    _warm(fn_a, args)
    _warm(fn_b, args)
    best_a = best_b = None
    for _ in range(rounds):
        da = _one_window(fn_a, args, iters)
        db = _one_window(fn_b, args, iters)
        best_a = da if best_a is None else min(best_a, da)
        best_b = db if best_b is None else min(best_b, db)
    return best_a, best_b


def _max_rel_err(a, b):
    import jax
    import numpy as np

    a = np.asarray(jax.device_get(a), np.float32)
    b = np.asarray(jax.device_get(b), np.float32)
    denom = np.maximum(np.abs(b).max(), 1e-6)
    return float(np.abs(a - b).max() / denom)


def _flash_ab(iters=30, B=8, H=12, T=512, D=64, causal=False,
              dtype="float32", masked=True):
    """``masked``: a key-padding mask of random lengths rides along (the
    BERT case); without it and in bfloat16 the leg is a training cell's own
    call. The oracle always computes from the same values in float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.kernels.flash_attention import (
        flash_attention,
        reference_attention,
    )

    r = np.random.default_rng(0)
    q = jnp.asarray(r.normal(size=(B, H, T, D)), dtype)
    k = jnp.asarray(r.normal(size=(B, H, T, D)), dtype)
    v = jnp.asarray(r.normal(size=(B, H, T, D)), dtype)
    lens = r.integers(T // 2, T + 1, B)
    key_mask = jnp.asarray(
        (np.arange(T)[None, :] < lens[:, None]).astype(np.float32)
    ) if masked else None

    out = {"shape": f"B{B} H{H} T{T} D{D} {dtype}", "iters": iters}

    def reference(q, k, v):
        return reference_attention(
            *(x.astype(jnp.float32) for x in (q, k, v)),
            key_mask=key_mask, causal=causal)

    flash_f = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, key_mask=key_mask, causal=causal, backend="pallas"))
    ref_f = jax.jit(reference)

    of, orf = flash_f(q, k, v), ref_f(q, k, v)
    # Padded key rows of the reference produce uniform-attention outputs that
    # callers never read; compare only live queries (all queries are live —
    # key_mask masks keys, so outputs differ only via masked softmax: both
    # implement it, all rows comparable).
    out["fwd_max_rel_err"] = _max_rel_err(of, orf)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, key_mask=key_mask, causal=causal,
            backend="pallas").astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference(q, k, v) ** 2)

    gflash = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))
    gref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))
    gf, gr = gflash(q, k, v), gref(q, k, v)
    out["bwd_max_rel_err"] = max(_max_rel_err(a, b) for a, b in zip(gf, gr))

    fp, fx = _time_pair(flash_f, ref_f, (q, k, v), iters)
    out["fwd_ms"] = {"pallas": fp, "xla": fx}
    bp, bx = _time_pair(lambda *a: gflash(*a)[0], lambda *a: gref(*a)[0],
                        (q, k, v), iters)
    out["bwd_ms"] = {"pallas": bp, "xla": bx}
    out["fwd_speedup"] = round(out["fwd_ms"]["xla"] / out["fwd_ms"]["pallas"], 3)
    out["bwd_speedup"] = round(out["bwd_ms"]["xla"] / out["bwd_ms"]["pallas"], 3)
    out["parity"] = bool(out["fwd_max_rel_err"] < 2e-2
                         and out["bwd_max_rel_err"] < 2e-2)
    return out


def _lstm_ab(iters=30):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.kernels import lstm_scan
    from deeplearning4j_tpu.ops import rnn as opsrnn

    N, T, H, C = 32, 256, 256, 256
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(N, T, C)) * 0.1, jnp.float32)
    w_x = jnp.asarray(r.normal(size=(C, 4 * H)) * 0.05, jnp.float32)
    w_h = jnp.asarray(r.normal(size=(H, 4 * H)) * 0.05, jnp.float32)
    b = jnp.zeros((4 * H,), jnp.float32)
    peep = tuple(jnp.asarray(r.normal(size=(H,)) * 0.05, jnp.float32)
                 for _ in range(3))

    out = {"shape": f"N{N} T{T} H{H}", "iters": iters}

    pallas_f = jax.jit(lambda x: lstm_scan.lstm(x, w_x, w_h, b, peepholes=peep,
                                                forget_bias=1.0)[0])
    xla_f = jax.jit(lambda x: opsrnn.lstm(x, w_x, w_h, b, peepholes=peep,
                                          forget_bias=1.0)[0])
    op, ox = pallas_f(x), xla_f(x)
    out["fwd_max_rel_err"] = _max_rel_err(op, ox)

    gpallas = jax.jit(jax.grad(lambda x: jnp.sum(pallas_f(x) ** 2)))
    gxla = jax.jit(jax.grad(lambda x: jnp.sum(xla_f(x) ** 2)))
    gp, gx = gpallas(x), gxla(x)
    out["bwd_max_rel_err"] = _max_rel_err(gp, gx)

    fp, fx = _time_pair(pallas_f, xla_f, (x,), iters)
    out["fwd_ms"] = {"pallas": fp, "xla": fx}
    bp, bx = _time_pair(gpallas, gxla, (x,), iters)
    out["bwd_ms"] = {"pallas": bp, "xla": bx}
    out["fwd_speedup"] = round(out["fwd_ms"]["xla"] / out["fwd_ms"]["pallas"], 3)
    out["bwd_speedup"] = round(out["bwd_ms"]["xla"] / out["bwd_ms"]["pallas"], 3)
    out["parity"] = bool(out["fwd_max_rel_err"] < 2e-2
                         and out["bwd_max_rel_err"] < 2e-2)
    return out


def _gru_ab(iters=30):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.kernels import gru_scan
    from deeplearning4j_tpu.ops import rnn as opsrnn

    N, T, H, C = 32, 256, 256, 256
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(N, T, C)) * 0.1, jnp.float32)
    w_x = jnp.asarray(r.normal(size=(C, 3 * H)) * 0.05, jnp.float32)
    w_h = jnp.asarray(r.normal(size=(H, 3 * H)) * 0.05, jnp.float32)
    b = jnp.asarray(r.normal(size=(3 * H,)) * 0.05, jnp.float32)

    out = {"shape": f"N{N} T{T} H{H}", "iters": iters}

    pallas_f = jax.jit(lambda x: gru_scan.gru(x, w_x, w_h, b)[0])
    xla_f = jax.jit(lambda x: opsrnn.gru(x, w_x, w_h, b)[0])
    op, ox = pallas_f(x), xla_f(x)
    out["fwd_max_rel_err"] = _max_rel_err(op, ox)

    gpallas = jax.jit(jax.grad(lambda x: jnp.sum(pallas_f(x) ** 2)))
    gxla = jax.jit(jax.grad(lambda x: jnp.sum(xla_f(x) ** 2)))
    gp, gx = gpallas(x), gxla(x)
    out["bwd_max_rel_err"] = _max_rel_err(gp, gx)

    fp, fx = _time_pair(pallas_f, xla_f, (x,), iters)
    out["fwd_ms"] = {"pallas": fp, "xla": fx}
    bp, bx = _time_pair(gpallas, gxla, (x,), iters)
    out["bwd_ms"] = {"pallas": bp, "xla": bx}
    out["fwd_speedup"] = round(out["fwd_ms"]["xla"] / out["fwd_ms"]["pallas"], 3)
    out["bwd_speedup"] = round(out["bwd_ms"]["xla"] / out["bwd_ms"]["pallas"], 3)
    out["parity"] = bool(out["fwd_max_rel_err"] < 2e-2
                         and out["bwd_max_rel_err"] < 2e-2)
    return out


def _flash_tune(iters=8, B=8, H=12, T=512, D=64, causal=False):
    """On-chip block-size sweep for the flash kernel.

    Times fwd+bwd with all three kernels at each (block_q, block_k) and
    reports the best. The dispatch defaults
    (kernels/_dispatch.flash_block_sizes) came from a per-kernel sweep of
    the same grid read from a profiler trace (PERF.md section 5).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.kernels.flash_attention import flash_attention

    r = np.random.default_rng(0)
    q = jnp.asarray(r.normal(size=(B, H, T, D)), jnp.float32)
    k = jnp.asarray(r.normal(size=(B, H, T, D)), jnp.float32)
    v = jnp.asarray(r.normal(size=(B, H, T, D)), jnp.float32)

    geometries = [(128, 128), (128, 256), (256, 256), (256, 512),
                  (512, 512), (128, 512),
                  # r5: wider kv blocks for the T=1024 fwd gap (0.83x in
                  # r4) — bk=T collapses the sequential kv sweep to one
                  # iteration; score tile 512x1024 f32 = 2 MB, in VMEM
                  (256, 1024), (512, 1024), (1024, 1024)]
    out = {"shape": f"B{B} H{H} T{T} D{D} causal={causal}", "iters": iters,
           "sweep": {}}
    best = None
    for bq, bk in geometries:
        if bq > T or bk > T:
            continue
        key = f"q{bq}_k{bk}"
        try:
            f = jax.jit(lambda q, k, v, bq=bq, bk=bk: flash_attention(
                q, k, v, causal=causal, backend="pallas",
                block_q=bq, block_k=bk))
            g = jax.jit(jax.grad(
                lambda q, k, v, bq=bq, bk=bk: jnp.sum(flash_attention(
                    q, k, v, causal=causal, backend="pallas",
                    block_q=bq, block_k=bk) ** 2), argnums=(0, 1, 2)))
            fwd = _time_fn(f, (q, k, v), iters)
            bwd = _time_fn(lambda *a: g(*a)[0], (q, k, v), iters)
            out["sweep"][key] = {"fwd_ms": round(fwd, 3), "bwd_ms": round(bwd, 3)}
            if best is None or fwd + bwd < best[1]:
                best = (key, fwd + bwd)
        except Exception as e:  # noqa: BLE001 - record, keep sweeping
            out["sweep"][key] = {"error": str(e)[:160]}
    if best:
        out["best"] = best[0]
    return out


def run_kernels_ab(diag: dict, include_tune: bool = True,
                   canonical: bool = False) -> dict:
    import jax

    from deeplearning4j_tpu.runtime.compilecache import enable_compile_cache

    platform = jax.devices()[0].platform
    if platform != "tpu":
        # off-TPU the LSTM/GRU scans route to XLA (kernels/_dispatch.py):
        # the "A/B" would compare XLA against itself
        raise RuntimeError(
            f"refusing to A/B on platform {platform!r}: the compiled "
            "Pallas kernels exist only on TPU")
    enable_compile_cache()
    result = {"metric": "pallas_kernel_ab", "platform": platform, **diag}
    # The long-context shape is where the flash kernel's O(T) memory is the
    # point (the T^2 score materialization of the XLA reference is ~1 GiB
    # here): record whether the dispatch policy's DL4J_TPU_FLASH_MIN_SEQ
    # crossover is justified.
    flash_long = lambda: _flash_ab(iters=10, B=2, H=8, T=4096, D=64,
                                   causal=True)
    # The auto-dispatch crossover (DL4J_TPU_FLASH_MIN_SEQ=1024): measure
    # the A/B exactly at the boundary shape so the policy is justified by
    # a recorded number rather than interpolation.
    flash_1024 = lambda: _flash_ab(iters=15, B=4, H=12, T=1024, D=64,
                                   causal=True)
    # gpt2_small.train_s1024's own call (PERF.md section 4): bf16, causal,
    # no key mask, the geometry the dispatch chooses for it.
    flash_cell = lambda: _flash_ab(iters=10, B=16, H=12, T=1024, D=64,
                                   causal=True, dtype="bfloat16",
                                   masked=False)
    tune_long = lambda: _flash_tune(iters=6, B=2, H=8, T=2048, D=64,
                                    causal=True)
    tune_1024 = lambda: _flash_tune(iters=8, B=4, H=12, T=1024, D=64,
                                    causal=True)
    tune_legs = [("flash_tune_512", _flash_tune),
                 ("flash_tune_1024", tune_1024),
                 ("flash_tune_2048", tune_long)] if include_tune else []
    legs = ([("flash_attention", _flash_ab),
             ("flash_attention_1024", flash_1024),
             ("flash_attention_cell", flash_cell),
             ("flash_attention_long", flash_long)]
            + tune_legs
            + [("lstm_scan", _lstm_ab), ("gru_scan", _gru_ab)])
    # Canonical-protocol provenance: concurrent host load can flip a
    # speedup ratio (see _time_pair docstring). Sample the load average
    # BEFORE and AFTER the legs — a quiet start instant does not certify
    # a minutes-long run — and mark the table canonical only when both
    # samples are quiet.
    try:
        load_before = os.getloadavg()
    except OSError:  # pragma: no cover
        load_before = None
    # Per-LEG load certification: each sample's own-CPU correction uses
    # only that leg's interval, so it tracks the 1-min loadavg EWMA far
    # better than a whole-run average (which would let early compile
    # bursts mask late foreign load, or a long quiet tail fail a clean
    # run). foreign ~ loadavg - own_cpu_share over the same interval.
    leg_loads = []
    certified = load_before is not None and load_before[0] < 2.0
    t_leg, cpu_leg = time.time(), sum(os.times()[:4])
    for name, fn in legs:
        result[name] = fn()  # a leg that fails fails the A/B
        try:
            la = os.getloadavg()[0]
        except OSError:  # pragma: no cover
            certified = False
            continue
        now, cpu_now = time.time(), sum(os.times()[:4])
        own = (cpu_now - cpu_leg) / max(now - t_leg, 1e-6)
        foreign = max(0.0, la - own)
        leg_loads.append({"leg": name, "load1": round(la, 2),
                          "own_cpu_util": round(own, 2),
                          "foreign_est": round(foreign, 2)})
        if foreign >= 2.0:
            certified = False
        t_leg, cpu_leg = now, cpu_now
    if load_before is not None:
        result["host_loadavg"] = {
            "before": [round(x, 2) for x in load_before],
            "per_leg": leg_loads}
        result["canonical"] = bool(canonical and certified)
    return result
