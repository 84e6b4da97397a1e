#!/usr/bin/env python3
"""Chip smoke: the quickest proof that the system still starts on the TPU.

One process drives the main path once through the public entry points at
the published widths of ``gpt2_small`` (12 layers, hidden 768, 12 heads,
vocab 50257, context 1024), weights random from a seed:

1. device    — jax in-process; exits non-zero before building anything
               unless the platform is ``tpu``. Also checks once that a
               host clock around ``block_until_ready`` is sane.
2. trainer   — ``Trainer(gpt2_small)`` with the ``bench_gpt`` settings
               (bf16 mixed precision, hardware RNG, Adam) at batch 8 x
               sequence 1024: the compiled Pallas flash forward and
               backward run inside the full model.
3. four_chip — only where ``jax.device_count() >= 4``: the README's
               data-parallel recipe and the dp x tp2 plan of
               ``__graft_entry__.dryrun_multichip`` on the real devices.
               Skipped by name on fewer chips.
4. serving   — ``GenerationEngine`` behind ``ModelServer``, warmed, then
               concurrent ``ServingClient.generate()`` streams over
               loopback HTTP, short and long prompts.
5. kernels   — every Pallas kernel a model dispatches to, compiled by
               Mosaic, forward and backward against its XLA reference.

Prints one summary line and, only when every leg passed on a TPU, the
result line ``{"ok": true, "device": {...}}`` last; exits non-zero
otherwise. Times in the summary are set-up and sanity readings of this
run, not benchmark metrics.

``--rehearse`` is the CPU rehearsal at tiny widths (four virtual devices,
kernels interpreted): it says so, and never prints the result line.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import statistics
import sys
import threading
import time
import traceback
import urllib.request

# Kernel parity bound: max |pallas - xla| over max |xla|, the bound the
# on-chip kernel tests use (tests/test_kernels_tpu.py via kernels_ab.py).
# Both sides run their matmuls in single-pass bf16 on the MXU.
KERNEL_TOL = 2e-2
# prefill_chunk vs the full forward on one short prompt, same metric.
LOGITS_TOL = 2e-2
# Step-1 loss, four chips vs one: same seed and batch, but dropout masks
# come from the hardware generator per shard and reductions reassociate.
# Absolute, on a loss of about ln(50257) = 10.8; 1e-4 was measured (PR 21).
MESH_LOSS_TOL = 0.01


@dataclasses.dataclass(frozen=True)
class Sizes:
    gpt: dict            # GptConfig overrides on top of gpt2_small
    batch: int
    seq: int
    train_steps: int
    mesh_steps: int
    num_slots: int
    max_len: int
    min_kv_bucket: int
    min_prompt_bucket: int
    prompts: tuple       # (prompt_len, max_new_tokens) per stream
    flash: tuple         # B, H, T, D
    rnn: tuple           # batch, seq, hidden, vocab (bench_lstm shape)
    sync_dim: int


CHIP = Sizes(
    gpt={}, batch=8, seq=1024, train_steps=6, mesh_steps=3,
    # 4 slot buckets x 4 kv buckets + 5 prompt buckets = 21 programs
    num_slots=8, max_len=1024, min_kv_bucket=128, min_prompt_bucket=64,
    prompts=((5, 24), (40, 24), (100, 32), (300, 16), (700, 16),
             (990, 24), (64, 8), (17, 40)),
    flash=(8, 12, 1024, 64), rnn=(32, 256, 256, 77), sync_dim=4096)

REHEARSAL = Sizes(
    # no dropout: at this width a step moves the loss less than a mask does
    gpt=dict(hidden=64, num_layers=2, num_heads=2, intermediate=128,
             vocab_size=256, max_position=64, dropout=0.0,
             attention_dropout=0.0),
    batch=8, seq=32, train_steps=8, mesh_steps=8,
    num_slots=2, max_len=32, min_kv_bucket=16, min_prompt_bucket=8,
    prompts=((3, 6), (9, 4), (20, 5), (30, 2)),
    flash=(1, 2, 32, 16), rnn=(8, 8, 128, 16), sync_dim=256)


def log(msg: str):
    print(f"chip_smoke: {msg}", flush=True)


def _max_rel_err(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-6))


def _sig(x: float) -> float:
    return float(f"{x:.3g}")


def _check(cond: bool, what: str):
    if not cond:
        raise AssertionError(what)


# -- leg: device ---------------------------------------------------------------

def leg_sync(sz: Sizes) -> dict:
    """A host clock around ``block_until_ready`` must agree with one that
    closes on a device_get of a value data-dependent on the work."""
    import jax
    import jax.numpy as jnp

    n, reps = sz.sync_dim, 20
    a = jnp.ones((n, n), jnp.bfloat16)

    @jax.jit
    def chain(x):
        for _ in range(reps):
            x = (x @ a) * (1.0 / n)
        return x

    def timed(close) -> float:
        close(chain(a))  # compile, and the slice program of the get
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            close(chain(a))
            best = min(best, time.perf_counter() - t0)
        return best

    t_block = timed(jax.block_until_ready)
    t_get = timed(lambda x: float(jax.device_get(x[0, 0])))
    out = {"block_until_ready_ms": round(t_block * 1e3, 3),
           "device_get_ms": round(t_get * 1e3, 3),
           "sanity_tflops": round(2 * n ** 3 * reps / t_block / 1e12, 1)}
    # dispatch-only timing would read orders of magnitude short
    _check(t_block > 0.5 * t_get,
           f"block_until_ready returned early: {out}")
    return out


# -- leg: trainer --------------------------------------------------------------

def _gpt_model(sz: Sizes):
    from deeplearning4j_tpu.models.gpt import gpt2_small
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.train.updaters import Adam

    return gpt2_small(
        net=NeuralNetConfiguration(
            updater=Adam(1e-4), mixed_precision=True, rng_impl="rbg"),
        **sz.gpt)


def _gpt_batch(sz: Sizes, vocab: int):
    import numpy as np

    ids = np.random.default_rng(0).integers(
        0, vocab, (sz.batch, sz.seq)).astype(np.int32)
    return {"features": {"token_ids": ids}}


def _run_steps(trainer, ts, batch, n: int):
    """n train steps on one fixed batch -> (ts, losses, per-step seconds)."""
    import jax

    losses, secs = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        ts, m = trainer.train_step(ts, batch)
        jax.block_until_ready(ts.params)
        losses.append(float(m["total_loss"]))
        secs.append(time.perf_counter() - t0)
    return ts, losses, secs


def _check_losses(losses, what: str):
    import numpy as np

    _check(bool(np.isfinite(losses).all()), f"{what}: non-finite {losses}")
    _check(losses[-1] < losses[0], f"{what}: not decreasing {losses}")


def leg_trainer(sz: Sizes, on_chip: bool) -> dict:
    import jax

    from deeplearning4j_tpu.train.trainer import Trainer

    model = _gpt_model(sz)
    trainer = Trainer(model)
    ts = trainer.init_state()
    batch = jax.device_put(_gpt_batch(sz, model.config.vocab_size))

    t0 = time.perf_counter()
    lowered = trainer.train_step.lower(ts, batch)
    mosaic_lowered = lowered.as_text().count("tpu_custom_call")
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    mosaic_compiled = compiled.as_text().count("tpu_custom_call")
    del compiled, lowered
    if on_chip:
        # T=1024 crosses flash_min_seq(): the kernel must not have given
        # way to reference_attention anywhere between trace and binary
        _check(mosaic_lowered > 0 and mosaic_compiled > 0,
               f"no Mosaic custom call in the train step (lowered "
               f"{mosaic_lowered}, compiled {mosaic_compiled})")

    ts, losses, secs = _run_steps(trainer, ts, batch, sz.train_steps)
    _check_losses(losses, "trainer")
    stats = jax.devices()[0].memory_stats() or {}
    return {
        "params": model.num_params(trainer.variables(ts)),
        "batch_x_seq": [sz.batch, sz.seq],
        "mosaic_calls": {"lowered": mosaic_lowered,
                         "compiled": mosaic_compiled},
        "setup_s": {"lower": round(t_lower, 1),
                    "cold_compile": round(t_compile, 1),
                    "first_step": round(secs[0], 1)},
        "sanity_warm_step_ms": round(statistics.median(secs[1:]) * 1e3, 1),
        "losses": [round(x, 4) for x in losses],
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
    }


# -- leg: four chips -----------------------------------------------------------

def _device_ids(tree) -> set:
    import jax

    return {s.device.id for leaf in jax.tree_util.tree_leaves(tree)
            for s in leaf.addressable_shards}


def _mesh_run(sz: Sizes, name: str, mesh, plan, ref_loss: float) -> dict:
    import jax

    from deeplearning4j_tpu.train.trainer import Trainer

    model = _gpt_model(sz)
    template = Trainer(model).init_state()
    state_sh, batch_sh = plan(mesh, template)
    trainer = Trainer(model, mesh=mesh, state_sharding=state_sh,
                      batch_sharding=batch_sh)
    ts = jax.device_put(template, state_sh)
    batch = jax.device_put(_gpt_batch(sz, model.config.vocab_size),
                           batch_sh)
    n_mesh = mesh.devices.size
    on_params, on_batch = _device_ids(ts.params), _device_ids(batch)
    _check(len(on_params) == n_mesh and len(on_batch) == n_mesh,
           f"{name}: params on devices {sorted(on_params)}, batch on "
           f"{sorted(on_batch)}, mesh has {n_mesh}")
    t0 = time.perf_counter()
    ts, losses, secs = _run_steps(trainer, ts, batch, sz.mesh_steps)
    _check_losses(losses, name)
    _check(len(_device_ids(ts.params)) == n_mesh,
           f"{name}: updated params left the mesh")
    _check(abs(losses[0] - ref_loss) <= MESH_LOSS_TOL,
           f"{name}: step-1 loss {losses[0]:.4f} vs one-chip "
           f"{ref_loss:.4f} (tolerance {MESH_LOSS_TOL})")
    leaves = jax.tree_util.tree_leaves(ts.params)
    return {
        "mesh": dict(mesh.shape),
        "devices": sorted(on_params),
        "split_leaves": sum(
            1 for a in leaves
            if a.addressable_shards[0].data.shape != a.shape),
        "losses": [round(x, 4) for x in losses],
        "step1_minus_one_chip": round(losses[0] - ref_loss, 4),
        "setup_s": {"first_step": round(secs[0], 1)},
        "sanity_warm_step_ms": round(min(secs[1:]) * 1e3, 1),
        "wall_s": round(time.perf_counter() - t0, 1),
    }


def leg_four_chip(sz: Sizes, ref_loss: float) -> dict:
    from deeplearning4j_tpu.parallel.specs import (
        data_parallel_plan,
        tensor_parallel_plan,
        train_state_sharding,
    )
    from deeplearning4j_tpu.runtime.device import MeshSpec, build_mesh

    def dp_plan(mesh, template):
        return data_parallel_plan(mesh)

    def tp_plan(mesh, template):
        params_sh, batch_sh = tensor_parallel_plan(mesh, template.params)
        return train_state_sharding(mesh, template, params_sh), batch_sh

    out = {"data_parallel": _mesh_run(
        sz, "data_parallel", build_mesh(MeshSpec(data=-1)), dp_plan,
        ref_loss)}
    gc.collect()
    out["dp_x_tp2"] = _mesh_run(
        sz, "dp_x_tp2", build_mesh(MeshSpec(data=-1, model=2)), tp_plan,
        ref_loss)
    _check(out["dp_x_tp2"]["split_leaves"] > 0,
           "dp_x_tp2: no parameter is actually split over the model axis")
    return out


# -- leg: serving --------------------------------------------------------------

def leg_serving(sz: Sizes) -> dict:
    import jax
    import numpy as np

    from deeplearning4j_tpu.models.gpt import gpt2_small
    from deeplearning4j_tpu.observability.flightrecorder import (
        get_flight_recorder,
    )
    from deeplearning4j_tpu.serving import (
        GenerationEngine,
        ModelServer,
        ServingClient,
    )

    model = gpt2_small(**sz.gpt)
    variables = model.init(seed=0)
    vocab = model.config.vocab_size
    rng = np.random.default_rng(1)

    # reference on a small input: whole-prompt prefill against the full
    # forward the Trainer uses (shared block code, separate programs)
    ids = rng.integers(0, vocab, (1, sz.min_prompt_bucket)).astype(np.int32)
    lg_prefill, _ = jax.jit(model.prefill_chunk)(variables["params"], ids)
    lg_full, _ = jax.jit(lambda v, x: model.apply(v, x))(variables, ids)
    lg_prefill = np.asarray(lg_prefill)
    _check(lg_prefill.shape == (1, sz.min_prompt_bucket, vocab),
           f"prefill logits shape {lg_prefill.shape}")
    _check(bool(np.isfinite(lg_prefill).all()), "prefill logits not finite")
    logits_err = _max_rel_err(lg_prefill, lg_full)
    _check(logits_err < LOGITS_TOL,
           f"prefill vs full forward: {logits_err:.2e} >= {LOGITS_TOL}")

    engine = GenerationEngine(
        model, variables, name="gpt2", num_slots=sz.num_slots,
        max_len=sz.max_len, max_new_tokens=max(n for _, n in sz.prompts),
        min_kv_bucket=sz.min_kv_bucket,
        min_prompt_bucket=sz.min_prompt_bucket, temperature=0.0, seed=0)
    n_programs = len(engine.prompt_buckets) + \
        len(engine.slot_buckets) * len(engine.kv_buckets)
    log(f"serving: warm vocabulary {n_programs} programs = "
        f"{len(engine.prompt_buckets)} prefill {engine.prompt_buckets} + "
        f"{len(engine.slot_buckets)}x{len(engine.kv_buckets)} decode "
        f"(slots {engine.slot_buckets}, kv {engine.kv_buckets})")
    server = ModelServer(port=0, generators={"gpt2": engine})
    t0 = time.perf_counter()
    server.start(warm=True)
    t_ready = time.perf_counter() - t0
    try:
        with urllib.request.urlopen(server.url + "/readyz", timeout=30) as r:
            _check(r.status == 200, f"/readyz answered {r.status}")
        results: list = [None] * len(sz.prompts)

        def stream(i: int, prompt_len: int, n_new: int):
            try:
                prompt = np.random.default_rng(100 + i).integers(
                    0, vocab, prompt_len).tolist()
                toks = list(ServingClient(server.url, timeout=300).generate(
                    "gpt2", prompt, max_new_tokens=n_new,
                    deadline_ms=300000.0))
                # generate() returns only after the terminal done line;
                # anything else raises a typed error
                results[i] = toks
            except Exception as e:  # noqa: BLE001 - reported per stream
                results[i] = e

        t0 = time.perf_counter()
        threads = [threading.Thread(target=stream, args=(i, p, n))
                   for i, (p, n) in enumerate(sz.prompts)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600)
        t_streams = time.perf_counter() - t0
        _check(not any(th.is_alive() for th in threads),
               "a generate() stream did not finish in 600 s")
        for (p, n), got in zip(sz.prompts, results):
            _check(not isinstance(got, Exception),
                   f"stream prompt={p}: {got!r}")
            _check(len(got) == n and all(0 <= t < vocab for t in got),
                   f"stream prompt={p}: wanted {n} tokens, got {got}")
        errors = get_flight_recorder().events(kinds=["generation.error"])
        _check(not errors, f"generation.error events: {errors}")
        _check(engine.compiles_after_warm == 0,
               f"compiles_after_warm = {engine.compiles_after_warm}")
        desc = engine.describe()
    finally:
        server.stop()
    warm = engine.warm_stats
    top = str(engine.prompt_buckets[-1])
    return {
        "programs": n_programs,
        "prefill_vs_full_forward_max_rel_err": _sig(logits_err),
        "streams": len(sz.prompts),
        "tokens": sum(n for _, n in sz.prompts),
        "decode_steps": desc["decode_steps"],
        "compiles_after_warm": engine.compiles_after_warm,
        "generation_error_events": 0,
        "kv_bytes": engine.kv_bytes,
        "setup_s": {
            "start_warm": round(t_ready, 1),
            "prefill_warm": warm["prefill"],
            f"prefill_top_bucket_{top}": warm["prefill"][top],
            "decode_warm_total": round(sum(warm["decode"].values()), 1),
        },
        "sanity_streams_wall_s": round(t_streams, 2),
    }


# -- leg: kernels --------------------------------------------------------------

def _mosaic(fn, *args) -> int:
    import jax

    return jax.jit(fn).lower(*args).as_text().count("tpu_custom_call")


def _kernel_flash(sz: Sizes, on_chip: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.kernels.flash_attention import (
        flash_attention,
        reference_attention,
    )

    r = np.random.default_rng(0)
    q, k, v = (jnp.asarray(r.normal(size=sz.flash), jnp.float32)
               for _ in range(3))

    def pallas(q, k, v):
        return flash_attention(q, k, v, causal=True, backend="pallas")

    def xla(q, k, v):
        return reference_attention(q, k, v, causal=True)

    def grads(f):
        return jax.jit(jax.grad(lambda q, k, v: jnp.sum(f(q, k, v) ** 2),
                                argnums=(0, 1, 2)))

    calls = _mosaic(grads(pallas), q, k, v)
    if on_chip:
        _check(calls >= 3, f"flash fwd+bwd lowered {calls} Mosaic calls")
    fwd = _max_rel_err(jax.jit(pallas)(q, k, v), jax.jit(xla)(q, k, v))
    bwd = max(_max_rel_err(a, b) for a, b in
              zip(grads(pallas)(q, k, v), grads(xla)(q, k, v)))
    _check(fwd < KERNEL_TOL and bwd < KERNEL_TOL,
           f"flash_attention parity fwd {fwd:.2e} bwd {bwd:.2e}")
    return {"shape": list(sz.flash), "mosaic_calls": calls,
            "fwd_max_rel_err": _sig(fwd), "bwd_max_rel_err": _sig(bwd)}


def _kernel_lstm(sz: Sizes, on_chip: bool) -> dict:
    """GravesLSTM exactly as ``bench_lstm`` builds it: the two-layer
    ``text_generation_lstm`` (peepholes), loss and parameter gradients,
    Pallas scan against the XLA scan over the same variables."""
    import jax
    import numpy as np

    from deeplearning4j_tpu.models.zoo.classic import text_generation_lstm

    b, t, hidden, vocab = sz.rnn
    models = {be: text_generation_lstm(vocab_size=vocab, hidden=hidden,
                                       seq_len=t, backend=be)
              for be in ("pallas", "xla")}
    variables = models["xla"].init(seed=0)
    r = np.random.default_rng(0)
    ids = r.integers(0, vocab, (b, t + 1))
    eye = np.eye(vocab, dtype=np.float32)
    batch = {"features": eye[ids[:, :-1]], "labels": eye[ids[:, 1:]]}

    def loss_and_grads(model):
        def f(params):
            return model.loss_fn(params, variables["state"], batch)[0]
        return jax.value_and_grad(f)

    calls = _mosaic(loss_and_grads(models["pallas"]), variables["params"])
    if on_chip:
        _check(calls >= 4, f"lstm fwd+bwd lowered {calls} Mosaic calls")
    lp, gp = jax.jit(loss_and_grads(models["pallas"]))(variables["params"])
    lx, gx = jax.jit(loss_and_grads(models["xla"]))(variables["params"])
    fwd = abs(float(lp) - float(lx)) / abs(float(lx))
    bwd = max(_max_rel_err(a, b) for a, b in zip(
        jax.tree_util.tree_leaves(gp), jax.tree_util.tree_leaves(gx)))
    _check(fwd < KERNEL_TOL and bwd < KERNEL_TOL,
           f"lstm_scan parity loss {fwd:.2e} grads {bwd:.2e}")
    return {"shape": list(sz.rnn), "mosaic_calls": calls,
            "loss_rel_err": _sig(fwd), "bwd_max_rel_err": _sig(bwd)}


def _kernel_gru(sz: Sizes, on_chip: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.nn.layers import GRU

    b, t, hidden, _ = sz.rnn
    layers = {be: GRU(units=hidden, backend=be) for be in ("pallas", "xla")}
    params, _ = layers["xla"].init(jax.random.key(0), (t, hidden),
                                   jnp.float32)
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(b, t, hidden)) * 0.1, jnp.float32)

    def out_and_grads(layer):
        def f(params, x):
            y, _ = layer.apply(params, {}, x)
            return jnp.sum(y ** 2), y
        return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)

    calls = _mosaic(out_and_grads(layers["pallas"]), params, x)
    if on_chip:
        _check(calls >= 2, f"gru fwd+bwd lowered {calls} Mosaic calls")
    (_, yp), gp = jax.jit(out_and_grads(layers["pallas"]))(params, x)
    (_, yx), gx = jax.jit(out_and_grads(layers["xla"]))(params, x)
    fwd = _max_rel_err(yp, yx)
    bwd = max(_max_rel_err(a, b) for a, b in zip(
        jax.tree_util.tree_leaves(gp), jax.tree_util.tree_leaves(gx)))
    _check(fwd < KERNEL_TOL and bwd < KERNEL_TOL,
           f"gru_scan parity fwd {fwd:.2e} bwd {bwd:.2e}")
    return {"shape": [b, t, hidden], "mosaic_calls": calls,
            "fwd_max_rel_err": _sig(fwd), "bwd_max_rel_err": _sig(bwd)}


def leg_kernels(sz: Sizes, on_chip: bool) -> dict:
    if not on_chip:
        # the rehearsal's one way into interpret mode (kernels/_dispatch)
        os.environ["DL4J_TPU_FORCE_PALLAS"] = "1"
    try:
        return {"flash_attention": _kernel_flash(sz, on_chip),
                "lstm_scan": _kernel_lstm(sz, on_chip),
                "gru_scan": _kernel_gru(sz, on_chip)}
    finally:
        if not on_chip:
            del os.environ["DL4J_TPU_FORCE_PALLAS"]


# -- driver --------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny widths; never prints the "
                         "result line")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()

    t_start = time.perf_counter()
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"platform={device['platform']} device_kind={device['kind']!r} "
        f"count={device['count']} jax={jax.__version__}")
    on_chip = device["platform"] == "tpu"
    if args.rehearse:
        log("REHEARSAL on CPU at tiny widths: not a chip result")
    elif not on_chip:
        print(f"chip_smoke: platform is {device['platform']!r}, not 'tpu': "
              "no accelerator, nothing was built", file=sys.stderr)
        return 2
    sz = REHEARSAL if args.rehearse else CHIP

    from deeplearning4j_tpu.runtime.compilecache import enable_compile_cache

    cache = enable_compile_cache()
    cache_events = {"hits": 0, "misses": 0}

    def on_event(event: str, **_kw):
        if event.endswith("/compilation_cache/cache_hits"):
            cache_events["hits"] += 1
        elif event.endswith("/compilation_cache/cache_misses"):
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)

    legs: dict = {}
    failed: list = []

    def run(name: str, fn, *a):
        log(f"leg {name}: start")
        t0 = time.perf_counter()
        before = dict(cache_events)
        try:
            legs[name] = {"ok": True, **fn(*a)}
        except Exception as e:  # noqa: BLE001 - recorded as a FAILED leg
            traceback.print_exc()
            legs[name] = {"ok": False,
                          "error": f"{type(e).__name__}: {e}"[:400]}
            failed.append(name)
        legs[name]["wall_s"] = round(time.perf_counter() - t0, 1)
        legs[name]["persistent_cache"] = {
            k: cache_events[k] - before[k] for k in cache_events}
        gc.collect()
        log(f"leg {name}: {'ok' if legs[name]['ok'] else 'FAILED'} "
            f"{json.dumps(legs[name])}")

    run("sync", leg_sync, sz)
    run("trainer", leg_trainer, sz, on_chip)
    if device["count"] >= 4 and legs["trainer"]["ok"]:
        run("four_chip", leg_four_chip, sz, legs["trainer"]["losses"][0])
    elif device["count"] >= 4:
        legs["four_chip"] = {"ok": False,
                             "error": "needs the trainer leg's step-1 loss"}
        failed.append("four_chip")
    else:
        legs["four_chip"] = {"skipped": f"SKIPPED four_chip: needs >= 4 "
                                        f"devices, have {device['count']}"}
        log(legs["four_chip"]["skipped"])
    run("serving", leg_serving, sz)
    run("kernels", leg_kernels, sz, on_chip)

    summary = {
        "chip_smoke": "rehearsal" if args.rehearse else "chip",
        "device": device,
        "legs": {k: ("skipped" if "skipped" in v else
                     "ok" if v["ok"] else "FAILED")
                 for k, v in legs.items()},
        "setup_s": {k: v["setup_s"] for k, v in legs.items()
                    if "setup_s" in v},
        "sanity_warm_step_ms": legs["trainer"].get("sanity_warm_step_ms"),
        "persistent_cache": {"dir": str(cache.directory), **cache_events},
        "wall_s": round(time.perf_counter() - t_start, 1),
        "claim": None,
    }
    print("chip_smoke summary: " + json.dumps(summary), flush=True)
    if failed:
        print(f"chip_smoke: FAILED legs: {failed}", file=sys.stderr)
        return 1
    if args.rehearse:
        log("REHEARSAL passed on CPU: not a chip result")
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
