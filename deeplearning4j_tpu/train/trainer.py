"""Training driver: one compiled SPMD program per step.

ref: org.deeplearning4j.optimize.{Solver, solvers.StochasticGradientDescent}
+ MultiLayerUpdater + the fit() loops of MultiLayerNetwork/ComputationGraph
(SURVEY §3.1). The reference's step = hundreds of per-op JNI dispatches
(forward per layer, backward per layer, updater per block); here the step is
ONE jit/pjit-compiled XLA program with donated state — forward, backward,
gradient transforms, updater, and metric accumulation all fused by XLA, and
under a data-parallel mesh the gradient all-reduce over ICI is inserted by
the compiler (↔ ParallelWrapper/SharedTrainingMaster replacement).
"""

from __future__ import annotations

import atexit
import dataclasses
import os
import threading
from collections import deque
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from deeplearning4j_tpu.kernels._dispatch import kernel_mesh as _kernel_mesh
from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
from deeplearning4j_tpu.observability.vocab import SCOPE_OPTIMIZER, STEP_COUNTERS
from deeplearning4j_tpu.ops import math as opsmath
from deeplearning4j_tpu.train.updaters import apply_updates, resolve_updater

# Background step-cost analyses (Trainer.step_flops) run XLA compiles on
# daemon threads; the interpreter killing one mid-compile at process exit
# segfaults inside XLA. The atexit hook stops new compiles from starting
# and waits (bounded) for in-flight ones, so SIGTERM-preempted runs still
# exit cleanly.
_COST_THREADS: set = set()
_COST_SHUTDOWN = threading.Event()


def _join_cost_threads():
    _COST_SHUTDOWN.set()
    for t in list(_COST_THREADS):
        t.join(timeout=120)


atexit.register(_join_cost_threads)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    """Complete training state pytree (donated every step).

    ↔ the reference's {flat param vector, flat updater state, iteration
    counter, RNG} scattered across MultiLayerNetwork/Updater/Nd4j.random;
    here it is one immutable pytree, shardable by pjit.
    """

    params: Any
    model_state: Any
    opt_state: Any
    step: jax.Array
    rng: jax.Array


def _normalize_gradients(grads, net: NeuralNetConfiguration):
    """↔ GradientNormalization enum handling in BaseLayer.update."""
    mode = net.gradient_normalization
    thr = net.gradient_normalization_threshold
    if mode is None:
        return grads
    if mode == "clip_value":
        return jax.tree_util.tree_map(lambda g: jnp.clip(g, -thr, thr), grads)
    if mode == "clip_l2_global":
        clipped, _ = opsmath.clip_by_global_norm(grads, thr)
        return clipped
    if mode == "clip_l2_per_param":
        return jax.tree_util.tree_map(lambda g: opsmath.clip_by_norm(g, thr), grads)
    if mode == "renormalize_l2_per_layer":
        return jax.tree_util.tree_map(
            lambda g: g / jnp.maximum(jnp.sqrt(jnp.sum(jnp.square(g))), 1e-12), grads
        )
    raise ValueError(f"unknown gradient normalization {mode}")


def _is_time_distributed(key: str, v, t: int) -> bool:
    """Which batch entries get split along the time axis under TBPTT.

    Only the four keys the loss path reads are ever split, and only with an
    unambiguous time layout: rank>=3 [N,T,...] for features/labels, rank-2
    [N,T] for mask/weights. A rank-2 'labels' of [N,C] with C == T is NOT
    split (full-sequence targets are invalid under TBPTT and are rejected
    by _fit_tbptt_batch's validation instead of silently windowed).
    """
    if key in ("features", "labels"):
        return hasattr(v, "ndim") and v.ndim >= 3 and v.shape[1] == t
    if key in ("mask", "weights"):
        return hasattr(v, "ndim") and v.ndim == 2 and v.shape[1] == t
    return False


class Trainer:
    """Builds and runs the compiled train step for a model.

    model: SequentialModel | GraphModel (anything with .net and
    .loss_fn(params, state, batch, rng) -> (loss, (new_state, metrics))).

    ``mesh``/``state_sharding``/``batch_sharding``: optional pjit placement
    (see parallel/ for policy builders). Without a mesh, runs single-device
    jit — the same program, so single-chip and pod use identical code.

    ``frozen_layers``: top-level param-tree keys (layer names) excluded from
    training (↔ FrozenLayer wrapping in the reference's transfer-learning
    path). Gradients for frozen layers are zeroed BEFORE the updater (so
    Adam-style moments stay zero) and their updates are zeroed AFTER it
    (so decoupled weight decay à la AdamW cannot move them either).

    ``check_nan``: NaN/inf guard mode (↔ OpExecutionerUtil.checkForNAN /
    ND4JEnvironmentVars checkForNAN; SURVEY §5.2). When on, the compiled
    step is instrumented with ``checkify`` float checks: the FIRST op that
    produces a non-finite value raises host-side with the op name and
    traceback, instead of the NaN silently poisoning training. Defaults to
    the process-wide ``DL4J_TPU_CHECK_NUMERICS`` flag. Debug tool — the
    instrumentation costs compile time and some step time.
    """

    def __init__(
        self,
        model,
        *,
        mesh: Optional[Mesh] = None,
        state_sharding=None,
        batch_sharding=None,
        extra_metrics: Optional[Callable] = None,
        frozen_layers: Optional[Sequence[str]] = None,
        check_nan: Optional[bool] = None,
        grad_accum: int = 1,
        grad_metrics: bool = False,
    ):
        self.model = model
        self.net: NeuralNetConfiguration = model.net
        self.mesh = mesh
        bt = getattr(self.net, "backprop_type", "standard")
        if bt not in ("standard", "tbptt"):
            raise ValueError(
                f"unknown backprop_type {bt!r}: expected 'standard' or "
                "'tbptt' (↔ BackpropType.{Standard,TruncatedBPTT})")
        self.frozen_layers = frozenset(frozen_layers or ())
        if self.frozen_layers:
            known = set(getattr(model, "layer_names", [])) or None
            unknown = (self.frozen_layers - known) if known else set()
            if unknown:
                raise ValueError(f"frozen_layers not in model: {sorted(unknown)}")
        upd_init, upd_update = resolve_updater(self.net.updater).make()
        self._upd_init = upd_init
        self._upd_update = upd_update
        self._extra_metrics = extra_metrics
        self._batch_sharding = batch_sharding

        mixed = bool(getattr(self.net, "mixed_precision", False))

        # Post-update weight projections (↔ BaseLayer.constrainWeights +
        # constraint.*): collect once; the step applies them only when any
        # layer declares one, so unconstrained models pay nothing.
        named = (model.named_layers()
                 if hasattr(model, "named_layers") else [])
        self._constrained_layers = [
            (n, l) for n, l in named
            if getattr(l, "constraints", None)]

        def _to_bf16(tree):
            return jax.tree_util.tree_map(
                lambda a: a.astype(jnp.bfloat16)
                if jnp.issubdtype(a.dtype, jnp.floating) and a.dtype == jnp.float32
                else a,
                tree,
            )

        def _cast_batch(batch):
            # bf16 compute / fp32 master params + optimizer state: the
            # cast sits inside grad, so grads come back fp32 (MXU runs
            # bf16, accumulation and updates stay fp32).
            if mixed:
                return dict(batch, features=_to_bf16(batch["features"]))
            return batch

        def _grad_of(params, model_state, batch, rng):
            """Shared loss+grad core for the plain and accumulating steps
            (one copy of the mixed-precision param cast)."""
            def loss_of(p):
                if mixed:
                    with jax.named_scope(SCOPE_OPTIMIZER):
                        p = _to_bf16(p)
                return self.model.loss_fn(p, model_state, batch, rng=rng)

            # trace-time: Pallas kernels in the model place themselves
            # inside shard_map over this mesh (kernels/_dispatch.py)
            with _kernel_mesh(self.mesh):
                (loss, (new_state, metrics)), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(params)
            return loss, new_state, metrics, grads

        def train_step(ts: TrainState, batch) -> tuple[TrainState, Dict[str, jax.Array]]:
            step_rng = jax.random.fold_in(ts.rng, ts.step)
            batch = _cast_batch(batch)
            loss, new_model_state, metrics, grads = _grad_of(
                ts.params, ts.model_state, batch, step_rng)
            return self._finish_step(
                ts, grads, new_model_state, metrics, loss, batch)

        if not isinstance(grad_accum, int) or grad_accum < 1:
            raise ValueError(
                f"grad_accum must be an int >= 1, got {grad_accum!r}")
        if grad_accum > 1 and bt == "tbptt":
            raise ValueError(
                "grad_accum is not supported with backprop_type='tbptt' "
                "(windows already bound the per-update memory; accumulate "
                "by widening tbptt_length instead)")
        self.grad_accum = grad_accum
        self.grad_metrics = bool(grad_metrics)

        def train_step_accum(ts: TrainState, batch):
            """Gradient accumulation: the batch's leading dim splits into
            ``grad_accum`` microbatches scanned INSIDE the compiled step —
            activation memory is one microbatch's, the update sees the
            mean gradient of the full batch (the HBM lever for effective
            batch sizes beyond a chip's activation budget; TPU-idiomatic
            lax.scan, not a host loop). Stateful layers (BatchNorm) see
            microbatches sequentially, exactly like running the reference
            on k smaller batches with one deferred update.

            Weighting: if the model exposes ``loss_weight(batch) -> scalar``
            (the total loss-weight in a batch, e.g. the non-padding token
            count — Gpt does), each microbatch's loss/grads are combined
            weighted by that sum, which makes the accumulated step EXACTLY
            equal to the full-batch weighted-mean loss even when mask
            density varies across microbatches. Without the hook,
            microbatches are weighted equally — exact for unweighted mean
            losses, an approximation for masked/weighted ones."""
            k = self.grad_accum
            step_rng = jax.random.fold_in(ts.rng, ts.step)
            batch = _cast_batch(batch)
            weight_of = getattr(self.model, "loss_weight", None)

            # Shapes are trace-time constants: a ragged final batch (normal
            # at epoch end) falls back to the plain un-accumulated step for
            # that shape instead of crashing mid-epoch — the full-batch
            # weighted mean, i.e. the same semantics the weighted
            # accumulation reproduces, just without the memory split.
            n0 = jax.tree_util.tree_leaves(batch)[0].shape[0]
            if n0 % k:
                loss, new_model_state, metrics, grads = _grad_of(
                    ts.params, ts.model_state, batch, step_rng)
                return self._finish_step(
                    ts, grads, new_model_state, metrics, loss, batch)

            micro = jax.tree_util.tree_map(
                lambda l: l.reshape(k, l.shape[0] // k, *l.shape[1:]),
                batch)

            def micro_grad(model_state, mb, i):
                return _grad_of(ts.params, model_state, mb,
                                jax.random.fold_in(step_rng, i))

            # carry structures from eval_shape (costs a trace, not a second
            # copy of the differentiated graph in the executable)
            mb0 = jax.tree_util.tree_map(lambda l: l[0], micro)
            loss_sd, _, metrics_sd, grads_sd = jax.eval_shape(
                micro_grad, ts.model_state, mb0, 0)
            def zeros(sd):
                return jax.tree_util.tree_map(
                    lambda s: jnp.zeros(s.shape, s.dtype), sd)

            def body(carry, xs):
                model_state, gsum, loss_sum, msum, wsum = carry
                i, mb = xs
                loss, new_state, metrics, grads = micro_grad(
                    model_state, mb, i)
                w = (jnp.asarray(weight_of(mb), jnp.float32)
                     if weight_of is not None else jnp.float32(1.0))
                gsum = jax.tree_util.tree_map(
                    lambda s, g: (s + w * g).astype(s.dtype), gsum, grads)
                msum = jax.tree_util.tree_map(
                    lambda s, m: (s + w * m).astype(s.dtype), msum, metrics)
                loss_sum = (loss_sum + w * loss).astype(loss_sum.dtype)
                return (new_state, gsum, loss_sum, msum, wsum + w), None

            (final_state, gsum, loss_sum, msum, wsum), _ = jax.lax.scan(
                body,
                (ts.model_state, zeros(grads_sd), zeros(loss_sd),
                 zeros(metrics_sd), jnp.float32(0.0)),
                (jnp.arange(k), micro))
            denom = jnp.maximum(wsum, jnp.float32(1e-12))
            grads = jax.tree_util.tree_map(lambda g: g / denom, gsum)
            metrics = jax.tree_util.tree_map(lambda m: m / denom, msum)
            return self._finish_step(
                ts, grads, final_state, metrics, loss_sum / denom, batch)

        if self.grad_accum > 1:
            train_step = train_step_accum
        self._raw_step = train_step  # unjitted; reused by make_chained_step

        def tbptt_window_step(ts: TrainState, batch, carries):
            """One TBPTT window: loss over the window from ``carries``,
            gradients truncated at the window start, one parameter update
            (↔ one reference iteration), carries handed to the next window."""
            step_rng = jax.random.fold_in(ts.rng, ts.step)
            batch = _cast_batch(batch)

            def loss_of(params):
                if mixed:
                    with jax.named_scope(SCOPE_OPTIMIZER):
                        params = _to_bf16(params)
                return self.model.loss_fn_tbptt(
                    params, ts.model_state, batch, carries, rng=step_rng)

            (loss, (new_model_state, metrics, new_carries)), grads = (
                jax.value_and_grad(loss_of, has_aux=True)(ts.params))
            new_ts, metrics = self._finish_step(
                ts, grads, new_model_state, metrics, loss, batch)
            return new_ts, new_carries, metrics

        self._raw_tbptt_step = tbptt_window_step
        self._mixed = mixed
        self._to_bf16 = _to_bf16
        self._tbptt_progs: Dict[Any, Any] = {}

        jit_kwargs: Dict[str, Any] = {"donate_argnums": (0,)}
        if mesh is not None and state_sharding is not None:
            jit_kwargs["in_shardings"] = (state_sharding, batch_sharding)
            jit_kwargs["out_shardings"] = (state_sharding, None)
        self._jit_kwargs = jit_kwargs

        if check_nan is None:
            from deeplearning4j_tpu.runtime.environment import get_environment

            check_nan = get_environment().check_numerics
        self.check_nan = bool(check_nan)
        self.train_step = self._jit_with_nan_guard(train_step, jit_kwargs)
        # analytic step-cost cache (diagnostics plane): batch-shape key ->
        # float FLOPs | "pending" | "failed"; filled by a background
        # compile so the fit loop never blocks on cost analysis
        self._step_cost_cache: Dict[Any, Any] = {}
        self._step_cost_lock = threading.Lock()
        # module name of the step the last analysis described
        self._step_module: Optional[str] = None
        # whatever this trainer compiles from here on is an event with its
        # time, stage and function (observability/runtime.compile_events)
        _watch_compiles()

    # -- analytic step cost (observability) ---------------------------------

    def step_flops(self, ts: "TrainState", batch) -> Optional[float]:
        """Analytic FLOPs of the compiled step for this batch shape, or
        None while unknown. First call per shape kicks off a background
        thread that lowers + compiles the step ABSTRACTLY (ShapeDtype
        structs — no live buffers held, donation-safe) and reads XLA's
        ``cost_analysis``; later calls return the cached number. The same
        lowering, compiled again when asked (a read of the compilation
        cache where one is armed), says which component scope each of the
        step's HLO instructions belongs to; both go to the process table
        of ``observability/runtime.py`` (:meth:`step_description`). Disable
        with ``DL4J_TPU_STEP_COST_ANALYSIS=0`` (a second compile of a
        huge model, even off-thread, may not be worth the gauge)."""
        if os.environ.get("DL4J_TPU_STEP_COST_ANALYSIS", "1") == "0":
            return None
        key = tuple(
            (tuple(leaf.shape), leaf.dtype)
            for leaf in jax.tree_util.tree_leaves(batch)
            if hasattr(leaf, "shape"))
        with self._step_cost_lock:
            val = self._step_cost_cache.get(key)
            if val is None:
                self._step_cost_cache[key] = "pending"
        if isinstance(val, float):
            return val
        if val is not None:  # pending or failed
            return None

        def abstract(tree):
            return jax.tree_util.tree_map(
                lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype),
                tree)

        a_ts, a_batch = abstract(ts), abstract(batch)

        def lower():
            return jax.jit(self._raw_step,
                           **self._jit_kwargs).lower(a_ts, a_batch)

        def _compute():
            from deeplearning4j_tpu.train.profiling import (
                normalize_cost_analysis,
            )

            try:
                if _COST_SHUTDOWN.is_set():
                    result = "failed"  # process exiting: never start a
                else:                  # compile the exit would tear down
                    with _span("train.step_cost_analysis"):
                        lowered = lower()
                        costs = normalize_cost_analysis(
                            lowered.compile().cost_analysis())
                    flops = float(costs.get("flops") or 0.0)
                    result = flops if flops > 0 else "failed"
                    # what the step is made of: its text is fetched when
                    # the table is first read (observability/runtime.py
                    # says why not here); every step carries
                    # _finish_step's scope
                    module = str(lowered.compiler_ir().operation
                                 .attributes["sym_name"]).strip('"')
                    _publish_program(
                        module, flops=flops if flops > 0 else None,
                        text=lambda: lower().compile().as_text(),
                        carries=SCOPE_OPTIMIZER)
                    self._step_module = module
            except Exception:  # noqa: BLE001 — diagnostics never kill a fit
                result = "failed"
            with self._step_cost_lock:
                self._step_cost_cache[key] = result
            _COST_THREADS.discard(threading.current_thread())

        t = threading.Thread(target=_compute, daemon=True,
                             name="step-cost-analysis")
        _COST_THREADS.add(t)
        t.start()
        return None

    def step_description(self) -> Optional[Dict[str, Any]]:
        """What the compiled step is made of, once :meth:`step_flops`'s
        background analysis has run: ``{"module", "flops", "scopes",
        "stale"}`` — the step program's name on a trace's ``XLA Modules``
        line, XLA's cost-model FLOPs (the ``train_step_flops`` gauge), per
        HLO instruction its component scope (``observability/vocab.py``)
        or None, and whether the executable came from a compile-cache
        entry whose metadata predate the scopes. None while unknown. The
        first call fetches the step's text and can take seconds."""
        module = self._step_module
        entry = _program_table().get(module) if module else None
        return None if entry is None else dict(entry, module=module)

    @jax.named_scope(SCOPE_OPTIMIZER)
    def _finish_step(self, ts: TrainState, grads, new_model_state, metrics,
                     loss, batch):
        """Shared back half of every step kind: freeze-mask, normalize,
        updater, constraints, metric assembly, TrainState rebuild. Keeping
        it in ONE place is what guarantees the standard, chained, and TBPTT
        paths can never diverge on gradient handling. All of it is the
        ``optimizer`` component scope of a profiler trace."""
        raw_grad_norms = {}
        if self.grad_metrics:
            # RAW per-layer norms, before freeze-masking and clipping —
            # the explode/vanish diagnostic must see the gradient the
            # model produced, not the one the clip already capped
            for lname, g in grads.items():
                sq = sum(jnp.sum(jnp.square(leaf))
                         for leaf in jax.tree_util.tree_leaves(g))
                raw_grad_norms[f"grad_norm/{lname}"] = jnp.sqrt(sq)
        grads = self._mask_frozen(grads)
        grads = _normalize_gradients(grads, self.net)
        updates, new_opt = self._upd_update(
            grads, ts.opt_state, ts.params, ts.step)
        updates = self._mask_frozen(updates)
        new_params = apply_updates(ts.params, updates)
        if self._constrained_layers:
            from deeplearning4j_tpu.nn.constraints import constrain_params

            new_params = constrain_params(self._constrained_layers, new_params)
        metrics = dict(metrics)
        metrics["total_loss"] = loss
        feats = jax.tree_util.tree_leaves(batch["features"])
        metrics["batch_size"] = jnp.asarray(feats[0].shape[0])
        metrics.update(raw_grad_norms)
        if self._extra_metrics is not None:
            metrics.update(self._extra_metrics(new_params, batch))
        new_ts = TrainState(
            params=new_params,
            model_state=new_model_state,
            opt_state=new_opt,
            step=ts.step + 1,
            rng=ts.rng,
        )
        return new_ts, metrics

    def _jit_with_nan_guard(self, fn, kwargs):
        """jit ``fn``; under ``check_nan``, checkify-instrument it first
        (↔ OpExecutionerUtil.checkForNAN, SURVEY §5.2). checkify preserves
        the wrapped fn's signature (returns (err, out)), so donation and
        mesh in/out shardings apply unchanged to arg 0 / the state output;
        the error pytree rides along as an extra replicated output."""
        if not self.check_nan:
            return jax.jit(fn, **kwargs)
        from jax.experimental import checkify

        checked_kwargs = dict(kwargs)
        if "out_shardings" in checked_kwargs:
            checked_kwargs["out_shardings"] = (
                None, checked_kwargs["out_shardings"])
        checked = jax.jit(
            checkify.checkify(fn, errors=checkify.float_checks),
            **checked_kwargs)

        def guarded(*args):
            err, out = checked(*args)
            checkify.check_error(err)  # raises with the offending op name
            return out

        return guarded

    def make_chained_step(self, n_steps: int):
        """One jitted program that runs ``n_steps`` train steps on-device.

        ``lax.scan`` over the raw step: the step body compiles once, the
        device iterates without returning to the host, and the only outputs
        are the final TrainState plus the per-step loss vector. This is how
        benchmarks measure the chip instead of the host dispatch path (the
        reference's equivalent overhead was one JNI round-trip per op; here
        it is one dispatch per step, and a chained window removes that
        too). Also the building block for profiled runs
        (train/profiling.py).

        Returns ``chained(ts, batch) -> (ts, losses[n_steps])``, jitted with
        the same donation/sharding — and the same ``check_nan`` guard —
        as ``train_step``.
        """
        raw = self._raw_step

        def chained(ts: TrainState, batch):
            def body(carry, _):
                new_ts, metrics = raw(carry, batch)
                return new_ts, metrics["total_loss"]

            final_ts, losses = jax.lax.scan(body, ts, None, length=n_steps)
            return final_ts, losses

        kwargs = dict(self._jit_kwargs)
        if "out_shardings" in kwargs:
            kwargs["out_shardings"] = (kwargs["out_shardings"][0], None)
        return self._jit_with_nan_guard(chained, kwargs)

    # -- truncated BPTT (↔ BackpropType.TruncatedBPTT, SURVEY §5.7) --------

    def _zero_carries(self, ts: TrainState, x_window):
        """Zero recurrent carries matching one window's forward, derived by
        shape-only evaluation (no FLOPs; works eagerly or at trace time —
        eval_shape only reads avals, and jnp.zeros is cheap either way)."""
        params = self._to_bf16(ts.params) if self._mixed else ts.params
        xw = self._to_bf16(x_window) if self._mixed else x_window
        shapes = jax.eval_shape(
            lambda p, s, x: self.model.apply_tbptt(
                {"params": p, "state": s}, x, None, train=False)[2],
            params, ts.model_state, xw)
        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes)

    def _tbptt_jit_kwargs(self, *, with_carries_arg: bool):
        """_jit_kwargs adapted to the TBPTT signatures: outputs grow to
        (state, metrics, carries); the single-window step additionally takes
        carries as a third, unconstrained input (the scan program does not —
        it builds carries internally)."""
        kwargs = dict(self._jit_kwargs)
        if with_carries_arg and "in_shardings" in kwargs:
            kwargs["in_shardings"] = (*kwargs["in_shardings"], None)
        if "out_shardings" in kwargs:
            kwargs["out_shardings"] = (
                kwargs["out_shardings"][0], None, None)
        return kwargs

    def make_tbptt_step(self, n_windows: int, window_len: int):
        """One jitted program: ``lax.scan`` over ``n_windows`` TBPTT windows
        of ``window_len`` steps, the parameter update INSIDE the scan body.

        The reference walks windows on the host, re-dispatching every op per
        window (SURVEY §3.1); here the whole truncated-BPTT pass over a batch
        of long sequences — every window forward, truncated backward, and
        updater application — is a single XLA program.

        Returns ``prog(ts, batch) -> (ts, metrics, carries)`` where
        ``metrics`` is the per-window stack of the full train_step metric
        dict; batch time axes must be exactly ``n_windows * window_len``
        long. The returned carries let a caller run a shorter remainder
        window (ragged tail) through ``train_step_tbptt``.
        """
        raw = self._raw_tbptt_step
        span = n_windows * window_len

        def split_time(a):
            # [N, span, ...] -> [n_windows, N, window_len, ...]
            n = a.shape[0]
            a = a.reshape(n, n_windows, window_len, *a.shape[2:])
            return jnp.moveaxis(a, 1, 0)

        def program(ts: TrainState, batch):
            timed = {k: split_time(v) for k, v in batch.items()
                     if _is_time_distributed(k, v, span)}
            static = {k: v for k, v in batch.items() if k not in timed}
            carries0 = self._zero_carries(ts, timed["features"][0])

            def body(carry, wb):
                ts_c, carries = carry
                new_ts, new_carries, metrics = raw(
                    ts_c, dict(static, **wb), carries)
                return (new_ts, new_carries), metrics

            (ts_f, carries_f), metrics = jax.lax.scan(
                body, (ts, carries0), timed)
            return ts_f, metrics, carries_f

        return self._jit_with_nan_guard(
            program, self._tbptt_jit_kwargs(with_carries_arg=False))

    def train_step_tbptt(self, ts: TrainState, batch, carries):
        """Single TBPTT window step (jitted lazily); used for ragged tail
        windows and as the building block callers can drive directly."""
        if not hasattr(self, "_tbptt_single_jit"):
            self._tbptt_single_jit = self._jit_with_nan_guard(
                self._raw_tbptt_step,
                self._tbptt_jit_kwargs(with_carries_arg=True))
        return self._tbptt_single_jit(ts, batch, carries)

    def _fit_tbptt_batch(self, ts: TrainState, batch):
        """Fit one batch of long sequences by truncated BPTT: full windows
        through the compiled scan program, any remainder through a single
        shorter window continuing from the scanned-out carries (the
        reference also trains the shorter tail window).

        Returns (ts, [per-window metrics dict]) — one dict per window, the
        same keys the standard step reports.
        """
        if not hasattr(self.model, "loss_fn_tbptt"):
            raise ValueError(
                "backprop_type='tbptt' requires a model with TBPTT support "
                f"(SequentialModel); {type(self.model).__name__} has none")
        length = int(self.net.tbptt_length)
        if length <= 0:
            raise ValueError("backprop_type='tbptt' requires tbptt_length>0")
        feats = batch["features"]
        if not (hasattr(feats, "ndim") and feats.ndim >= 3):
            raise ValueError(
                "TBPTT needs sequence features [N, T, ...]; got shape "
                f"{getattr(feats, 'shape', None)}")
        t_total = feats.shape[1]
        labels = batch.get("labels")
        if labels is not None and not _is_time_distributed(
                "labels", labels, t_total):
            raise ValueError(
                "TBPTT requires per-timestep labels [N, T, ...] matching the "
                f"feature time axis (T={t_total}); got labels shape "
                f"{getattr(labels, 'shape', None)} — full-sequence targets "
                "cannot be trained per truncated window")
        n_w, rem = divmod(t_total, length)
        span = n_w * length

        def time_slice(k, v, lo, hi):
            if _is_time_distributed(k, v, t_total):
                return v[:, lo:hi]
            return v

        wmetrics = []
        carries = None
        if n_w:
            prog = self._tbptt_progs.get((n_w, length))
            if prog is None:
                prog = self.make_tbptt_step(n_w, length)
                self._tbptt_progs[(n_w, length)] = prog
            head = {k: time_slice(k, v, 0, span) for k, v in batch.items()}
            ts, stacked, carries = prog(ts, head)
            wmetrics = [{k: v[i] for k, v in stacked.items()}
                        for i in range(n_w)]
        if rem:
            tail = {k: time_slice(k, v, span, t_total)
                    for k, v in batch.items()}
            if carries is None:
                carries = self._zero_carries(ts, tail["features"])
            ts, _, metrics = self.train_step_tbptt(ts, tail, carries)
            wmetrics.append(metrics)
        return ts, wmetrics

    def _mask_frozen(self, tree):
        if not self.frozen_layers:
            return tree
        return {
            k: (jax.tree_util.tree_map(jnp.zeros_like, v)
                if k in self.frozen_layers else v)
            for k, v in tree.items()
        }

    # -- state construction ------------------------------------------------

    def init_state(self, variables=None, seed: Optional[int] = None) -> TrainState:
        with _span("train.init_state"):
            variables = (variables if variables is not None
                         else self.model.init(seed))
            seed = self.net.seed if seed is None else seed
            return TrainState(
                params=variables["params"],
                model_state=variables["state"],
                opt_state=self._upd_init(variables["params"]),
                step=jnp.zeros((), jnp.int32),
                rng=jax.random.key(
                    seed, impl=getattr(self.net, "rng_impl", None)),
            )

    def variables(self, ts: TrainState):
        return {"params": ts.params, "state": ts.model_state}

    # -- fit loop (host side; ↔ MultiLayerNetwork.fit(DataSetIterator)) ----

    def fit(
        self,
        ts: TrainState,
        data: Iterable,
        *,
        epochs: int = 1,
        listeners: Optional[List] = None,
        steps_per_epoch: Optional[int] = None,
    ) -> TrainState:
        listeners = listeners or []
        wmetrics: List[Dict[str, jax.Array]] = []
        # Shared-registry telemetry (observability/metrics.py): step/read
        # timing + throughput counters, sampled once per fit so a disabled
        # switch costs nothing in the loop. None of it syncs the device —
        # step_seconds measures the host loop's dispatch pace.
        om = _training_metrics()
        # the iteration and its legs: spans on the profiler's clock (a
        # device trace names the host's part of every idle gap) and, while
        # instrumentation is on, the two timing histograms and a row of the
        # host timeline under this fit's root entry, which opens here; each
        # boundary is read off the clock once
        # (observability/trace.IterationLegs)
        legs = _IterationLegs(om, annotate=_annotate)
        # persistent compile cache (JAX_COMPILATION_CACHE_DIR): a
        # supervisor-relaunched or re-expanded worker restores its step
        # programs from disk instead of recompiling — activation is
        # idempotent and a no-op when the env is unset
        _maybe_enable_compile_cache()
        # opt-in starvation remediation (DL4J_TPU_AUTO_PREFETCH=1): the
        # data_starved detector below names the read-dominated step; this
        # is its minimal fix — reads move to a background prefetch thread
        # so they overlap the compiled step (no-op unless armed)
        data = _maybe_auto_prefetch(data)
        for lst in listeners:
            lst.on_fit_start(self, ts)
        stop = False
        # One host sync up front; after that the step counter is tracked
        # host-side so the dispatch pipeline never blocks on the device.
        host_step = int(jax.device_get(ts.step))
        tele = _StepTelemetry(self, om) if om is not None else None
        # incident pipeline: while a fit loop is live, the sentinel's
        # "train" profile hook can capture the NEXT N steps on demand
        # (observability/incidents.py; the per-step check below is one
        # global load when nothing is pending)
        _incidents_enter_training()
        # on_fit_end must run even when a step raises (non-finite loss,
        # OOM, interrupt): listeners hold resources whose teardown
        # re-raises swallowed failures (async checkpoint writers).
        try:
            for epoch in range(epochs):
                for lst in listeners:
                    lst.on_epoch_start(epoch)
                it = iter(data)
                n = 0
                while True:
                    with legs.step(host_step + 1):
                        try:
                            with legs.read:
                                batch = next(it)
                        except StopIteration:
                            break
                        batch = _as_batch_dict(batch)
                        if _fault_injector().enabled:
                            # "train.worker_kill" (SIGKILL/raise at the N-th
                            # step — the elastic supervisor's relaunch
                            # trigger) and "train.step_nan" poison-batch
                            # injection points (resilience/faults.py); no-op
                            # unless DL4J_TPU_FAULTS armed a plan
                            _fault_injector().maybe_fail("train.worker_kill")
                            batch = _fault_injector().maybe_poison_batch(
                                batch)
                        if self._batch_sharding is not None:
                            if om is not None:
                                _record_batch_transfer(batch)
                            with legs.put:
                                batch = jax.device_put(
                                    batch, self._batch_sharding)
                        with legs.dispatch:
                            if getattr(self.net, "backprop_type",
                                       "standard") == "tbptt":
                                # ↔ TruncatedBPTT: every window is an
                                # iteration (the reference fires
                                # iterationDone once per window).
                                ts, wmetrics = self._fit_tbptt_batch(ts, batch)
                            else:
                                ts, metrics = self.train_step(ts, batch)
                                wmetrics = [metrics]
                        if om is not None:
                            om.steps_total.inc(len(wmetrics))
                            feats = jax.tree_util.tree_leaves(
                                batch["features"])
                            om.samples_total.inc(feats[0].shape[0])
                            tele.on_step(ts, batch, legs.read_s, legs.step_s,
                                         host_step + len(wmetrics))
                        n += 1
                        # step boundary for an armed incident device capture
                        # (a no-op global check unless one is pending)
                        _incidents_note_step()
                        # progress beacon for the elastic supervisor's hang
                        # detector (resilience/cluster.py); a no-op global
                        # check unless a supervisor armed a heartbeat
                        _touch_heartbeat()
                        # step attribution for cluster trace stitching: the
                        # next collective's span joins THIS step's cluster-
                        # wide trace id (runtime/distributed.py; a bare
                        # global int store)
                        _note_step(host_step + len(wmetrics))
                        with legs.listeners:
                            for wm in wmetrics:
                                host_step += 1
                                for lst in listeners:
                                    if lst.on_iteration(epoch, host_step, ts,
                                                        wm):
                                        stop = True
                        if (steps_per_epoch is not None
                                and n >= steps_per_epoch):
                            break
                        if stop:
                            break
                for lst in listeners:
                    if lst.on_epoch_end(epoch, ts):
                        stop = True
                if om is not None:
                    om.epochs_total.inc()
                    from deeplearning4j_tpu.observability.flightrecorder import (  # noqa: E501
                        record_event,
                    )

                    record_event("train.epoch", epoch=epoch, steps=n)
                if hasattr(data, "reset"):
                    data.reset()
                if stop:
                    break
        finally:
            legs.close()
            _incidents_exit_training()
            for lst in listeners:
                lst.on_fit_end(self, ts)
        # the counters the last step carries in its metrics (the experts'
        # load): one read as the fit returns, none inside the loop
        counters = {k: wmetrics[-1][k] for k in STEP_COUNTERS
                    if wmetrics and k in wmetrics[-1]}
        if counters:
            _publish_step_counters({k: v.tolist() for k, v in
                                    jax.device_get(counters).items()})
        return ts


def _training_metrics():
    """The shared-registry training bundle, or None when instrumentation
    is globally disabled (bench.py's bare-vs-instrumented comparison)."""
    from deeplearning4j_tpu.observability import metrics as _obsm

    return _obsm.get_training_metrics() if _obsm.enabled() else None


class _StepTelemetry:
    """Per-fit diagnostics feeding the shared registry + flight recorder:

    - ``train_step_flops``: the step's XLA cost-model FLOPs (computed once
      per batch shape off-thread by ``Trainer.step_flops``). No rate is
      derived from it here: the host sees a step's *dispatch* time, which
      under asynchronous dispatch is not the step's time — the benchmark's
      ``mfu_train`` reads the device trace for that;
    - data-starvation detector: when data-read latency exceeds
      ``STARVE_FRACTION`` of recent loop wall-time, the input pipeline —
      not the chip — is the bottleneck: ``train_data_starved`` flips to 1
      and the transition lands in the flight recorder;
    - sampled ``train.step`` flight events (every ``STEP_EVENT_EVERY``-th
      step + the first) so crash timelines carry training progress
      without flooding the ring at ms-scale step rates.

    Used by both ``Trainer.fit`` and ``FaultTolerantTrainer.fit``; all
    methods are host-side arithmetic — nothing here syncs the device.
    """

    WINDOW = 32
    MIN_STEPS = 8
    STARVE_FRACTION = 0.5
    STEP_EVENT_EVERY = 16

    def __init__(self, trainer: "Trainer", om):
        self.trainer = trainer
        self.om = om
        self._samples: deque = deque(maxlen=self.WINDOW)
        self._read_sum = 0.0
        self._step_sum = 0.0
        self._starved = False
        # resolved-FLOPs fast path keyed by the features shape: the full
        # step_flops cache key (every leaf's shape+dtype) costs ~10 µs a
        # step — too much for a per-step hot loop once the answer is known
        self._flops_by_shape: Dict[Any, float] = {}

    def on_step(self, ts, batch, read_s: float, step_s: float,
                step_no: int):
        from deeplearning4j_tpu.observability.flightrecorder import (
            record_event,
        )

        om = self.om
        # the gauge refreshes on the sampled cadence: a gauge is a
        # last-value instrument, and a .set() lock per step is real money
        # on a ~1 ms step
        if step_no == 1 or step_no % self.STEP_EVENT_EVERY == 0:
            shape_key = getattr(batch.get("features"), "shape", None) \
                if isinstance(batch, dict) else None
            flops = (self._flops_by_shape.get(shape_key)
                     if shape_key else None)
            if flops is None:
                flops = self.trainer.step_flops(ts, batch)
                if flops and shape_key is not None:
                    self._flops_by_shape[shape_key] = flops
            if flops:
                om.step_flops.set(flops)
        # rolling read-vs-step attribution over the trailing window
        if len(self._samples) == self._samples.maxlen:
            old_r, old_s = self._samples[0]
            self._read_sum -= old_r
            self._step_sum -= old_s
        self._samples.append((read_s, step_s))
        self._read_sum += read_s
        self._step_sum += step_s
        if len(self._samples) >= self.MIN_STEPS:
            wall = self._read_sum + self._step_sum
            starved = (wall > 0 and
                       self._read_sum / wall > self.STARVE_FRACTION)
            if starved != self._starved:
                self._starved = starved
                om.data_starved.set(1.0 if starved else 0.0)
                record_event(
                    "train.data_starvation" if starved
                    else "train.data_recovered",
                    step=step_no,
                    read_fraction=round(self._read_sum / wall, 3))
                if starved:
                    # remediation breadcrumb next to the detection: the
                    # post-mortem timeline names the fix, not just the
                    # symptom
                    record_event(
                        "data.starved", step=step_no,
                        read_fraction=round(self._read_sum / wall, 3),
                        hint=("input pipeline dominates the step: wrap "
                              "the training iterator in "
                              "data.AsyncDataSetIterator, or arm "
                              "DL4J_TPU_AUTO_PREFETCH=1 to do it "
                              "automatically"))
        if step_no == 1 or step_no % self.STEP_EVENT_EVERY == 0:
            record_event("train.step", step=step_no,
                         seconds=round(step_s, 6),
                         read_seconds=round(read_s, 6))


def _record_batch_transfer(batch):
    from deeplearning4j_tpu.observability.runtime import record_transfer

    record_transfer("h2d", sum(getattr(l, "nbytes", 0)
                               for l in jax.tree_util.tree_leaves(batch)))


from deeplearning4j_tpu.data.dataset import as_batch_dict as _as_batch_dict  # noqa: E402
from deeplearning4j_tpu.data.iterators import maybe_auto_prefetch as _maybe_auto_prefetch  # noqa: E402
from deeplearning4j_tpu.runtime.compilecache import maybe_enable_compile_cache as _maybe_enable_compile_cache  # noqa: E402
from deeplearning4j_tpu.observability.incidents import (  # noqa: E402
    enter_training as _incidents_enter_training,
    exit_training as _incidents_exit_training,
    note_train_step as _incidents_note_step,
)
from deeplearning4j_tpu.resilience.cluster import touch_heartbeat as _touch_heartbeat  # noqa: E402
from deeplearning4j_tpu.resilience.faults import get_fault_injector as _fault_injector  # noqa: E402
from deeplearning4j_tpu.runtime.distributed import note_step as _note_step  # noqa: E402
from deeplearning4j_tpu.observability.trace import (  # noqa: E402
    IterationLegs as _IterationLegs,
    annotate as _annotate,
    span as _span,
)
from deeplearning4j_tpu.observability.runtime import (  # noqa: E402
    program_table as _program_table,
    publish_program as _publish_program,
    publish_step_counters as _publish_step_counters,
    watch_compiles as _watch_compiles,
)
