"""A training cell: ``Trainer.fit`` over a seeded feed, timed by the host's
clock, compared with the configuration's plain reference.

Set-up builds one ``Trainer`` with its state, drives it through the first
``check_steps`` steps by the very call and feed the window uses, reads the
numbers that are compared, and hands the same objects to the window. The
reference follows those steps once the window has closed and the
program's state is freed.
"""

from __future__ import annotations

import gc
import math
import os
import shutil
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark.configs import reference_common as rc
from benchmark.harness import manifest, report, verdict
from benchmark.harness.device import (
    arm_compile_cache,
    check_devices,
    log,
    peak_bytes,
)
from benchmark.harness import traffic as traffic_mod


# -- the feed -------------------------------------------------------------------

class Feed:
    """The window's batches: a fixed set made from the seed, yielded round
    robin. It ends when told to (``budget`` steps) or when the clock says
    (``until``), and never runs more than ``max_in_flight`` steps ahead of
    the device, as a bounded prefetch would not."""

    def __init__(self, batches: List[Any], probe: "Probe",
                 max_in_flight: int):
        self.batches = batches
        self.probe = probe
        self.max_in_flight = max_in_flight
        self.served = 0
        self.budget: Optional[int] = None
        self.until: Optional[float] = None

    def __iter__(self):
        return self

    def __next__(self):
        if self.budget is not None and self.budget <= 0:
            raise StopIteration
        self.probe.wait_for(len(self.probe.losses) - self.max_in_flight)
        if self.until is not None and time.perf_counter() >= self.until:
            raise StopIteration
        if self.budget is not None:
            self.budget -= 1
        batch = self.batches[self.served % len(self.batches)]
        self.served += 1
        return batch


def make_probe(listener_base):
    class Probe(listener_base):
        """Keeps every step's loss (on the device until asked), reads the
        optimizer's first moment after step 1, and opens and closes the
        profiler's slice in a traced run."""

        def __init__(self):
            self.losses: List[Any] = []
            self.first_moment_norms = None
            self._norms = None
            self.trace_dir: Optional[str] = None
            self.trace_from = math.inf
            self.trace_for = 0.0
            self.tracing = False
            self.traced = False

        def wait_for(self, index: int):
            if index >= 0:
                self.losses[index].block_until_ready()

        def on_iteration(self, epoch, step, ts, metrics):
            import jax

            self.losses.append(metrics["total_loss"])
            if step == 1 and self.first_moment_norms is None:
                if self._norms is None:
                    self._norms = jax.jit(rc.leaf_norms)
                self.first_moment_norms = self._norms(ts.opt_state["m"])
            if self.trace_dir is not None:
                now = time.perf_counter()
                if (not self.tracing and not self.traced
                        and now >= self.trace_from):
                    jax.profiler.start_trace(self.trace_dir)
                    self.tracing = True
                    self.trace_from = time.perf_counter()
                elif self.tracing and now >= self.trace_from + self.trace_for:
                    self.stop_trace()
            return False

        def stop_trace(self):
            import jax

            if self.tracing:
                jax.profiler.stop_trace()
                self.tracing, self.traced = False, True

    return Probe()


# -- the program ---------------------------------------------------------------

def build_trainer(cell: manifest.Cell):
    """The system under test, built as the configuration's file says."""
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.train.trainer import Trainer
    from deeplearning4j_tpu.train.updaters import Adam

    training = cell.config["training"]
    adam = training["adam"]
    net = NeuralNetConfiguration(
        updater=Adam(lr=adam["lr"], beta1=adam["beta1"], beta2=adam["beta2"],
                     eps=adam["eps"]),
        mixed_precision=training["mixed_precision"],
        rng_impl=training["rng_impl"])
    program = cell.config["program"]
    model = manifest.resolve(program["factory"])(net=net, **program["kwargs"])
    return Trainer(model)


def follow_reference(cell: manifest.Cell, seed: int, batches: List[Any],
                     precision: str = "float32") -> Dict[str, Any]:
    """The plain reference over the first steps, from the seed alone."""
    import jax

    ref, cfg = cell.reference, cell.config
    check = cell.workload["check"]
    mm = rc.Matmul(precision)
    params = rc.make_params(ref.param_shapes(cfg), seed,
                            cfg["initializer_range"])
    with jax.default_matmul_precision("highest"):
        return rc.follow_training(
            lambda p, rows: ref.loss_parts(cfg, p, rows, mm),
            ref.part_weights, params, batches,
            adam=cell.config["training"]["adam"],
            row_block=check["reference_row_block"])


def first_steps(cell: manifest.Cell, trainer, seed: int, batches: List[Any]):
    """Weights on the device from the seed, the trainer's state, and the
    first ``check.steps`` steps through the window's own call and feed.
    Returns the state, the probe and the feed for the window to go on
    with, and the numbers of the program that are compared."""
    import jax

    from deeplearning4j_tpu.train.listeners import TrainingListener

    ref, cfg = cell.reference, cell.config
    n_check = int(cell.workload["check"]["steps"])
    shapes = ref.param_shapes(cfg)
    std, key = cfg["initializer_range"], rc.seed_key(seed)
    ts = trainer.init_state(
        variables={"params": rc.params_from_key(shapes, key, std),
                   "state": {}},
        seed=rc.seed_to_int31(seed))
    probe = make_probe(TrainingListener)
    feed = Feed(batches, probe, int(cell.traffic["max_in_flight"]))
    feed.budget = n_check
    ts = trainer.fit(ts, feed, listeners=[probe])
    feed.budget = None
    change = jax.jit(lambda p, k: rc.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, p, rc.params_from_key(shapes, k, std))))(
            ts.params, key)
    beta1 = cfg["training"]["adam"]["beta1"]
    numbers = {
        "losses": [float(x) for x in probe.losses[:n_check]],
        "grad_norms": {k: float(v) / (1.0 - beta1)
                       for k, v in probe.first_moment_norms.items()},
        "change_norms": {k: float(v) for k, v in change.items()},
    }
    return ts, probe, feed, numbers


# -- a run ---------------------------------------------------------------------

def run(cell: manifest.Cell, *, seed: int, seconds: float, trace: bool,
        t_start: float, require_tpu: bool = True,
        scratch: Optional[str] = None) -> Dict[str, Any]:
    import jax

    device = check_devices(cell.chips, require_tpu)
    if require_tpu:
        arm_compile_cache()
    ref, cfg, work = cell.reference, cell.config, cell.workload
    traffic, check = cell.traffic, work["check"]
    n_check = int(check["steps"])

    def mark(what):
        log(f"set-up: {what} at {time.perf_counter() - t_start:.1f} s")

    mark("devices found")
    batches = traffic_mod.generate(cell, seed, seconds)
    if len(batches) < n_check:
        raise ValueError("the check follows more steps than there are "
                         "distinct batches")

    trainer = build_trainer(cell)
    mark("trainer built")
    ts, probe, feed, program = first_steps(cell, trainer, seed, batches)
    mark("first steps done")
    # the program's own background compile (its cost analysis of the step)
    # has to end before the window opens
    for t in threading.enumerate():
        if t.name == "step-cost-analysis":
            t.join()
    jax.block_until_ready(ts.params)
    mark("the program's cost analysis done")

    trace_dir = None
    if trace:
        trace_dir = os.path.join(scratch or os.path.join(
            manifest.ROOT, ".bench_scratch"), f"trace_{cell.name}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
        probe.trace_dir = trace_dir
        probe.trace_for = float(work["trace_slice_s"])

    # the window
    steps_before = len(probe.losses)
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    feed.until = t0 + seconds
    if trace:
        probe.trace_from = t0 + 0.4 * seconds
    ts = trainer.fit(ts, feed, listeners=[probe])
    jax.block_until_ready(ts.params)
    t1 = time.perf_counter()
    probe.stop_trace()
    steps = len(probe.losses) - steps_before
    window_losses = np.asarray(
        [float(x) for x in probe.losses[steps_before:]], np.float64)
    failed = int(np.sum(~np.isfinite(window_losses)))
    tokens = steps * ref.tokens_per_step(traffic)
    # the step's scratch is real: memory_analysis() plans 2.44 GB of it for
    # bert_base.train_s128 and 10.90 GB for gpt2_small.train_s1024, and the
    # allocator reserved 2.36 and 10.85 GB (tools/memory_probe.py)
    memory_peak = peak_bytes(with_reserved=True)

    counters = {
        "steps": steps, "tokens": tokens, "window_s": t1 - t0,
        "flops_per_step": ref.train_flops(cfg, traffic),
        "rows": traffic["rows"], "seq_len": traffic["seq_len"],
        **{k: v for k, v in cfg.items() if isinstance(v, (int, float))},
    }
    counters.update(work.get("counters", {}))
    log(f"window: {steps} steps in {t1 - t0:.3f} s, set-up {setup_s:.1f} s, "
        f"loss {window_losses[0]:.4f} -> {window_losses[-1]:.4f}")

    # free the program's state before the reference runs
    del ts, trainer, probe, feed
    gc.collect()
    jax.clear_caches()

    reference = follow_reference(cell, seed, batches[:n_check])
    correct, compared = verdict.judge(
        verdict.training_numbers(
            program, reference, rc.leaf_sizes(ref.param_shapes(cfg))),
        work["limits"])

    result = report.result_line(
        cell, correct=correct, attempted=steps, failed=failed,
        device=dict(device, memory_peak_bytes=memory_peak),
        values={"train_tok_s_chip": tokens / (t1 - t0) / cell.chips,
                "setup_s": setup_s},
        counters=counters, compared=compared, trace_dir=trace_dir,
        step_module=work["step_module"])
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return result
