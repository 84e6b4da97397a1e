"""A serving cell: ``ServingClient.generate`` -> ``ModelServer`` ->
``GenerationEngine`` under an open-loop schedule, timed from the clients'
side, compared with the configuration's plain reference.

The process that holds the chip builds the weights on the device from the
seed, the engine and the server, warms every program, and starts the load
generator as a child that stays off JAX (``harness/loadgen.py``). The
child stamps every token as it arrives. Once the window has closed, the
streams have drained, the peak of memory is read and the program's state
is freed, the reference follows a sample of the requests, drawn from the
seed before the window, over the tokens the engine itself emitted.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark.configs import reference_common as rc
from benchmark.harness import manifest, report, verdict
from benchmark.harness import traffic as traffic_mod
from benchmark.harness.device import (
    arm_compile_cache,
    check_devices,
    log,
    peak_bytes,
)
from benchmark.harness.readers import nearest_rank

LOADGEN = os.path.join(manifest.BENCH_DIR, "harness", "loadgen.py")
ROUTE = "model"


# -- the program ---------------------------------------------------------------

class Program:
    """The system under test, built as the configuration's and the
    cell's files say, and the handles a run reads it through."""

    def __init__(self, cell: manifest.Cell, seed: int):
        import jax

        from deeplearning4j_tpu.serving import GenerationEngine, ModelServer

        cfg, serving = cell.config, cell.config["serving"]
        factory = manifest.resolve(cfg["program"]["factory"])
        model = factory(**cfg["program"]["kwargs"])
        shapes = cell.reference.param_shapes(cfg)
        params = rc.make_params(shapes, seed, cfg["initializer_range"],
                                dtype=np.dtype(serving["dtype"]))
        self.engine = GenerationEngine(
            model, {"params": params, "state": {}}, name=ROUTE,
            temperature=serving["temperature"], seed=rc.seed_to_int31(seed),
            **cell.workload["engine"])
        self.server = ModelServer(port=0, generators={ROUTE: self.engine})

    def start(self):
        self.server.start(warm=True)

    def counts(self) -> Dict[str, int]:
        d = self.engine.describe()
        return {"decode_steps": d["decode_steps"],
                "compiles_after_warm": d["compiles_after_warm"]}

    def ledger_field(self, cids: List[str], field: str) -> List[float]:
        """``field`` of the request ledger's records of these requests."""
        from deeplearning4j_tpu.observability import reqlog

        ledger = reqlog.get_request_ledger()
        if ledger is None:
            return []
        records = (ledger.get(cid) for cid in cids)
        return [float(r[field]) for r in records
                if r is not None and r.get(field) is not None]

    def stop(self):
        self.server.stop(drain=False)


# -- the load generator ----------------------------------------------------------

class LoadGenerator:
    """The child process that sends the schedule."""

    def __init__(self, scratch: str, url: str, requests, seconds: float,
                 drain_s: float):
        os.makedirs(scratch, exist_ok=True)
        self.out = os.path.join(scratch, "requests.json")
        schedule = os.path.join(scratch, "schedule.json")
        if os.path.exists(self.out):
            os.remove(self.out)
        with open(schedule, "w", encoding="utf-8") as f:
            json.dump({
                "url": url, "model": ROUTE, "seconds": seconds,
                "drain_s": drain_s,
                "requests": [{"index": r.index, "due_s": r.due_s,
                              "prompt": r.prompt, "cid": cid_of(r.index),
                              "max_new_tokens": r.max_new_tokens}
                             for r in requests]}, f)
        self.limit_s = seconds + drain_s
        self.child = subprocess.Popen(
            [sys.executable, LOADGEN, schedule, self.out],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))

    def wait_ready(self):
        line = self.child.stdout.readline()
        if line.strip() != "ready":
            self.kill()
            raise RuntimeError(f"the load generator said {line!r}")

    def go(self, t0: float):
        self.child.stdin.write(f"go {t0!r}\n")
        self.child.stdin.flush()

    def rows(self) -> Dict[str, Any]:
        """Wait for the child to end and read what it stamped."""
        try:
            self.child.wait(timeout=self.limit_s + 30.0)
        except subprocess.TimeoutExpired:
            self.kill()
            raise RuntimeError("the load generator outlived its drain limit")
        if self.child.returncode != 0:
            raise RuntimeError(
                f"the load generator exited with {self.child.returncode}")
        with open(self.out, encoding="utf-8") as f:
            return json.load(f)

    def kill(self):
        if self.child.poll() is None:
            self.child.kill()
        self.child.wait()


def cid_of(index: int) -> str:
    return f"bench-{index:06d}"


# -- the window's numbers -------------------------------------------------------

def window_numbers(rows: List[Dict[str, Any]], requests, seconds: float,
                   end_s: float) -> Dict[str, Any]:
    """What the clients saw. The requests offered in the window are those
    due in it (the lead-in's are not); one that failed or did not finish
    counts, in the tail of the time to first token, as the worst value:
    the whole time from its due time to the end of the drain. Tokens and
    gaps count where they arrived inside the window, whoever sent the
    request."""
    ttft, gaps, lag = [], [], []
    tokens_in_window = failed = offered = 0
    for row, request in zip(rows, requests):
        stamps = row["token_s"]
        if request.due_s >= 0:
            ok = row["done"] and len(row["tokens"]) == request.max_new_tokens
            offered += 1
            failed += not ok
            if row["sent_s"] is not None:
                lag.append(row["sent_s"] - row["due_s"])
            ttft.append(stamps[0] - row["due_s"] if ok
                        else end_s - row["due_s"])
        gaps.extend(b - a for a, b in zip(stamps, stamps[1:])
                    if 0 <= b <= seconds)
        tokens_in_window += sum(1 for s in stamps if 0 <= s <= seconds)
    return {"ttft_s": ttft, "itl_s": gaps, "send_lag_s": lag,
            "tokens_in_window": tokens_in_window, "failed": int(failed),
            "offered": offered}


# -- the comparison ------------------------------------------------------------

def sample_requests(requests, seed: int, count: int) -> List[int]:
    """The requests that are compared, chosen before the window from the
    seed among those due in it: the one with the most tokens, and others
    drawn at random."""
    offered = [r for r in requests if r.due_s >= 0]
    longest = max(offered,
                  key=lambda r: len(r.prompt) + r.max_new_tokens).index
    rng = np.random.default_rng([seed, 0x5E12])
    others = [r.index for r in rng.permutation(np.array(offered, object))
              if r.index != longest]
    return [longest] + others[:max(0, count - 1)]


def served_numbers(cell: manifest.Cell, seed: int, served,
                   controls=()) -> Dict[str, Dict[str, Any]]:
    """The plain reference over each sampled prompt with its served
    tokens: the widest gap by which a served token's logit lies below the
    reference's best. ``served`` is a list of (prompt, tokens); every
    row is padded to one length, which changes nothing before the pad
    under a causal mask. ``controls`` names lower precisions: for each
    the same reading of the token that the reference puts first when it
    is computed in that precision."""
    import jax
    import jax.numpy as jnp

    ref, cfg = cell.reference, cell.config
    pad = int(cell.workload["check"]["pad_to"])
    params = rc.make_params(ref.param_shapes(cfg), seed,
                            cfg["initializer_range"])
    exact = rc.Matmul("float32")

    def gaps_fn(low):
        return jax.jit(lambda p, ids, targets: ref.served_gaps(
            cfg, p, ids, targets, exact, low))

    fns = {"served": gaps_fn(None),
           **{name: gaps_fn(rc.Matmul(name)) for name in controls}}
    widest = {name: 0.0 for name in fns}
    where = {name: None for name in fns}
    compared = 0
    with jax.default_matmul_precision("highest"):
        for index, prompt, tokens in served:
            n, m = len(prompt), len(tokens)
            if m == 0:
                continue
            if n + m - 1 > pad:
                raise ValueError(f"a request of {n}+{m} tokens does not fit "
                                 f"the reference's {pad} positions")
            ids = np.zeros(pad, np.int32)
            ids[:n + m - 1] = (list(prompt) + list(tokens))[:-1]
            targets = np.zeros(pad, np.int32)
            targets[n - 1:n - 1 + m] = tokens
            compared += m
            for name, fn in fns.items():
                gaps = np.asarray(fn(params, jnp.asarray(ids),
                                     jnp.asarray(targets)))[n - 1:n - 1 + m]
                worst = float(np.max(gaps)) if np.all(np.isfinite(gaps)) \
                    else float("inf")
                if worst >= widest[name]:
                    widest[name] = worst
                    where[name] = [index, int(np.argmax(gaps))]
    numbers = {"served_logit_gap": {"value": widest["served"],
                                    "at": where["served"]},
               "served_tokens_missing": {
                   "value": 0.0 if compared else 1.0, "compared": compared}}
    for name in controls:
        numbers[f"control_{name}_logit_gap"] = {"value": widest[name],
                                                "at": where[name]}
    return numbers


# -- a run ---------------------------------------------------------------------

def start_tracer(trace_dir: str, t0: float, seconds: float,
                 slice_s: float) -> threading.Thread:
    """Trace a slice in the middle of the window, from a thread of its
    own; the host's Python is not traced, which would slow every thread
    of the server."""
    import jax

    def body():
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        time.sleep(max(0.0, t0 + 0.4 * seconds - time.monotonic()))
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        time.sleep(slice_s)
        jax.profiler.stop_trace()

    thread = threading.Thread(target=body, name="bench-tracer", daemon=True)
    thread.start()
    return thread


def run(cell: manifest.Cell, *, seed: int, seconds: float, trace: bool,
        t_start: float, require_tpu: bool = True,
        scratch: Optional[str] = None, controls=()) -> Dict[str, Any]:
    """One run of the cell. ``controls`` adds the controls' readings to
    the numbers shown (``tools/readings.py``)."""
    import jax

    device = check_devices(cell.chips, require_tpu)
    if require_tpu:
        arm_compile_cache()
    work, check = cell.workload, cell.workload["check"]
    scratch = os.path.join(scratch or os.path.join(
        manifest.ROOT, ".bench_scratch"), cell.name)

    def mark(what):
        log(f"set-up: {what} at {time.perf_counter() - t_start:.1f} s")

    mark("devices found")
    requests = traffic_mod.generate(cell, seed, seconds)
    sampled = sample_requests(requests, seed, int(check["requests"]))

    program = Program(cell, seed)
    loadgen = LoadGenerator(scratch, program.server.url, requests, seconds,
                            float(work["drain_s"]))
    mark("engine built, load generator started")
    try:
        program.start()
        mark("server warm")
        loadgen.wait_ready()
        before = program.counts()

        trace_dir = None
        lead = max(0.0, -requests[0].due_s) + 0.05
        t0 = time.monotonic() + lead
        setup_s = time.perf_counter() + lead - t_start
        if trace:
            trace_dir = os.path.join(scratch, "trace")
            shutil.rmtree(trace_dir, ignore_errors=True)
            os.makedirs(trace_dir)
            tracer = start_tracer(trace_dir, t0, seconds,
                                  float(work["trace_slice_s"]))
        loadgen.go(t0)
        out = loadgen.rows()
        if trace:
            tracer.join()
        after = program.counts()
        queue_wait = program.ledger_field(
            [cid_of(r.index) for r in requests], "queue_wait_s")
        memory_peak = peak_bytes(with_reserved=False)
    finally:
        loadgen.kill()
        program.stop()

    rows = out["rows"]
    seen = window_numbers(rows, requests, seconds, out["end_s"])
    output_tokens = sum(len(r["tokens"]) for r in rows)
    started = sum(1 for r in rows if r["tokens"])
    counters = {
        "window_s": seconds, "send_lag_s": seen["send_lag_s"],
        "ttft_s": seen["ttft_s"],
        "queue_wait_s": queue_wait,
        "output_tokens": output_tokens - started,
        "decode_steps": after["decode_steps"] - before["decode_steps"],
        "compiles_in_window": (after["compiles_after_warm"]
                               - before["compiles_after_warm"]),
    }
    values = {
        "itl_p95_ms": (1e3 * nearest_rank(sorted(seen["itl_s"]), 95)
                       if seen["itl_s"] else float(1e3 * out["end_s"])),
        "serve_tok_s": seen["tokens_in_window"] / seconds,
        "setup_s": setup_s,
    }
    log(f"window: {seen['offered']} requests, {seen['failed']} failed, "
        f"{seen['tokens_in_window']} tokens in {seconds} s, drained at "
        f"{out['end_s']:.2f} s, set-up {setup_s:.1f} s, "
        f"{counters['decode_steps']} decode steps")

    # free the program's state before the reference runs
    del program
    gc.collect()
    jax.clear_caches()

    served = [(i, requests[i].prompt, rows[i]["tokens"]) for i in sampled
              if rows[i]["done"]]
    correct, compared = verdict.judge(
        served_numbers(cell, seed, served, controls), work["limits"])

    result = report.result_line(
        cell, correct=correct, attempted=seen["offered"],
        failed=seen["failed"],
        device=dict(device, memory_peak_bytes=memory_peak),
        values=values, counters=counters, compared=compared,
        trace_dir=trace_dir, host_lines=None)
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return result
