"""What the host timeline costs a training cell, and what its helper
costs alone.

``python3 benchmark/tools/timeline_overhead.py --workload <cell> --seed <n>
[--pairs 10] [--seconds 8]`` sets the cell up as ``benchmark/run.py`` does
(one trainer, its first steps) and then runs ``--pairs`` pairs of short
windows through the same ``Trainer.fit``, one of each pair with the
program's instrumentation on (the timeline's rows, the two timing
histograms, the step telemetry) and one with ``metrics.set_enabled(False)``,
alternating which comes first; it writes each window's tokens per second,
both sides' medians and quartiles and the timeline's numbers of the last
instrumented window to ``chiprun_out/timeline_overhead_<cell>.json``.

Before that, with ``jax`` imported and nothing on the device, it times
``IterationLegs`` in a loop of its own against the loop the program had
before it (an ``annotate`` a leg and a ``perf_counter`` pair around two of
them, written out below), with the metrics on and off: ns an iteration, the
least of seven rounds.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _loop_before(om, annotate, n):
    """The fit loop's clock readings and annotations as they stood before
    the helper, around nothing."""
    clock = time.perf_counter
    for i in range(n):
        with annotate("train.step", step_num=i + 1):
            t_read = clock() if om is not None else 0.0
            with annotate("train.read"):
                pass
            read_s = clock() - t_read if om is not None else 0.0
            if om is not None:
                om.data_read_seconds.observe(read_s)
            t_step = clock() if om is not None else 0.0
            with annotate("train.dispatch"):
                pass
            if om is not None:
                om.step_seconds.observe(clock() - t_step)
            with annotate("train.listeners"):
                pass


def _loop_with_the_helper(om, annotate, n):
    from deeplearning4j_tpu.observability import trace

    legs = trace.IterationLegs(om, annotate=annotate,
                               timeline=trace.Timeline())
    for i in range(n):
        with legs.step(i + 1):
            with legs.read:
                pass
            with legs.dispatch:
                pass
            with legs.listeners:
                pass
    legs.close()


def helper_alone(n=20000, rounds=7):
    import jax  # noqa: F401  (an annotation is a no-op without it)

    from deeplearning4j_tpu.observability import metrics
    from deeplearning4j_tpu.observability.trace import annotate

    out = {}
    for label, om in (("metrics_on", metrics.get_training_metrics()),
                      ("metrics_off", None)):
        took = {"before": [], "helper": []}
        for _ in range(rounds):
            for name, loop in (("before", _loop_before),
                               ("helper", _loop_with_the_helper)):
                t0 = time.perf_counter()
                loop(om, annotate, n)
                took[name].append(1e9 * (time.perf_counter() - t0) / n)
        out[label] = {
            "before_ns": min(took["before"]), "helper_ns": min(took["helper"]),
            "more_ns": min(took["helper"]) - min(took["before"])}
    return out


def quartiles(values):
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return {"q1": q1, "median": q2, "q3": q3}


def measure(cell, *, seed: int, pairs: int, seconds: float,
            require_tpu: bool = True):
    """The pairs of windows, on one trainer."""
    import jax

    from benchmark.harness import host_readers
    from benchmark.harness import traffic as traffic_mod
    from benchmark.harness.device import arm_compile_cache, check_devices
    from benchmark.harness.readers import Context
    from benchmark.kinds import train as kind
    from deeplearning4j_tpu.observability import metrics

    device = check_devices(cell.chips, require_tpu)
    if require_tpu:
        arm_compile_cache()
    batches = traffic_mod.generate(cell, seed, seconds)
    trainer = kind.build_trainer(cell)
    ts, probe, feed, _ = kind.first_steps(cell, trainer, seed, batches)
    jax.block_until_ready(ts.params)
    tokens_a_step = cell.reference.tokens_per_step(cell.traffic)

    def window(on: bool):
        nonlocal ts
        metrics.set_enabled(on)
        try:
            before = len(probe.losses)
            t0 = time.perf_counter()
            feed.until = t0 + seconds
            ts = trainer.fit(ts, feed, listeners=[probe])
            jax.block_until_ready(ts.params)
            t1 = time.perf_counter()
        finally:
            metrics.set_enabled(True)
        steps = len(probe.losses) - before
        return steps * tokens_a_step / (t1 - t0) / cell.chips, steps

    window(True)  # both sides' first window is a warm one
    rows = []
    for i in range(pairs):
        order = (True, False) if i % 2 == 0 else (False, True)
        got = {on: window(on) for on in order}
        rows.append({"first": "on" if order[0] else "off",
                     "on": got[True][0], "off": got[False][0],
                     "steps": [got[True][1], got[False][1]]})
        print("pair", json.dumps(rows[-1]), file=sys.stderr, flush=True)
    # the timeline's last fit is the last window that wrote one
    ctx = Context(trace=None, counters={}, peaks={}, cell=cell)
    last = {m.name: m.reader(ctx, **m.args) for m in cell.per_layer
            if m.reader is host_readers.window_metric}
    on, off = [p["on"] for p in rows], [p["off"] for p in rows]
    return {
        "device": device, "window_s": seconds, "pairs": rows,
        "on": quartiles(on), "off": quartiles(off),
        "on_over_off": statistics.median(on) / statistics.median(off),
        "on_wins": sum(p["on"] > p["off"] for p in rows),
        "last_window": last, "fit": ctx.notes.get("fit"),
        "host_stalls": ctx.notes.get("host_stalls"),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--pairs", type=int, default=10)
    parser.add_argument("--seconds", type=float, default=8.0)
    args = parser.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    alone = helper_alone()
    print("helper alone", json.dumps(alone), file=sys.stderr, flush=True)

    from benchmark.harness import manifest

    out = {"workload": args.workload, "seed": args.seed,
           "helper_alone": alone,
           **measure(manifest.load_cell(args.workload), seed=args.seed,
                     pairs=args.pairs, seconds=args.seconds)}
    path = os.path.join(ROOT, "chiprun_out")
    os.makedirs(path, exist_ok=True)
    path = os.path.join(path, f"timeline_overhead_{args.workload}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: v for k, v in out.items()
                      if k not in ("pairs", "host_stalls")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
