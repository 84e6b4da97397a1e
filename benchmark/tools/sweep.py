"""Find a serving cell's knee: the highest of a few rates at which the
backlog does not grow over a window. One process, one warm-up, one window
for each rate.

``python benchmark/tools/sweep.py --workload <cell> --speeds 0.6,0.8,1,1.25
--seconds 30`` replays the cell's schedule at each speed (its due times
divided by the speed, so the rate is multiplied by it), prints one row for
each and appends them to ``chiprun_out/sweep_<cell>.jsonl``. The backlog is
the number of requests that were due and had not finished; it is read at
the middle and at the end of the window, each averaged over two seconds.
The cell's schedule is then written at four fifths of the knee.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def backlog(rows, at_s: float) -> int:
    """Requests due by ``at_s``, the lead-in's too, whose last token had
    not arrived by then."""
    n = 0
    for r in rows:
        if r["due_s"] <= at_s:
            done_at = r["token_s"][-1] if r["done"] else float("inf")
            n += done_at > at_s
    return n


def mean_backlog(rows, lo: float, hi: float, points: int = 9) -> float:
    step = (hi - lo) / (points - 1)
    return sum(backlog(rows, lo + i * step) for i in range(points)) / points


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--speeds", required=True)
    parser.add_argument("--seconds", type=float, default=30.0)
    parser.add_argument("--seed", type=int, default=3_000_000_019)
    parser.add_argument("--cpu", action="store_true",
                        help="rehearsal off the chip; readings mean nothing")
    args = parser.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import time

    from benchmark.harness import manifest
    from benchmark.harness import traffic as traffic_mod
    from benchmark.harness.device import arm_compile_cache, check_devices
    from benchmark.harness.readers import nearest_rank
    from benchmark.kinds import serve

    cell = manifest.load_cell(args.workload)
    check_devices(cell.chips, not args.cpu)
    if not args.cpu:
        arm_compile_cache()
    scratch = os.path.join(manifest.ROOT, ".bench_scratch", "sweep")
    os.makedirs("chiprun_out", exist_ok=True)
    program = serve.Program(cell, args.seed)
    t = time.perf_counter()
    program.start()
    print(f"# warm in {time.perf_counter() - t:.1f} s", flush=True)
    try:
        for i, speed in enumerate(float(r) for r in args.speeds.split(",")):
            requests = traffic_mod.generate(cell, args.seed + i,
                                            args.seconds * speed)
            for r in requests:
                r.due_s /= speed
            loadgen = serve.LoadGenerator(
                scratch, program.server.url, requests, args.seconds,
                float(cell.workload["drain_s"]))
            try:
                loadgen.wait_ready()
                before = program.counts()
                loadgen.go(time.monotonic() + 0.05
                           + max(0.0, -requests[0].due_s))
                out = loadgen.rows()
            finally:
                loadgen.kill()
            after = program.counts()
            rows = out["rows"]
            seen = serve.window_numbers(rows, requests, args.seconds,
                                        out["end_s"])
            half = args.seconds / 2
            row = {
                "speed": speed, "requests": seen["offered"],
                "failed": seen["failed"],
                "backlog_mid": mean_backlog(rows, half - 1, half + 1),
                "backlog_end": mean_backlog(rows, args.seconds - 2,
                                            args.seconds),
                "drained_at_s": out["end_s"],
                "serve_tok_s": seen["tokens_in_window"] / args.seconds,
                "ttft_p50_ms": 1e3 * nearest_rank(sorted(seen["ttft_s"]), 50),
                "ttft_p95_ms": 1e3 * nearest_rank(sorted(seen["ttft_s"]), 95),
                "itl_p50_ms": 1e3 * nearest_rank(sorted(seen["itl_s"]), 50),
                "itl_p95_ms": 1e3 * nearest_rank(sorted(seen["itl_s"]), 95),
                "send_lag_p95_ms": 1e3 * nearest_rank(
                    sorted(seen["send_lag_s"]), 95),
                "decode_steps": after["decode_steps"] - before["decode_steps"],
                "compiles": (after["compiles_after_warm"]
                             - before["compiles_after_warm"]),
            }
            print(json.dumps(row), flush=True)
            with open(f"chiprun_out/sweep_{cell.name}.jsonl", "a") as f:
                f.write(json.dumps(row) + "\n")
    finally:
        program.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
