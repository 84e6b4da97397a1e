"""Which host span owns a training cell's slow iterations.

``python3 benchmark/tools/host_stalls.py --workload <cell> --seed <n>
--seconds <s>`` runs the cell as ``benchmark/run.py`` does, with the
trainer's host spans (``train.read``, ``train.put``, ``train.dispatch``,
``train.listeners``) timed on the host's clock instead of handed to the
profiler, and writes ``chiprun_out/host_stalls_<cell>_<seed>.json``: every
iteration of the window that took over 1.25 times the median, split by
span into wall and CPU seconds of the loop's thread, with the thread's
time on the run queue (``/proc/thread-self/schedstat``), the collector's
pauses that fell inside it, how late a 5 ms ticker woke in a thread of
this process and in a process of its own, and what the machine took from
the process meanwhile (``host``: CPU time stolen from
the machine, the control group's throttling, the CPU pressure, read twice
a second).

Reading it: ``train.read`` holds the feed's wait for the device, so a long
read with no CPU and short iterations after it is the host catching up
with a queue it had let run dry; wall without CPU outside the read is the
thread blocked or off the core (``runq_ms`` says which); CPU beside a
collector's pause is the collector; a ticker as late as the iteration is
long is every thread of the process held up at once: if the ticker
outside was late too the whole machine stood still, and ``host`` says
whether it (``steal_ms``) or its control group (``throttled_ms``) counted
that; if not, something in this process held the interpreter's lock.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

SPANS = ("train.read", "train.put", "train.dispatch", "train.listeners")

# the same ticker in a process that shares nothing with this one but the
# machine and its clock; it prints when it began a sleep that ended late
TICKER_OUTSIDE = """
import time
while True:
    t = time.perf_counter()
    time.sleep(0.005)
    over = time.perf_counter() - t - 0.005
    if over > 0.03:
        print(t, over, flush=True)
"""


def _runq_ns() -> int:
    try:
        with open("/proc/thread-self/schedstat") as f:
            return int(f.read().split()[1])
    except (OSError, IndexError, ValueError):
        return 0


def _host_counters() -> dict:
    """What the machine withheld from this process so far, in ms: time
    stolen from the virtual CPUs, the control group's throttling, and the
    time some task waited for a CPU. A file that is not there adds no key."""
    out = {}
    try:
        with open("/proc/stat") as f:
            out["steal_ms"] = 10.0 * int(f.readline().split()[8])  # USER_HZ
    except (OSError, IndexError, ValueError):
        pass
    for path in ("/sys/fs/cgroup/cpu.stat", "/sys/fs/cgroup/cpu/cpu.stat"):
        try:
            with open(path) as f:
                stat = dict(line.split() for line in f)
        except (OSError, ValueError):
            continue
        if "throttled_usec" in stat:
            out["throttled_ms"] = 1e-3 * int(stat["throttled_usec"])
        elif "throttled_time" in stat:
            out["throttled_ms"] = 1e-6 * int(stat["throttled_time"])
        break
    try:
        with open("/proc/pressure/cpu") as f:
            out["cpu_pressure_ms"] = 1e-3 * int(
                f.readline().rsplit("total=", 1)[1])
    except (OSError, IndexError, ValueError):
        pass
    return out


def _host_between(samples, a, b) -> dict:
    """The counters' growth from the last sample before ``a`` to the first
    after ``b``."""
    before = [c for t, c in samples if t <= a] or [samples[0][1]]
    after = [c for t, c in samples if t >= b] or [samples[-1][1]]
    return {k: round(after[0][k] - before[-1][k], 1) for k in before[-1]
            if k in after[0]}


class _Span:
    __slots__ = ("name", "log", "t0", "c0", "q0")

    def __init__(self, name, log):
        self.name, self.log = name, log

    def __enter__(self):
        self.q0 = _runq_ns() if self.name == "train.step" else 0
        self.c0 = time.thread_time()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.log.append((self.name, self.t0, t1, time.thread_time() - self.c0,
                         _runq_ns() - self.q0 if self.q0 else 0))
        return False


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    args = parser.parse_args(argv)

    from benchmark import run as bench_run
    from deeplearning4j_tpu.train import trainer as trainer_mod

    log, pauses, late = [], [], []
    trainer_mod._annotate = lambda name, **attrs: _Span(name, log)

    def on_gc(phase, info, _open=[0.0]):
        if phase == "start":
            _open[0] = time.perf_counter()
        else:
            pauses.append((info["generation"], _open[0], time.perf_counter()))

    gc.callbacks.append(on_gc)

    host = [(time.perf_counter(), _host_counters())]

    def tick(period=0.005):
        while True:
            t = time.perf_counter()
            time.sleep(period)
            over = time.perf_counter() - t - period
            if over > 0.03:
                late.append((t, over))
            if t - host[-1][0] >= 0.5:
                host.append((time.perf_counter(), _host_counters()))

    threading.Thread(target=tick, name="host-stalls-ticker",
                     daemon=True).start()
    outside = subprocess.Popen([sys.executable, "-c", TICKER_OUTSIDE],
                               stdout=subprocess.PIPE, text=True)

    # the result line goes to standard output as the command's would
    rc = bench_run.main(["--workload", args.workload, "--seed", str(args.seed),
                         "--seconds", str(args.seconds), "--trace", "0"])
    gc.callbacks.remove(on_gc)
    outside.terminate()
    late_outside = [tuple(map(float, line.split()))
                    for line in outside.communicate()[0].splitlines()]
    host.append((time.perf_counter(), _host_counters()))

    # a fit ends with an iteration that reads nothing and dispatches
    # nothing; the window is the last fit (set-up's first steps are a fit
    # of their own before it)
    fits, dispatched = [[]], False
    for record in log:  # spans are logged as they close, the inner first
        if record[0] == "train.dispatch":
            dispatched = True
        elif record[0] == "train.step":
            if dispatched:
                fits[-1].append(record)
            elif fits[-1]:
                fits.append([])
            dispatched = False
    window = [fit for fit in fits if fit][-1] if any(fits) else []
    if len(window) < 3:
        print("no window to read", file=sys.stderr)
        return rc or 1
    starts = [r[1] for r in window]
    intervals = [b - a for a, b in zip(starts, starts[1:])]
    median = statistics.median(intervals)
    inner = [r for r in log if r[0] in SPANS]
    slow = []
    for i, dt in enumerate(intervals):
        if dt <= 1.25 * median:
            continue
        a, b = starts[i], starts[i + 1]
        spans = {}
        for name, t0, t1, cpu, _ in inner:
            if a <= t0 < b:
                spans[name] = {"wall_ms": round(1e3 * (t1 - t0), 2),
                               "cpu_ms": round(1e3 * cpu, 2)}
        slow.append({
            "step": i, "at_s": round(a - starts[0], 3),
            "interval_ms": round(1e3 * dt, 2),
            "next_ms": [round(1e3 * x, 1) for x in intervals[i + 1:i + 4]],
            "cpu_ms": round(1e3 * window[i][3], 2),
            "runq_ms": round(1e-6 * window[i][4], 2),
            "spans": spans,
            "gc": [{"gen": g, "ms": round(1e3 * (t1 - t0), 2)}
                   for g, t0, t1 in pauses if a <= t0 < b],
            "ticker_late_ms": [round(1e3 * o, 1) for t, o in late
                               if a <= t < b],
            "ticker_outside_late_ms": [round(1e3 * o, 1)
                                       for t, o in late_outside if a <= t < b],
            "host": _host_between(host, a, b),
        })
    in_window = [(g, t1 - t0) for g, t0, t1 in pauses
                 if starts[0] <= t0 <= starts[-1]]
    out = {
        "workload": args.workload, "seed": args.seed,
        "steps": len(window), "median_interval_ms": round(1e3 * median, 3),
        # the window's time over what its steps take at the median pace
        "lost_ms": round(1e3 * (sum(intervals) - median * len(intervals)), 1),
        "gc_in_window": {str(g): {"n": sum(1 for x, _ in in_window if x == g),
                                  "ms": round(1e3 * sum(s for x, s in in_window
                                                        if x == g), 1)}
                         for g in (0, 1, 2)},
        "ticker_late": sum(starts[0] <= t <= starts[-1] for t, _ in late),
        "ticker_outside_late": sum(starts[0] <= t <= starts[-1]
                                   for t, _ in late_outside),
        "host": _host_between(host, starts[0], starts[-1]),
        "process_cpu_s": round(time.process_time(), 1),
        "threads": sorted(t.name for t in threading.enumerate()),
        "slow": slow,
        "intervals_ms": [round(1e3 * x, 1) for x in intervals],
    }
    path = os.path.join(ROOT, "chiprun_out")
    os.makedirs(path, exist_ok=True)
    path = os.path.join(path, f"host_stalls_{args.workload}_{args.seed}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: v for k, v in out.items()
                      if k not in ("slow", "intervals_ms")}), file=sys.stderr)
    for row in slow[:12]:
        print("slow", json.dumps(row), file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
