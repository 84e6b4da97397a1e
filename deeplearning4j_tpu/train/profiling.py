"""Profiling & tracing (↔ org.nd4j.linalg.profiler.{OpProfiler,
ProfilerConfig} + deeplearning4j ProfilingListener; SURVEY §5.1).

TPU-era design: the reference intercepts per-op JNI dispatches and
aggregates host-side timings. Under XLA there are no per-op dispatches to
intercept — the step is one fused program — so profiling is (a) the XLA
profiler (``jax.profiler``) capturing a device trace viewable in
TensorBoard/Perfetto, and (b) host-side step wall-time statistics with
forced-materialization sync (dispatch is asynchronous) for the per-step
breakdown.

Nothing in this module annotates a step. The steps show as rows because
the fit loops do it: ``Trainer.fit`` and ``FaultTolerantTrainer.fit``
wrap every iteration in ``StepTraceAnnotation("train.step",
step_num=...)`` with ``train.read`` / ``train.put`` / ``train.dispatch``
/ ``train.listeners`` inside (``observability/trace.annotate``), and the
compiled step's operations carry the component scopes of
``observability/vocab.py``; ``ProfilingListener`` only starts and stops
the capture around them.

``analyze_trace``/``compare_traces`` are the ProfileAnalyzer analogue:
they parse a captured ``.trace.json.gz`` (Chrome trace format) and
aggregate device-op durations, so a regression between two runs is
attributable to named XLA ops. They read only that file, never the
``.xplane.pb`` beside it, so they see a capture only where
``start_trace`` also wrote the Chrome form; the server's
``POST /debug/profile`` is their reader. The benchmark reads the
``.xplane.pb`` instead (``benchmark/harness/trace_reduce.py``: busy
union, step window, host spans on the device's clock), which
``analyze_trace`` has none of.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import time
from collections import defaultdict
from typing import Dict, List, Optional

from deeplearning4j_tpu.train.listeners import TrainingListener


class ProfilingListener(TrainingListener):
    """Capture an XLA device trace for steps [start_step, end_step).

    Usage::

        lst = ProfilingListener("/tmp/tb_profile", start_step=5, end_step=8)
        trainer.fit(ts, data, listeners=[lst])
        report = lst.report()          # host-side step-time stats
        ops = analyze_trace(lst.log_dir)  # device-op breakdown

    The trace lands under ``<log_dir>/plugins/profile/...`` (TensorBoard's
    profile plugin layout) plus a Perfetto-compatible trace.json.gz.
    """

    def __init__(self, log_dir: str, *, start_step: int = 2,
                 end_step: Optional[int] = None, sync_every_step: bool = True):
        self.log_dir = log_dir
        self.start_step = start_step
        self.end_step = end_step if end_step is not None else start_step + 3
        self.sync_every_step = sync_every_step
        self.step_ms: List[float] = []
        self._active = False
        self._t_prev: Optional[float] = None

    # -- trace control -----------------------------------------------------

    def _start(self):
        import jax

        os.makedirs(self.log_dir, exist_ok=True)
        jax.profiler.start_trace(self.log_dir)
        self._active = True

    def _stop(self):
        import jax

        if self._active:
            jax.profiler.stop_trace()
            self._active = False

    # -- listener protocol -------------------------------------------------

    def on_iteration(self, epoch, step, ts, metrics):
        import jax

        if self.sync_every_step:
            # Forced host materialization serializes the dispatch pipeline
            # while profiling — that is the point (per-step attribution).
            float(jax.device_get(metrics["total_loss"]))
        now = time.perf_counter()
        if self._t_prev is not None and self._active:
            self.step_ms.append((now - self._t_prev) * 1000)
        if step == self.start_step and not self._active:
            self._start()
        elif self._active and step >= self.end_step:
            self._stop()
        self._t_prev = now
        return False

    def on_fit_end(self, trainer, ts):
        self._stop()

    # -- reporting ---------------------------------------------------------

    def report(self) -> Dict[str, float]:
        if not self.step_ms:
            return {"steps": 0}
        s = sorted(self.step_ms)
        n = len(s)
        return {
            "steps": n,
            "mean_ms": sum(s) / n,
            "p50_ms": s[n // 2],
            "min_ms": s[0],
            "max_ms": s[-1],
        }


def _find_trace_file(log_dir: str) -> str:
    pats = [os.path.join(log_dir, "**", "*.trace.json.gz"),
            os.path.join(log_dir, "**", "*.trace.json")]
    for pat in pats:
        hits = sorted(glob.glob(pat, recursive=True), key=os.path.getmtime)
        if hits:
            return hits[-1]
    raise FileNotFoundError(f"no trace file under {log_dir}")


# Process lanes that carry XLA device ops in profiler traces: the
# TensorBoard/Perfetto layout names them "/device:TPU:0", "/device:GPU:0
# (...)", etc. via "process_name" metadata events. Host-side lanes
# ("/host:CPU", "python", TSL runtime threads) must NOT match.
_DEVICE_LANE_RE = re.compile(r"/device:(TPU|GPU|XLA|CUSTOM)", re.IGNORECASE)


def _device_pids(events: List[Dict]) -> set:
    """pids whose process_name metadata marks a device/XLA-op lane."""
    pids = set()
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            pname = str((ev.get("args") or {}).get("name", ""))
            if _DEVICE_LANE_RE.search(pname):
                pids.add(ev.get("pid"))
    return pids


def analyze_trace(log_dir: str, top: int = 20) -> List[Dict]:
    """Aggregate device-op durations from the newest captured trace
    (↔ ProfileAnalyzer summarize): [{name, total_us, count, pct}] sorted
    by total duration descending.

    Only the device/XLA-op lanes are aggregated (identified by the
    trace's ``process_name`` metadata events): summing host-side
    Python/runtime lanes into the totals would dilute every device op's
    ``pct``. When the capture has no device lane (CPU backend), all
    complete events are aggregated instead — a host-side breakdown beats
    an empty one."""
    path = _find_trace_file(log_dir)
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as fh:
        trace = json.load(fh)
    events = trace.get("traceEvents", [])
    device_pids = _device_pids(events)
    agg = defaultdict(lambda: [0.0, 0])
    for ev in events:
        if ev.get("ph") != "X" or "dur" not in ev:
            continue
        if device_pids and ev.get("pid") not in device_pids:
            continue
        name = ev.get("name", "?")
        agg[name][0] += float(ev["dur"])
        agg[name][1] += 1
    total = sum(v[0] for v in agg.values()) or 1.0
    rows = [{"name": k, "total_us": round(v[0], 1), "count": v[1],
             "pct": round(100 * v[0] / total, 2)}
            for k, v in agg.items()]
    rows.sort(key=lambda r: -r["total_us"])
    return rows[:top]


def compare_traces(log_dir_a: str, log_dir_b: str, top: int = 15) -> List[Dict]:
    """↔ ProfileAnalyzer.compareProfiles: per-op total-duration delta between
    two captured runs, sorted by |delta|."""
    a = {r["name"]: r for r in analyze_trace(log_dir_a, top=10_000)}
    b = {r["name"]: r for r in analyze_trace(log_dir_b, top=10_000)}
    rows = []
    for name in set(a) | set(b):
        ta = a.get(name, {}).get("total_us", 0.0)
        tb = b.get(name, {}).get("total_us", 0.0)
        rows.append({"name": name, "a_us": ta, "b_us": tb,
                     "delta_us": round(tb - ta, 1)})
    rows.sort(key=lambda r: -abs(r["delta_us"]))
    return rows[:top]


def normalize_cost_analysis(ca) -> Dict[str, float]:
    """Flatten XLA's ``cost_analysis()`` result into a plain float dict.

    jax returns a dict, a 1-element list of dicts (version-dependent), or
    None when the backend implements no cost analysis — callers get ``{}``
    for the latter so every consumer shares one fallback."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if ca is None:
        return {}
    return {k: float(v) for k, v in dict(ca).items()
            if isinstance(v, (int, float))}


def op_costs(fn, *example_args, top: int = 0, **jit_kwargs) -> Dict[str, float]:
    """Static whole-program cost analysis of a jitted function (↔ the
    OpProfiler's FLOP/bandwidth estimates, recast for XLA).

    The reference's OpProfiler accumulated per-op-class counters at each
    JNI dispatch; under jit there are no per-op dispatches, but the
    compiled executable carries the compiler's own cost model. This
    returns XLA's ``cost_analysis()`` for the whole program — keys such as
    ``flops``, ``bytes accessed``, ``transcendentals``, plus per-memory-
    space traffic — so callers can compute analytic MFU / arithmetic
    intensity without running anything on a device.

    ``op_costs(step_fn, state, batch)`` → {"flops": ..., "bytes accessed":
    ..., ...}. Works on CPU and TPU backends alike (compilation only, no
    execution). With ``top > 0``, also returns the dominant HLO ops by
    estimated FLOPs under key ``"_top_flops_ops"`` when the backend's cost
    analysis exposes per-op detail (TPU PJRT returns program totals only;
    the key is then absent).
    """
    import jax

    compiled = jax.jit(fn, **jit_kwargs).lower(*example_args).compile()
    out = normalize_cost_analysis(compiled.cost_analysis())
    if top > 0:
        per_op = [(k[len("flops:"):], v) for k, v in out.items()
                  if k.startswith("flops:")]
        if per_op:
            per_op.sort(key=lambda kv: -kv[1])
            out["_top_flops_ops"] = dict(per_op[:top])  # type: ignore
    return out


def arithmetic_intensity(costs: Dict[str, float]) -> Optional[float]:
    """FLOPs per HBM byte from an ``op_costs`` result — the roofline
    abscissa. None when the backend reports no byte traffic (some PJRT
    plugins omit it)."""
    flops = costs.get("flops")
    byts = costs.get("bytes accessed")
    if not flops or not byts:
        return None
    return flops / byts
