"""Readers of the host's side of a training run: what the fit loop did
with every iteration of the window, and what set-up was made of.

The program keeps a timeline of its own on the clock this benchmark takes
``T_START`` from (``deeplearning4j_tpu/observability/trace.py::Timeline``:
a row an iteration with the ends of ``train.read``, ``train.put``,
``train.dispatch`` and ``train.listeners``, under a ``train.fit`` entry for
each fit; set-up's phases as spans in the process ring) and every
compilation's stages with their time and function
(``observability/runtime.py::compile_events``). The readers take the
**last fit**, which in a training cell is the window, all of it and not
the traced slice, and need no device trace. A program without the
timeline, as a parent commit, or one that ran no fit, gives nothing to
read: the readers return ``None`` and the harness leaves the metrics out.

Beside the three metrics the first reader called writes four notes into
the run's line: ``fit``, ``host_stalls``, ``setup_phases`` and
``compile_events``.
"""

from __future__ import annotations

import statistics
import sys
from typing import Any, Dict, List, Optional

from benchmark.harness.readers import Context

STEP = "train.step"
LEGS = ("train.read", "train.put", "train.dispatch", "train.listeners")
FIT = "train.fit"
# set-up's spans in the process ring (``observability/vocab.HOST_SPANS``)
PHASES = ("import.deeplearning4j_tpu", "train.init_state",
          "train.step_cost_analysis", "program_table.resolve")
COMPILED = "backend_compile_duration"
STALLS_LISTED = 10
EVENTS_LISTED = 40


def _timeline() -> Optional[Dict[str, Any]]:
    """What the program recorded: ``fit`` (the last fit's spans, the root
    first), ``fits`` (every fit's root), ``phases`` (set-up's spans),
    ``compiles`` (the events) and ``cache`` (hits and misses); every time
    on ``trace.now()``'s clock, with ``origin`` the process's start on it
    (``benchmark/run.py``'s ``T_START`` where that is the command)."""
    from deeplearning4j_tpu.observability import runtime, trace

    if not hasattr(trace, "get_timeline") or not hasattr(
            runtime, "compile_events"):
        return None
    timeline = trace.get_timeline()
    if not timeline.fits():
        return None
    started = getattr(sys.modules.get("__main__"), "T_START", None)
    fit = timeline.spans()
    return {
        "fit": fit,
        "fits": [timeline.spans(earlier)[0]
                 for earlier in timeline.fits()[:-1]] + fit[:1],
        "phases": [s for s in trace.get_tracer().spans()
                   if s.name in PHASES],
        "compiles": runtime.compile_events(),
        "cache": runtime.cache_counts(),
        "origin": (None if started is None
                   else trace.from_perf_counter(started)),
    }


def _iterations(spans) -> List[Dict[str, float]]:
    """A fit's spans as a row an iteration: its step number, its seconds
    and each leg's."""
    rows: Dict[str, Dict[str, float]] = {}
    for s in spans:
        if s.name == STEP:
            rows[s.span_id] = {"step": s.attrs.get("step"),
                               "seconds": s.duration,
                               **{leg: 0.0 for leg in LEGS}}
    for s in spans:
        if s.name in LEGS and s.parent_id in rows:
            rows[s.parent_id][s.name] = s.duration
    return list(rows.values())


def _phases(t: Dict[str, Any], window, origin: float
            ) -> List[Dict[str, Any]]:
    """Set-up's account: every phase the program recorded and every fit,
    by start, and between those on the window's thread what nobody owns
    (the harness's own work: the backend's start, the weights, the feed),
    from the process's start to the window's opening."""
    owned = sorted(t["phases"] + t["fits"], key=lambda s: s.start)
    rows = [{"name": s.name, "at_s": s.start - origin, "seconds": s.duration,
             "thread": s.thread} for s in owned]
    at = origin
    for s in owned:
        if s.thread != window.thread or s.start > window.start:
            continue
        if s.start - at >= 1e-3:
            rows.append({"name": "unowned", "at_s": at - origin,
                         "seconds": s.start - at, "thread": window.thread})
        at = max(at, s.end)
    return sorted(rows, key=lambda r: r["at_s"])


def _compiles(t: Dict[str, Any], phases, window, origin: float
              ) -> Dict[str, Any]:
    """The compilations up to the window's end: totals by stage, and the
    longest events each with the phase its end fell in."""
    events = [e for e in t["compiles"] if e["end"] <= window.end]
    rows = []
    for e in events:
        at = e["end"] - origin
        inside = [r for r in phases
                  if r["at_s"] <= at <= r["at_s"] + r["seconds"]]
        rows.append({
            "kind": e["kind"], "fun_name": e["fun_name"],
            "seconds": e["seconds"], "end_at_s": at,
            "cache": e.get("cache"), "thread": e["thread"],
            "phase": min(inside, key=lambda r: r["seconds"])["name"]
            if inside else None})

    def total(kind):
        return sum(e["seconds"] for e in events if e["kind"] == kind)

    compiled = [e for e in events if e["kind"] == COMPILED]
    return {
        "totals": {
            "compiles": len(compiled),
            "hits": sum(e.get("cache") == "hit" for e in compiled),
            "misses": sum(e.get("cache") == "miss" for e in compiled),
            "compile_s": total(COMPILED),
            "trace_s": total("jaxpr_trace_duration"),
            "lower_s": total("jaxpr_to_mlir_module_duration"),
            "cache_read_s": total("cache_retrieval_time_sec"),
            "cache_saved_s": total("compile_time_saved_sec"),
            "process_hits": t["cache"]["hit"],
            "process_misses": t["cache"]["miss"],
            "outside_any_phase": sum(r["phase"] is None for r in rows),
        },
        # (the seconds a cache hit saved were not spent)
        "longest": sorted((r for r in rows
                           if r["kind"] != "compile_time_saved_sec"),
                          key=lambda r: -r["seconds"])[:EVENTS_LISTED],
    }


def _window(ctx: Context) -> Optional[Dict[str, Any]]:
    """The three metrics of the last fit, computed once a run; the notes
    are written beside them."""
    if "fit" in ctx.notes:
        return ctx.notes["fit"]
    t = _timeline()
    if t is None or not t["fit"]:
        return None
    window = t["fit"][0]
    rows = _iterations(t["fit"])
    if not rows or window.duration <= 0:
        return None
    # what the host does before it can ask for the next batch
    outside_read = [r["seconds"] - r["train.read"] for r in rows]
    # the harness starts and stops its profiler inside the listeners
    outside_listeners = [r["seconds"] - r["train.listeners"] for r in rows]
    usual = statistics.median(outside_listeners)
    excess = sum(max(0.0, x - 2 * usual) for x in outside_listeners)
    in_fit = [e for e in t["compiles"] if e["kind"] == COMPILED
              and window.start <= e["end"] <= window.end]
    origin = t["origin"] if t["origin"] is not None else min(
        s.start for s in t["phases"] + t["fits"])
    phases = _phases(t, window, origin)
    ctx.notes["setup_phases"] = phases
    ctx.notes["compile_events"] = _compiles(t, phases, window, origin)
    ctx.notes["host_stalls"] = sorted(
        rows, key=lambda r: -r["seconds"])[:STALLS_LISTED]
    ctx.notes["fit"] = {
        "steps": len(rows), "seconds": window.duration,
        "opens_at_s": window.start - origin,
        "legs_ms": {leg: 1e3 * statistics.median(r[leg] for r in rows)
                    for leg in LEGS},
        "host_ms_per_step": 1e3 * statistics.median(outside_read),
        "host_stall_share_window": 100.0 * excess / window.duration,
        "compiles_in_fit": len(in_fit),
        "compiled_in_fit": [e["fun_name"] for e in in_fit],
    }
    return ctx.notes["fit"]


def window_metric(ctx: Context, *, name: str) -> Optional[float]:
    """One of ``host_ms_per_step`` (ms: the median over the fit's
    iterations of the iteration's time outside ``train.read``: put,
    dispatch, listeners and the loop's own work), ``host_stall_share_window``
    (%: the summed excess of each iteration's time outside
    ``train.listeners`` over twice the fit's median of that time, as a share
    of the fit's wall time) and ``compiles_in_fit`` (count: backend
    compilations, on any thread, that ended inside the fit)."""
    found = _window(ctx)
    return None if found is None else float(found[name])
