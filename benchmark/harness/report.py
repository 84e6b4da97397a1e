"""From a run's readings to its result line: the cell's end-to-end
metrics in a ``--trace 0`` run, and in a ``--trace 1`` run its per-layer
metrics, each taken by its own reader, with the device's busy time and
the breakdown from the trace."""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

from benchmark.harness import peaks, trace_reduce
from benchmark.harness.readers import Context


def result_line(cell, *, correct: bool, attempted: int, failed: int,
                device: Dict[str, Any], values: Dict[str, float],
                counters: Dict[str, Any], compared: List[Dict[str, Any]],
                trace_dir: Optional[str] = None,
                step_module: Optional[str] = None,
                host_lines: Optional[Sequence[str]] = ("python",)
                ) -> Dict[str, Any]:
    """``values`` are the end-to-end metrics by name; ``counters`` is
    what the per-layer readers read beside the trace. With ``trace_dir``
    the line is a traced run's; ``host_lines`` are the host threads whose
    spans may own an idle gap (``None``: all of them, for a kind that
    leaves the host's Python out of the trace)."""
    result: Dict[str, Any] = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed)}
    if trace_dir is None:
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}
        result["device"] = device
    else:
        sliced = trace_reduce.reduce_trace(
            trace_reduce.find_trace(trace_dir), step_module=step_module,
            host_lines=host_lines)
        ctx = Context(trace=sliced, counters=counters,
                      peaks=peaks.peaks_for(device["kind"]), cell=cell)
        metrics = {}
        for m in cell.per_layer:
            value = m.reader(ctx, **m.args)
            if value is not None:
                metrics[m.name] = {"value": value, "unit": m.unit}
        device["busy_s"], device["window_s"] = sliced.busy_s, sliced.window_s
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": sliced.device_ops(),
                               "idle_gaps": sliced.idle_gaps()}
        result["notes"] = {"traced_steps": sliced.steps, **ctx.notes}
    result["compared"] = compared
    return result
