"""Readers of per-layer metrics. A metric's file names one of these (or a
function of a later PR's own module) with its arguments; the reader takes
the number from the run's context and returns ``None`` where it finds
nothing to read, and the harness then leaves the metric out of the line.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark.harness import flops as flops_mod


class Context:
    """What a run hands to the readers."""

    def __init__(self, *, trace, counters: Dict[str, Any],
                 peaks: Dict[str, Any], cell):
        self.trace = trace          # trace_reduce.TraceSlice or None
        self.counters = counters    # the run's counts, by name
        self.peaks = peaks          # the chip's row of the table of peaks
        self.cell = cell            # manifest.Cell
        self.notes: Dict[str, Any] = {}


def step_mfu(ctx: Context) -> Optional[float]:
    """Required operations of the steps in the traced slice over what the
    chips could do in the slice's time, in percent."""
    t = ctx.trace
    if t is None or not t.steps:
        return None
    done = ctx.counters["flops_per_step"] * t.steps
    peak = ctx.cell.chips * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * done / (t.window_s * peak)


def busy_ms_per_step(ctx: Context) -> Optional[float]:
    t = ctx.trace
    if t is None or not t.steps:
        return None
    return 1e3 * t.busy_s / t.steps


def idle_share(ctx: Context) -> Optional[float]:
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def kernel_roofline(ctx: Context, *, pattern: str, cost: str,
                    cost_args: Dict[str, str]) -> Optional[float]:
    """The least time the chip could take for the kernel's required
    operations and bytes, over the summed device time of the operations
    matching ``pattern``, in percent. ``cost`` names a function of
    ``flops.py``; ``cost_args`` maps its arguments to counters."""
    t = ctx.trace
    if t is None or not t.steps:
        return None
    seconds, count = t.matching_s(pattern)
    if count == 0 or seconds <= 0:
        return None
    need = getattr(flops_mod, cost)(
        **{k: ctx.counters[v] for k, v in cost_args.items()})
    least = flops_mod.roofline_seconds(
        need["flops"], need["bytes"], ctx.peaks["bf16_flops_per_s"],
        ctx.peaks["hbm_bytes_per_s"])
    ctx.notes[f"{pattern}"] = {"bound": least["bound"], "events": count,
                               "device_s": seconds}
    return 100.0 * least["seconds"] * t.steps / seconds


def percentile(ctx: Context, *, counter: str, q: float,
               scale: float = 1.0) -> Optional[float]:
    """The ``q``-th percentile of the readings kept under ``counter``
    (nearest rank, so it is one of the readings)."""
    values = sorted(ctx.counters.get(counter) or [])
    if not values:
        return None
    return scale * nearest_rank(values, q)


def nearest_rank(ordered, q: float) -> float:
    """The ``q``-th percentile of readings already in order."""
    rank = max(1, -(-len(ordered) * q // 100))
    return float(ordered[int(rank) - 1])


def ratio(ctx: Context, *, num: str, den: str,
          scale: float = 1.0) -> Optional[float]:
    """One counter over another; nothing where the second is nought."""
    n, d = ctx.counters.get(num), ctx.counters.get(den)
    if n is None or not d:
        return None
    return scale * n / d


def counter(ctx: Context, *, name: str) -> Optional[float]:
    value = ctx.counters.get(name)
    return None if value is None else float(value)

