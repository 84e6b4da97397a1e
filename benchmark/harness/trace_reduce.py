"""From a profiler trace (``.xplane.pb``) to numbers. The only code of the
benchmark that touches a trace.

A device plane is named ``/device:TPU:<n>``. Its ``XLA Modules`` line has
one event for each run of a compiled program, its ``XLA Ops`` line one
for each operation the core ran (asynchronous copies are on a line of
their own and are not counted as busy time: while the core waits for one,
the ``XLA Ops`` line shows the wait). Host threads are lines of the
``/host:CPU`` plane, on the same clock.
"""

from __future__ import annotations

import glob
import gzip
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]  # start and end, nanoseconds
Event = Tuple[str, float, float]  # name, start and end, nanoseconds

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def find_trace(directory: str) -> str:
    """The newest ``.xplane.pb`` that the profiler wrote under ``directory``."""
    found = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def load(path: str):
    """``ProfileData`` of an ``.xplane.pb`` file, gzipped or not."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _events(plane, line_name: str) -> List[Event]:
    out: List[Event] = []
    for line in plane.lines:
        if line.name == line_name:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events)
    out.sort(key=lambda e: e[1])
    return out


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """The union of the intervals, as disjoint intervals in order."""
    merged: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(lo, hi) for lo, hi in merged]


def length(intervals: Iterable[Interval]) -> float:
    return sum(hi - lo for lo, hi in intervals)


def clip(events: Iterable[Event], window: Interval) -> List[Event]:
    lo, hi = window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def subtract(intervals: Sequence[Interval],
             cover: Sequence[Interval]) -> List[Interval]:
    """What is left of ``intervals`` (disjoint, in order) outside ``cover``
    (disjoint, in order)."""
    out: List[Interval] = []
    for lo, hi in intervals:
        at = lo
        for c_lo, c_hi in cover:
            if c_hi <= at:
                continue
            if c_lo >= hi:
                break
            if c_lo > at:
                out.append((at, c_lo))
            at = max(at, c_hi)
        if at < hi:
            out.append((at, hi))
    return out


def op_name(hlo: str) -> str:
    """``%fusion.7 = bf16[...] fusion(...)`` to ``fusion.7``; for a custom
    call the target is appended."""
    head = hlo.split(" = ", 1)[0].lstrip("%")
    target = re.search(r'custom_call_target="([^"]+)"', hlo)
    return f"{head}[{target.group(1)}]" if target else head


class DeviceSlice:
    """One device's events inside the measured slice of the trace."""

    def __init__(self, name: str, ops: List[Event], modules: List[Event],
                 window: Interval, steps: Optional[int]):
        self.name = name
        self.window = window
        self.steps = steps
        self.ops = clip(ops, window)
        self.modules = clip(modules, window)
        self.busy = merge((s, e) for _, s, e in self.ops)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return length(self.busy) / 1e9

    def matching_s(self, pattern: str) -> Tuple[float, int]:
        """Summed device time, and the count, of the operations whose
        text matches ``pattern``."""
        rx = re.compile(pattern)
        hit = [(s, e) for n, s, e in self.ops if rx.search(n)]
        return sum(e - s for s, e in hit) / 1e9, len(hit)

    def gaps(self) -> List[Interval]:
        return subtract([self.window], self.busy)


class TraceSlice:
    """The reduction of one trace: every device's slice and the host's
    spans beside them."""

    def __init__(self, devices: List[DeviceSlice], host: List[Event]):
        if not devices:
            raise ValueError("the trace has no /device:TPU plane with "
                             "operations in it")
        self.devices = devices
        self.host = host

    @property
    def busy_s(self) -> float:
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    @property
    def window_s(self) -> float:
        return sum(d.window_s for d in self.devices) / len(self.devices)

    @property
    def steps(self) -> Optional[int]:
        return self.devices[0].steps

    def matching_s(self, pattern: str) -> Tuple[float, int]:
        """Averaged over the devices."""
        per = [d.matching_s(pattern) for d in self.devices]
        return (sum(p[0] for p in per) / len(per),
                sum(p[1] for p in per) // len(per))

    def device_ops(self, top: int = 10) -> List[List[object]]:
        """The operations of the first device that took most time."""
        total: Dict[str, float] = {}
        for n, s, e in self.devices[0].ops:
            key = op_name(n)
            total[key] = total.get(key, 0.0) + (e - s) / 1e9
        rows = sorted(total.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v] for k, v in rows]

    def idle_gaps(self, top: int = 10) -> List[List[object]]:
        """The first device's longest idle gaps, each named by the
        innermost host span that covers the middle of the gap."""
        gaps = sorted(self.devices[0].gaps(), key=lambda g: g[0] - g[1])[:top]
        rows = []
        for lo, hi in gaps:
            mid = (lo + hi) / 2
            covering = [(e - s, n) for n, s, e in self.host if s <= mid <= e]
            owner = min(covering)[1] if covering else "no_host_span"
            rows.append([owner[:80], (hi - lo) / 1e9])
        return rows


def reduce_trace(path: str, *, step_module: Optional[str] = None,
                 host_lines: Optional[Sequence[str]] = ("python",)
                 ) -> TraceSlice:
    """Read a trace and cut it to the measured slice.

    With ``step_module`` (a pattern of the step program's name on the
    ``XLA Modules`` line) the slice runs from the start of the first such
    run to the start of the last, which is a whole number of steps:
    ``steps`` is their count. Without it the slice runs from the first
    operation's start to the last one's end. ``host_lines`` names the
    host threads whose spans may own an idle gap; ``None`` takes all.
    """
    data = load(path)
    devices: List[DeviceSlice] = []
    host: List[Event] = []
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                if host_lines is None or line.name in host_lines:
                    host.extend((e.name, e.start_ns,
                                 e.start_ns + e.duration_ns)
                                for e in line.events)
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops = _events(plane, OPS_LINE)
        modules = _events(plane, MODULES_LINE)
        if not ops:
            continue
        steps = None
        window = (ops[0][1], max(e for _, _, e in ops))
        if step_module is not None:
            rx = re.compile(step_module)
            runs = [m for m in modules if rx.search(m[0])]
            if len(runs) < 2:
                raise ValueError(
                    f"{plane.name}: {len(runs)} runs of a program matching "
                    f"{step_module!r} in the trace; a slice needs two")
            window = (runs[0][1], runs[-1][1])
            steps = len(runs) - 1
        devices.append(DeviceSlice(plane.name, ops, modules, window, steps))
    return TraceSlice(devices, host)
