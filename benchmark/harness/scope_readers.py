"""Readers that split the step's device time by what the program says its
instructions are: ``scope_ms_per_step`` by component scope, ``op_ms_per_step``
by an operation's name.

A device trace names each operation by its HLO instruction (``%fusion.13 =
...``); which part of the model step that is, only the program knows. It
publishes a table, module name -> ``{"scopes": {instruction: scope}}``
(``deeplearning4j_tpu.observability.runtime.program_table``), read from the
``op_name`` metadata that its ``jax.named_scope`` calls left in the step it
compiled. The join is by instruction name, so it holds only if the table is
of the very program that ran: ``notes["scopes"]["unknown_share"]`` is the
share of the step's device time on instructions the table does not know,
and must read 0. ``notes["scopes"]["stale_metadata"]`` is true where the
program says that its executable came from a compile-cache entry written
before its scopes existed (jax keys that cache on the program without its
metadata): every scope then reads 0 and ``other`` holds the step, until
the entry is removed.

Where the program publishes no table for the module (one older than the
table, a serving trace) the scope reader returns ``None`` and the harness
leaves the metric out of the line.
"""

from __future__ import annotations

import bisect
import re
from typing import Any, Dict, List, Optional

from benchmark.harness import trace_reduce
from benchmark.harness.readers import Context

# the components a step's time is split into; whatever is in none of them
# (the ``embed`` scope and the unscoped) is ``other``
COMPONENTS = ("attn", "mlp", "head", "optimizer")
OTHER = "other"
UNSCOPED = "unscoped"


def published(module: str) -> Optional[Dict[str, Any]]:
    """The program's entry for ``module`` (``scopes``, and ``stale`` where
    the executable's metadata are an older program's), or ``None`` where
    it has none."""
    from deeplearning4j_tpu.observability import runtime

    table = getattr(runtime, "program_table", None)  # a parent has none
    return table().get(module) if table is not None else None


def instruction(event_name: str) -> str:
    """``%fusion.7 = bf16[...] fusion(...)`` to ``fusion.7``."""
    return trace_reduce.op_name(event_name).split("[", 1)[0]


def _split(ctx: Context) -> Optional[Dict[str, Any]]:
    """Every scope's device time in the runs of the cell's step program,
    in ms a step, computed once a run and kept in ``ctx.notes``."""
    if "scopes" in ctx.notes:
        return ctx.notes["scopes"]
    t = ctx.trace
    pattern = ctx.cell.workload.get("step_module")
    if t is None or not t.steps or pattern is None:
        return None
    rx = re.compile(pattern)
    total: Dict[str, float] = {}
    by_instruction: Dict[str, List[Any]] = {}  # instruction: [scope, ns]
    unknown, stale = 0.0, False
    for d in t.devices:
        runs = sorted((s, e, name) for name, s, e in d.modules
                      if rx.search(name))
        starts = [s for s, _, _ in runs]
        tables: Dict[str, Optional[Dict[str, Any]]] = {}
        for name, s, e in d.ops:
            at = bisect.bisect_right(starts, s) - 1
            if at < 0 or s >= runs[at][1]:
                continue  # another program's operation
            module = runs[at][2].split("(", 1)[0]
            if module not in tables:
                tables[module] = published(module)
                if tables[module] is None:
                    return None
                stale = stale or bool(tables[module].get("stale"))
            table = tables[module]["scopes"]
            ins = instruction(name)
            scope = table.get(ins)
            if ins not in table:
                unknown += e - s
            if scope is None:
                scope = UNSCOPED
            total[scope] = total.get(scope, 0.0) + (e - s)
            by_instruction.setdefault(ins, [scope, 0.0])[1] += e - s
    if not total:
        return None
    per_step_ms = 1e-6 / (t.steps * len(t.devices))
    longest = sorted(by_instruction.items(), key=lambda kv: -kv[1][1])
    kinds: Dict[str, float] = {}  # the unscoped, ``copy-done.25`` as ``copy-done``
    for ins, (scope, ns) in by_instruction.items():
        if scope == UNSCOPED:
            kind = re.sub(r"[.\d]+$", "", ins)
            kinds[kind] = kinds.get(kind, 0.0) + ns * per_step_ms
    ctx.notes["scopes"] = {
        "ms_per_step": {k: v * per_step_ms for k, v in sorted(total.items())},
        "unknown_share": unknown / sum(total.values()),
        # the scope of each instruction that breakdown.device_ops names
        "longest": [[k, scope, ns * per_step_ms]
                    for k, (scope, ns) in longest[:10]],
        "longest_unscoped": [[k, ns * per_step_ms]
                             for k, (scope, ns) in longest
                             if scope == UNSCOPED][:5],
        "unscoped_by_kind": dict(sorted(kinds.items(),
                                        key=lambda kv: -kv[1])[:8]),
        "stale_metadata": stale,
    }
    return ctx.notes["scopes"]


def scope_ms_per_step(ctx: Context, *, scopes: List[str]) -> Optional[float]:
    """Summed device time of the step program's operations whose
    instruction the program's table puts in one of ``scopes``, over steps
    and devices, in ms; ``"other"`` stands for every scope that is not one
    of ``COMPONENTS``, the unscoped included."""
    split = _split(ctx)
    if split is None:
        return None
    ms = split["ms_per_step"]
    wanted = set(scopes)
    if OTHER in wanted:
        wanted |= {k for k in ms if k not in COMPONENTS}
    return sum(v for k, v in ms.items() if k in wanted)


def op_ms_per_step(ctx: Context, *, pattern: str) -> Optional[float]:
    """Summed device time of the operations whose text matches
    ``pattern``, over steps (and averaged over devices), in ms."""
    t = ctx.trace
    if t is None or not t.steps:
        return None
    seconds, count = t.matching_s(pattern)
    if count == 0:
        return None
    return 1e3 * seconds / t.steps
