"""Readers of what a routed-experts model adds to the step: device time
by the program's sub-scopes, the experts' share of their roofline at the
load the program counted, and that load itself.

The program's table (``scope_readers.published``) gives, beside each
instruction's component scope, its innermost sub-scope under
``subscopes`` (``cca_mix``, ``moe_route``, ``moe_experts``:
``deeplearning4j_tpu/observability/vocab.py``). A program older than
that key, or one that has no such scope, gives nothing to read: the
readers return ``None`` and the harness leaves the metric out.
"""

from __future__ import annotations

import bisect
import re
from typing import Any, Dict, Optional, Sequence

from benchmark.harness import flops as flops_mod
from benchmark.harness import scope_readers
from benchmark.harness.readers import Context

BF16 = 2  # bytes


def _subscopes(ctx: Context) -> Optional[Dict[str, float]]:
    """Every sub-scope's device time in the runs of the cell's step
    program, in ms a step; computed once a run, kept in ``ctx.notes``."""
    if "subscopes" in ctx.notes:
        return ctx.notes["subscopes"]
    t = ctx.trace
    pattern = ctx.cell.workload.get("step_module")
    if t is None or not t.steps or pattern is None:
        return None
    rx = re.compile(pattern)
    total: Dict[str, float] = {}
    for d in t.devices:
        runs = sorted((s, e, name) for name, s, e in d.modules
                      if rx.search(name))
        starts = [s for s, _, _ in runs]
        tables: Dict[str, Dict[str, str]] = {}
        for name, s, e in d.ops:
            at = bisect.bisect_right(starts, s) - 1
            if at < 0 or s >= runs[at][1]:
                continue  # another program's operation
            module = runs[at][2].split("(", 1)[0]
            if module not in tables:
                entry = scope_readers.published(module)
                if entry is None or "subscopes" not in entry:
                    return None
                tables[module] = entry["subscopes"]
            sub = tables[module].get(scope_readers.instruction(name))
            if sub is not None:
                total[sub] = total.get(sub, 0.0) + (e - s)
    if not total:
        return None
    per_step_ms = 1e-6 / (t.steps * len(t.devices))
    ctx.notes["subscopes"] = {k: v * per_step_ms
                              for k, v in sorted(total.items())}
    return ctx.notes["subscopes"]


def subscope_ms_per_step(ctx: Context, *, scope: str) -> Optional[float]:
    """Summed device time of the step program's operations whose
    instruction the program's table puts in the sub-scope ``scope``, over
    steps and devices, in ms."""
    split = _subscopes(ctx)
    return None if split is None else split.get(scope)


def expert_train_cost(*, tokens_by_layer: Sequence[float], hidden: int,
                      inner: int, experts_held: int) -> Dict[str, float]:
    """Required operations and HBM bytes of the experts' three grouped
    products (gate, up, down) over one training step in which
    ``tokens_by_layer[l]`` tokens landed on the experts that layer ``l``
    holds. Forward once, backward twice (the rows' gradient and the
    weights'). Bytes, in bf16: each of the nine products reads its two
    operands and writes its result once, and a stack of matrices moves
    whole whatever its experts' load."""
    weights = experts_held * hidden * inner * BF16  # one stack of matrices
    flops = bytes_ = 0.0
    for tokens in tokens_by_layer:
        flops += 9 * 2.0 * tokens * hidden * inner
        # a product's rows, in and out, and its stack
        bytes_ += 9 * (tokens * (hidden + inner) * BF16 + weights)
    return {"flops": flops, "bytes": bytes_}


def expert_roofline(ctx: Context) -> Optional[float]:
    """The least time the chip could take for the experts' grouped
    products of a step, over the device time of the ``moe_experts``
    sub-scope, in percent; whatever implements the product. The load is
    the one the program counted (``moe.tokens_here``, the last step of the
    window's fit), not the balanced one: training moves the routing, and
    the products' operations with it."""
    ms = subscope_ms_per_step(ctx, scope="moe_experts")
    here = _step_counters().get("moe.tokens_here")
    if not ms or here is None:
        return None
    c = ctx.counters
    tokens = [float(sum(layer)) for layer in here]
    need = expert_train_cost(
        tokens_by_layer=tokens, hidden=c["hidden_size"],
        inner=c["moe_intermediate_size"], experts_held=c["num_experts"])
    least = flops_mod.roofline_seconds(
        need["flops"], need["bytes"], ctx.peaks["bf16_flops_per_s"],
        ctx.peaks["hbm_bytes_per_s"])
    ctx.notes["expert_roofline"] = {"bound": least["bound"],
                                    "least_ms": 1e3 * least["seconds"],
                                    "tokens_by_layer": tokens}
    return 100.0 * 1e3 * least["seconds"] / ms


def _step_counters() -> Dict[str, Any]:
    """The counters of the last step of the program's last fit
    (``observability/runtime.step_counters``); empty where the program has
    no such table, as a parent has not."""
    from deeplearning4j_tpu.observability import runtime

    table = getattr(runtime, "step_counters", None)
    return table() if table is not None else {}


def step_counter(ctx: Context, *, name: str) -> Optional[float]:
    """One of the program's step counters; nothing where it has no such
    counter."""
    counters = _step_counters()
    if counters.get(name) is None:
        return None
    ctx.notes.setdefault("step_counters", counters)
    return float(counters[name])
