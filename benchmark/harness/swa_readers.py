"""Readers of what a decoder with windowed and global attention layers
adds to the step: the attention kernels' share of their roofline over the
pairs the configuration's own lists require, and the pairs the program's
kernels touched over those its layers required.

The work such a model *requires* of its attention kernels is fixed by the
configuration: in a layer whose ``sliding_window_layout`` entry is 1 a
query attends to the last ``sliding_window_size`` keys of its past, itself
counted; in the others to all of it. Whoever implements the kernels (a
dense kernel under a mask, one that skips the tiles behind the window),
the same number of pairs has to be scored, so the roofline counts them
from the configuration and the traffic and takes nothing from the program.
What the program's kernels *touched* is the program's to say
(``swa.pairs_touched`` beside ``swa.pairs_required`` in the step's
counters, ``deeplearning4j_tpu/observability/vocab.py``); a program
without those counters, as a parent has not, gives nothing to read: the
reader returns ``None`` and the harness leaves the metric out.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmark.harness import flops as flops_mod
from benchmark.harness.dsa_readers import FLASH_KERNELS
from benchmark.harness.moe_readers import _step_counters
from benchmark.harness.readers import Context


def attended_pairs(seq_len: int, window: Optional[int]) -> int:
    """Query-key pairs of one sequence and one head under a causal mask
    and, where ``window`` is a number, a window of that many keys that
    counts the query's own position."""
    if window is None or window >= seq_len:
        return flops_mod.causal_pairs(seq_len)
    return flops_mod.causal_pairs(window) + (seq_len - window) * window


def pairs_by_layer(config: Dict[str, Any], seq_len: int) -> List[int]:
    """For each layer the configuration holds (the first
    ``num_hidden_layers`` entries of ``sliding_window_layout``), the pairs
    its kind requires of one sequence and one head."""
    return [attended_pairs(
        seq_len, config["sliding_window_size"] if windowed else None)
        for windowed in
        config["sliding_window_layout"][:config["num_hidden_layers"]]]


def attend_train_cost(*, pairs_by_layer: List[int], rows: int, heads: int,
                      seq_len: int, head_dim: int,
                      bytes_per_element: int = 2) -> Dict[str, float]:
    """Required operations and HBM bytes of attention's forward and
    backward kernels over one training step in which a sequence's head
    attends ``pairs_by_layer[l]`` pairs in layer ``l``.

    The six counted products of ``flops.flash_train_cost`` (QK^T and PV
    forward; dV, dP, dQ and dK backward; the recomputed QK^T is not
    counted), each over the pairs required. Bytes as there: q, k, v, o, do
    and the three gradients (4 tensors forward, 8 backward), at the
    ``heads`` query heads each."""
    tensor = float(rows * heads * seq_len * head_dim * bytes_per_element)
    return {"flops": 6 * 2.0 * rows * heads * head_dim * sum(pairs_by_layer),
            "bytes": len(pairs_by_layer) * (4 + 8) * tensor}


def attend_roofline(ctx: Context) -> Optional[float]:
    """The least time the chip could take for attention over the pairs the
    configuration's lists require, over the summed device time of the
    three flash kernels by name, in percent."""
    t = ctx.trace
    if t is None or not t.steps:
        return None
    seconds, count = t.matching_s(FLASH_KERNELS)
    if count == 0 or seconds <= 0:
        return None
    c = ctx.counters
    pairs = pairs_by_layer(ctx.cell.config, c["seq_len"])
    need = attend_train_cost(
        pairs_by_layer=pairs, rows=c["rows"], heads=c["heads"],
        seq_len=c["seq_len"], head_dim=c["head_dim"])
    least = flops_mod.roofline_seconds(
        need["flops"], need["bytes"], ctx.peaks["bf16_flops_per_s"],
        ctx.peaks["hbm_bytes_per_s"])
    ctx.notes["swa_attend_roofline"] = {
        "bound": least["bound"], "least_ms": 1e3 * least["seconds"],
        "events": count, "device_ms_per_step": 1e3 * seconds / t.steps,
        "pairs_by_layer": pairs}
    return 100.0 * least["seconds"] * t.steps / seconds


def pairs_touched_over_required(ctx: Context) -> Optional[float]:
    """The pairs the program's attention kernels computed over the pairs
    its layers' kinds required, both summed over the layers
    (``swa.pairs_touched``, ``swa.pairs_required``, the last step of the
    window's fit); nothing where the program has no such counters."""
    counters = _step_counters()
    touched = counters.get("swa.pairs_touched")
    required = counters.get("swa.pairs_required")
    if touched is None or required is None or not sum(required):
        return None
    ctx.notes["swa_pairs"] = {"touched": list(touched),
                              "required": list(required)}
    return float(sum(touched)) / float(sum(required))
