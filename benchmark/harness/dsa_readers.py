"""Readers of what indexed sparse attention adds to the step: the attention
kernels' share of their roofline over the pairs the program counted as
selected.

The work a model with a learned selection *requires* of its attention
kernels is the selected pairs, not the causal ones: whoever implements the
kernels (a dense kernel under a mask, one that skips the tiles in which
nothing is selected, one that gathers the selected keys), the same number
of pairs has to be scored. The program counts them (``dsa.pairs_selected``
in the step's counters, ``deeplearning4j_tpu/observability/vocab.py``); a
program without that counter, as a parent has not, gives nothing to read:
the reader returns ``None`` and the harness leaves the metric out.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

from benchmark.harness import flops as flops_mod
from benchmark.harness.moe_readers import _step_counters
from benchmark.harness.readers import Context

# the three flash kernels by name, as ``flash_roofline_named`` reads them
FLASH_KERNELS = "^%?flash_(fwd|bwd_dkv|bwd_dq)[.0-9]* = "
MASK_READS = 3  # each of the three kernels reads the pair mask once


def attend_train_cost(*, pairs_by_layer: Sequence[float], rows: int,
                      heads: int, seq_len: int, head_dim: int,
                      bytes_per_element: int = 2) -> Dict[str, float]:
    """Required operations and HBM bytes of attention's forward and
    backward kernels over one training step in which ``pairs_by_layer[l]``
    query-key pairs (summed over the batch; one set for all ``heads``)
    were selected in layer ``l``.

    The six counted products of ``flops.flash_train_cost`` (QK^T and PV
    forward; dV, dP, dQ and dK backward; the recomputed QK^T is not
    counted), each over the selected pairs. Bytes: q, k, v, o, do and the
    three gradients as there (4 tensors forward, 8 backward), and the pair
    mask, one byte a pair of the whole ``seq_len`` x ``seq_len`` square,
    once for each of the three kernels: a kernel that skips unselected
    tiles still has to learn which they are."""
    tensor = float(rows * heads * seq_len * head_dim * bytes_per_element)
    mask = float(rows * seq_len * seq_len)
    return {"flops": 6 * 2.0 * heads * head_dim * sum(pairs_by_layer),
            "bytes": len(pairs_by_layer) * ((4 + 8) * tensor
                                            + MASK_READS * mask)}


def attend_roofline(ctx: Context) -> Optional[float]:
    """The least time the chip could take for attention over the pairs the
    program counted as selected (``dsa.pairs_selected``, the last step of
    the window's fit), over the summed device time of the three flash
    kernels by name, in percent."""
    t = ctx.trace
    pairs = _step_counters().get("dsa.pairs_selected")
    if t is None or not t.steps or pairs is None:
        return None
    seconds, count = t.matching_s(FLASH_KERNELS)
    if count == 0 or seconds <= 0:
        return None
    c = ctx.counters
    need = attend_train_cost(
        pairs_by_layer=[float(p) for p in pairs], rows=c["rows"],
        heads=c["heads"], seq_len=c["seq_len"], head_dim=c["head_dim"])
    least = flops_mod.roofline_seconds(
        need["flops"], need["bytes"], ctx.peaks["bf16_flops_per_s"],
        ctx.peaks["hbm_bytes_per_s"])
    ctx.notes["dsa_attend_roofline"] = {
        "bound": least["bound"], "least_ms": 1e3 * least["seconds"],
        "events": count, "device_ms_per_step": 1e3 * seconds / t.steps,
        "pairs_by_layer": [float(p) for p in pairs]}
    return 100.0 * least["seconds"] * t.steps / seconds
