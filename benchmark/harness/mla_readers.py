"""Readers of what latent attention (MLA) adds to the step: the attention
kernels' share of their roofline where the query and key are wider than
the value.

The work MLA *requires* of its attention kernels is fixed by the
configuration: every head of every layer attends its causal pairs, the
products that read q and k (QK^T forward, dQ and dK backward) at the
query-key width ``qk_head_dim``, those that read v or the output (PV
forward, dV and dP backward) at ``v_head_dim``. Whoever implements the
kernels, the same products have to be computed, so the roofline counts them
from the configuration's keys and the traffic (``rows``, ``seq_len``) and
the layers held, and takes nothing from the program: a kernel that pads v
to the query-key width does more work than is required and reads lower.
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmark.harness import flops as flops_mod
from benchmark.harness.dsa_readers import FLASH_KERNELS
from benchmark.harness.readers import Context


def attend_train_cost(*, rows: int, heads: int, seq_len: int, qk_dim: int,
                      v_dim: int, layers: int,
                      bytes_per_element: int = 2) -> Dict[str, float]:
    """Required operations and HBM bytes of causal attention's forward and
    backward kernels over one training step, q and k ``qk_dim`` wide and v
    ``v_dim`` wide.

    Operations: the six counted products of ``flops.flash_train_cost``
    over the causal pairs, each at its own width: QK^T, dQ and dK at
    ``qk_dim``; PV, dV and dP at ``v_dim`` (the recomputed QK^T is not
    counted). Bytes: forward reads q, k, v and writes o; backward reads q,
    k, v, o and do and writes dq, dk, dv; each at its width, for every
    query head (the row statistics are left out as small)."""
    pairs = rows * heads * flops_mod.causal_pairs(seq_len)
    flops = 2.0 * pairs * (3 * qk_dim + 3 * v_dim)
    # q, k twice read and their gradients once written; v, o twice read,
    # do once, dv once written
    widths = 3 * 2 * qk_dim + 3 * 2 * v_dim
    tensor = float(rows * heads * seq_len * bytes_per_element)
    return {"flops": layers * flops, "bytes": layers * widths * tensor}


def attend_roofline(ctx: Context) -> Optional[float]:
    """The least time the chip could take for the causal attention of the
    layers held, at the configuration's two widths, over the summed device
    time of the three flash kernels by name, in percent."""
    t = ctx.trace
    if t is None or not t.steps:
        return None
    seconds, count = t.matching_s(FLASH_KERNELS)
    if count == 0 or seconds <= 0:
        return None
    c = ctx.counters
    need = attend_train_cost(
        rows=c["rows"], heads=c["num_attention_heads"], seq_len=c["seq_len"],
        qk_dim=c["qk_head_dim"], v_dim=c["v_head_dim"], layers=c["layers"])
    least = flops_mod.roofline_seconds(
        need["flops"], need["bytes"], ctx.peaks["bf16_flops_per_s"],
        ctx.peaks["hbm_bytes_per_s"])
    ctx.notes["mla_attend_roofline"] = {
        "bound": least["bound"], "least_ms": 1e3 * least["seconds"],
        "events": count, "device_ms_per_step": 1e3 * seconds / t.steps}
    return 100.0 * least["seconds"] * t.steps / seconds
