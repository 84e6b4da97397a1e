"""Operations and bytes that the algorithms *require*, from shapes alone.

A multiply-add counts as two operations. Training counts the forward pass
once and the backward pass twice (one product for the input's gradient,
one for the weight's). Recomputation is never counted, and a causal
attention counts only the pairs on and under the diagonal, so a share of a
peak built on these counts cannot pass 100% when a kernel skips the masked
blocks or recomputes less.
"""

from __future__ import annotations

from typing import Dict


def causal_pairs(seq_len: int) -> int:
    """Query-key pairs a causal attention over ``seq_len`` must score."""
    return seq_len * (seq_len + 1) // 2


def block_forward_flops(tokens: int, attended_pairs: int, hidden: int,
                        intermediate: int) -> float:
    """One transformer block forward: the four projections, the two
    feed-forward products, and QK^T and PV over the attended pairs
    (``attended_pairs`` is summed over the rows; heads cancel out, since
    each pair costs 2 x 2 x hidden over all heads)."""
    projections = 4 * 2 * tokens * hidden * hidden
    ffn = 2 * 2 * tokens * hidden * intermediate
    attention = 2 * 2 * attended_pairs * hidden
    return float(projections + ffn + attention)


def bert_train_flops(*, rows: int, seq_len: int, hidden: int,
                     intermediate: int, layers: int, vocab: int,
                     predictions: int) -> float:
    """One BERT pre-training step with the gathered masked-LM head."""
    tokens = rows * seq_len
    blocks = layers * block_forward_flops(
        tokens, rows * seq_len * seq_len, hidden, intermediate)
    slots = rows * predictions
    mlm = 2 * slots * hidden * hidden + 2 * slots * hidden * vocab
    nsp = 2 * rows * hidden * hidden + 2 * rows * hidden * 2
    return 3.0 * (blocks + mlm + nsp)


def gpt_train_flops(*, rows: int, seq_len: int, hidden: int,
                    intermediate: int, layers: int, vocab: int) -> float:
    """One causal language-model training step; the head is counted over
    the ``seq_len - 1`` positions that have a next token."""
    tokens = rows * seq_len
    blocks = layers * block_forward_flops(
        tokens, rows * causal_pairs(seq_len), hidden, intermediate)
    head = 2 * rows * (seq_len - 1) * hidden * vocab
    return 3.0 * (blocks + head)


def flash_train_cost(*, rows: int, heads: int, seq_len: int, head_dim: int,
                     layers: int, causal: bool = True,
                     bytes_per_element: int = 2) -> Dict[str, float]:
    """Required operations and HBM bytes of flash attention's forward and
    backward kernels over one training step.

    Forward: QK^T and PV (2 products). Backward: dV, dP, dQ and dK
    (4 products); the recomputed QK^T is not counted. Bytes: the forward
    reads q, k, v and writes o; the backward reads q, k, v, o and do and
    writes dq, dk, dv; the row statistics are left out as small.
    """
    pairs = causal_pairs(seq_len) if causal else seq_len * seq_len
    per_product = 2.0 * rows * heads * pairs * head_dim
    tensor = float(rows * heads * seq_len * head_dim * bytes_per_element)
    return {"flops": layers * 6 * per_product,
            "bytes": layers * (4 + 8) * tensor}


def roofline_seconds(flops: float, bytes_: float, peak_flops: float,
                     peak_bytes: float) -> Dict[str, object]:
    """The least time a chip with those peaks could take, and which of
    the two bounds it."""
    compute, memory = flops / peak_flops, bytes_ / peak_bytes
    return {"seconds": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}
