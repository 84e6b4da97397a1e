"""Plain reference of GPT-2 (Radford et al. 2019) as the ``gpt2_small``
configuration runs it.

Word + learned position embeddings, pre-LN causal blocks with GELU in its
tanh form (``gelu_new``), a final LayerNorm and a head tied to the word
embedding. Departure from the published model, listed in the
configuration's file: the program learns a bias on the head (zero at the
start), which the published model does not have.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax.numpy as jnp
import numpy as np

from benchmark.configs import reference_common as rc


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    e, v = cfg["n_embd"], cfg["vocab_size"]
    shapes: Dict[str, Any] = {
        "embeddings": {"word": ((v, e), "normal"),
                       "position": ((cfg["n_positions"], e), "normal")},
        "final": {"ln_gamma": ((e,), "ones"), "ln_beta": ((e,), "zeros"),
                  "out_b": ((v,), "zeros")},
    }
    for i in range(cfg["n_layer"]):
        shapes[f"layer_{i}"] = rc.block_shapes(e, cfg["n_inner"])
    return shapes


def make_batch(cfg: Dict[str, Any], rng: np.random.Generator,
               traffic: Dict[str, Any]) -> Dict[str, Any]:
    """One language-modelling batch: every row a different random
    sequence of ids drawn uniformly over the vocabulary."""
    ids = rng.integers(0, cfg["vocab_size"],
                       (traffic["rows"], traffic["seq_len"]))
    return {"features": {"token_ids": ids.astype(np.int32)}}


def vocab_size(cfg: Dict[str, Any]) -> int:
    return cfg["vocab_size"]


def tokens_per_step(traffic: Dict[str, Any]) -> int:
    return traffic["rows"] * traffic["seq_len"]


def part_weights(batch) -> Dict[str, float]:
    n, t = batch["features"]["token_ids"].shape
    return {"lm": float(n * (t - 1))}


def logits(cfg, params, ids, mm: rc.Matmul):
    """[N,T] ids to [N,T,V] next-token logits."""
    emb = params["embeddings"]
    x = emb["word"][ids] + emb["position"][: ids.shape[1]][None]
    for i in range(cfg["n_layer"]):
        x = rc.transformer_block(
            mm, x, params[f"layer_{i}"], num_heads=cfg["n_head"],
            eps=cfg["layer_norm_epsilon"], causal=True, post_ln=False,
            key_mask=None)
    f = params["final"]
    x = rc.layer_norm(x, f["ln_gamma"], f["ln_beta"],
                      cfg["layer_norm_epsilon"])
    return mm("nte,ve->ntv", x, emb["word"]) + f["out_b"]


def loss_parts(cfg, params, rows, mm: rc.Matmul) -> Dict[str, Any]:
    ids = rows["features"]["token_ids"]
    lg = logits(cfg, params, ids, mm)[:, :-1]
    return {"lm": jnp.sum(rc.cross_entropy(lg, ids[:, 1:]))}


def served_gaps(cfg, params, ids, targets, mm: rc.Matmul,
                low: Optional[rc.Matmul] = None):
    """For one row of ``ids`` [T] (a prompt and the tokens served after
    it) the gap, at every position, by which the logit of ``targets`` [T]
    (the token served next) lies below the reference's best. With ``low``
    the target is instead the token that the same reference, computed in
    that lower precision, puts first: the control."""
    best_of = logits(cfg, params, ids[None], mm)[0]
    if low is not None:
        targets = jnp.argmax(logits(cfg, params, ids[None], low)[0], axis=-1)
    served = jnp.take_along_axis(best_of, targets[:, None], axis=-1)[:, 0]
    return jnp.max(best_of, axis=-1) - served


def train_flops(cfg: Dict[str, Any], traffic: Dict[str, Any]) -> float:
    from benchmark.harness import flops

    return flops.gpt_train_flops(
        rows=traffic["rows"], seq_len=traffic["seq_len"],
        hidden=cfg["n_embd"], intermediate=cfg["n_inner"],
        layers=cfg["n_layer"], vocab=cfg["vocab_size"])
