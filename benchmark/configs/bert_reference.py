"""Plain reference of BERT pre-training (Devlin et al. 2018), as the
``bert_base`` configuration runs it.

Embeddings (word + position + token type, LayerNorm), post-LN encoder
blocks, the masked-LM head over the gathered prediction slots (dense,
GELU, LayerNorm, decoder tied to the word embedding plus a bias) and the
next-sentence head (tanh pooler over the first token, 2-way classifier).
Loss: mean masked-LM cross entropy over the weighted slots plus mean
next-sentence cross entropy. Departure from the published model, listed in
the configuration's file: GELU in its tanh form (the program's ``"gelu"``).
"""

from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp
import numpy as np

from benchmark.configs import reference_common as rc


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    shapes: Dict[str, Any] = {
        "embeddings": {
            "word": ((v, e), "normal"),
            "position": ((cfg["max_position_embeddings"], e), "normal"),
            "type": ((cfg["type_vocab_size"], e), "normal"),
            "ln_gamma": ((e,), "ones"), "ln_beta": ((e,), "zeros"),
        },
        "mlm": {
            "W": ((e, e), "normal"), "b": ((e,), "zeros"),
            "ln_gamma": ((e,), "ones"), "ln_beta": ((e,), "zeros"),
            "out_b": ((v,), "zeros"),
        },
        "pooler": {"W": ((e, e), "normal"), "b": ((e,), "zeros")},
        "nsp": {"W": ((e, 2), "normal"), "b": ((2,), "zeros")},
    }
    for i in range(cfg["num_hidden_layers"]):
        shapes[f"layer_{i}"] = rc.block_shapes(e, cfg["intermediate_size"])
    return shapes


def make_batch(cfg: Dict[str, Any], rng: np.random.Generator,
               traffic: Dict[str, Any]) -> Dict[str, Any]:
    """One masked-LM batch in the program's gathered layout: every row a
    different random sequence, ``max_predictions`` slots a row."""
    n, t = traffic["rows"], traffic["seq_len"]
    p = traffic["max_predictions"]
    ids = rng.integers(5, cfg["vocab_size"], (n, t)).astype(np.int32)
    positions = np.zeros((n, p), np.int32)
    weights = np.zeros((n, p), np.float32)
    labels = np.zeros((n, p), np.int32)
    inputs = ids.copy()
    for row in range(n):
        chosen = np.flatnonzero(rng.random(t) < traffic["mask_frac"])[:p]
        positions[row, :len(chosen)] = chosen
        weights[row, :len(chosen)] = 1.0
        labels[row, :len(chosen)] = ids[row, chosen]
        inputs[row, chosen] = traffic["mask_id"]
    return {
        "features": {"token_ids": inputs,
                     "segment_ids": np.zeros((n, t), np.int32),
                     "mask": np.ones((n, t), np.float32)},
        "labels": {"mlm_labels": labels, "mlm_positions": positions,
                   "mlm_weights": weights,
                   "nsp": rng.integers(0, 2, n).astype(np.int32)},
    }


def tokens_per_step(traffic: Dict[str, Any]) -> int:
    return traffic["rows"] * traffic["seq_len"]


def part_weights(batch) -> Dict[str, float]:
    return {"mlm": max(float(np.sum(batch["labels"]["mlm_weights"])), 1.0),
            "nsp": float(batch["labels"]["nsp"].shape[0])}


def encode(cfg, params, features, mm: rc.Matmul):
    eps = cfg["layer_norm_eps"]
    emb = params["embeddings"]
    ids = features["token_ids"]
    x = (emb["word"][ids] + emb["position"][: ids.shape[1]][None]
         + emb["type"][features["segment_ids"]])
    x = rc.layer_norm(x, emb["ln_gamma"], emb["ln_beta"], eps)
    for i in range(cfg["num_hidden_layers"]):
        x = rc.transformer_block(
            mm, x, params[f"layer_{i}"],
            num_heads=cfg["num_attention_heads"], eps=eps, causal=False,
            post_ln=True, key_mask=features["mask"])
    return x


def loss_parts(cfg, params, rows, mm: rc.Matmul) -> Dict[str, Any]:
    """Sums over the block's rows of each loss part."""
    eps = cfg["layer_norm_eps"]
    labels = rows["labels"]
    hidden = encode(cfg, params, rows["features"], mm)
    gathered = jnp.take_along_axis(
        hidden, labels["mlm_positions"][:, :, None], axis=1)
    head = params["mlm"]
    h = rc.gelu_tanh(mm("npe,ef->npf", gathered, head["W"]) + head["b"])
    h = rc.layer_norm(h, head["ln_gamma"], head["ln_beta"], eps)
    logits = mm("npe,ve->npv", h, params["embeddings"]["word"]) + head["out_b"]
    mlm = jnp.sum(rc.cross_entropy(logits, labels["mlm_labels"])
                  * labels["mlm_weights"])
    pooled = jnp.tanh(mm("ne,ef->nf", hidden[:, 0, :], params["pooler"]["W"])
                      + params["pooler"]["b"])
    nsp_logits = mm("ne,ef->nf", pooled, params["nsp"]["W"]) + params["nsp"]["b"]
    nsp = jnp.sum(rc.cross_entropy(nsp_logits, labels["nsp"]))
    return {"mlm": mlm, "nsp": nsp}


def train_flops(cfg: Dict[str, Any], traffic: Dict[str, Any]) -> float:
    from benchmark.harness import flops

    return flops.bert_train_flops(
        rows=traffic["rows"], seq_len=traffic["seq_len"],
        hidden=cfg["hidden_size"], intermediate=cfg["intermediate_size"],
        layers=cfg["num_hidden_layers"], vocab=cfg["vocab_size"],
        predictions=traffic["max_predictions"])
