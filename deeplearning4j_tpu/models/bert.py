"""BERT model family (north-star workload #4).

ref: the reference runs BERT-base by importing a TF frozen graph into
SameDiff and interpreting it op-by-op (SURVEY §3.2). Here BERT is a native
model: one traced function → one XLA program per step. Masked-LM + NSP
pretraining heads included; the encoder is a stack of
TransformerEncoderBlock (Pallas flash attention inside).

Batch convention (all host-built, static shapes):
    features = {"token_ids": [N,T] int32, "segment_ids": [N,T] int32,
                "mask": [N,T] 1/0 float}
    labels   = {"mlm_labels": [N,T] int32 (original ids at masked slots),
                "mlm_mask":   [N,T] 1/0 float (which slots are masked),
                "nsp": [N] int32 (optional next-sentence label)}

MLM loss supports two equivalent batch layouts:

* dense — loss over *all* positions weighted by ``mlm_mask`` [N,T];
* gathered — the batch additionally carries ``mlm_positions`` [N,P] int32,
  ``mlm_weights`` [N,P] and position-indexed ``mlm_labels`` [N,P], with P a
  FIXED max-predictions count (static shapes; padded slots weight 0). The
  decoder matmul then runs over [N,P,H] instead of [N,T,H] — at the
  standard mask rate P ≈ 0.15·T, cutting the vocab-size GEMM ~6x with
  bit-identical loss semantics (only masked slots ever contribute). This is
  the layout the reference's TF BERT graph itself uses
  (gather_indexes + label_weights in the masked-LM head).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.activations import get_activation
from deeplearning4j_tpu.nn.config import NeuralNetConfiguration, register_config
from deeplearning4j_tpu.nn.layers.attention import TransformerEncoderBlock
from deeplearning4j_tpu.observability.vocab import SCOPE_EMBED, SCOPE_HEAD
from deeplearning4j_tpu.ops import loss as losses
from deeplearning4j_tpu.ops import nn as opsnn
from deeplearning4j_tpu.train.updaters import Adam


@register_config
@dataclass
class BertConfig:
    """Architecture config (JSON round-trip via the config registry)."""

    vocab_size: int = 30522
    hidden: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate: int = 3072
    max_position: int = 512
    type_vocab: int = 2
    dropout: float = 0.1
    attention_dropout: float = 0.1
    activation: str = "gelu"
    eps: float = 1e-12
    use_nsp: bool = True
    initializer_range: float = 0.02
    # jax.checkpoint each encoder block (recompute-in-backward): the
    # memory lever for long-context / deep configs — see
    # TransformerEncoderBlock.remat.
    remat: bool = False
    net: NeuralNetConfiguration = field(
        default_factory=lambda: NeuralNetConfiguration(updater=Adam(1e-4))
    )


class Bert:
    """BERT encoder + MLM/NSP pretraining heads.

    Same model protocol as SequentialModel/GraphModel: ``init`` →
    variables pytree, ``apply``/``loss_fn`` pure (Trainer-compatible).
    """

    def __init__(self, config: BertConfig):
        self.config = config
        self.net = config.net
        self._block = TransformerEncoderBlock(
            num_heads=config.num_heads,
            intermediate=config.intermediate,
            activation=config.activation,
            dropout=config.dropout,
            attention_dropout=config.attention_dropout,
            post_ln=True,
            eps=config.eps,
            remat=config.remat,
        )

    # -- construction ------------------------------------------------------

    def init(self, seed: Optional[int] = None) -> Dict[str, Any]:
        c = self.config
        seed = self.net.seed if seed is None else seed
        rng = jax.random.key(seed)
        dtype = jnp.dtype(self.net.dtype)
        std = c.initializer_range

        def trunc(key, shape):
            return std * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)

        ks = jax.random.split(rng, 8 + c.num_layers)
        params: Dict[str, Any] = {
            "embeddings": {
                "word": trunc(ks[0], (c.vocab_size, c.hidden)),
                "position": trunc(ks[1], (c.max_position, c.hidden)),
                "type": trunc(ks[2], (c.type_vocab, c.hidden)),
                "ln_gamma": jnp.ones((c.hidden,), dtype),
                "ln_beta": jnp.zeros((c.hidden,), dtype),
            },
            "mlm": {
                "W": trunc(ks[3], (c.hidden, c.hidden)),
                "b": jnp.zeros((c.hidden,), dtype),
                "ln_gamma": jnp.ones((c.hidden,), dtype),
                "ln_beta": jnp.zeros((c.hidden,), dtype),
                # decoder shares the word embedding; only a bias is learned
                "out_b": jnp.zeros((c.vocab_size,), dtype),
            },
        }
        if c.use_nsp:
            params["pooler"] = {
                "W": trunc(ks[4], (c.hidden, c.hidden)),
                "b": jnp.zeros((c.hidden,), dtype),
            }
            params["nsp"] = {
                "W": trunc(ks[5], (c.hidden, 2)),
                "b": jnp.zeros((2,), dtype),
            }
        for i in range(c.num_layers):
            p, _ = self._block.init(ks[8 + i], (c.max_position, c.hidden), dtype)
            params[f"layer_{i}"] = p
        return {"params": params, "state": {}}

    # -- pure functions ----------------------------------------------------

    def encode(self, params, features, *, train=False, rng=None):
        """Token/segment ids → contextual embeddings [N,T,H]."""
        c = self.config
        ids = features["token_ids"]
        seg = features.get("segment_ids")
        mask = features.get("mask")
        t = ids.shape[1]
        emb = params["embeddings"]
        with jax.named_scope(SCOPE_EMBED):
            x = opsnn.embedding_lookup(emb["word"], ids)
            x = x + emb["position"][:t][None, :, :]
            if seg is not None:
                x = x + opsnn.embedding_lookup(emb["type"], seg)
            x = opsnn.layer_norm(x, emb["ln_gamma"], emb["ln_beta"],
                                 eps=c.eps)
            if train and c.dropout > 0.0 and rng is not None:
                x = opsnn.dropout(x, c.dropout, jax.random.fold_in(rng, 999))
        for i in range(c.num_layers):
            lrng = jax.random.fold_in(rng, i) if rng is not None else None
            x, _ = self._block.apply(
                params[f"layer_{i}"], {}, x, train=train, rng=lrng, mask=mask
            )
        return x

    def apply(self, variables, features, *, train=False, rng=None):
        """Returns (hidden_states [N,T,H], state). Feature dict or raw ids."""
        if not isinstance(features, dict):
            features = {"token_ids": features}
        x = self.encode(variables["params"], features, train=train, rng=rng)
        return x, variables.get("state", {})

    def mlm_logits(self, params, hidden):
        c = self.config
        m = params["mlm"]
        h = opsnn.linear(hidden, m["W"], m["b"])
        h = get_activation(c.activation)(h)
        h = opsnn.layer_norm(h, m["ln_gamma"], m["ln_beta"], eps=c.eps)
        return jnp.einsum("nth,vh->ntv", h, params["embeddings"]["word"]) + m["out_b"]

    def nsp_logits(self, params, hidden):
        pooled = jnp.tanh(
            opsnn.linear(hidden[:, 0, :], params["pooler"]["W"], params["pooler"]["b"])
        )
        return opsnn.linear(pooled, params["nsp"]["W"], params["nsp"]["b"])

    def loss_fn(self, params, state, batch, rng=None):
        features = batch["features"]
        labels = batch["labels"]
        hidden = self.encode(params, features, train=True, rng=rng)
        # both heads and the losses over them are one component scope
        with jax.named_scope(SCOPE_HEAD):
            return self._heads_loss(params, state, hidden, labels)

    def _heads_loss(self, params, state, hidden, labels):
        c = self.config
        if "mlm_positions" in labels:
            # Gathered head: decoder GEMM over the P masked slots only.
            pos = labels["mlm_positions"]  # [N,P] int32
            gathered = jnp.take_along_axis(
                hidden, pos[:, :, None], axis=1)  # [N,P,H]
            logits = self.mlm_logits(params, gathered)  # [N,P,V]
            mlm_mask = labels["mlm_weights"].astype(jnp.float32)
        else:
            logits = self.mlm_logits(params, hidden)  # [N,T,V]
            mlm_mask = labels["mlm_mask"].astype(jnp.float32)
        per_tok = losses.sparse_softmax_cross_entropy(
            logits, labels["mlm_labels"], reduction="none"
        )  # [N,T] or [N,P]
        denom = jnp.maximum(jnp.sum(mlm_mask), 1.0)
        mlm_loss = jnp.sum(per_tok * mlm_mask) / denom
        metrics = {"mlm_loss": mlm_loss}
        total = mlm_loss

        if c.use_nsp and "nsp" in labels:
            nsp = losses.sparse_softmax_cross_entropy(
                self.nsp_logits(params, hidden), labels["nsp"]
            )
            metrics["nsp_loss"] = nsp
            total = total + nsp
        metrics["loss"] = total
        return total, (state, metrics)

    def num_params(self, variables) -> int:
        return sum(p.size for p in jax.tree_util.tree_leaves(variables["params"]))


def bert_base(**kw) -> Bert:
    """BERT-base-uncased dims (12L/768H/12A) — north-star config #4."""
    return Bert(BertConfig(**kw))


def bert_tiny(**kw) -> Bert:
    """2L/128H/2A toy config for tests and CPU dry-runs."""
    kw.setdefault("hidden", 128)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 2)
    kw.setdefault("intermediate", 512)
    kw.setdefault("vocab_size", 1000)
    kw.setdefault("max_position", 128)
    return Bert(BertConfig(**kw))


def make_mlm_batch(rng, batch_size, seq_len, vocab_size, *, mask_frac=0.15,
                   mask_id=103, pad_frac=0.0, max_predictions=None):
    """Host-side synthetic MLM batch builder (tests/benchmarks).

    ``max_predictions``: when set, the batch uses the gathered layout —
    ``mlm_positions``/``mlm_weights``/[N,P] ``mlm_labels`` with P =
    max_predictions (masked slots beyond P are UNMASKED again so the dense
    and gathered views of the same batch stay semantically identical).
    """
    import numpy as np

    r = np.random.default_rng(rng)
    ids = r.integers(5, vocab_size, (batch_size, seq_len)).astype(np.int32)
    mlm_mask = (r.random((batch_size, seq_len)) < mask_frac).astype(np.float32)
    attn = np.ones((batch_size, seq_len), np.float32)
    if pad_frac > 0:
        lens = r.integers(int(seq_len * (1 - pad_frac)), seq_len + 1, batch_size)
        attn = (np.arange(seq_len)[None, :] < lens[:, None]).astype(np.float32)
        mlm_mask = mlm_mask * attn
    seg = np.zeros((batch_size, seq_len), np.int32)
    nsp = r.integers(0, 2, batch_size).astype(np.int32)

    labels: Dict[str, Any]
    if max_predictions is not None:
        p = int(max_predictions)
        if p <= 0:
            raise ValueError(f"max_predictions must be >= 1, got {p}")
        positions = np.zeros((batch_size, p), np.int32)
        weights = np.zeros((batch_size, p), np.float32)
        plabels = np.zeros((batch_size, p), np.int32)
        for n in range(batch_size):
            idx = np.flatnonzero(mlm_mask[n])
            if len(idx) > p:       # drop overflow AND unmask it
                mlm_mask[n, idx[p:]] = 0.0
                idx = idx[:p]
            positions[n, :len(idx)] = idx
            weights[n, :len(idx)] = 1.0
            plabels[n, :len(idx)] = ids[n, idx]
        labels = {"mlm_labels": plabels, "mlm_positions": positions,
                  "mlm_weights": weights, "nsp": nsp}
    else:
        labels = {"mlm_labels": ids, "mlm_mask": mlm_mask, "nsp": nsp}
    inp = np.where(mlm_mask > 0, mask_id, ids).astype(np.int32)
    return {
        "features": {"token_ids": inp, "segment_ids": seg, "mask": attn},
        "labels": labels,
    }
