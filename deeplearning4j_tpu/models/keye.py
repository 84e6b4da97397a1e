"""Decoder-only language model of Keye-VL-2.0-30B-A3B (Kwai-Keye), for
training.

Every layer is two pre-norm sub-layers, each ``x <- x + f(RMSNorm(x))``:
grouped-query attention over the keys a learned indexer selects for each
query (``nn.layers.attention.indexed_attention``: per-head q-k RMSNorm,
full rotary positions, the ``index_top_k`` best-scored keys of each
query's past, one set for all heads) and then ``experts_per_token`` of
``experts_total`` SiLU-gated experts behind a linear router with the chosen
weights renormalised (``nn.layers.moe.RoutedExperts``, told which of the
experts it holds). No biases; the head is a matrix of its own, not the
embedding. The vocabulary given is the slice held here: ids, logits and
loss are over it. A tree of leaves a layer (``params["layer_<i>"]``), as
``Zaya`` keeps them.

``residual_init_scale`` states how the two projections that write into the
residual stream (``Wo`` and the experts' ``down``) start, for a caller
that draws every matrix at one spread: each is held as that constant
times its stored leaf, so a leaf drawn at the initialiser's spread starts
the projection at ``residual_init_scale`` times it (a scaled
initialisation of the residual projections, as GPT-2's 1 / sqrt(2 x
layers)). At 1, the default, the stored leaf is the projection.

The loss is the language model's alone. The indexer reads the hidden state
with its gradient stopped and the selected set is a constant of the
backward pass, so the indexer's leaves get a zero gradient: the term that
trains it (a KL divergence to the main attention's head-summed
distribution, DeepSeek-V3.2-Exp's recipe) is left out (ROADMAP Queue B).
The vision tower is left out: the model is the text decoder, and with text
alone M-RoPE's three position streams are equal, which is plain rotary.

Training only (``Trainer.fit``). Serving it wants a cache that the
indexer's keys share with the model's and the selection at decode.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.config import NeuralNetConfiguration, register_config
from deeplearning4j_tpu.nn.layers.attention import indexed_attention
from deeplearning4j_tpu.nn.layers.moe import RoutedExperts
from deeplearning4j_tpu.observability.vocab import (
    COUNTER_DSA_KEYS_MEAN,
    COUNTER_DSA_PAIRS,
    COUNTER_DSA_TILES_EMPTY,
    COUNTER_MOE_LOAD,
    COUNTER_MOE_PIECES_RUN,
    COUNTER_MOE_TOKENS_HERE,
    SCOPE_ATTN,
    SCOPE_EMBED,
    SCOPE_HEAD,
    SCOPE_MLP,
)
from deeplearning4j_tpu.ops import loss as losses
from deeplearning4j_tpu.ops import nn as opsnn
from deeplearning4j_tpu.train.updaters import Adam


@register_config
@dataclass
class KeyeConfig:
    """Architecture config; the defaults are the language model of
    Keye-VL-2.0-30B-A3B's ``config.json``."""

    vocab_size: int = 151936
    hidden: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    experts_total: int = 128
    experts_held: Tuple[int, ...] = tuple(range(128))
    experts_per_token: int = 8
    expert_units: int = 768
    index_heads: int = 16
    index_dim: int = 64
    index_top_k: int = 2048
    rope_theta: float = 1e7
    eps: float = 1e-6
    initializer_range: float = 0.02
    residual_init_scale: float = 1.0
    net: NeuralNetConfiguration = field(
        default_factory=lambda: NeuralNetConfiguration(updater=Adam(3e-4))
    )


def _token_ids(features):
    return features["token_ids"] if isinstance(features, dict) else features


class Keye:
    """Trainer-compatible (init/apply/loss_fn) Keye-VL-2.0 text decoder."""

    def __init__(self, config: KeyeConfig):
        config.experts_held = tuple(config.experts_held)
        self.config = config
        self.net = config.net

    def _experts(self) -> RoutedExperts:
        c = self.config
        return RoutedExperts(
            experts_total=c.experts_total, experts_held=c.experts_held,
            units=c.expert_units, top_k=c.experts_per_token,
            router="linear")

    # -- construction ------------------------------------------------------

    def init(self, seed: Optional[int] = None) -> Dict[str, Any]:
        c = self.config
        seed = self.net.seed if seed is None else seed
        dtype = jnp.dtype(self.net.dtype)
        root, drawn = jax.random.key(seed), itertools.count()

        def normal(shape):
            return c.initializer_range * jax.random.normal(
                jax.random.fold_in(root, next(drawn)), shape, dtype)

        e, d = c.hidden, c.head_dim
        wq, wk = c.num_heads * d, c.num_kv_heads * d

        def ones(size):  # a buffer each: the step donates its state
            return jnp.ones((size,), dtype)

        params: Dict[str, Any] = {
            "embeddings": {"word": normal((c.vocab_size, e))},
            "final": {"norm": ones(e)},
            "head": {"out": normal((c.vocab_size, e))},
        }
        moe = jax.eval_shape(
            lambda: self._experts().init(root, (e,), dtype)[0])
        for i in range(c.num_layers):
            attn = {
                "norm": ones(e), "Wq": normal((e, wq)),
                "Wk": normal((e, wk)), "Wv": normal((e, wk)),
                "Wo": normal((wq, e)),
                "q_norm": ones(d), "k_norm": ones(d),
                "index": {
                    "Wq": normal((e, c.index_heads * c.index_dim)),
                    "Wk": normal((e, c.index_dim)),
                    "k_gamma": ones(c.index_dim),
                    "k_beta": jnp.zeros((c.index_dim,), dtype),
                    "Ww": normal((e, c.index_heads)),
                },
            }
            params[f"layer_{i}"] = {
                "attn": attn,
                "moe": dict({k: normal(v.shape) for k, v in moe.items()},
                            norm=ones(e))}
        return {"params": params, "state": {}}

    # -- pure functions ----------------------------------------------------

    def encode(self, params, ids):
        """[N,T] int32 -> (hidden [N,T,H] with the final norm applied, what
        the layers counted: the token-expert pairs that landed on each
        expert held [layers, held], the pieces of the sorted pairs that
        ran [layers], the pairs the indexer selected [layers] and the
        share of flash_fwd's live tiles in which it selected nothing
        [layers])."""
        c = self.config
        with jax.named_scope(SCOPE_EMBED):
            x = opsnn.embedding_lookup(params["embeddings"]["word"], ids)

        # the expert sub-layer's activations are rows of token-expert pairs,
        # experts_per_token times the tokens: recomputed in the backward
        # pass, not kept (PERF.md section 4: what that buys at the cell's size)
        @jax.checkpoint
        def experts(p, x):
            y, routed = self._experts().apply(
                p, {}, opsnn.rms_norm(x, p["norm"], c.eps))
            return x + c.residual_init_scale * y, {
                k: routed[k] for k in ("tokens_here", "pieces_run")}

        counted = []
        for i in range(c.num_layers):
            layer = params[f"layer_{i}"]
            p = layer["attn"]
            with jax.named_scope(SCOPE_ATTN):
                a, selected = indexed_attention(
                    p, opsnn.rms_norm(x, p["norm"], c.eps),
                    num_heads=c.num_heads, num_kv_heads=c.num_kv_heads,
                    index_heads=c.index_heads, top_k=c.index_top_k,
                    rope_theta=c.rope_theta, eps=c.eps)
                x = x + c.residual_init_scale * a
            with jax.named_scope(SCOPE_MLP):
                x, routed = experts(layer["moe"], x)
            counted.append(dict(selected, **routed))
        with jax.named_scope(SCOPE_HEAD):
            x = opsnn.rms_norm(x, params["final"]["norm"], c.eps)
        return x, {k: jnp.stack([layer[k] for layer in counted])
                   for k in counted[0]}

    def logits(self, params, hidden):
        with jax.named_scope(SCOPE_HEAD):
            return jnp.einsum("nth,vh->ntv", hidden, params["head"]["out"])

    def apply(self, variables, features, *, train=False, rng=None):
        """Returns (logits [N,T,V], state)."""
        h, _ = self.encode(variables["params"], _token_ids(features))
        return self.logits(variables["params"], h), variables.get("state", {})

    def loss_fn(self, params, state, batch, rng=None):
        """Mean next-token cross entropy over the T - 1 positions that
        have a next token. The step's metrics carry the experts' load and
        what the indexer selected (``observability.vocab.STEP_COUNTERS``)."""
        ids = _token_ids(batch["features"])
        h, counted = self.encode(params, ids)
        with jax.named_scope(SCOPE_HEAD):
            loss = jnp.mean(losses.linear_softmax_cross_entropy(
                h[:, :-1], params["head"]["out"], ids[:, 1:]))
        load = counted["tokens_here"].astype(jnp.float32)
        metrics = {
            "loss": loss,
            COUNTER_MOE_TOKENS_HERE: counted["tokens_here"],
            COUNTER_MOE_LOAD: jnp.mean(
                jnp.max(load, axis=1)
                / jnp.maximum(jnp.mean(load, axis=1), 1.0)),
            COUNTER_MOE_PIECES_RUN: counted["pieces_run"],
            COUNTER_DSA_PAIRS: counted["pairs_selected"],
            COUNTER_DSA_KEYS_MEAN: jnp.mean(
                counted["pairs_selected"].astype(jnp.float32)) / ids.size,
            COUNTER_DSA_TILES_EMPTY: jnp.mean(counted["tiles_empty_share"]),
        }
        return loss, (state, metrics)

    def loss_weight(self, batch):
        """The batch's count of next-token positions (the trainer's
        gradient accumulation weights micro-batches by it)."""
        n, t = _token_ids(batch["features"]).shape
        return jnp.float32(n * (t - 1))

    def num_params(self, variables) -> int:
        return sum(p.size for p in
                   jax.tree_util.tree_leaves(variables["params"]))


def keye_vl2_30b_a3b(**kw) -> Keye:
    """Keye-VL-2.0-30B-A3B's published widths; ``num_layers``,
    ``experts_held`` and ``vocab_size`` say which share of it is held
    here."""
    return Keye(KeyeConfig(**kw))


def keye_tiny(**kw) -> Keye:
    """2 layers, hidden 64, 4 and 2 heads of 16, an indexer of 2 heads of 8
    that keeps 8 keys, top-2 of 8 experts of 32: tests and CPU runs."""
    for key, value in dict(
            hidden=64, num_layers=2, num_heads=4, num_kv_heads=2,
            head_dim=16, experts_total=8, experts_held=tuple(range(8)),
            experts_per_token=2, expert_units=32, index_heads=2,
            index_dim=8, index_top_k=8, vocab_size=96).items():
        kw.setdefault(key, value)
    return Keye(KeyeConfig(**kw))
