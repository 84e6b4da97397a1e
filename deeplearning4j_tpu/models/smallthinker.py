"""Decoder-only language model of SmallThinker-21BA3B-Instruct
(PowerInfer), for training.

Every layer is a router and two pre-norm sub-layers. The router comes
first and reads the layer's input as it arrives, before any norm: it
chooses ``experts_per_token`` of ``experts_total`` experts for each token
and weighs them by the softmax over the chosen logits. Then
``x <- x + attention(RMSNorm(x))``: grouped-query attention
(``nn.layers.attention.grouped_query_attention``) whose kind the layer's
place decides, by two lists of the config: where ``rope_layout[l]`` is 1
the queries and keys carry rotary positions, where 0 the layer sees no
positions at all; where ``sliding_window_layout[l]`` is 1 a query reaches
back over its own position and the ``sliding_window - 1`` before it, where
0 over everything before it. Then ``x <- x + experts(RMSNorm(x))``: the
ReLU-gated experts the router chose (``nn.layers.moe.RoutedExperts``, told
which of the experts it holds, handed the router's rows apart from its
own). No biases, no q-k norm, no shared expert; the head is a matrix of
its own, not the embedding. The vocabulary given is the slice held here:
ids, logits and loss are over it. A tree of leaves a layer
(``params["layer_<i>"]``), as ``Keye`` keeps them.

``residual_init_scale`` states how the two projections that write into the
residual stream (``Wo`` and the experts' ``down``) start, as in ``Keye``:
each is held as that constant times its stored leaf.

The loss is the language model's alone: no balancing bias and no auxiliary
loss on the router (the config has no key for either). The expert
sub-layer is recomputed in the backward pass (``jax.checkpoint``), as in
``Keye``: without that the cell's step does not fit the chip.

Training only (``Trainer.fit``). Serving it wants a cache that gives a
windowed layer its window and a global layer everything (ROADMAP Queue B).
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.config import NeuralNetConfiguration, register_config
from deeplearning4j_tpu.nn.layers.attention import grouped_query_attention
from deeplearning4j_tpu.nn.layers.moe import (
    RoutedExperts,
    load_max_over_mean,
)
from deeplearning4j_tpu.observability.vocab import (
    COUNTER_MOE_LOAD,
    COUNTER_MOE_PIECES_RUN,
    COUNTER_MOE_TOKENS_HERE,
    COUNTER_SWA_PAIRS_REQUIRED,
    COUNTER_SWA_PAIRS_TOUCHED,
    SCOPE_ATTN,
    SCOPE_EMBED,
    SCOPE_HEAD,
    SCOPE_MLP,
)
from deeplearning4j_tpu.ops import loss as losses
from deeplearning4j_tpu.ops import nn as opsnn
from deeplearning4j_tpu.train.updaters import Adam

_PERIOD = (0, 1, 1, 1)  # one global layer without positions, three windowed


@register_config
@dataclass
class SmallThinkerConfig:
    """Architecture config; the defaults are SmallThinker-21BA3B-Instruct's
    ``config.json``. ``rope_layout`` and ``sliding_window_layout`` have one
    entry a layer, or are None for the published period repeated."""

    vocab_size: int = 151936
    hidden: int = 2560
    num_layers: int = 52
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    experts_total: int = 64
    experts_held: Tuple[int, ...] = tuple(range(64))
    experts_per_token: int = 6
    expert_units: int = 768
    rope_layout: Optional[Tuple[int, ...]] = None
    sliding_window_layout: Optional[Tuple[int, ...]] = None
    sliding_window: int = 4096
    rope_theta: float = 1.5e6
    eps: float = 1e-6
    initializer_range: float = 0.02
    residual_init_scale: float = 1.0
    net: NeuralNetConfiguration = field(
        default_factory=lambda: NeuralNetConfiguration(updater=Adam(3e-4))
    )


def _token_ids(features):
    return features["token_ids"] if isinstance(features, dict) else features


class SmallThinker:
    """Trainer-compatible (init/apply/loss_fn) SmallThinker decoder."""

    def __init__(self, config: SmallThinkerConfig):
        config.experts_held = tuple(config.experts_held)
        for name in ("rope_layout", "sliding_window_layout"):
            layout = getattr(config, name)
            layout = tuple(_PERIOD[i % len(_PERIOD)]
                           for i in range(config.num_layers)
                           ) if layout is None else tuple(layout)
            if len(layout) != config.num_layers:
                raise ValueError(f"{name} has {len(layout)} entries for "
                                 f"{config.num_layers} layers")
            setattr(config, name, layout)
        self.config = config
        self.net = config.net

    def _experts(self) -> RoutedExperts:
        c = self.config
        return RoutedExperts(
            experts_total=c.experts_total, experts_held=c.experts_held,
            units=c.expert_units, top_k=c.experts_per_token,
            router="linear", gate_activation="relu")

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
            params[f"layer_{i}"] = {
                "attn": {"norm": ones(e), "Wq": normal((e, wq)),
                         "Wk": normal((e, wk)), "Wv": normal((e, wk)),
                         "Wo": normal((wq, e))},
                "moe": dict({k: normal(v.shape) for k, v in moe.items()},
                            norm=ones(e))}
        return {"params": params, "state": {}}

    # -- pure functions ----------------------------------------------------

    def encode(self, params, ids):
        """[N,T] int32 -> (hidden [N,T,H] with the final norm applied, what
        the layers counted: the token-expert pairs that landed on each
        expert held [layers, held], the pieces of the sorted pairs that
        ran [layers], and the query-key pairs of a head, over the batch,
        that each layer's kind requires and that what runs computes
        [layers] each)."""
        c = self.config
        with jax.named_scope(SCOPE_EMBED):
            x = opsnn.embedding_lookup(params["embeddings"]["word"], ids)

        # the expert sub-layer's activations are rows of token-expert pairs,
        # experts_per_token times the tokens, beside the router's float32
        # copies: recomputed in the backward pass, not kept (PERF.md section
        # 4: the compiler's plan for the cell's step with and without)
        @jax.checkpoint
        def experts(p, x, arrived):
            y, routed = self._experts().apply(
                p, {}, opsnn.rms_norm(x, p["norm"], c.eps),
                router_input=arrived)
            return x + c.residual_init_scale * y, {
                k: routed[k] for k in ("tokens_here", "pieces_run")}

        counted = []
        for i in range(c.num_layers):
            layer = params[f"layer_{i}"]
            arrived = x  # what the router reads: no norm, no attention yet
            p = layer["attn"]
            with jax.named_scope(SCOPE_ATTN):
                a, pairs = grouped_query_attention(
                    p, opsnn.rms_norm(x, p["norm"], c.eps),
                    num_heads=c.num_heads, num_kv_heads=c.num_kv_heads,
                    rope_theta=c.rope_theta if c.rope_layout[i] else None,
                    window=(c.sliding_window if c.sliding_window_layout[i]
                            else None))
                x = x + c.residual_init_scale * a
            with jax.named_scope(SCOPE_MLP):
                x, routed = experts(layer["moe"], x, arrived)
            counted.append(dict(pairs, **routed))
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
        the pairs of each layer's attention
        (``observability.vocab.STEP_COUNTERS``)."""
        ids = _token_ids(batch["features"])
        h, counted = self.encode(params, ids)
        with jax.named_scope(SCOPE_HEAD):
            loss = jnp.mean(losses.linear_softmax_cross_entropy(
                h[:, :-1], params["head"]["out"], ids[:, 1:]))
        metrics = {
            "loss": loss,
            COUNTER_MOE_TOKENS_HERE: counted["tokens_here"],
            COUNTER_MOE_LOAD: load_max_over_mean(counted["tokens_here"]),
            COUNTER_MOE_PIECES_RUN: counted["pieces_run"],
            COUNTER_SWA_PAIRS_REQUIRED: counted["pairs_required"],
            COUNTER_SWA_PAIRS_TOUCHED: counted["pairs_touched"],
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


def smallthinker_21b_a3b(**kw) -> SmallThinker:
    """SmallThinker-21BA3B-Instruct's published widths; ``num_layers``,
    ``experts_held`` and ``vocab_size`` say which share of it is held
    here."""
    return SmallThinker(SmallThinkerConfig(**kw))


def smallthinker_tiny(**kw) -> SmallThinker:
    """4 layers (one period), hidden 64, 6 and 2 heads of 16 (3 a group),
    a window of 8 keys, top-3 of 8 ReLU-gated experts of 32: tests and CPU
    runs."""
    for key, value in dict(
            hidden=64, num_layers=4, num_heads=6, num_kv_heads=2,
            head_dim=16, experts_total=8, experts_held=tuple(range(8)),
            experts_per_token=3, expert_units=32, sliding_window=8,
            vocab_size=96).items():
        kw.setdefault(key, value)
    return SmallThinker(SmallThinkerConfig(**kw))
