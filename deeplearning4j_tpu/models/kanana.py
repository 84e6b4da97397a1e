"""Decoder-only language model of kanana-2-30b-a3b-instruct-2601
(kakaocorp; ``model_type`` ``deepseek_v3``), for training.

Every layer is two pre-norm sub-layers, each ``x <- x + f(RMSNorm(x))``.
The first is multi-head latent attention (``nn.layers.attention.
latent_attention``: no query latent, a 512-wide key-value latent and a
rotary key of 64 that all heads share, query-key heads 192 wide over value
heads 128 wide, rotary positions on interleaved pairs). The second is, in
the first ``first_dense`` layers, a dense SiLU-gated MLP, and in the others
``experts_per_token`` of ``experts_total`` SiLU-gated experts behind a
sigmoid router that chooses by the score plus a correction bias and weighs
by the score alone, renormalised and scaled, beside shared experts that
every token runs (``nn.layers.moe.RoutedExperts``, told which of the
experts it holds). No biases; the head is a matrix of its own, not the
embedding. The vocabulary given is the slice held here: ids, logits and
loss are over it. A tree of leaves a layer (``params["layer_<i>"]``), as
``Keye`` keeps them.

``residual_init_scale`` states how the projections that write into the
residual stream (``Wo``, the dense MLP's, the shared experts' and every
routed expert's down-projection) start, as in ``Keye``: each is held as
that constant times its stored leaf.

``correction_bias`` is a constant of each expert layer (``noaux_tc``'s
``e_score_correction_bias``), one row of ``experts_total`` numbers a layer,
or None for zeros: fed to the router as its ``bias``, it moves the choice
and no weight, and no gradient reaches it. The update that balances load with it between steps is left
out, as is any auxiliary loss: the loss is the language model's alone.
The expert sub-layer is recomputed in the backward pass (``jax.checkpoint``),
as in ``Keye``; nothing else is (the cell's step fits the chip without:
PERF.md section 4).

Training only (``Trainer.fit``). Serving it wants a cache of the latent
and the rotary key (ROADMAP Queue B).
"""

from __future__ import annotations

import functools
import itertools
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.config import NeuralNetConfiguration, register_config
from deeplearning4j_tpu.nn.layers.attention import latent_attention
from deeplearning4j_tpu.nn.layers.moe import (
    RoutedExperts,
    load_max_over_mean,
)
from deeplearning4j_tpu.observability.vocab import (
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
class KananaConfig:
    """Architecture config; the defaults are kanana-2-30b-a3b-instruct-2601's
    ``config.json``."""

    vocab_size: int = 128256
    hidden: int = 2048
    num_layers: int = 48
    first_dense: int = 1          # first_k_dense_replace
    num_heads: int = 32
    nope_dim: int = 128           # qk_nope_head_dim
    rope_dim: int = 64            # qk_rope_head_dim
    v_dim: int = 128              # v_head_dim
    kv_lora_rank: int = 512
    intermediate: int = 6144      # the dense layers' MLP
    experts_total: int = 128
    experts_held: Tuple[int, ...] = tuple(range(128))
    experts_per_token: int = 6
    expert_units: int = 768
    shared_experts: int = 2       # merged into one MLP of 2 x expert_units
    routed_scaling: float = 2.448
    correction_bias: Optional[Any] = None  # [expert layers, experts_total]
    rope_theta: float = 1e6
    eps: float = 1e-6
    initializer_range: float = 0.02
    residual_init_scale: float = 1.0
    net: NeuralNetConfiguration = field(
        default_factory=lambda: NeuralNetConfiguration(updater=Adam(3e-4))
    )


def _token_ids(features):
    return features["token_ids"] if isinstance(features, dict) else features


class Kanana:
    """Trainer-compatible (init/apply/loss_fn) kanana-2 decoder."""

    def __init__(self, config: KananaConfig):
        config.experts_held = tuple(config.experts_held)
        self.config = config
        self.net = config.net

    def _experts(self) -> RoutedExperts:
        """The expert sub-layer of every expert layer."""
        c = self.config
        return RoutedExperts(
            experts_total=c.experts_total, experts_held=c.experts_held,
            units=c.expert_units, top_k=c.experts_per_token,
            router="sigmoid", scaling=c.routed_scaling,
            shared_units=c.shared_experts * c.expert_units)

    # -- construction ------------------------------------------------------

    def init(self, seed: Optional[int] = None) -> Dict[str, Any]:
        c = self.config
        seed = self.net.seed if seed is None else seed
        dtype = jnp.dtype(self.net.dtype)
        root, drawn = jax.random.key(seed), itertools.count()

        def normal(shape):
            return c.initializer_range * jax.random.normal(
                jax.random.fold_in(root, next(drawn)), shape, dtype)

        def ones(size):  # a buffer each: the step donates its state
            return jnp.ones((size,), dtype)

        e, h = c.hidden, c.num_heads
        params: Dict[str, Any] = {
            "embeddings": {"word": normal((c.vocab_size, e))},
            "final": {"norm": ones(e)},
            "head": {"out": normal((c.vocab_size, e))},
        }
        moe = jax.eval_shape(
            lambda: self._experts().init(root, (e,), dtype)[0])
        for i in range(c.num_layers):
            layer = {"attn": {
                "norm": ones(e),
                "Wq": normal((e, h * (c.nope_dim + c.rope_dim))),
                "Wkva": normal((e, c.kv_lora_rank + c.rope_dim)),
                "kv_norm": ones(c.kv_lora_rank),
                "Wkvb": normal((c.kv_lora_rank, h * (c.nope_dim + c.v_dim))),
                "Wo": normal((h * c.v_dim, e))}}
            if i < c.first_dense:
                layer["mlp"] = {"norm": ones(e),
                                "gate": normal((e, c.intermediate)),
                                "up": normal((e, c.intermediate)),
                                "down": normal((c.intermediate, e))}
            else:
                layer["moe"] = dict(
                    {k: normal(v.shape) for k, v in moe.items()}, norm=ones(e))
            params[f"layer_{i}"] = layer
        return {"params": params, "state": {}}

    # -- pure functions ----------------------------------------------------

    def encode(self, params, ids):
        """[N,T] int32 -> (hidden [N,T,H] with the final norm applied, what
        the expert layers counted: the token-expert pairs that landed on
        each expert held [expert layers, held] and the pieces of the sorted
        pairs that ran [expert layers])."""
        c = self.config
        s = c.residual_init_scale
        with jax.named_scope(SCOPE_EMBED):
            x = opsnn.embedding_lookup(params["embeddings"]["word"], ids)

        def dense(p, x):
            z = opsnn.rms_norm(x, p["norm"], c.eps)
            inner = jax.nn.silu(z @ p["gate"]) * (z @ p["up"])
            return x + s * (inner @ p["down"])

        # the expert sub-layer's activations are rows of token-expert pairs,
        # experts_per_token times the tokens, beside the router's float32
        # copies: recomputed in the backward pass, not kept, as in Keye
        @functools.partial(jax.checkpoint, static_argnums=(2,))
        def experts(p, x, index):
            if c.correction_bias is not None:
                p = dict(p, bias=jnp.asarray(c.correction_bias[index]))
            y, routed = self._experts().apply(
                p, {}, opsnn.rms_norm(x, p["norm"], c.eps))
            return x + s * y, {
                k: routed[k] for k in ("tokens_here", "pieces_run")}

        counted = []
        for i in range(c.num_layers):
            layer = params[f"layer_{i}"]
            p = layer["attn"]
            with jax.named_scope(SCOPE_ATTN):
                a = latent_attention(
                    p, opsnn.rms_norm(x, p["norm"], c.eps),
                    num_heads=c.num_heads, nope_dim=c.nope_dim,
                    rope_dim=c.rope_dim, v_dim=c.v_dim,
                    rope_theta=c.rope_theta, eps=c.eps)
                x = x + s * a
            with jax.named_scope(SCOPE_MLP):
                if i < c.first_dense:
                    x = dense(layer["mlp"], x)
                else:
                    x, routed = experts(layer["moe"], x,
                                        i - c.first_dense)
                    counted.append(routed)
        with jax.named_scope(SCOPE_HEAD):
            x = opsnn.rms_norm(x, params["final"]["norm"], c.eps)
        return x, {k: jnp.stack([layer[k] for layer in counted])
                   for k in ("tokens_here", "pieces_run")}

    def logits(self, params, hidden):
        with jax.named_scope(SCOPE_HEAD):
            return jnp.einsum("nth,vh->ntv", hidden, params["head"]["out"])

    def apply(self, variables, features, *, train=False, rng=None):
        """Returns (logits [N,T,V], state)."""
        h, _ = self.encode(variables["params"], _token_ids(features))
        return self.logits(variables["params"], h), variables.get("state", {})

    def loss_fn(self, params, state, batch, rng=None):
        """Mean next-token cross entropy over the T - 1 positions that
        have a next token. The step's metrics carry the experts' load
        (``observability.vocab.MOE_COUNTERS``)."""
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


def kanana2_30b_a3b(**kw) -> Kanana:
    """kanana-2-30b-a3b-instruct-2601's published widths; ``num_layers``,
    ``experts_held`` and ``vocab_size`` say which share of it is held here
    (``num_layers``, ``experts_held``, ``vocab_size`` and
    ``residual_init_scale`` are what the benchmark's configuration
    passes)."""
    return Kanana(KananaConfig(**kw))


def kanana_tiny(**kw) -> Kanana:
    """3 layers (the dense one and two with experts), hidden 64, 4 heads
    of 16 + 8 over values of 16, a latent of 32, top-3 of 8 experts of 32
    beside two shared ones, vocabulary 96: tests and CPU runs."""
    for key, value in dict(
            hidden=64, num_layers=3, num_heads=4, nope_dim=16, rope_dim=8,
            v_dim=16, kv_lora_rank=32, intermediate=96, experts_total=8,
            experts_held=tuple(range(8)), experts_per_token=3,
            expert_units=32, vocab_size=96).items():
        kw.setdefault(key, value)
    return Kanana(KananaConfig(**kw))
