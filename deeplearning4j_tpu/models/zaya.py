"""Decoder-only language model of the ZAYA1 family (Zyphra,
arXiv:2511.17127), for training.

Every layer is two sub-layers, each ``x <- a * x + c * f(RMSNorm(x))``
with learned residual scales ``a`` and ``c``: compressed convolutional
attention (``nn.layers.attention.cca_attention``: grouped-query heads in
a latent, mixed along the sequence by causal convolutions, half-rotary
positions) and then top-1 routed SiLU-gated experts behind an MLP router
whose state crosses layers (``nn.layers.moe.RoutedExperts``, told which
of the experts it holds). No biases; the head is tied to the embedding.
The vocabulary given is the slice held here: ids, logits and loss are
over it.

Training only (``Trainer.fit``). Serving it wants what the engine lacks:
a cache for the latent keys and values and the one-token state of the
convolutions and of the value shift (ROADMAP Queue B).
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.config import NeuralNetConfiguration, register_config
from deeplearning4j_tpu.nn.layers.attention import cca_attention
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
class ZayaConfig:
    """Architecture config; the defaults are ZAYA1-8B's ``config.json``."""

    vocab_size: int = 262272
    hidden: int = 2048
    num_layers: int = 40
    num_heads: int = 8
    num_kv_heads: int = 2
    head_dim: int = 128
    experts_total: int = 16
    experts_held: Tuple[int, ...] = tuple(range(16))
    expert_units: int = 2048
    router_hidden: int = 256
    cca_time0: int = 2
    cca_time1: int = 2
    rope_theta: float = 5e6
    rotary_share: float = 0.5
    eps: float = 1e-5
    initializer_range: float = 0.02
    net: NeuralNetConfiguration = field(
        default_factory=lambda: NeuralNetConfiguration(updater=Adam(3e-4))
    )


def _token_ids(features):
    return features["token_ids"] if isinstance(features, dict) else features


class Zaya:
    """Trainer-compatible (init/apply/loss_fn) ZAYA1 decoder."""

    def __init__(self, config: ZayaConfig):
        config.experts_held = tuple(config.experts_held)
        self.config = config
        self.net = config.net

    def _experts(self, layer: int) -> RoutedExperts:
        c = self.config
        return RoutedExperts(
            experts_total=c.experts_total, experts_held=c.experts_held,
            units=c.expert_units, router_hidden=c.router_hidden,
            carries_router=layer > 0)

    # -- construction ------------------------------------------------------

    def init(self, seed: Optional[int] = None) -> Dict[str, Any]:
        c = self.config
        seed = self.net.seed if seed is None else seed
        dtype = jnp.dtype(self.net.dtype)
        root, drawn = jax.random.key(seed), itertools.count()

        def normal(shape):
            return c.initializer_range * jax.random.normal(
                jax.random.fold_in(root, next(drawn)), shape, dtype)

        def scaled():  # a buffer each: the step donates its state
            return {k: jnp.ones((c.hidden,), dtype)
                    for k in ("norm", "res_a", "res_c")}

        e, d = c.hidden, c.head_dim
        wq, wk = c.num_heads * d, c.num_kv_heads * d
        params: Dict[str, Any] = {
            "embeddings": {"word": normal((c.vocab_size, e))},
            "final": {"norm": jnp.ones((e,), dtype)},
        }
        for i in range(c.num_layers):
            attn = dict(
                scaled(), Wq=normal((e, wq)), Wk=normal((e, wk)),
                Wva=normal((e, d)), Wvb=normal((e, d)), Wo=normal((wq, e)),
                conv0_q=normal((c.cca_time0, wq)),
                conv1_q=normal((c.cca_time1, c.num_heads, d, d)),
                conv0_k=normal((c.cca_time0, wk)),
                conv1_k=normal((c.cca_time1, c.num_kv_heads, d, d)),
                tau=jnp.ones((c.num_kv_heads,), dtype))
            moe = jax.eval_shape(
                lambda i=i: self._experts(i).init(root, (e,), dtype)[0])
            moe = {k: normal(v.shape) if v.ndim >= 2
                   else jnp.zeros(v.shape, dtype)  # gamma, the bias
                   for k, v in moe.items()}
            params[f"layer_{i}"] = {"attn": attn, "moe": dict(scaled(), **moe)}
        return {"params": params, "state": {}}

    # -- pure functions ----------------------------------------------------

    def encode(self, params, ids):
        """[N,T] int32 -> (hidden [N,T,H] with the final norm applied,
        what the layers counted: the tokens that landed on each expert
        held [layers, held], the pieces of the sorted tokens that ran
        [layers])."""
        c = self.config
        with jax.named_scope(SCOPE_EMBED):
            x = opsnn.embedding_lookup(params["embeddings"]["word"], ids)
        routed: Dict[str, Any] = {}
        counted = []
        for i in range(c.num_layers):
            p = params[f"layer_{i}"]["attn"]
            with jax.named_scope(SCOPE_ATTN):
                a = cca_attention(
                    p, opsnn.rms_norm(x, p["norm"], c.eps),
                    num_heads=c.num_heads, num_kv_heads=c.num_kv_heads,
                    rope_theta=c.rope_theta, rotary_share=c.rotary_share)
                x = p["res_a"] * x + p["res_c"] * a
            p = params[f"layer_{i}"]["moe"]
            with jax.named_scope(SCOPE_MLP):
                y, routed = self._experts(i).apply(
                    p, routed, opsnn.rms_norm(x, p["norm"], c.eps))
                x = p["res_a"] * x + p["res_c"] * y
            counted.append(routed)
        with jax.named_scope(SCOPE_HEAD):
            x = opsnn.rms_norm(x, params["final"]["norm"], c.eps)
        return x, {k: jnp.stack([layer[k] for layer in counted])
                   for k in ("tokens_here", "pieces_run")}

    def logits(self, params, hidden):
        with jax.named_scope(SCOPE_HEAD):
            return jnp.einsum("nth,vh->ntv", hidden,
                              params["embeddings"]["word"])

    def apply(self, variables, features, *, train=False, rng=None):
        """Returns (logits [N,T,V], state)."""
        h, _ = self.encode(variables["params"], _token_ids(features))
        return self.logits(variables["params"], h), variables.get("state", {})

    def loss_fn(self, params, state, batch, rng=None):
        """Mean next-token cross entropy over the T - 1 positions that
        have a next token. The step's metrics carry the experts' load
        (``observability.vocab.STEP_COUNTERS``)."""
        ids = _token_ids(batch["features"])
        h, counted = self.encode(params, ids)
        with jax.named_scope(SCOPE_HEAD):
            loss = jnp.mean(losses.linear_softmax_cross_entropy(
                h[:, :-1], params["embeddings"]["word"], ids[:, 1:]))
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


def zaya1_8b(**kw) -> Zaya:
    """ZAYA1-8B's published widths; ``num_layers``, ``experts_held`` and
    ``vocab_size`` say which share of it is held here."""
    return Zaya(ZayaConfig(**kw))


def zaya_tiny(**kw) -> Zaya:
    """2 layers, hidden 64, 4 experts, heads of 16: tests and CPU runs."""
    for key, value in dict(
            hidden=64, num_layers=2, num_heads=4, num_kv_heads=2,
            head_dim=16, experts_total=4, experts_held=(0, 1, 2, 3),
            expert_units=64, router_hidden=16, vocab_size=96).items():
        kw.setdefault(key, value)
    return Zaya(ZayaConfig(**kw))
