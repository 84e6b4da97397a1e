"""Plain reference of SmallThinker-21BA3B-Instruct (PowerInfer) as the
``smallthinker_21b_a3b`` configuration runs it: a decoder whose every layer
is a router, one attention sub-layer and one expert sub-layer. The router
stands first and reads the layer's input. Attention is grouped-query (28
query heads over 4 key-value heads of 128) and of two kinds, by the
config's two lists: where ``sliding_window_layout[l]`` is 1 a query attends
to the last ``sliding_window_size`` (4,096) keys of its past, itself
counted, and where 0 to all of it; where ``rope_layout[l]`` is 1 the
queries and keys carry rotary positions, and where 0 the layer sees no
positions at all. The published lists are ``[0, 1, 1, 1]`` thirteen times:
one global layer without positions, then three windowed rotary layers. The
experts are top-6 of 64 ReLU-gated ones of width 768, the chosen weights
the softmax over the chosen logits. RMSNorm, no biases, an untied head.

The sizes are the published ``config.json``'s. What that file does not
carry is set by the family's convention (the public graph of the family's
code; ``described_as`` in the catalog); each such item is a key of
``assumed`` in ``smallthinker_21b_a3b.json``, in these words:

- ``residual``: pre-norm, x1 = x + attention(RMSNorm(x)), x2 = x1 +
  experts(RMSNorm(x1)), in every layer; every layer has both sub-layers and
  no dense feed-forward.
- ``router``: first in the layer, on the layer's input x itself, before the
  input RMSNorm and before attention: z = x Wg (2560 -> 64, no bias) in
  float32 at highest; E_t = the 6 largest of z[t]; c_e = exp(z_e) / sum over
  E_t of exp(z) (moe_primary_router_apply_softmax and norm_topk_prob: the
  softmax over all 64, then the chosen weights normalised, which is the
  softmax over the 6 chosen logits), wherever the chosen are held. No
  balancing bias, no auxiliary loss in the step (the config has no key for
  either).
- ``attention``: h = RMSNorm(x); q = h Wq (2560 -> 28 x 128), k = h Wk, v = h
  Wv (2560 -> 4 x 128), no bias, no q-k norm (the config has no key for
  either); o_h[t] = sum_s softmax_s(q_h[t] . k_g(h)[s] / sqrt(128)) v_g(h)[s]
  over the keys s that query t attends to, 7 query heads to a key-value
  head; y = concat_h(o_h) Wo (3584 -> 2560).
- ``rotary``: where rope_layout[l] is 1, on all 128 dimensions of q and k at
  theta 1.5e6, pairing dimension i with i + 64; where 0, nothing is added or
  rotated: the layer sees no positions.
- ``window``: query t attends to the keys s <= t and, where
  sliding_window_layout[l] is 1, only to those with t - s < 4096: the window
  counts the query's own position, 4,096 keys at most.
- ``experts``: g = RMSNorm(x1); y2 = sum over e in E_t held here of c_e
  Wdown_e(relu(Wgate_e g) * (Wup_e g)), three 2560 x 768 matrices an expert,
  no bias, no shared expert.
- ``head``: final RMSNorm, logits = h Wout (untied), mean cross-entropy
  over the T - 1 positions that have a next token.
- ``secondary_experts``: left out: described_as names 'primary+secondary
  experts', and the config has keys for the primary ones only.
- ``sparsity_predictor``: left out: any predictor of which gate outputs are
  zero is an inference device and no part of the function.
- ``residual_projection_init``: the two projections that write into the
  residual stream, Wo and every expert's Wdown, start at initializer_range /
  (2 x 52 layers): small enough that after four layers the stream still
  carries each token's own embedding, so that the routers, which read the
  stream raw, still route by each token's own embedding and the load on the
  experts held is the balanced one at every seed (PERF.md section 6, PR 33).
  The harness draws every matrix at the one spread initializer_range, so
  program and reference hold each of these projections as the constant
  residual_init_scale = 1/104 times its leaf.

**Where a chosen expert is not held here** (``experts_held``), it adds
nothing to y2: the deployment's other chips add it; the weights c_e are
normalised over all 6 chosen all the same. With every expert held the same
code is the uncut model.

The parameters are a tree of leaves a layer (``params["layer_<i>"]``). How
the arithmetic is cut into pieces changes no value and keeps three float32
steps at 16,384 positions inside one chip's memory: the experts held are a
``jax.lax.scan`` over theirs, attention runs over blocks of ``QUERY_BLOCK``
queries (each against every key, the pairs outside the causal mask and the
window masked), the head over blocks of ``HEAD_BLOCK`` positions, and
``jax.checkpoint`` wraps each block and each sub-layer. The layers are a
Python loop: their kinds differ, and there are four.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.configs import reference_common as rc

QUERY_BLOCK = 256
HEAD_BLOCK = 2048


def _sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    return {
        "e": cfg["hidden_size"], "d": cfg["head_dim"],
        "hq": cfg["num_attention_heads"], "hk": cfg["num_key_value_heads"],
        "f": cfg["moe_ffn_hidden_size"],
        "total": cfg["moe_num_primary_experts_total"],
        "held": len(cfg["experts_held"]),
        "per_token": cfg["moe_num_active_primary_experts"],
        "layers": cfg["num_hidden_layers"], "v": cfg["vocab_size"],
    }


def layer_kinds(cfg: Dict[str, Any]):
    """For each layer held (the first ``num_hidden_layers`` of the
    published lists): whether it has rotary positions, and its window or
    None."""
    return [(bool(cfg["rope_layout"][i]),
             cfg["sliding_window_size"] if cfg["sliding_window_layout"][i]
             else None) for i in range(cfg["num_hidden_layers"])]


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    s = _sizes(cfg)
    e, d = s["e"], s["d"]
    wq, wk = s["hq"] * d, s["hk"] * d
    attn = {
        "norm": ((e,), "ones"),
        "Wq": ((e, wq), "normal"), "Wk": ((e, wk), "normal"),
        "Wv": ((e, wk), "normal"), "Wo": ((wq, e), "normal"),
    }
    moe = {
        "norm": ((e,), "ones"),
        "Wg": ((e, s["total"]), "normal"),
        "gate": ((s["held"], e, s["f"]), "normal"),
        "up": ((s["held"], e, s["f"]), "normal"),
        "down": ((s["held"], s["f"], e), "normal"),
    }
    return {
        "embeddings": {"word": ((s["v"], e), "normal")},
        "final": {"norm": ((e,), "ones")},
        "head": {"out": ((s["v"], e), "normal")},
        **{f"layer_{i}": {"attn": attn, "moe": moe}
           for i in range(s["layers"])},
    }


def make_batch(cfg: Dict[str, Any], rng: np.random.Generator,
               traffic: Dict[str, Any]) -> Dict[str, Any]:
    """One language-modelling batch: ids uniform over the slice of the
    vocabulary that is held here."""
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


# -- the layers ----------------------------------------------------------------

def rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                        + eps) * weight


def rotary(x, theta: float):
    """Rotary positions on the whole last axis of ``x`` [N,T,heads,d];
    dimension i is paired with i + d / 2."""
    t, d = x.shape[1], x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attended(positions, keys: int, window: Optional[int]):
    """[Q, keys] of bool: which of the keys 0..keys-1 the queries at
    ``positions`` attend to."""
    behind = positions[:, None] - jnp.arange(keys)[None, :]
    if window is None:
        return behind >= 0
    return (behind >= 0) & (behind < window)


def attention(cfg, mm: rc.Matmul, h, p, *, positions: bool,
              window: Optional[int]):
    """The attention sub-layer's f: ``h`` [N,T,E] (normed) to [N,T,E]."""
    s = _sizes(cfg)
    n, t, _ = h.shape
    d, hq, hk = s["d"], s["hq"], s["hk"]
    q = mm("nte,ef->ntf", h, p["Wq"]).reshape(n, t, hq, d)
    k = mm("nte,ef->ntf", h, p["Wk"]).reshape(n, t, hk, d)
    v = mm("nte,ef->ntf", h, p["Wv"]).reshape(n, t, hk, d)
    if positions:
        q, k = rotary(q, cfg["rope_theta"]), rotary(k, cfg["rope_theta"])
    q = q.reshape(n, t, hk, hq // hk, d)  # each group with its key-value head

    @jax.checkpoint
    def block(q, at):
        scores = mm("nqkgd,nskd->nkgqs", q, k) / math.sqrt(d)
        scores = jnp.where(attended(at, t, window)[None, None, None],
                           scores, -1e30)
        return mm("nkgqs,nskd->nqkgd", jax.nn.softmax(scores, axis=-1), v)

    size = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    blocks = t // size
    o = jax.lax.map(lambda args: block(*args), (
        jnp.moveaxis(q.reshape((n, blocks, size) + q.shape[2:]), 1, 0),
        jnp.arange(t).reshape(blocks, size)))
    o = jnp.moveaxis(o, 0, 1).reshape(n, t, hq * d)
    return mm("ntf,fe->nte", o, p["Wo"])


def route(cfg, mm: rc.Matmul, x, p):
    """For every token its experts [N,T,6] and the weights c_e of their
    outputs, the softmax over the chosen logits, wherever the chosen are
    held. ``x`` is the layer's input as it arrives."""
    z = mm("nte,ex->ntx", x, p["Wg"])
    chosen = jnp.argsort(-z, axis=-1)[
        ..., :cfg["moe_num_active_primary_experts"]]
    return chosen, jax.nn.softmax(
        jnp.take_along_axis(z, chosen, axis=-1), axis=-1)


def experts(cfg, mm: rc.Matmul, g, p, chosen, weight):
    """What the experts held here give: every held expert over every
    token, weighted by c_e where the token chose it and by 0 elsewhere."""

    @jax.checkpoint
    def one(y, expert):
        e, gate, up, down = expert
        inner = (jax.nn.relu(mm("nte,ef->ntf", g, gate))
                 * mm("nte,ef->ntf", g, up))
        out = mm("ntf,fe->nte", inner, down)
        c_e = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)
        return y + c_e[..., None] * out, None

    held = jnp.asarray(cfg["experts_held"], chosen.dtype)
    return jax.lax.scan(one, jnp.zeros_like(g),
                        (held, p["gate"], p["up"], p["down"]))[0]


def expert_sublayer(cfg, mm: rc.Matmul, x, x1, p):
    """f of the expert sub-layer: routed on the layer's input ``x``, fed
    the normed ``x1``."""
    chosen, weight = route(cfg, mm, x, p)
    return experts(cfg, mm, rms_norm(x1, p["norm"], cfg["rms_norm_eps"]), p,
                   chosen, weight)


def hidden(cfg, params, ids, mm: rc.Matmul):
    """[N,T] ids to the hidden state [N,T,E] the head reads, normed."""
    eps, scale = cfg["rms_norm_eps"], cfg["residual_init_scale"]
    x = params["embeddings"]["word"][ids]
    for i, (positions, window) in enumerate(layer_kinds(cfg)):
        p = params[f"layer_{i}"]

        @jax.checkpoint
        def attend(x, p):
            return x + scale * attention(
                cfg, mm, rms_norm(x, p["norm"], eps), p, positions=positions,
                window=window)

        @jax.checkpoint
        def mixture(x, x1, p):
            return x1 + scale * expert_sublayer(cfg, mm, x, x1, p)

        x = mixture(x, attend(x, p["attn"]), p["moe"])
    return rms_norm(x, params["final"]["norm"], eps)


def logits(cfg, params, ids, mm: rc.Matmul):
    """[N,T] ids to [N,T,V] next-token logits."""
    return mm("nte,ve->ntv", hidden(cfg, params, ids, mm),
              params["head"]["out"])


def loss_parts(cfg, params, rows, mm: rc.Matmul) -> Dict[str, Any]:
    """The summed cross-entropy of the T - 1 positions that have a next
    token, the head a block of positions at a time."""
    ids = rows["features"]["token_ids"]
    n, t = ids.shape
    x = hidden(cfg, params, ids, mm)
    labels = jnp.concatenate([ids[:, 1:], jnp.zeros((n, 1), ids.dtype)], 1)
    counts = (jnp.arange(t) < t - 1).astype(x.dtype)

    @jax.checkpoint
    def block(args):
        x, labels, counts = args
        lg = mm("nte,ve->ntv", x, params["head"]["out"])
        return jnp.sum(rc.cross_entropy(lg, labels) * counts)

    size = HEAD_BLOCK if t % HEAD_BLOCK == 0 else t
    blocks = t // size
    sums = jax.lax.map(block, (
        jnp.moveaxis(x.reshape(n, blocks, size, -1), 1, 0),
        jnp.moveaxis(labels.reshape(n, blocks, size), 1, 0),
        counts.reshape(blocks, size)))
    return {"lm": jnp.sum(sums)}


# -- required operations -------------------------------------------------------

def attended_pairs(seq_len: int, window: Optional[int]) -> int:
    """The query-key pairs of one sequence and one head: each query its
    past and itself, the last ``window`` of them where there is a window."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def train_flops_parts(cfg: Dict[str, Any],
                      traffic: Dict[str, Any]) -> Dict[str, float]:
    """One training step in three parts, backward twice forward, nothing
    recomputed. ``attention``: QK^T and PV over the pairs each layer's kind
    requires. ``matrices``: a layer's four projections, its router and the
    three products of an expert times the token-expert pairs that land here
    at balanced load. ``head``: over the ``seq_len - 1`` positions that
    have a next token."""
    s = _sizes(cfg)
    rows, t = traffic["rows"], traffic["seq_len"]
    e, d = s["e"], s["d"]
    wq, wk = s["hq"] * d, s["hk"] * d
    pairs = sum(attended_pairs(t, window) for _, window in layer_kinds(cfg))
    projections = 2 * (e * wq + 2 * e * wk + wq * e)
    router = 2 * e * s["total"]
    experts_ = 3 * 2 * e * s["f"] * s["per_token"] * s["held"] / s["total"]
    return {
        "attention": 3.0 * 2 * 2 * wq * rows * pairs,
        "matrices": 3.0 * s["layers"] * rows * t * (projections + router
                                                    + experts_),
        "head": 3.0 * 2 * rows * (t - 1) * e * s["v"],
    }


def train_flops(cfg: Dict[str, Any], traffic: Dict[str, Any]) -> float:
    return float(sum(train_flops_parts(cfg, traffic).values()))
