"""Plain reference of the language model of Keye-VL-2.0-30B-A3B (Kwai-Keye)
as the ``keye_vl2_30b_a3b`` configuration runs it: a decoder whose every
layer is one attention sub-layer (grouped-query attention, 32 query heads
over 4 key-value heads of 128, over the keys that a learned indexer selects
for each query: the 2,048 best-scored of its past, one set for all heads)
and then one expert sub-layer (top-8 of 128 SiLU-gated experts of width 768
behind a linear router, the chosen weights renormalised), RMSNorm, full
rotary positions, no biases, an untied head.

The sizes are the published ``config.json``'s (``sa_config`` carries every
size of the indexer). What that file does not carry is set by the family's
convention (the Qwen3-MoE block its keys match; DeepSeek-V3.2-Exp's
published indexer); each such item is a key of ``assumed`` in
``keye_vl2_30b_a3b.json``, in these words:

- ``residual``: pre-norm, x <- x + f(RMSNorm(x)) for each sub-layer f,
  attention first, then the experts, in every layer.
- ``qk_norm``: per-head RMSNorm of q and of k over their 128 dimensions, a
  learned gain of 128 each, eps 1e-6 (the config has no key for it; the
  Qwen3 block has it).
- ``rotary``: on all 128 dimensions of q and k, theta 1e7, pairing
  dimension i with i + 64. With text alone the three position streams of
  M-RoPE are equal and the sections [16, 24, 24] give plain rotary.
- ``indexer``: on g = stop_gradient(h): qI = g WqI (2048 -> 16 x 64); kI =
  LayerNorm(g WkI) (2048 -> 64, one head for all 16; gain and bias of 64,
  eps 1e-6); rotary on all 64 dimensions of qI and kI, same theta, pairing
  i with i + 32; w = g Ww (2048 -> 16) times 16^-1/2 * 64^-1/2; I[t, s] =
  sum_j w[t, j] * relu(qI[t, j] . kI[s]) for s <= t. The indexer runs in
  float32, every product at highest, from WqI, WkI, Ww on.
- ``selection``: tau_t = the 2,048th largest of {I[t, s] : s <= t} where
  t + 1 > 2048, else minus infinity; S_t = {s <= t : I[t, s] >= tau_t};
  where equal scores at tau_t make S_t larger than 2,048, all are kept.
  The set is a constant of the backward pass. q_chunk_size and
  kv_chunk_size tile this computation and change no result.
- ``router``: z = h Wg (2048 -> 128, no bias) in float32 at highest; p =
  softmax(z) over all 128; the 8 largest are the token's experts, each
  weighted by p_e over the sum of the 8 chosen p's (norm_topk_prob),
  wherever they are held. No balancing bias, no auxiliary loss in the step
  (the config has no key for either).
- ``experts``: y = sum over the chosen experts held here of c_e
  Wdown_e(silu(Wgate_e h) * (Wup_e h)), no bias, no shared expert.
- ``head``: final RMSNorm, logits = h Wout (untied), mean cross-entropy
  over the T - 1 positions that have a next token.
- ``indexer_training``: the indexer's KL term is left out: its leaves get
  no gradient. (DeepSeek-V3.2-Exp trains the indexer by a KL divergence to
  the main attention's head-summed distribution, beside the
  language-model loss; the loss here is the language model's alone.)
- ``vision_tower``: the vision tower is left out: the catalog's config is
  the language model's, and the job is text.
- ``residual_projection_init``: the two projections that write into the
  residual stream, Wo and every expert's Wdown, start at initializer_range /
  (2 x 48 layers), a ninety-sixth of the spread of the rest: small enough
  that after four layers the stream still carries each token's own
  embedding, as a trained checkpoint's does. (At the one spread 0.02, and
  still at GPT-2's and Megatron-LM's 0.02 / sqrt(2 x layers), the attention
  sub-layers write the running mean of the values, of spread near 1, over an
  embedding of spread 0.02: by the second layer every token's hidden state
  points one way and all tokens choose the same 8 experts, so what lands on
  the 16 held is the seed's draw: PERF.md section 6.) The harness draws
  every matrix at the one spread initializer_range, so program and reference
  hold each of these projections as the constant residual_init_scale = 1/96
  times its leaf: x <- x + residual_init_scale * f(RMSNorm(x)) with f's last
  matrix the leaf.

**Where a chosen expert is not held here** (``experts_held``), it adds
nothing to y: the deployment's other chips add it; the weights c_e are
normalised over all 8 chosen all the same. With every expert held the same
code is the uncut model.

The parameters are a tree of leaves a layer (``params["layer_<i>"]``). How
the arithmetic is cut into pieces changes no value, and keeps the float32
backward pass of 8,192 positions inside one chip's memory beside the
weights, two Adam moments and two gradients, in a program small enough to
compile in a fraction of a minute: the layers are alike, so they are
stacked and run as one ``jax.lax.scan``, the experts held as one over
theirs, attention over blocks of ``QUERY_BLOCK`` queries
(each against every key with the pairs outside S_t masked), the head over
blocks of ``HEAD_BLOCK`` positions, and ``jax.checkpoint`` wraps each block
and each sub-layer. The selection is by ``jnp.sort`` of each masked row.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.configs import reference_common as rc

QUERY_BLOCK = 256
HEAD_BLOCK = 2048


def _sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    sa = cfg["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("the indexer is written for one key head")
    return {
        "e": cfg["hidden_size"], "d": cfg["head_dim"],
        "hq": cfg["num_attention_heads"], "hk": cfg["num_key_value_heads"],
        "f": cfg["moe_intermediate_size"], "total": cfg["num_experts_total"],
        "held": len(cfg["experts_held"]), "per_token": cfg["num_experts_per_tok"],
        "ji": sa["indexer_num_heads"], "di": sa["indexer_head_dim"],
        "top_k": sa["topk"], "layers": cfg["num_hidden_layers"],
        "v": cfg["vocab_size"],
    }


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    s = _sizes(cfg)
    e, d = s["e"], s["d"]
    wq, wk = s["hq"] * d, s["hk"] * d
    attn = {
        "norm": ((e,), "ones"),
        "Wq": ((e, wq), "normal"), "Wk": ((e, wk), "normal"),
        "Wv": ((e, wk), "normal"), "Wo": ((wq, e), "normal"),
        "q_norm": ((d,), "ones"), "k_norm": ((d,), "ones"),
        "index": {
            "Wq": ((e, s["ji"] * s["di"]), "normal"),
            "Wk": ((e, s["di"]), "normal"),
            "k_gamma": ((s["di"],), "ones"),
            "k_beta": ((s["di"],), "zeros"),
            "Ww": ((e, s["ji"]), "normal"),
        },
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


def index_scores(mm: rc.Matmul, q_index, k_index, w_index):
    """I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]): q_index [N,Q,J,D],
    k_index [N,S,D], w_index [N,Q,J] to [N,Q,S]."""
    per_head = mm("nqjd,nsd->nqjs", q_index, k_index)
    return jnp.sum(jax.nn.relu(per_head) * w_index[..., None], axis=2)


def selected(scores, positions, top_k: int):
    """S_t as a mask: ``scores`` [N,Q,S] of the queries at ``positions``
    [Q] against keys 0..S-1 to bool [N,Q,S]. A sort of each masked row
    gives tau_t, its ``top_k``-th largest."""
    s_len = scores.shape[-1]
    past = jnp.arange(s_len)[None, :] <= positions[:, None]
    masked = jnp.where(past, scores, -jnp.inf)
    if s_len <= top_k:
        return jnp.broadcast_to(past, scores.shape)
    tau = jnp.sort(masked, axis=-1)[..., s_len - top_k][..., None]
    tau = jnp.where((positions + 1 > top_k)[:, None], tau, -jnp.inf)
    return past & (masked >= tau)


def indexer(cfg, mm: rc.Matmul, h, p):
    """qI [N,T,J,D], kI [N,T,D] and w [N,T,J] from the normed hidden state.
    No gradient passes: not into the hidden state, and none out of what
    the indexer computes (the selected set is a constant of the backward
    pass)."""
    s = _sizes(cfg)
    n, t, _ = h.shape
    g = jax.lax.stop_gradient(h)
    q_index = mm("nte,ef->ntf", g, p["Wq"]).reshape(n, t, s["ji"], s["di"])
    k_index = rc.layer_norm(mm("nte,ef->ntf", g, p["Wk"]), p["k_gamma"],
                            p["k_beta"], cfg["rms_norm_eps"])
    q_index = rotary(q_index, cfg["rope_theta"])
    k_index = rotary(k_index[:, :, None], cfg["rope_theta"])[:, :, 0]
    w_index = mm("nte,ej->ntj", g, p["Ww"]) / math.sqrt(s["ji"] * s["di"])
    return jax.lax.stop_gradient((q_index, k_index, w_index))


def selected_pairs(cfg, mm: rc.Matmul, h, p):
    """The whole pair mask [N,T,T] at once (the CPU tests compare it with
    the program's; ``attention`` below never holds it whole)."""
    q_index, k_index, w_index = indexer(cfg, mm, h, p["index"])
    return selected(index_scores(mm, q_index, k_index, w_index),
                    jnp.arange(h.shape[1]), _sizes(cfg)["top_k"])


def attention(cfg, mm: rc.Matmul, h, p):
    """The attention sub-layer's f: ``h`` [N,T,E] (normed) to [N,T,E]."""
    s = _sizes(cfg)
    n, t, _ = h.shape
    d, hq, hk = s["d"], s["hq"], s["hk"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    q = mm("nte,ef->ntf", h, p["Wq"]).reshape(n, t, hq, d)
    k = mm("nte,ef->ntf", h, p["Wk"]).reshape(n, t, hk, d)
    v = mm("nte,ef->ntf", h, p["Wv"]).reshape(n, t, hk, d)
    q = rotary(rms_norm(q, p["q_norm"], eps), theta)
    k = rotary(rms_norm(k, p["k_norm"], eps), theta)
    q = q.reshape(n, t, hk, hq // hk, d)  # each group with its key-value head
    q_index, k_index, w_index = indexer(cfg, mm, h, p["index"])

    @jax.checkpoint
    def block(q, q_index, w_index, positions):
        keep = selected(index_scores(mm, q_index, k_index, w_index),
                        positions, s["top_k"])
        scores = mm("nqkgd,nskd->nkgqs", q, k) / math.sqrt(d)
        scores = jnp.where(keep[:, None, None], scores, -1e30)
        return mm("nkgqs,nskd->nqkgd", jax.nn.softmax(scores, axis=-1), v)

    size = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    blocks = t // size

    def cut(x):  # [N,T,...] to [blocks,N,size,...]
        return jnp.moveaxis(x.reshape((n, blocks, size) + x.shape[2:]), 1, 0)

    o = jax.lax.map(lambda args: block(*args), (
        cut(q), cut(q_index), cut(w_index),
        jnp.arange(t).reshape(blocks, size)))
    o = jnp.moveaxis(o, 0, 1).reshape(n, t, hq * d)
    return mm("ntf,fe->nte", o, p["Wo"])


def route(cfg, mm: rc.Matmul, h, p):
    """For every token its experts [N,T,8] and the weights c_e of their
    outputs, normalised over all 8 wherever they are held."""
    prob = jax.nn.softmax(mm("nte,ex->ntx", h, p["Wg"]), axis=-1)
    chosen = jnp.argsort(-prob, axis=-1)[..., :cfg["num_experts_per_tok"]]
    weight = jnp.take_along_axis(prob, chosen, axis=-1)
    return chosen, weight / jnp.sum(weight, axis=-1, keepdims=True)


def experts(cfg, mm: rc.Matmul, h, p, chosen, weight):
    """What the experts held here give: every held expert over every
    token, weighted by c_e where the token chose it and by 0 elsewhere."""

    @jax.checkpoint
    def one(y, expert):
        e, gate, up, down = expert
        inner = (jax.nn.silu(mm("nte,ef->ntf", h, gate))
                 * mm("nte,ef->ntf", h, up))
        out = mm("ntf,fe->nte", inner, down)
        c_e = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)
        return y + c_e[..., None] * out, None

    held = jnp.asarray(cfg["experts_held"], chosen.dtype)
    return jax.lax.scan(one, jnp.zeros_like(h),
                        (held, p["gate"], p["up"], p["down"]))[0]


def expert_sublayer(cfg, mm: rc.Matmul, h, p):
    """f of the expert sub-layer."""
    chosen, weight = route(cfg, mm, h, p)
    return experts(cfg, mm, h, p, chosen, weight)


def hidden(cfg, params, ids, mm: rc.Matmul):
    """[N,T] ids to the hidden state [N,T,E] the head reads, normed."""
    eps, scale = cfg["rms_norm_eps"], cfg["residual_init_scale"]

    @jax.checkpoint
    def attend(x, p):
        return x + scale * attention(cfg, mm, rms_norm(x, p["norm"], eps), p)

    @jax.checkpoint
    def mixture(x, p):
        return x + scale * expert_sublayer(
            cfg, mm, rms_norm(x, p["norm"], eps), p)

    def layer(x, p):
        return mixture(attend(x, p["attn"]), p["moe"]), None

    x = params["embeddings"]["word"][ids]
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves),
        *(params[f"layer_{i}"] for i in range(cfg["num_hidden_layers"])))
    x, _ = jax.lax.scan(layer, x, stacked)
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

def selected_pairs_per_query(seq_len: int, top_k: int) -> float:
    """The mean over the positions t of min(t + 1, top_k): the pairs a
    query attends to (ties apart)."""
    full = min(seq_len, top_k)
    return (full * (full + 1) / 2 + (seq_len - full) * top_k) / seq_len


def layer_forward_flops_per_token(cfg: Dict[str, Any],
                                  seq_len: int) -> Dict[str, float]:
    """One layer forward, a token, in two parts. ``trained`` (the backward
    pass doubles it): the four projections, QK^T and PV over the selected
    pairs, the router, and the three products of an expert times the
    token-expert pairs that land here at balanced load. ``indexer`` (no
    backward pass): its three projections and its score over the causal
    pairs."""
    s = _sizes(cfg)
    e, d = s["e"], s["d"]
    wq, wk = s["hq"] * d, s["hk"] * d
    projections = 2 * (e * wq + 2 * e * wk + wq * e)
    attention_ = 2 * 2 * wq * selected_pairs_per_query(seq_len, s["top_k"])
    router = 2 * e * s["total"]
    experts_ = 3 * 2 * e * s["f"] * s["per_token"] * s["held"] / s["total"]
    index_projections = 2 * e * (s["ji"] * s["di"] + s["di"] + s["ji"])
    index_scores_ = 2 * s["ji"] * s["di"] * (seq_len + 1) / 2
    return {"trained": float(projections + attention_ + router + experts_),
            "indexer": float(index_projections + index_scores_)}


def train_flops(cfg: Dict[str, Any], traffic: Dict[str, Any]) -> float:
    """One training step: backward twice forward but for the indexer, which
    has no backward; nothing recomputed; attention over the selected pairs
    only; the head over the ``seq_len - 1`` positions that have a next
    token."""
    rows, t = traffic["rows"], traffic["seq_len"]
    layer = layer_forward_flops_per_token(cfg, t)
    layers = (cfg["num_hidden_layers"] * rows * t
              * (3.0 * layer["trained"] + layer["indexer"]))
    head = 2 * rows * (t - 1) * cfg["hidden_size"] * cfg["vocab_size"]
    return layers + 3.0 * head
