"""Plain reference of kanana-2-30b-a3b-instruct-2601 (kakaocorp,
``model_type`` ``deepseek_v3``) as the ``kanana2_30b_a3b`` configuration
runs it: a decoder whose every layer is one attention sub-layer, multi-head
latent attention (MLA) with 32 heads whose queries and keys are 192 wide
(128 without positions, 64 with) over values 128 wide, and one feed-forward
sub-layer: in the first ``first_k_dense_replace`` (1) layers a dense
SiLU-gated MLP of 6,144, in the others top-6 of 128 SiLU-gated experts of
768 behind a sigmoid router with a correction bias, beside two shared
experts. RMSNorm, no biases, an untied head.

The sizes are the published ``config.json``'s. What that file does not
carry is set by the convention of the family's public code (the
``deepseek_v3`` modelling in ``transformers``); each such item is a key of
``assumed`` in ``kanana2_30b_a3b.json``, in these words:

- ``residual``: pre-norm, x <- x + f(RMSNorm(x)) for each sub-layer f,
  attention first, then the feed-forward, in every layer.
- ``attention``: y = RMSNorm(x); q = y Wq (2048 -> 32 x 192, no query
  latent: q_lora_rank is null), each head split into q_nope (128) and q_pe
  (64); [c, k_pe] = y Wkva (2048 -> 512 + 64); c = RMSNorm_kv(c) (a gain
  of 512, eps 1e-6); [k_nope, v] = c Wkvb (512 -> 32 x (128 + 128)); k =
  [k_nope, k_pe] with the one k_pe a position shared by the 32 heads, q =
  [q_nope, q_pe]; a = softmax(q k^T / sqrt(192)) under the causal mask, o
  = a v; the sub-layer's output is o Wo (4096 -> 2048). No mscale:
  rope_scaling is null. The unabsorbed form: keys and values are built
  from the latent and attended as they are.
- ``rotary``: q_pe and k_pe turned at theta 1e6 on interleaved pairs
  (rope_interleave): dimensions (2i, 2i + 1) rotate by pos * theta **
  (-2i / 64), i = 0..31. Here, as in the family's code, q_pe and k_pe are
  first permuted alike so that the pairs become (i, i + 32), then turned
  by halves; the products q . k are the same.
- ``router``: z = y Wr (2048 -> 128, no bias) in float32 at highest; p =
  sigmoid(z); a token's experts are the 6 largest of p + b (noaux_tc;
  n_group 1 and topk_group 1: no group restriction), b the layer's
  e_score_correction_bias, with no gradient; each weighted by w_e = p_e /
  (sum over the 6 chosen of p + 1e-20) * 2.448 (norm_topk_prob,
  routed_scaling_factor), p and not p + b, wherever the chosen are held.
- ``shared_experts``: the n_shared_experts (2) merged into one SiLU-gated
  MLP of 2 x 768 = 1536 that every token runs, added to the routed sum.
- ``correction_bias``: b starts at 0, as in DeepSeek-V3's training, and is
  held frozen: the update b_e += gamma * sign(mean load - load_e) is a
  state update between steps, left out (DeepSeek-V3 sets gamma to 0 for
  its last 500B tokens, which is a fine-tuning job's regime). ``cfg
  ["correction_bias"]``, where given, is one row of 128 a layer with
  experts; absent, zeros.
- ``aux_loss``: DeepSeek-V3's sequence-wise balance term (alpha 1e-4) is
  left out: the config has no key for it.
- ``head``: final RMSNorm, logits = h Wout (untied), mean cross-entropy
  over the T - 1 positions that have a next token.
- ``residual_projection_init``: the projections that write into the
  residual stream, Wo and every down-projection (the dense layer's, the
  shared experts' and the routed experts'), start at initializer_range /
  (2 x 48 layers); the harness draws every matrix at the one spread
  initializer_range, so program and reference hold each as the constant
  residual_init_scale = 1/96 times its leaf (as keye_vl2_30b_a3b's file
  gives the reasoning).

**Where a chosen expert is not held here** (``experts_held``), it adds
nothing: the deployment's other chips add it; the weights w_e are
normalised over all 6 chosen all the same. The shared experts and
attention are whole here. With every expert held the same code is the
uncut model.

The parameters are a tree of leaves a layer (``params["layer_<i>"]``). How
the arithmetic is cut into pieces changes no value and keeps three float32
steps at 8,192 positions inside one chip's memory: the experts held are a
``jax.lax.scan`` over theirs, attention runs over blocks of
``QUERY_BLOCK`` (256) queries (each against every key, the pairs above
the diagonal masked), the head over blocks of ``HEAD_BLOCK`` (2,048)
positions, and
``jax.checkpoint`` wraps each block and each sub-layer. The layers are a
Python loop: the first differs from the rest, and there are five.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.configs import reference_common as rc

# Three float32 steps at 8,192 positions hold the weights, their starting
# copy, Adam's two moments and two gradients (13.8 GB). At these blocks
# the fp8 control, whose rounded operands are copies too, ran out of one
# chip's memory by 14 MB (PERF.md section 2); halving both is untried.
QUERY_BLOCK = 256
HEAD_BLOCK = 2048


def _sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    return {
        "e": cfg["hidden_size"], "h": cfg["num_attention_heads"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "dv": cfg["v_head_dim"], "latent": cfg["kv_lora_rank"],
        "dense": cfg["intermediate_size"], "f": cfg["moe_intermediate_size"],
        "total": cfg["n_routed_experts_total"],
        "held": len(cfg["experts_held"]),
        "per_token": cfg["num_experts_per_tok"],
        "shared": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        "layers": cfg["num_hidden_layers"],
        "dense_layers": cfg["first_k_dense_replace"], "v": cfg["vocab_size"],
    }


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    s = _sizes(cfg)
    e, h = s["e"], s["h"]
    attn = {
        "norm": ((e,), "ones"),
        "Wq": ((e, h * (s["nope"] + s["rope"])), "normal"),
        "Wkva": ((e, s["latent"] + s["rope"]), "normal"),
        "kv_norm": ((s["latent"],), "ones"),
        "Wkvb": ((s["latent"], h * (s["nope"] + s["dv"])), "normal"),
        "Wo": ((h * s["dv"], e), "normal"),
    }
    mlp = {
        "norm": ((e,), "ones"),
        "gate": ((e, s["dense"]), "normal"),
        "up": ((e, s["dense"]), "normal"),
        "down": ((s["dense"], e), "normal"),
    }
    moe = {
        "norm": ((e,), "ones"),
        "Wg": ((e, s["total"]), "normal"),
        "gate": ((s["held"], e, s["f"]), "normal"),
        "up": ((s["held"], e, s["f"]), "normal"),
        "down": ((s["held"], s["f"], e), "normal"),
        "shared_gate": ((e, s["shared"]), "normal"),
        "shared_up": ((e, s["shared"]), "normal"),
        "shared_down": ((s["shared"], e), "normal"),
    }
    return {
        "embeddings": {"word": ((s["v"], e), "normal")},
        "final": {"norm": ((e,), "ones")},
        "head": {"out": ((s["v"], e), "normal")},
        **{f"layer_{i}": {"attn": attn,
                          **({"mlp": mlp} if i < s["dense_layers"]
                             else {"moe": moe})}
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


def rotary_interleaved(x, theta: float):
    """Rotary positions on interleaved pairs of the last axis of ``x``
    [N,T,heads,d], as the family's code applies them: the pairs (2i,
    2i + 1) first gathered into (i, i + d/2), then turned by halves, by
    pos * theta ** (-2i / d). The result is that permutation of the turned
    vector; q and k are permuted alike."""
    t, d = x.shape[1], x.shape[-1]
    half = d // 2
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(cfg, mm: rc.Matmul, y, p):
    """The attention sub-layer's f: ``y`` [N,T,E] (normed) to [N,T,E]."""
    s = _sizes(cfg)
    n, t, _ = y.shape
    h, nope, rope, latent = s["h"], s["nope"], s["rope"], s["latent"]
    theta, eps = cfg["rope_theta"], cfg["rms_norm_eps"]
    q = mm("nte,ef->ntf", y, p["Wq"]).reshape(n, t, h, nope + rope)
    compressed = mm("nte,ef->ntf", y, p["Wkva"])
    c = rms_norm(compressed[..., :latent], p["kv_norm"], eps)
    k_pe = rotary_interleaved(compressed[..., None, latent:], theta)
    kv = mm("ntc,cf->ntf", c, p["Wkvb"]).reshape(n, t, h, nope + s["dv"])
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (n, t, h, rope))], axis=-1)
    v = kv[..., nope:]
    q = jnp.concatenate(
        [q[..., :nope], rotary_interleaved(q[..., nope:], theta)], axis=-1)

    @jax.checkpoint
    def block(q, at):
        scores = mm("nqhd,nshd->nhqs", q, k) / math.sqrt(nope + rope)
        seen = at[:, None] >= jnp.arange(t)[None, :]
        scores = jnp.where(seen[None, None], scores, -1e30)
        return mm("nhqs,nshd->nqhd", jax.nn.softmax(scores, axis=-1), v)

    size = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    blocks = t // size
    o = jax.lax.map(lambda args: block(*args), (
        jnp.moveaxis(q.reshape((n, blocks, size) + q.shape[2:]), 1, 0),
        jnp.arange(t).reshape(blocks, size)))
    o = jnp.moveaxis(o, 0, 1).reshape(n, t, h * s["dv"])
    return mm("ntf,fe->nte", o, p["Wo"])


def gated(mm: rc.Matmul, z, gate, up, down):
    """down(silu(z gate) * (z up))."""
    inner = jax.nn.silu(mm("nte,ef->ntf", z, gate)) * mm("nte,ef->ntf", z, up)
    return mm("ntf,fe->nte", inner, down)


def route(cfg, mm: rc.Matmul, z, p, bias):
    """For every token its experts [N,T,6], chosen by p + b, and their
    weights w_e, from p alone, wherever the chosen are held."""
    prob = jax.nn.sigmoid(mm("nte,ex->ntx", z, p["Wg"]))
    chosen = jnp.argsort(-(prob + bias), axis=-1)[
        ..., :cfg["num_experts_per_tok"]]
    picked = jnp.take_along_axis(prob, chosen, axis=-1)
    return chosen, picked / (jnp.sum(picked, -1, keepdims=True)
                             + 1e-20) * cfg["routed_scaling_factor"]


def experts(cfg, mm: rc.Matmul, z, p, chosen, weight):
    """What the experts held here give: every held expert over every
    token, weighted by w_e where the token chose it and by 0 elsewhere."""

    @jax.checkpoint
    def one(y, expert):
        e, gate, up, down = expert
        w_e = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)
        return y + w_e[..., None] * gated(mm, z, gate, up, down), None

    held = jnp.asarray(cfg["experts_held"], chosen.dtype)
    return jax.lax.scan(one, jnp.zeros_like(z),
                        (held, p["gate"], p["up"], p["down"]))[0]


def correction_bias(cfg, expert_layer: int):
    """The ``expert_layer``-th expert layer's b: the configuration's row,
    or zeros."""
    rows = cfg.get("correction_bias")
    if rows is None:
        return jnp.zeros((cfg["n_routed_experts_total"],), jnp.float32)
    return jnp.asarray(rows[expert_layer], jnp.float32)


def expert_sublayer(cfg, mm: rc.Matmul, z, p, bias):
    """f of an expert layer's feed-forward: the shared experts and the
    routed ones held here, on the normed ``z``."""
    chosen, weight = route(cfg, mm, z, p, bias)
    return (gated(mm, z, p["shared_gate"], p["shared_up"], p["shared_down"])
            + experts(cfg, mm, z, p, chosen, weight))


def hidden(cfg, params, ids, mm: rc.Matmul):
    """[N,T] ids to the hidden state [N,T,E] the head reads, normed."""
    eps, scale = cfg["rms_norm_eps"], cfg["residual_init_scale"]
    dense_layers = cfg["first_k_dense_replace"]
    x = params["embeddings"]["word"][ids]
    for i in range(cfg["num_hidden_layers"]):
        layer = params[f"layer_{i}"]

        @jax.checkpoint
        def attend(x, p):
            return x + scale * attention(cfg, mm, rms_norm(x, p["norm"], eps),
                                         p)

        @jax.checkpoint
        def dense(x, p):
            return x + scale * gated(mm, rms_norm(x, p["norm"], eps),
                                     p["gate"], p["up"], p["down"])

        @jax.checkpoint
        def mixture(x, p, bias):
            return x + scale * expert_sublayer(
                cfg, mm, rms_norm(x, p["norm"], eps), p, bias)

        x = attend(x, layer["attn"])
        if i < dense_layers:
            x = dense(x, layer["mlp"])
        else:
            x = mixture(x, layer["moe"],
                        correction_bias(cfg, i - dense_layers))
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

def train_flops_parts(cfg: Dict[str, Any],
                      traffic: Dict[str, Any]) -> Dict[str, float]:
    """One training step in four parts, backward twice forward, nothing
    recomputed. ``attention``: QK^T at the query-key width and PV at the
    value width over the causal pairs. ``latent``: each layer's four
    projections (Wq, Wkva, Wkvb, Wo). ``feed_forward``: the dense layers'
    MLP; the expert layers' router, shared experts and the three products
    of an expert times the token-expert pairs that land here at balanced
    load. ``head``: over the ``seq_len - 1`` positions that have a next
    token."""
    s = _sizes(cfg)
    rows, t = traffic["rows"], traffic["seq_len"]
    tokens = rows * t
    e, h = s["e"], s["h"]
    qk, dv = s["nope"] + s["rope"], s["dv"]
    pairs = rows * t * (t + 1) // 2
    projections = 2 * (e * h * qk + e * (s["latent"] + s["rope"])
                       + s["latent"] * h * (s["nope"] + dv) + h * dv * e)
    dense = 3 * 2 * e * s["dense"]
    expert_layer = (2 * e * s["total"] + 3 * 2 * e * s["shared"]
                    + 3 * 2 * e * s["f"] * s["per_token"] * s["held"]
                    / s["total"])
    moe_layers = s["layers"] - s["dense_layers"]
    return {
        "attention": 3.0 * s["layers"] * 2 * h * (qk + dv) * pairs,
        "latent": 3.0 * s["layers"] * tokens * projections,
        "feed_forward": 3.0 * tokens * (s["dense_layers"] * dense
                                        + moe_layers * expert_layer),
        "head": 3.0 * 2 * rows * (t - 1) * e * s["v"],
    }


def train_flops(cfg: Dict[str, Any], traffic: Dict[str, Any]) -> float:
    return float(sum(train_flops_parts(cfg, traffic).values()))
