"""Plain reference of ZAYA1-8B (Zyphra) as the ``zaya1_8b`` configuration
runs it: a decoder whose every layer is one attention sub-layer (CCA:
attention wholly inside a compressed latent, mixed along the sequence by
two causal convolutions) and then one expert sub-layer (top-1 of 16
SiLU-gated experts behind a small MLP router that carries a 256-wide
state from layer to layer), RMSNorm, half-rotary positions, no biases, a
tied head.

The sizes are the published ``config.json``'s. What that file does not
carry is taken from the two papers as remembered without a network (CCA:
arXiv:2510.04476; ZAYA1: arXiv:2511.17127); each such item is a key of
``assumed`` in ``zaya1_8b.json``, in these words:

- ``residual``: x <- a * x + c * f(RMSNorm(x)) for each sub-layer f, with
  learned vectors a, c of the hidden size, both ones at the start; the
  mixture-of-depths of the 74B sibling is left out.
- ``value_shift``: v_t = [h_t Wva ; h_{t-1} Wvb], h_{-1} = 0: key-value
  head 0 reads the token, head 1 the token before it.
- ``convolutions``: on q~ and on k~, each with weights of its own, first
  a depthwise causal convolution along the sequence (every channel its
  own ``cca_time0`` taps, padded on the left), then a causal convolution
  grouped by head (each head's channels mixed by a ``[cca_time1, head_dim,
  head_dim]`` kernel, padded on the left), nothing between the two.
- ``qk_mean``: q' = q^ + (q~ + repeat_g(k~)) / 2, k' = k^ + (mean_g(q~) +
  k~) / 2 over the query heads g of each key-value head's group.
- ``qk_norm``: per head q'' = sqrt(d) q' / |q'|, k'' = tau sqrt(d) k' /
  |k'|, tau a learned scalar for each key-value head, 1 at the start.
- ``router``: r_l = h Wr + gamma_l r_{l-1} (gamma_l a learned scalar, 0 at
  the start; no such term in the first layer held); z = Wc gelu(Wb gelu(Wa
  r_l)), gelu in its tanh form; p = softmax(z) over all experts; e* =
  argmax(p + b), b a balancing bias that no gradient reaches, 0 here; the
  router runs in float32 from Wr on.
- ``experts``: y = p[e*] Wdown(silu(Wgate h) * (Wup h)), no bias, no
  shared expert.
- ``sublayer_order``: attention first, then the experts, in every layer.

Rotary positions turn the first ``partial_rotary_factor`` of each head's
dimensions, paired as the ``default`` rope type pairs them (dimension i
with i + half). **Where e\\* is an expert that is not held here**
(``experts_held``), that token's y is 0: the deployment's other chip adds
it. With every expert held the same code is the uncut model.

``jax.checkpoint`` around each sub-layer changes no value; it keeps the
float32 backward pass of 4,096 positions inside one chip's memory.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.configs import reference_common as rc


def _sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    if cfg["num_key_value_heads"] != 2:
        raise ValueError("the value shift is written for 2 key-value heads")
    return {
        "e": cfg["hidden_size"], "d": cfg["head_dim"],
        "hq": cfg["num_attention_heads"], "hk": cfg["num_key_value_heads"],
        "r": cfg["router_hidden_size"], "f": cfg["moe_intermediate_size"],
        "total": cfg["num_experts_total"], "held": len(cfg["experts_held"]),
        "t0": cfg["cca_time0"], "t1": cfg["cca_time1"],
        "layers": cfg["num_hidden_layers"], "v": cfg["vocab_size"],
    }


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    s = _sizes(cfg)
    e, d, r = s["e"], s["d"], s["r"]
    wq, wk = s["hq"] * d, s["hk"] * d

    def scaled():
        return {"norm": ((e,), "ones"), "res_a": ((e,), "ones"),
                "res_c": ((e,), "ones")}

    shapes: Dict[str, Any] = {
        "embeddings": {"word": ((s["v"], e), "normal")},
        "final": {"norm": ((e,), "ones")},
    }
    for i in range(s["layers"]):
        attn = dict(
            scaled(),
            Wq=((e, wq), "normal"), Wk=((e, wk), "normal"),
            Wva=((e, d), "normal"), Wvb=((e, d), "normal"),
            Wo=((wq, e), "normal"),
            conv0_q=((s["t0"], wq), "normal"),
            conv1_q=((s["t1"], s["hq"], d, d), "normal"),
            conv0_k=((s["t0"], wk), "normal"),
            conv1_k=((s["t1"], s["hk"], d, d), "normal"),
            tau=((s["hk"],), "ones"))
        moe = dict(
            scaled(),
            Wr=((e, r), "normal"), Wa=((r, r), "normal"),
            Wb=((r, r), "normal"), Wc=((r, s["total"]), "normal"),
            bias=((s["total"],), "zeros"),
            gate=((s["held"], e, s["f"]), "normal"),
            up=((s["held"], e, s["f"]), "normal"),
            down=((s["held"], s["f"], e), "normal"))
        if i > 0:
            moe["gamma"] = ((), "zeros")
        shapes[f"layer_{i}"] = {"attn": attn, "moe": moe}
    return shapes


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


def shift(x, by: int):
    """``x`` [N,T,...] moved ``by`` positions later, zeros coming in."""
    if by == 0:
        return x
    pad = [(0, 0), (by, 0)] + [(0, 0)] * (x.ndim - 2)
    return jnp.pad(x, pad)[:, : x.shape[1]]


def causal_convs(mm: rc.Matmul, x, w0, w1):
    """``x`` [N,T,heads,d] through the depthwise convolution ``w0``
    [taps, heads * d] and then the convolution grouped by head ``w1``
    [taps, heads, d, d]; the last tap of each reads the token itself."""
    n, t, heads, d = x.shape
    taps0 = jnp.stack([shift(x, w0.shape[0] - 1 - j)
                       for j in range(w0.shape[0])], axis=2)
    y = mm("ntjhd,jhd->nthd", taps0, w0.reshape(-1, heads, d))
    taps1 = jnp.stack([shift(y, w1.shape[0] - 1 - j)
                       for j in range(w1.shape[0])], axis=2)
    return mm("ntjhi,jhio->ntho", taps1, w1)


def l2_scaled(x):
    """Each head's vector at the length sqrt(d)."""
    return x * math.sqrt(x.shape[-1]) / jnp.sqrt(
        jnp.sum(jnp.square(x), -1, keepdims=True))


def rotary(x, theta: float, share: float):
    """Rotary positions on the first ``share`` of the last axis of ``x``
    [N,T,heads,d]; dimension i is paired with i + half."""
    t, d = x.shape[1], x.shape[-1]
    turned = int(d * share)
    half = turned // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / turned)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b, rest = x[..., :half], x[..., half:turned], x[..., turned:]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def cca(cfg, mm: rc.Matmul, h, p):
    """The attention sub-layer's f: ``h`` [N,T,E] (normed) to [N,T,E]."""
    s = _sizes(cfg)
    n, t, _ = h.shape
    d, hq, hk = s["d"], s["hq"], s["hk"]
    group = hq // hk
    q0 = mm("nte,ef->ntf", h, p["Wq"]).reshape(n, t, hq, d)
    k0 = mm("nte,ef->ntf", h, p["Wk"]).reshape(n, t, hk, d)
    v = jnp.stack([mm("nte,ef->ntf", h, p["Wva"]),
                   mm("nte,ef->ntf", shift(h, 1), p["Wvb"])], axis=2)
    q = causal_convs(mm, q0, p["conv0_q"], p["conv1_q"])
    k = causal_convs(mm, k0, p["conv0_k"], p["conv1_k"])
    q = q + (q0 + jnp.repeat(k0, group, axis=2)) / 2
    k = k + (jnp.mean(q0.reshape(n, t, hk, group, d), axis=3) + k0) / 2
    q = l2_scaled(q)
    k = l2_scaled(k) * p["tau"][:, None]
    rope = cfg["rope_parameters"]["hybrid"]
    q = rotary(q, rope["rope_theta"], rope["partial_rotary_factor"])
    k = rotary(k, rope["rope_theta"], rope["partial_rotary_factor"])
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = mm("nqhd,nkhd->nhqk", q, k) / math.sqrt(d)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None],
                       scores, -1e30)
    o = mm("nhqk,nkhd->nqhd", jax.nn.softmax(scores, axis=-1), v)
    return mm("ntf,fe->nte", o.reshape(n, t, hq * d), p["Wo"])


def route(cfg, mm: rc.Matmul, h, p, carried):
    """The router: its state r_l [N,T,R], and for every token the expert
    chosen and the share p[e*] its output is weighted by."""
    r = mm("nte,er->ntr", h, p["Wr"])
    if "gamma" in p:
        r = r + p["gamma"] * carried
    z = rc.gelu_tanh(mm("ntr,rs->nts", r, p["Wa"]))
    z = rc.gelu_tanh(mm("ntr,rs->nts", z, p["Wb"]))
    prob = jax.nn.softmax(mm("ntr,rx->ntx", z, p["Wc"]), axis=-1)
    chosen = jnp.argmax(prob + jax.lax.stop_gradient(p["bias"]), axis=-1)
    share = jnp.take_along_axis(prob, chosen[..., None], axis=-1)[..., 0]
    return r, chosen, share


def experts(cfg, mm: rc.Matmul, h, p, chosen, share):
    """What the experts held here give: every held expert over every
    token, kept where the token chose it."""
    y = jnp.zeros_like(h)
    for j, e in enumerate(cfg["experts_held"]):
        inner = (jax.nn.silu(mm("nte,ef->ntf", h, p["gate"][j]))
                 * mm("nte,ef->ntf", h, p["up"][j]))
        out = mm("ntf,fe->nte", inner, p["down"][j])
        y = y + jnp.where((chosen == e)[..., None],
                          share[..., None] * out, 0.0)
    return y


def expert_sublayer(cfg, mm: rc.Matmul, h, p, carried):
    """f of the expert sub-layer and the router's state it hands on."""
    r, chosen, share = route(cfg, mm, h, p, carried)
    return experts(cfg, mm, h, p, chosen, share), r


def logits(cfg, params, ids, mm: rc.Matmul):
    """[N,T] ids to [N,T,V] next-token logits."""
    eps = cfg["rms_norm_eps"]
    word = params["embeddings"]["word"]
    x = word[ids]
    carried = None

    @jax.checkpoint
    def attention(x, p):
        return p["res_a"] * x + p["res_c"] * cca(
            cfg, mm, rms_norm(x, p["norm"], eps), p)

    @jax.checkpoint
    def mixture(x, p, carried):
        y, r = expert_sublayer(cfg, mm, rms_norm(x, p["norm"], eps), p,
                               carried)
        return p["res_a"] * x + p["res_c"] * y, r

    for i in range(cfg["num_hidden_layers"]):
        layer = params[f"layer_{i}"]
        x = attention(x, layer["attn"])
        x, carried = mixture(x, layer["moe"], carried)
    x = rms_norm(x, params["final"]["norm"], eps)
    return mm("nte,ve->ntv", x, word)


def loss_parts(cfg, params, rows, mm: rc.Matmul) -> Dict[str, Any]:
    ids = rows["features"]["token_ids"]
    lg = logits(cfg, params, ids, mm)[:, :-1]
    return {"lm": jnp.sum(rc.cross_entropy(lg, ids[:, 1:]))}


# -- required operations -------------------------------------------------------

def layer_forward_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """One layer forward, a token: the projections, the convolutions, the
    router, the three products of the one expert a token uses times the
    share of the experts held (balanced load), and QK^T and PV over the
    causal pairs (averaged over the sequence's positions)."""
    s = _sizes(cfg)
    e, d, r = s["e"], s["d"], s["r"]
    wq, wk = s["hq"] * d, s["hk"] * d
    projections = 2 * (e * wq + e * wk + 2 * e * d + wq * e)
    convolutions = 2 * (s["t0"] + s["t1"] * d) * (wq + wk)
    router = 2 * (e * r + 2 * r * r + r * s["total"])
    experts_ = 3 * 2 * e * s["f"] * s["held"] / s["total"]
    attention = 2 * 2 * wq * (seq_len + 1) / 2
    return float(projections + convolutions + router + experts_ + attention)


def train_flops(cfg: Dict[str, Any], traffic: Dict[str, Any]) -> float:
    """One training step: backward twice forward, nothing recomputed,
    the head over the ``seq_len - 1`` positions that have a next token."""
    rows, t = traffic["rows"], traffic["seq_len"]
    layers = (cfg["num_hidden_layers"] * rows * t
              * layer_forward_flops_per_token(cfg, t))
    head = 2 * rows * (t - 1) * cfg["hidden_size"] * cfg["vocab_size"]
    return 3.0 * (layers + head)
