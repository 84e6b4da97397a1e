"""Attention layers.

ref: org.deeplearning4j.nn.conf.layers.{SelfAttentionLayer,
LearnedSelfAttentionLayer, RecurrentAttentionLayer} and
org.deeplearning4j.nn.conf.graph.AttentionVertex, all backed by the libnd4j
``multi_head_dot_product_attention`` op (O(T²) HBM score matrix, SURVEY
§5.7). Here attention lowers to the Pallas blockwise flash kernel
(kernels/flash_attention.py) — O(T·D) memory, MXU-tiled — with an XLA
fallback for biased/masked paths.

Layout convention: sequences are [N, T, E] (batch, time, embed) — the
TPU-friendly layout where the embed axis maps to lanes. The reference uses
[N, E, T] for RNN activations; converters in the Keras-import module handle
the transpose.
"""

from __future__ import annotations

import contextlib
import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.kernels.flash_attention import (
    flash_attention,
    forward_plan,
    pairs_of_call,
)
from deeplearning4j_tpu.nn.activations import get_activation
from deeplearning4j_tpu.nn.config import LayerConfig, register_config
from deeplearning4j_tpu.nn.initializers import get_initializer
from deeplearning4j_tpu.observability.vocab import (
    SCOPE_ATTN,
    SCOPE_ATTN_GLOBAL,
    SCOPE_ATTN_WINDOW,
    SCOPE_CCA_MIX,
    SCOPE_DSA_INDEX,
    SCOPE_DSA_SELECT,
    SCOPE_MLP,
)
from deeplearning4j_tpu.ops import nn as opsnn


def _split_heads(x, num_heads):
    n, t, e = x.shape
    return x.reshape(n, t, num_heads, e // num_heads).transpose(0, 2, 1, 3)


def _merge_heads(x):
    n, h, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(n, t, h * d)


def _init_qkv(rng, embeds, proj, out, dtype, w_init, use_bias):
    """Shared Q/K/V/O projection init. embeds = (eq, ek, ev)."""
    eq, ek, ev = embeds
    ks = jax.random.split(rng, 4)
    params = {
        "Wq": w_init(ks[0], (eq, proj), dtype),
        "Wk": w_init(ks[1], (ek, proj), dtype),
        "Wv": w_init(ks[2], (ev, proj), dtype),
        "Wo": w_init(ks[3], (proj, out), dtype),
    }
    if use_bias:
        params.update(
            bq=jnp.zeros((proj,), dtype), bk=jnp.zeros((proj,), dtype),
            bv=jnp.zeros((proj,), dtype), bo=jnp.zeros((out,), dtype),
        )
    return params


def _attend_tail(y_heads, params, *, dropout, train, rng, project=True):
    """Shared post-attention pipeline: merge heads, dropout, O-projection."""
    y = _merge_heads(y_heads)
    if train and dropout > 0.0 and rng is not None:
        y = opsnn.dropout(y, dropout, rng)
    if project:
        y = opsnn.linear(y, params["Wo"], params.get("bo"))
    return y


def _shift(x, by):
    """``x`` [N,T,...] moved ``by`` positions later, zeros coming in."""
    if by == 0:
        return x
    pad = [(0, 0), (by, 0)] + [(0, 0)] * (x.ndim - 2)
    return jnp.pad(x, pad)[:, : x.shape[1]]


def _causal_convs(x, w0, w1):
    """``x`` [N,T,heads,d] through a depthwise causal convolution along
    the sequence (``w0`` [taps, heads * d]) and then a causal convolution
    grouped by head (``w1`` [taps, heads, d, d]); each one's last tap
    reads the token itself."""
    heads, d = x.shape[2:]
    w0 = w0.reshape(-1, heads, d)
    y = sum(_shift(x, len(w0) - 1 - j) * w0[j] for j in range(len(w0)))
    return sum(jnp.einsum("nthi,hio->ntho", _shift(y, len(w1) - 1 - j), w1[j])
               for j in range(len(w1)))


def _rotary(x, theta, share):
    """Rotary positions on the first ``share`` of the last axis of ``x``
    [N,T,heads,d] (float32); dimension i is paired with i + half."""
    t, d = x.shape[1], x.shape[-1]
    turned = int(d * share)
    half = turned // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / turned)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b, rest = x[..., :half], x[..., half:turned], x[..., turned:]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def cca_attention(params, h, *, num_heads: int, num_kv_heads: int,
                  rope_theta: float, rotary_share: float):
    """Compressed convolutional attention (CCA, arXiv:2510.04476) over
    ``h`` [N,T,E], already normed: causal attention wholly inside a
    latent of ``num_heads`` query heads and 2 key-value heads.

    q~ = h Wq and k~ = h Wk are each mixed along the sequence by two
    causal convolutions with no function between them, added to the mean
    of q~ and k~ over each key-value head's group of query heads,
    L2-normalised per head to length sqrt(d) (the keys times a learned
    temperature ``tau``) and turned by rotary positions on the first
    ``rotary_share`` of each head. Key-value head 0's value reads the
    token (``Wva``), head 1's the token before it (``Wvb``). The
    score-and-value product is ``flash_attention``'s, with k and v
    repeated to the query heads; ``Wo`` projects the latent back.
    Everything but that call and ``Wo`` is the ``cca_mix`` sub-scope.
    """
    if num_kv_heads != 2:
        raise ValueError("CCA's value shift is written for 2 key-value "
                         f"heads, got {num_kv_heads}")
    n, t, _ = h.shape
    group = num_heads // num_kv_heads
    f32 = jnp.float32
    with jax.named_scope(SCOPE_CCA_MIX):
        q0 = opsnn.linear(h, params["Wq"]).reshape(n, t, num_heads, -1)
        k0 = opsnn.linear(h, params["Wk"]).reshape(n, t, num_kv_heads, -1)
        d = q0.shape[-1]
        v = jnp.stack([opsnn.linear(h, params["Wva"]),
                       opsnn.linear(_shift(h, 1), params["Wvb"])], axis=1)
        q = _causal_convs(q0, params["conv0_q"], params["conv1_q"])
        k = _causal_convs(k0, params["conv0_k"], params["conv1_k"])
        q0, k0 = q0.astype(f32), k0.astype(f32)
        q = q.astype(f32) + (q0 + jnp.repeat(k0, group, axis=2)) / 2
        k = k.astype(f32) + (
            jnp.mean(q0.reshape(n, t, num_kv_heads, group, d), axis=3)
            + k0) / 2

        def unit(x):
            return x * (d ** 0.5) * jax.lax.rsqrt(
                jnp.sum(jnp.square(x), axis=-1, keepdims=True))

        q = unit(q)
        k = unit(k) * params["tau"].astype(f32)[:, None]
        q = _rotary(q, rope_theta, rotary_share).astype(h.dtype)
        k = _rotary(k, rope_theta, rotary_share).astype(h.dtype)
        q, k = q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3)
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    y = flash_attention(q, k, v, causal=True)
    return opsnn.linear(_merge_heads(y), params["Wo"])


# Query rows of one piece of the indexer's score: a piece's per-head scores
# [N, rows, heads, keys] in float32 are the largest array the layer makes
# (537 MB at 2 x 512 x 16 x 8192), and pieces run one after another.
_INDEX_ROWS = 512


def _kth_largest(scores, k: int):
    """The ``k``-th largest of each row of ``scores`` [..., S] (float32, no
    NaN, at least ``k`` entries a row that are not ``-inf``), as the
    order-preserving unsigned key of its bit pattern, beside every entry's
    key: ``keys >= kth`` are the row's ``k`` largest and whatever ties the
    last of them. A search for the threshold by counting, a bit a pass
    from the top: no index is needed, so nothing is sorted."""
    bits = jax.lax.bitcast_convert_type(
        jnp.where(scores == 0, 0.0, scores), jnp.uint32)  # -0.0 as +0.0
    top = jnp.uint32(1 << 31)
    keys = jnp.where(bits >= top, ~bits, bits | top)

    def add_bit(i, kth):
        trial = kth | (top >> i.astype(jnp.uint32))
        enough = jnp.sum(keys >= trial, axis=-1, keepdims=True,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, trial, kth)

    kth = jax.lax.fori_loop(
        0, 32, add_bit, jnp.zeros(keys.shape[:-1] + (1,), jnp.uint32))
    return keys, kth


@functools.partial(jax.jit, static_argnums=(3,))
def _selected_pairs(q_index, k_index, w_index, top_k: int):
    """The pair mask [N, T, T] of int8: for query t the keys s <= t whose
    index score I[t, s] = sum_j w[t, j] relu(q_index[t, j] . k_index[s]) is
    among the ``top_k`` largest of its past (all of its past where that
    holds no more than ``top_k`` keys; ties with the last one kept).
    q_index [N,T,J,D], k_index [N,T,D], w_index [N,T,J], float32, every
    product at ``highest``. Rows are scored in pieces of ``_INDEX_ROWS``,
    each against the keys up to its last row only; the first ``top_k``
    rows select their whole past, so nothing is scored for them. Jitted on
    its own: the layers of a model trace it once."""
    n, t = k_index.shape[:2]
    first = min(top_k, t)
    pieces = [jnp.broadcast_to(
        jnp.tril(jnp.ones((first, t), jnp.int8))[None], (n, first, t))]
    for a in range(first, t, _INDEX_ROWS):
        b = min(a + _INDEX_ROWS, t)
        with jax.named_scope(SCOPE_DSA_INDEX):
            per_head = jnp.einsum(
                "ntjd,nsd->ntjs", q_index[:, a:b], k_index[:, :b],
                precision=jax.lax.Precision.HIGHEST)
            score = jnp.sum(jax.nn.relu(per_head) * w_index[:, a:b, :, None],
                            axis=2)
        with jax.named_scope(SCOPE_DSA_SELECT):
            past = (jnp.arange(b)[None, :] <= jnp.arange(a, b)[:, None])[None]
            keys, kth = _kth_largest(jnp.where(past, score, -jnp.inf), top_k)
            chosen = (past & (keys >= kth)).astype(jnp.int8)
            pieces.append(jnp.pad(chosen, ((0, 0), (0, 0), (0, t - b))))
    with jax.named_scope(SCOPE_DSA_SELECT):
        return jnp.concatenate(pieces, axis=1)


def _empty_tile_share(pair_mask, head_dim: int):
    """The share of ``flash_fwd``'s live tiles, at the geometry it would
    take for this call, in which ``pair_mask`` [N,T,T] selects nothing."""
    n, t, _ = pair_mask.shape
    plan = forward_plan(t, t, head_dim, causal=True, pair_mask=True)
    padded = jnp.pad(pair_mask, ((0, 0), (0, plan.n_q * plan.block_q - t),
                                 (0, plan.n_k * plan.block_k - t)))
    some = jnp.any(padded.reshape(n, plan.n_q, plan.block_q, plan.n_k,
                                  plan.block_k) != 0, axis=(2, 4))
    live = plan.live_tiles()
    empty = jnp.sum(~some & jnp.asarray(live)[None], dtype=jnp.int32)
    return empty / jnp.float32(n * int(live.sum()))


def indexed_attention(params, h, *, num_heads: int, num_kv_heads: int,
                      index_heads: int, top_k: int, rope_theta: float,
                      eps: float):
    """Causal grouped-query attention over the keys a learned indexer
    selects for each query (DeepSeek-V3.2-Exp's sparse attention, as
    Keye-VL-2.0's ``sa_config`` sizes it) over ``h`` [N,T,E], already
    normed. Returns the sub-layer's output and what it counted:
    ``pairs_selected`` (over the batch) and ``tiles_empty_share``.

    Main path (``_grouped_query``): q, k, v by ``Wq``, ``Wk``, ``Wv`` (no
    bias), RMSNorm of each head of q and of k with a learned gain
    (``q_norm``, ``k_norm``), rotary positions on the whole head
    (dimension i paired with i + half), k and v repeated to the query
    heads, ``flash_attention`` with the pair mask, ``Wo``.

    The indexer (leaves under ``params["index"]``) reads ``h`` with its
    gradient stopped, in float32 with every product at ``highest``:
    ``index_heads`` query heads (``Wq``) against one key head (``Wk``,
    LayerNorm with ``k_gamma``, ``k_beta``), rotary on both, and a weight a
    head a query (``Ww``, times heads^-1/2 * width^-1/2); the score of a
    pair is sum_j w[t, j] relu(q[t, j] . k[s]). Each query keeps the
    ``top_k`` largest of its past (``_selected_pairs``: exact, ties
    kept); the set is shared by all heads and is a constant of the
    backward pass, so the indexer's leaves get a zero gradient. The
    indexer's work is the ``dsa_index`` sub-scope, the threshold and the
    mask ``dsa_select``.
    """
    n, t, _ = h.shape
    f32, exact = jnp.float32, jax.lax.Precision.HIGHEST
    index = params["index"]
    with jax.named_scope(SCOPE_DSA_INDEX):
        g = jax.lax.stop_gradient(h).astype(f32)

        def product(name):
            return jnp.matmul(g, index[name].astype(f32), precision=exact)

        q_index = product("Wq").reshape(n, t, index_heads, -1)
        width = q_index.shape[-1]
        k_index = opsnn.layer_norm(
            product("Wk"), index["k_gamma"].astype(f32),
            index["k_beta"].astype(f32), eps=eps)
        q_index = _rotary(q_index, rope_theta, 1.0)
        k_index = _rotary(k_index[:, :, None], rope_theta, 1.0)[:, :, 0]
        w_index = product("Ww") * (index_heads ** -0.5 * width ** -0.5)
    _record_selection(t, top_k, index_heads, width)
    # the set is a constant of the backward pass: no tangent is traced
    pair_mask = _selected_pairs(*jax.lax.stop_gradient(
        (q_index, k_index, w_index)), top_k)

    y = _grouped_query(params, h, num_heads=num_heads,
                       num_kv_heads=num_kv_heads, rope_theta=rope_theta,
                       qk_norm_eps=eps, pair_mask=pair_mask)
    with jax.named_scope(SCOPE_DSA_SELECT):
        counted = {
            "pairs_selected": jnp.sum(pair_mask, dtype=jnp.int32),
            "tiles_empty_share": _empty_tile_share(
                pair_mask, params["Wq"].shape[-1] // num_heads),
        }
    return y, counted


def _grouped_query(params, h, *, num_heads: int, num_kv_heads: int,
                   rope_theta: Optional[float], qk_norm_eps=None,
                   pair_mask=None, window=None, scope=None):
    """Causal grouped-query attention over ``h`` [N,T,E], already normed:
    q, k, v by ``Wq``, ``Wk``, ``Wv`` (no bias); with ``qk_norm_eps``,
    RMSNorm of each head of q and of k (``q_norm``, ``k_norm``); with
    ``rope_theta``, rotary positions on the whole head (dimension i paired
    with i + half), else nothing that tells one position from another; k
    and v repeated to the query heads; ``flash_attention`` under the pair
    mask or the window, where there is one; ``Wo``. Everything between the
    projections and ``Wo`` carries the sub-scope ``scope``, where one is
    named."""
    n, t, _ = h.shape
    group = num_heads // num_kv_heads
    f32 = jnp.float32
    q = opsnn.linear(h, params["Wq"]).reshape(n, t, num_heads, -1)
    k = opsnn.linear(h, params["Wk"]).reshape(n, t, num_kv_heads, -1)
    v = opsnn.linear(h, params["Wv"]).reshape(n, t, num_kv_heads, -1)
    if qk_norm_eps is not None:
        q = opsnn.rms_norm(q, params["q_norm"], qk_norm_eps)
        k = opsnn.rms_norm(k, params["k_norm"], qk_norm_eps)
    with (jax.named_scope(scope) if scope else contextlib.nullcontext()):
        if rope_theta is not None:
            q = _rotary(q.astype(f32), rope_theta, 1.0).astype(h.dtype)
            k = _rotary(k.astype(f32), rope_theta, 1.0).astype(h.dtype)
        q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
        y = flash_attention(q, k, v, causal=True, pair_mask=pair_mask,
                            window=window)
    return opsnn.linear(_merge_heads(y), params["Wo"])


def grouped_query_attention(params, h, *, num_heads: int, num_kv_heads: int,
                            rope_theta: Optional[float],
                            window: Optional[int]):
    """One layer's attention of a decoder whose layers differ in kind
    (SmallThinker's ``rope_layout`` and ``sliding_window_layout``), over
    ``h`` [N,T,E], already normed: causal grouped-query attention
    (``_grouped_query``; no bias, no q-k norm) with rotary positions at
    ``rope_theta`` or, where that is None, no positions at all, over the
    last ``window`` keys of each query's past (itself counted) or, where
    that is None, over all of it. The attention proper is the sub-scope
    ``attn_window`` or ``attn_global``, by ``window``. Returns the
    sub-layer's output and what it counted, over the batch and for one
    head: ``pairs_required`` (what the layer's kind asks) and
    ``pairs_touched`` (what runs computes:
    ``kernels.flash_attention.pairs_of_call``)."""
    n, t, _ = h.shape
    y = _grouped_query(
        params, h, num_heads=num_heads, num_kv_heads=num_kv_heads,
        rope_theta=rope_theta, window=window,
        scope=SCOPE_ATTN_GLOBAL if window is None else SCOPE_ATTN_WINDOW)
    required, touched = pairs_of_call(
        t, t, params["Wq"].shape[-1] // num_heads, causal=True, window=window)
    return y, {"pairs_required": jnp.int32(n * required),
               "pairs_touched": jnp.int32(n * touched)}


def _record_selection(seq_len, top_k, index_heads, width):
    """One ``attention.dsa_select`` flight event a layer, at trace time:
    the selection's method and how its work is cut."""
    from deeplearning4j_tpu.observability.flightrecorder import record_event

    record_event(
        "attention.dsa_select", method="threshold_by_bit_search_xla",
        passes=32, seq_len=seq_len, top_k=top_k, index_heads=index_heads,
        index_width=width, rows_a_piece=_INDEX_ROWS,
        pieces=-(-max(seq_len - top_k, 0) // _INDEX_ROWS))


@register_config
@dataclass
class SelfAttention(LayerConfig):
    """↔ SelfAttentionLayer (multi-head dot-product self-attention with
    learned Q/K/V/O projections).

    nIn inferred from input shape; ``head_size`` defaults to nOut/num_heads.
    ``causal`` adds the autoregressive triangle (capability superset — the
    reference layer is bidirectional only).
    """

    num_heads: int = 1
    out_size: int = 0  # nOut; 0 → same as input embed size
    head_size: Optional[int] = None
    causal: bool = False
    dropout: float = 0.0
    weight_init: Optional[str] = None
    use_bias: bool = True
    # "ring" | "ulysses" | None — sequence/context parallelism (P9). Takes
    # effect when a sequence mesh is active (parallel.sequence.sequence_mesh);
    # the mesh is captured at trace time (see sharded_attention docstring).
    sequence_parallel: Optional[str] = None

    def __post_init__(self):
        if self.sequence_parallel is not None:
            from deeplearning4j_tpu.parallel.sequence import VALID_SP_IMPLS

            if self.sequence_parallel not in VALID_SP_IMPLS:
                raise ValueError(
                    f"sequence_parallel={self.sequence_parallel!r}; "
                    f"valid: {VALID_SP_IMPLS}")

    def _dims(self, e):
        out = self.out_size or e
        hd = self.head_size or out // self.num_heads
        return out, hd

    def output_shape(self, input_shape):
        t, e = input_shape
        out, _ = self._dims(e)
        return (t, out)

    def init(self, rng, input_shape, dtype):
        e = input_shape[-1]
        out, hd = self._dims(e)
        proj = self.num_heads * hd
        w_init = get_initializer(self.weight_init or "xavier")
        return _init_qkv(rng, (e, e, e), proj, out, dtype, w_init,
                         self.use_bias), {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None,
              attend=None):
        """``attend(qh, kh, vh) -> (y_heads, kept)``, where given, takes
        the place of the attention between the projections (a KV cache is
        one: it writes the new keys and values and attends over what it
        holds); ``kept`` is then returned in the place of ``state``."""
        q = opsnn.linear(x, params["Wq"], params.get("bq"))
        k = opsnn.linear(x, params["Wk"], params.get("bk"))
        v = opsnn.linear(x, params["Wv"], params.get("bv"))
        h = self.num_heads
        qh, kh, vh = _split_heads(q, h), _split_heads(k, h), _split_heads(v, h)
        if attend is not None:
            y, state = attend(qh, kh, vh)
        elif self.sequence_parallel:
            from deeplearning4j_tpu.parallel.sequence import sharded_attention

            y = sharded_attention(qh, kh, vh, impl=self.sequence_parallel,
                                  causal=self.causal, key_mask=mask)
        else:
            y = flash_attention(qh, kh, vh, causal=self.causal, key_mask=mask)
        return _attend_tail(y, params, dropout=self.dropout, train=train,
                            rng=rng), state


@register_config
@dataclass
class LearnedSelfAttention(SelfAttention):
    """↔ LearnedSelfAttentionLayer: attention against ``n_queries`` learned
    query vectors — output is [N, n_queries, out] regardless of T."""

    n_queries: int = 1

    def __post_init__(self):
        if self.sequence_parallel is not None:
            # Learned queries are n_queries long, not sequence-sharded;
            # refuse rather than silently running full-sequence attention.
            raise ValueError(
                "LearnedSelfAttention does not support sequence_parallel "
                "(queries are learned, not sequence-sharded)")

    def output_shape(self, input_shape):
        t, e = input_shape
        out, _ = self._dims(e)
        return (self.n_queries, out)

    def init(self, rng, input_shape, dtype):
        params, state = SelfAttention.init(self, rng, input_shape, dtype)
        e = input_shape[-1]
        _, hd = self._dims(e)
        proj = self.num_heads * hd
        qrng = jax.random.fold_in(rng, 17)
        params["Q"] = get_initializer(self.weight_init or "xavier")(
            qrng, (self.n_queries, proj), dtype
        )
        del params["Wq"]
        params.pop("bq", None)
        return params, state

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        n = x.shape[0]
        q = jnp.broadcast_to(params["Q"], (n, *params["Q"].shape))
        k = opsnn.linear(x, params["Wk"], params.get("bk"))
        v = opsnn.linear(x, params["Wv"], params.get("bv"))
        h = self.num_heads
        y = flash_attention(
            _split_heads(q, h), _split_heads(k, h), _split_heads(v, h),
            key_mask=mask,
        )
        return _attend_tail(y, params, dropout=self.dropout, train=train,
                            rng=rng), state


@register_config
@dataclass
class CrossAttention(LayerConfig):
    """↔ org.deeplearning4j.nn.conf.graph.AttentionVertex: multi-head
    dot-product attention whose queries/keys/values come from DIFFERENT
    graph inputs (machine-translation-style cross attention).

    A multi-input layer (GraphModel feeds it via the ``apply_multi``
    protocol). Input arities, matching the reference vertex:

    - 1 input  → self-attention (q = k = v);
    - 2 inputs → (queries, kv) — keys and values share the second input;
    - 3 inputs → (queries, keys, values).

    ``project_input=False`` skips the Q/K/V/O projections (reference
    ``projectInput`` flag) — then all inputs must share the embed size and
    ``num_heads`` must divide it. Lowered to the Pallas flash kernel / XLA
    fallback exactly like SelfAttention (no O(T²) HBM score matrix)."""

    num_heads: int = 1
    out_size: int = 0  # nOut; 0 → query embed size
    head_size: Optional[int] = None
    project_input: bool = True
    causal: bool = False
    dropout: float = 0.0
    weight_init: Optional[str] = None
    use_bias: bool = True

    def _dims(self, eq):
        out = self.out_size or eq
        hd = self.head_size or out // self.num_heads
        return out, hd

    def output_shape_multi(self, in_shapes):
        tq, eq = in_shapes[0]
        if not self.project_input:
            return (tq, eq)
        out, _ = self._dims(eq)
        return (tq, out)

    # Single-input fallbacks so the layer also works in SequentialModel.
    def output_shape(self, input_shape):
        return self.output_shape_multi([input_shape])

    def init(self, rng, input_shape, dtype):
        return self.init_multi(rng, [input_shape], dtype)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        y, s = self.apply_multi(params, state, [x], train=train, rng=rng,
                                mask=mask)
        return y, s

    def _resolve(self, xs):
        if len(xs) == 1:
            return xs[0], xs[0], xs[0]
        if len(xs) == 2:
            return xs[0], xs[1], xs[1]
        if len(xs) == 3:
            return xs[0], xs[1], xs[2]
        raise ValueError(
            f"CrossAttention takes 1-3 inputs (q[,k[,v]]), got {len(xs)}")

    def init_multi(self, rng, in_shapes, dtype):
        q_shape, k_shape, v_shape = self._resolve(list(in_shapes))
        eq, ek, ev = q_shape[-1], k_shape[-1], v_shape[-1]
        if not self.project_input:
            if not (eq == ek == ev):
                raise ValueError(
                    "project_input=False requires equal embed sizes, got "
                    f"{(eq, ek, ev)}")
            if eq % self.num_heads:
                raise ValueError(
                    f"num_heads={self.num_heads} must divide embed {eq} "
                    "when project_input=False")
            return {}, {}
        out, hd = self._dims(eq)
        proj = self.num_heads * hd
        w_init = get_initializer(self.weight_init or "xavier")
        return _init_qkv(rng, (eq, ek, ev), proj, out, dtype, w_init,
                         self.use_bias), {}

    def apply_multi(self, params, state, xs, *, train=False, rng=None,
                    mask=None):
        q_in, k_in, v_in = self._resolve(list(xs))
        if self.project_input:
            q = opsnn.linear(q_in, params["Wq"], params.get("bq"))
            k = opsnn.linear(k_in, params["Wk"], params.get("bk"))
            v = opsnn.linear(v_in, params["Wv"], params.get("bv"))
        else:
            q, k, v = q_in, k_in, v_in
        h = self.num_heads
        y = flash_attention(
            _split_heads(q, h), _split_heads(k, h), _split_heads(v, h),
            causal=self.causal, key_mask=mask,
        )
        return _attend_tail(y, params, dropout=self.dropout, train=train,
                            rng=rng, project=self.project_input), state


@register_config
@dataclass
class RecurrentAttention(LayerConfig):
    """↔ RecurrentAttentionLayer: an RNN whose step output attends over the
    FULL input sequence, with the attention query derived from the previous
    hidden state:

        a_t = MHA(q = h_{t-1} Wq, K = X Wk, V = X Wv) Wo
        h_t = act(x_t W + a_t R + b)

    Inherently sequential (the query depends on h_{t-1}), so it lowers to
    ``lax.scan`` over time — O(T²) FLOPs like the reference's SameDiff
    implementation, but O(T) activation memory (K/V are projected once
    outside the scan; each step is a single-query attention matvec, which
    XLA fuses — no [T,T] score matrix is ever materialized)."""

    units: int = 0  # nOut (required)
    num_heads: int = 1
    head_size: Optional[int] = None
    activation: str = "tanh"
    weight_init: Optional[str] = None

    def _proj(self):
        hd = self.head_size or self.units // self.num_heads
        return self.num_heads * hd

    def output_shape(self, input_shape):
        t, _ = input_shape
        return (t, self.units)

    def init(self, rng, input_shape, dtype):
        if self.units <= 0:
            raise ValueError("RecurrentAttention requires units > 0")
        e = input_shape[-1]
        proj = self._proj()
        w_init = get_initializer(self.weight_init or "xavier")
        ks = jax.random.split(rng, 6)
        params = {
            "Wq": w_init(ks[0], (self.units, proj), dtype),
            "Wk": w_init(ks[1], (e, proj), dtype),
            "Wv": w_init(ks[2], (e, proj), dtype),
            "Wo": w_init(ks[3], (proj, self.units), dtype),
            "W": w_init(ks[4], (e, self.units), dtype),
            "R": w_init(ks[5], (self.units, self.units), dtype),
            "b": jnp.zeros((self.units,), dtype),
        }
        return params, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        n, t, e = x.shape
        h_heads = self.num_heads
        hd = self._proj() // h_heads
        # K/V projected ONCE for the whole sequence (outside the scan).
        k = _split_heads(opsnn.linear(x, params["Wk"]), h_heads)  # [N,H,T,D]
        v = _split_heads(opsnn.linear(x, params["Wv"]), h_heads)
        scale = 1.0 / jnp.sqrt(jnp.asarray(hd, x.dtype))
        # Input projection hoisted out of the scan: x_t·W is h-independent,
        # so it runs as ONE [N·T,E]×[E,units] MXU GEMM instead of T small
        # per-step matmuls (same hoist ops/rnn.py does for the LSTM gates).
        xw_t = jnp.swapaxes(opsnn.linear(x, params["W"]) + params["b"], 0, 1)
        act = get_activation(self.activation)
        neg = jnp.asarray(-1e9, x.dtype)

        def step(h_prev, xw):
            q = opsnn.linear(h_prev, params["Wq"])            # [N, H*D]
            q = q.reshape(n, h_heads, hd)                     # [N,H,D]
            scores = jnp.einsum("nhd,nhtd->nht", q, k) * scale
            if mask is not None:
                scores = jnp.where(mask[:, None, :] > 0, scores, neg)
            w = jax.nn.softmax(scores, axis=-1)
            a = jnp.einsum("nht,nhtd->nhd", w, v).reshape(n, h_heads * hd)
            a = opsnn.linear(a, params["Wo"])                 # [N,units]
            h = act(xw + a @ params["R"])
            return h, h

        h0 = jnp.zeros((n, self.units),
                       jnp.result_type(x.dtype, params["W"].dtype))
        _, ys = jax.lax.scan(step, h0, xw_t)
        return jnp.swapaxes(ys, 0, 1), state


@register_config
@dataclass
class TransformerEncoderBlock(LayerConfig):
    """Pre/post-LN transformer encoder block: MHA + residual + LN, then
    FFN(intermediate, activation) + residual + LN.

    Capability superset of the reference (which composes SelfAttentionLayer
    manually); the BERT model family builds on this block. post_ln=True
    matches original BERT.
    """

    num_heads: int = 8
    intermediate: int = 0  # FFN hidden; 0 → 4×embed
    activation: str = "gelu"
    dropout: float = 0.0
    attention_dropout: float = 0.0
    causal: bool = False
    post_ln: bool = True
    eps: float = 1e-12
    weight_init: Optional[str] = None
    sequence_parallel: Optional[str] = None  # threaded to inner SelfAttention
    # Rematerialize the block under grad (jax.checkpoint): activations are
    # recomputed in backward instead of stored — the long-context /
    # deep-stack memory lever (HBM is the usual TPU bottleneck; trading
    # ~1/3 more FLOPs for O(layers) less activation memory raises the
    # trainable T and batch). Off by default: at short T it only costs.
    remat: bool = False

    def output_shape(self, input_shape):
        return tuple(input_shape)

    def init(self, rng, input_shape, dtype):
        e = input_shape[-1]
        inter = self.intermediate or 4 * e
        w_init = get_initializer(self.weight_init or "xavier")
        ks = jax.random.split(rng, 8)
        att = SelfAttention(
            num_heads=self.num_heads, causal=self.causal,
            dropout=self.attention_dropout, weight_init=self.weight_init,
            sequence_parallel=self.sequence_parallel,
        )
        att_p, _ = att.init(ks[0], input_shape, dtype)
        params = {
            "attention": att_p,
            "W1": w_init(ks[1], (e, inter), dtype),
            "b1": jnp.zeros((inter,), dtype),
            "W2": w_init(ks[2], (inter, e), dtype),
            "b2": jnp.zeros((e,), dtype),
            "ln1_gamma": jnp.ones((e,), dtype), "ln1_beta": jnp.zeros((e,), dtype),
            "ln2_gamma": jnp.ones((e,), dtype), "ln2_beta": jnp.zeros((e,), dtype),
        }
        return params, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None,
              attend=None):
        """``attend`` is ``SelfAttention.apply``'s; what it keeps is then
        returned in the place of ``state``."""
        def fwd(p, h, r, m):
            return self._forward(p, h, train=train, rng=r, mask=m,
                                 attend=attend)

        if self.remat:
            fwd = jax.checkpoint(fwd)
        x, kept = fwd(params, x, rng, mask)
        return x, (state if attend is None else kept)

    def _forward(self, params, x, *, train, rng, mask, attend=None):
        att = SelfAttention(
            num_heads=self.num_heads, causal=self.causal,
            dropout=self.attention_dropout,
            sequence_parallel=self.sequence_parallel,
        )
        r1, r2, r3 = (
            jax.random.split(rng, 3) if rng is not None else (None, None, None)
        )

        def ln(h, which):
            return opsnn.layer_norm(
                h, params[f"{which}_gamma"], params[f"{which}_beta"], eps=self.eps
            )

        def sublayer(x, which, r, f):
            """``ln(x + f(x))`` in original BERT's order (post-LN),
            ``x + f(ln(x))`` pre-LN (more stable for deep stacks); ``f``
            returns its output and what it keeps beside it."""
            y, kept = f(x if self.post_ln else ln(x, which))
            if train and self.dropout > 0.0 and r is not None:
                y = opsnn.dropout(y, self.dropout, r)
            return (ln(x + y, which) if self.post_ln else x + y), kept

        def mlp(h):
            h = opsnn.linear(h, params["W1"], params["b1"])
            h = get_activation(self.activation)(h)
            return opsnn.linear(h, params["W2"], params["b2"]), None

        # each sub-layer, with its norm and its residual add, is one
        # component scope of the profiler trace (observability/vocab.py)
        with jax.named_scope(SCOPE_ATTN):
            x, kept = sublayer(x, "ln1", r2, lambda h: att.apply(
                params["attention"], {}, h, train=train, rng=r1, mask=mask,
                attend=attend))
        with jax.named_scope(SCOPE_MLP):
            x, _ = sublayer(x, "ln2", r3, mlp)
        return x, kept


@register_config
@dataclass
class PositionalEmbedding(LayerConfig):
    """Learned absolute position embeddings added to [N,T,E] input
    (BERT-style; capability superset — the reference has no positional
    embedding layer)."""

    max_len: int = 512

    def output_shape(self, input_shape):
        return tuple(input_shape)

    def init(self, rng, input_shape, dtype):
        e = input_shape[-1]
        return {"P": 0.02 * jax.random.normal(rng, (self.max_len, e), dtype)}, {}

    def apply(self, params, state, x, *, train=False, rng=None):
        t = x.shape[1]
        return x + params["P"][:t][None, :, :], state
