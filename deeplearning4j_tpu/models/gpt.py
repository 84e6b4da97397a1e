"""Decoder-only causal language model (GPT family).

The reference's only text-generation model is TextGenerationLSTM
(rnnTimeStep char-RNN, SURVEY §2.7 zoo row); this is its transformer-era
counterpart, required by the build's first-class long-context story
(task §5 / SURVEY §5.7): causal flash attention (Pallas under the
auto-dispatch policy at long T), optional ring/Ulysses sequence
parallelism on a `seq` mesh axis, remat for deep stacks, and a KV-cache
autoregressive decoder that compiles the WHOLE generation loop into one
`lax.scan` program — the transformer analogue of the compiled char-RNN
generation in nn/generation.py (one dispatch per sequence, not per
token; through a ~69 ms-round-trip interconnect that is the difference
between usable and unusable sampling).

One block, TransformerEncoderBlock (pre-LN, causal=True), and one walk
over the layers serve every entry point: training and ``encode`` run it
with the block's own attention (so every Trainer feature — donation,
bf16 policy, NaN guard — applies unchanged); ``decode_step``,
``decode_step_slots`` and ``prefill_chunk`` hand it one of three cache
attends (write at a scalar position, write per row, write nowhere and
hand the keys and values back). Parity tests pin the cached logits to
the full forward's at every position
(tests/test_gpt.py::test_cached_decode_matches_full_forward).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.config import NeuralNetConfiguration, register_config
from deeplearning4j_tpu.nn.layers.attention import TransformerEncoderBlock
from deeplearning4j_tpu.observability.vocab import SCOPE_EMBED, SCOPE_HEAD
from deeplearning4j_tpu.ops import loss as losses
from deeplearning4j_tpu.ops import nn as opsnn
from deeplearning4j_tpu.train.updaters import Adam


@register_config
@dataclass
class GptConfig:
    """Architecture config (JSON round-trip via the config registry)."""

    vocab_size: int = 50257
    hidden: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate: int = 3072
    max_position: int = 1024
    dropout: float = 0.1
    attention_dropout: float = 0.1
    activation: str = "gelu"
    eps: float = 1e-5
    initializer_range: float = 0.02
    remat: bool = False
    # "ring" | "ulysses" | None — P9 sequence parallelism for long-context
    # training (takes effect inside a parallel.sequence.sequence_mesh).
    sequence_parallel: Optional[str] = None
    net: NeuralNetConfiguration = field(
        default_factory=lambda: NeuralNetConfiguration(updater=Adam(3e-4))
    )


class Gpt:
    """Causal transformer LM: Trainer-compatible (init/apply/loss_fn) plus
    a compiled KV-cache generator."""

    def __init__(self, config: GptConfig):
        self.config = config
        self.net = config.net
        self._block = TransformerEncoderBlock(
            num_heads=config.num_heads,
            intermediate=config.intermediate,
            activation=config.activation,
            dropout=config.dropout,
            attention_dropout=config.attention_dropout,
            causal=True,
            post_ln=False,  # pre-LN: stable for deep decoder stacks
            eps=config.eps,
            remat=config.remat,
            sequence_parallel=config.sequence_parallel,
        )

    # -- construction ------------------------------------------------------

    def init(self, seed: Optional[int] = None) -> Dict[str, Any]:
        c = self.config
        seed = self.net.seed if seed is None else seed
        rng = jax.random.key(seed)
        dtype = jnp.dtype(self.net.dtype)
        std = c.initializer_range

        def trunc(key, shape):
            return std * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                                     dtype)

        ks = jax.random.split(rng, 4 + c.num_layers)
        params: Dict[str, Any] = {
            "embeddings": {
                "word": trunc(ks[0], (c.vocab_size, c.hidden)),
                "position": trunc(ks[1], (c.max_position, c.hidden)),
            },
            # final pre-head LayerNorm (GPT-2 style); decoder weight is
            # tied to the word embedding, only a bias is learned
            "final": {
                "ln_gamma": jnp.ones((c.hidden,), dtype),
                "ln_beta": jnp.zeros((c.hidden,), dtype),
                "out_b": jnp.zeros((c.vocab_size,), dtype),
            },
        }
        for i in range(c.num_layers):
            p, _ = self._block.init(ks[4 + i], (c.max_position, c.hidden),
                                    dtype)
            params[f"layer_{i}"] = p
        return {"params": params, "state": {}}

    # -- pure functions ----------------------------------------------------

    def encode(self, params, ids, *, train=False, rng=None, mask=None):
        """[N,T] int32 → hidden [N,T,H] (pre-head LN applied)."""
        return self._walk(params, ids, train=train, rng=rng, mask=mask)[0]

    def _walk(self, params, ids, *, positions=None, attends=None,
              train=False, rng=None, mask=None):
        """The one walk over the layers: ids [N,T] → (hidden [N,T,H] with
        the pre-head LN applied, what each layer's attend kept).
        ``positions`` (a scalar, or [N,T]) index the position table where
        the tokens are not at 0..T-1; ``attends`` holds one
        ``SelfAttention.apply`` attend a layer."""
        c = self.config
        emb = params["embeddings"]
        with jax.named_scope(SCOPE_EMBED):
            x = opsnn.embedding_lookup(emb["word"], ids)
            if positions is None:
                # a slice, not a gather: the table's gradient stays a pad
                x = x + emb["position"][:ids.shape[1]][None, :, :]
            else:
                x = x + emb["position"][positions]
            if train and c.dropout > 0.0 and rng is not None:
                x = opsnn.dropout(x, c.dropout, jax.random.fold_in(rng, 999))
        kept = []
        for i in range(c.num_layers):
            lrng = jax.random.fold_in(rng, i) if rng is not None else None
            x, k = self._block.apply(
                params[f"layer_{i}"], {}, x, train=train, rng=lrng, mask=mask,
                attend=attends[i] if attends else None)
            kept.append(k)
        f = params["final"]
        with jax.named_scope(SCOPE_HEAD):
            return opsnn.layer_norm(x, f["ln_gamma"], f["ln_beta"],
                                    eps=c.eps), kept

    def logits(self, params, hidden):
        with jax.named_scope(SCOPE_HEAD):
            return (jnp.einsum("nth,vh->ntv", hidden,
                               params["embeddings"]["word"])
                    + params["final"]["out_b"])

    def apply(self, variables, features, *, train=False, rng=None):
        """Returns (logits [N,T,V], state)."""
        if isinstance(features, dict):
            ids = features["token_ids"]
            mask = features.get("mask")
        else:
            ids, mask = features, None
        h = self.encode(variables["params"], ids, train=train, rng=rng,
                        mask=mask)
        return self.logits(variables["params"], h), variables.get("state", {})

    def loss_fn(self, params, state, batch, rng=None):
        """Next-token cross entropy. batch["features"]["token_ids"] [N,T];
        optional features["mask"] [N,T] excludes padding from loss and
        attention; optional batch["labels"] overrides the shifted ids."""
        features = batch["features"]
        if not isinstance(features, dict):
            features = {"token_ids": features}
        ids = features["token_ids"]
        mask = features.get("mask")
        h = self.encode(params, ids, train=True, rng=rng, mask=mask)
        with jax.named_scope(SCOPE_HEAD):
            labels = batch.get("labels")
            if labels is None:
                labels = ids[:, 1:]
            w = (jnp.ones(labels.shape, jnp.float32) if mask is None
                 else mask[:, 1:].astype(jnp.float32))
            # the last position has no next token: cut from the hidden
            # state, so that no logits are made for it
            per_tok = losses.linear_softmax_cross_entropy(
                h[:, :-1], params["embeddings"]["word"], labels,
                params["final"]["out_b"])
            loss = jnp.sum(per_tok * w) / jnp.maximum(jnp.sum(w), 1.0)
        return loss, (state, {"loss": loss})

    def loss_weight(self, batch):
        """Total loss-weight of ``batch`` — non-padding next-token
        positions. The trainer's grad-accumulation scan uses this to
        combine microbatches exactly as the full-batch weighted mean
        would, even when mask density varies across microbatches.

        Deliberately UNclamped (unlike loss_fn's max(Σw,1) divide-guard):
        a fully-padded microbatch has loss 0 and must contribute weight 0
        to the combination, not a phantom 1 — w·loss = Σ per-token loss
        holds exactly either way."""
        features = batch["features"]
        if not isinstance(features, dict):
            features = {"token_ids": features}
        ids = features["token_ids"]
        mask = features.get("mask")
        if mask is None:
            n, t = ids.shape
            return jnp.float32(n * (t - 1))
        return jnp.sum(mask[:, 1:].astype(jnp.float32))

    def num_params(self, variables) -> int:
        return sum(p.size for p in
                   jax.tree_util.tree_leaves(variables["params"]))

    # -- KV-cache decoding -------------------------------------------------

    def init_cache(self, batch_size: int, max_len: int, dtype=jnp.float32):
        """Per-layer K/V ring buffers [N, heads, max_len, head_dim]."""
        c = self.config
        hd = c.hidden // c.num_heads
        shape = (batch_size, c.num_heads, max_len, hd)
        return [{"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
                for _ in range(c.num_layers)]

    def decode_step(self, params, caches, ids_t, pos):
        """One decode step: ids_t [N] int32 at position pos → (logits [N,V],
        updated caches)."""
        h, caches = self._walk(params, ids_t[:, None], positions=pos,
                               attends=[_attend_at(c, pos) for c in caches])
        return self.logits(params, h)[:, 0], caches

    def decode_step_slots(self, params, caches, ids_t, pos):
        """One iteration-level decode step over independent sequences:
        ids_t [N] int32, pos [N] int32 (each row's own 0-based position)
        → (logits [N,V], updated caches). Rows are decode *slots* —
        sequences at different depths batched into one device step, the
        core primitive of the continuous-batching serving engine
        (serving/generation.py)."""
        h, caches = self._walk(
            params, ids_t[:, None], positions=pos[:, None],
            attends=[_attend_at_rows(c, pos) for c in caches])
        return self.logits(params, h)[:, 0], caches

    def prefill_chunk(self, params, ids):
        """Whole-prompt prefill with full causal self-attention:
        ids [N,P] int32 → (logits [N,P,V], per-layer K/V
        ``[{"k": [N,h,P,hd], "v": ...}]``). One matmul-bound program
        instead of a P-step decode scan — the compute-shaped half of the
        prefill/decode split (decode is memory-bound; cuDNN-paper
        batched-primitive framing). Logits parity with the cached decode
        scan is pinned by tests/test_generation_serving.py."""
        h, kvs = self._walk(params, ids,
                            attends=[_attend_causal] * self.config.num_layers)
        return self.logits(params, h), kvs

    def generate(self, variables, prime_ids, *, n_steps: int, rng,
                 temperature: float = 1.0, top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 max_len: Optional[int] = None):
        """Sample n_steps continuation tokens after prime_ids [N,T0].

        Prefill runs the cached decoder over the prime with a lax.scan
        (teacher forcing), then a second scan samples; BOTH loops live in
        one jitted program per (shape, n_steps) — no per-token dispatch.
        temperature=0 is greedy argmax; ``top_k`` keeps the k most likely
        tokens, ``top_p`` nucleus-truncates to the smallest set with
        cumulative probability ≥ p (both before the categorical draw;
        combinable — top_k filters first). Returns [N, n_steps] int32.
        """
        params = variables["params"]
        n, t0 = prime_ids.shape
        total = max_len or (t0 + n_steps)
        if total < t0 + n_steps:
            raise ValueError(
                f"max_len {total} < prime {t0} + n_steps {n_steps}: the KV "
                "cache would clamp out-of-range writes to its last slot and "
                "sample from stale keys")
        if total > self.config.max_position:
            raise ValueError(
                f"generation length {total} exceeds max_position "
                f"{self.config.max_position}")
        if top_k is not None and top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {top_k}")
        if top_p is not None and not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        # normalize no-op filters so they share the plain program's jit
        # cache entry instead of recompiling identical behavior
        if top_k is not None and top_k >= self.config.vocab_size:
            top_k = None
        if top_p is not None and top_p >= 1.0:
            top_p = None
        fn = _generate_fn_cache(
            self, t0, n_steps, total, float(temperature),
            None if top_k is None else int(top_k),
            None if top_p is None else float(top_p))
        return fn(params, jnp.asarray(prime_ids, jnp.int32), rng)

    def beam_search(self, variables, prime_ids, *, n_steps: int,
                    beam_size: int = 4, length_penalty: float = 0.0,
                    eos_id: Optional[int] = None,
                    max_len: Optional[int] = None):
        """Beam-search n_steps continuation tokens after prime_ids [N,T0].

        Returns (sequences [N, beam_size, n_steps] int32, scores
        [N, beam_size] float32), best beam first. Scores are summed
        next-token log-probabilities; with ``length_penalty`` α > 0 they
        are GNMT-normalized by ((5+len)/6)^α. ``eos_id`` freezes a beam
        once it emits eos (it then continues on eos at logprob 0). The
        whole search — prefill, expansion, cache reordering, backtrace —
        compiles as one XLA program per shape (no per-token dispatch).
        beam_size=1 degenerates to greedy decoding."""
        params = variables["params"]
        n, t0 = prime_ids.shape
        total = max_len or (t0 + n_steps)
        if total < t0 + n_steps:
            raise ValueError(
                f"max_len {total} < prime {t0} + n_steps {n_steps}")
        if total > self.config.max_position:
            raise ValueError(
                f"generation length {total} exceeds max_position "
                f"{self.config.max_position}")
        if beam_size < 1:
            raise ValueError(f"beam_size must be >= 1, got {beam_size}")
        if beam_size > self.config.vocab_size:
            raise ValueError(
                f"beam_size {beam_size} > vocab {self.config.vocab_size}")
        if n_steps < 1:
            raise ValueError(f"n_steps must be >= 1, got {n_steps}")
        if length_penalty < 0:
            raise ValueError(
                f"length_penalty must be >= 0, got {length_penalty}")
        key = (t0, n_steps, total, int(beam_size), float(length_penalty),
               None if eos_id is None else int(eos_id))
        fn = _jit_cache(self, "_beam_cache", key, lambda: _build_beam_search_fn(
            self, t0, n_steps, total, int(beam_size),
            float(length_penalty), eos_id))
        return fn(params, jnp.asarray(prime_ids, jnp.int32))


def _attend_over(q, k, v, live):
    """Softmax attention of q [N,h,Q,hd] over the keys and values
    [N,h,L,hd] that ``live`` (broadcast to [N,h,Q,L]) lets each query see."""
    scores = jnp.einsum("nhqd,nhld->nhql", q, k) / jnp.sqrt(
        jnp.asarray(q.shape[-1], q.dtype))
    scores = jnp.where(live, scores, jnp.finfo(scores.dtype).min)
    return jnp.einsum("nhql,nhld->nhqd", jax.nn.softmax(scores, axis=-1), v)


def _attend_at(cache, pos):
    """The attend of one token a row, all rows at the scalar position
    ``pos``: written into the cache there, slots <= pos live."""
    def attend(q, k, v):
        kc = jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, pos, 0))
        vc = jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, pos, 0))
        live = jnp.arange(kc.shape[2]) <= pos
        return _attend_over(q, kc, vc, live), {"k": kc, "v": vc}

    return attend


def _attend_at_rows(cache, pos):
    """The attend of one token a row, row i at its own ``pos[i]``: a
    per-row scatter into the cache, slots <= pos[i] live for row i."""
    def attend(q, k, v):
        rows = jnp.arange(pos.shape[0])
        kc = cache["k"].at[rows, :, pos, :].set(k[:, :, 0])
        vc = cache["v"].at[rows, :, pos, :].set(v[:, :, 0])
        live = jnp.arange(kc.shape[2]) <= pos[:, None, None, None]
        return _attend_over(q, kc, vc, live), {"k": kc, "v": vc}

    return attend


def _attend_causal(q, k, v):
    """The attend of a whole prompt from position 0: nothing is written,
    the causal triangle is live, the keys and values are handed back."""
    live = jnp.tril(jnp.ones((q.shape[2], k.shape[2]), bool))
    return _attend_over(q, k, v, live), {"k": k, "v": v}


def _jit_cache(model, attr: str, key, build):
    """Per-model jit-program cache (generate/beam_search): repeated calls
    with the same static config never retrace."""
    cache = getattr(model, attr, None)
    if cache is None:
        cache = {}
        setattr(model, attr, cache)
    if key not in cache:
        cache[key] = build()
    return cache[key]


def _prefill(model: "Gpt", params, prime, t0: int, total: int):
    """Cached-decoder prefill over the prime (teacher forcing, one scan).
    Returns (caches, last-position logits). Shared by generate and
    beam_search so KV-parity is pinned once."""
    caches = model.init_cache(
        prime.shape[0], total, dtype=params["embeddings"]["word"].dtype)

    def step(carry, t):
        caches = carry
        lg, caches = model.decode_step(params, caches, prime[:, t], t)
        return caches, lg

    caches, lgs = jax.lax.scan(step, caches, jnp.arange(t0))
    return caches, lgs[-1]


def _truncate_logits(lg, top_k: Optional[int], top_p: Optional[float]):
    """Mask logits outside the top-k set and/or the nucleus (top-p) set to
    -inf. Pure function of static (k, p); vocab axis last."""
    neg = jnp.finfo(lg.dtype).min
    if top_k is not None and top_k < lg.shape[-1]:
        kth = jax.lax.top_k(lg, top_k)[0][..., -1:]
        lg = jnp.where(lg < kth, neg, lg)
    if top_p is not None and top_p < 1.0:
        sorted_lg = jnp.sort(lg, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_lg, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep tokens while the cumulative mass BEFORE them is < p (the
        # first token is always kept)
        keep = jnp.concatenate(
            [jnp.zeros_like(cum[..., :1]), cum[..., :-1]], axis=-1) < top_p
        # threshold = smallest kept sorted logit
        thresh = jnp.min(jnp.where(keep, sorted_lg, jnp.inf), axis=-1,
                         keepdims=True)
        lg = jnp.where(lg < thresh, neg, lg)
    return lg


def _build_generate_fn(model: Gpt, t0: int, n_steps: int, total: int,
                       temperature: float, top_k: Optional[int] = None,
                       top_p: Optional[float] = None):
    def run(params, prime, rng):
        # cache dtype follows the params (bf16 nets project bf16 K/V)
        caches, last_logits = _prefill(model, params, prime, t0, total)

        def sample(lg, key):
            if temperature == 0.0:
                return jnp.argmax(lg, axis=-1).astype(jnp.int32)
            # temperature FIRST, then nucleus/top-k on the tempered
            # distribution (standard semantics: the kept set holds mass p
            # of the distribution actually sampled)
            lg = lg / jnp.asarray(temperature, lg.dtype)
            lg = _truncate_logits(lg, top_k, top_p)
            return jax.random.categorical(key, lg, axis=-1).astype(jnp.int32)

        def step(carry, i):
            caches, lg, key = carry
            key, sub = jax.random.split(key)
            tok = sample(lg, sub)
            lg2, caches = model.decode_step(params, caches, tok, t0 + i)
            return (caches, lg2, key), tok

        (_, _, _), toks = jax.lax.scan(
            step, (caches, last_logits, rng), jnp.arange(n_steps))
        return toks.T  # [N, n_steps]

    return jax.jit(run)


def _generate_fn_cache(model: Gpt, t0: int, n_steps: int, total: int,
                       temperature: float, top_k: Optional[int] = None,
                       top_p: Optional[float] = None):
    """Per-model jit cache so repeated sampling never retraces."""
    return _jit_cache(
        model, "_gen_cache", (t0, n_steps, total, temperature, top_k, top_p),
        lambda: _build_generate_fn(model, t0, n_steps, total, temperature,
                                   top_k, top_p))


def _build_beam_search_fn(model: Gpt, t0: int, n_steps: int, total: int,
                          beam_size: int, length_penalty: float,
                          eos_id: Optional[int]):
    """Compiled beam search: prefill scan at beam 1, tile the KV caches to
    ``beam_size`` rows, then ONE lax.scan of expand→top-k(B·V)→reorder
    steps with parent backtrace — the whole search is a single XLA
    program (↔ the reference SameDiff's beam decoding, without per-step
    host dispatch). Finished beams (eos) continue on eos with logprob 0,
    the standard freeze."""
    B = beam_size
    neg = -1e30

    def run(params, prime):
        n = prime.shape[0]
        caches, last_logits = _prefill(model, params, prime, t0, total)
        v = last_logits.shape[-1]
        logp0 = jax.nn.log_softmax(last_logits.astype(jnp.float32), axis=-1)

        caches = jax.tree_util.tree_map(
            lambda x: jnp.repeat(x, B, axis=0), caches)
        # first expansion from the (identical) prefix only once — top-B
        # tokens of the prime's next-token distribution seed the beams
        scores, tok0 = jax.lax.top_k(logp0, B)          # [N,B]
        tok0 = tok0.astype(jnp.int32)
        finished = (tok0 == eos_id) if eos_id is not None \
            else jnp.zeros((n, B), bool)
        lengths = jnp.ones((n, B), jnp.int32)

        def step(carry, i):
            caches, scores, finished, lengths, tok = carry
            lg, caches = model.decode_step(
                params, caches, tok.reshape(n * B), t0 + i)
            lp = jax.nn.log_softmax(
                lg.reshape(n, B, v).astype(jnp.float32), axis=-1)
            if eos_id is not None:
                eos_only = jnp.where(
                    jnp.arange(v)[None, None, :] == eos_id, 0.0, neg)
                lp = jnp.where(finished[..., None], eos_only, lp)
            flat = (scores[..., None] + lp).reshape(n, B * v)
            new_scores, idx = jax.lax.top_k(flat, B)    # [N,B]
            parent = idx // v
            new_tok = (idx % v).astype(jnp.int32)
            rows = (jnp.arange(n)[:, None] * B + parent).reshape(-1)
            caches = jax.tree_util.tree_map(lambda x: x[rows], caches)
            new_fin = jnp.take_along_axis(finished, parent, axis=1)
            new_len = jnp.take_along_axis(lengths, parent, axis=1) \
                + jnp.where(new_fin, 0, 1)
            if eos_id is not None:
                new_fin = new_fin | (new_tok == eos_id)
            return ((caches, new_scores, new_fin, new_len, new_tok),
                    (new_tok, parent))

        # iteration i decodes the PREVIOUS token (first: tok0 at slot t0)
        # and expands to the next one — n_steps-1 expansions after tok0
        (caches, scores, finished, lengths, _), (toks, parents) = \
            jax.lax.scan(step, (caches, scores, finished, lengths, tok0),
                         jnp.arange(n_steps - 1))

        # backtrace the parent chain (newest step first)
        def back(beam_idx, x):
            tok_t, parent_t = x
            sel = jnp.take_along_axis(tok_t, beam_idx, axis=1)
            return jnp.take_along_axis(parent_t, beam_idx, axis=1), sel

        init_idx = jnp.tile(jnp.arange(B)[None, :], (n, 1))
        beam_idx, rev = jax.lax.scan(back, init_idx,
                                     (toks[::-1], parents[::-1]))
        first = jnp.take_along_axis(tok0, beam_idx, axis=1)
        seqs = jnp.concatenate([first[None], rev[::-1]], axis=0)
        seqs = jnp.moveaxis(seqs, 0, 2)                 # [N,B,n_steps]
        final = scores
        if length_penalty:
            final = final / (((5.0 + lengths.astype(jnp.float32)) / 6.0)
                             ** length_penalty)
        order = jnp.argsort(-final, axis=1)
        seqs = jnp.take_along_axis(seqs, order[..., None], axis=1)
        final = jnp.take_along_axis(final, order, axis=1)
        return seqs, final

    return jax.jit(run)


def gpt2_small(**kw) -> Gpt:
    """GPT-2 small dims (12L/768H/12A, 1024 ctx)."""
    return Gpt(GptConfig(**kw))


def gpt_tiny(**kw) -> Gpt:
    """2L/64H/2A toy config for tests and CPU runs."""
    kw.setdefault("hidden", 64)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 2)
    kw.setdefault("intermediate", 128)
    kw.setdefault("vocab_size", 128)
    kw.setdefault("max_position", 64)
    kw.setdefault("dropout", 0.0)
    kw.setdefault("attention_dropout", 0.0)
    return Gpt(GptConfig(**kw))


def gpt_long(**kw) -> Gpt:
    """Long-context config: ring-attention sequence parallelism + remat
    (train at T ≫ single-chip HBM limits on a `seq` mesh axis).

    Positions are learned absolute embeddings (GPT-2 convention — the
    [max_position, H] table is ~25M params at default dims); a rotary
    variant would shrink that and extrapolate, at the cost of diverging
    from the block layout every importer/test pins — future work, noted
    honestly rather than half-built."""
    kw.setdefault("sequence_parallel", "ring")
    kw.setdefault("remat", True)
    kw.setdefault("max_position", 32768)
    return Gpt(GptConfig(**kw))
