"""Word2Vec: skip-gram / CBOW with negative sampling (↔ deeplearning4j-nlp
org.deeplearning4j.models.word2vec.Word2Vec + SkipGram/CBOW learning impls,
SURVEY §2.7; the distributed variant replaces the VoidParameterServer
skip-gram shard routing of §2.6 P5).

TPU-first design: the reference trains embeddings with per-pair JVM updates
(SkipGramRequestMessage routed to parameter-server shards). Here training
batches thousands of (center, context, negatives) triples into ONE jit'd
SGNS step — embedding gathers + logistic loss; jax.grad turns the gathers
into scatter-adds, XLA fuses the whole update, and under a mesh the
embedding table shards on the `model` axis (tensor-parallel gather —
the P5 "parameter server for embeddings" capability without a server).
Pair generation (windowing, subsampling, negative draws) stays host-side
numpy, overlapped with device steps by simple pipelining.
"""

from __future__ import annotations

from typing import Callable, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from deeplearning4j_tpu.nlp.tokenization import (
    CommonPreprocessor,
    DefaultTokenizerFactory,
)
from deeplearning4j_tpu.nlp.vocab import (
    VocabCache,
    build_vocab,
    fixed_shape_batches,
)


class _SGNSModel:
    """Shared skip-gram-negative-sampling machinery (used by Word2Vec and
    ParagraphVectors). Two tables: `in_vecs` (target/center or doc) and
    `out_vecs` (context)."""

    def __init__(self, n_in: int, n_out: int, dim: int, seed: int):
        rs = np.random.RandomState(seed)
        self.in_vecs = ((rs.rand(n_in, dim) - 0.5) / dim).astype(np.float32)
        self.out_vecs = np.zeros((n_out, dim), np.float32)
        # AdaGrad accumulators: batching SGNS sums many per-pair gradients
        # into the same embedding rows; AdaGrad's per-row scaling keeps that
        # stable at any batch size (plain SGD diverges on hot rows).
        self._acc = (np.full((n_in, dim), 1e-6, np.float32),
                     np.full((n_out, dim), 1e-6, np.float32))
        self._step = None

    def _build_step(self, mode: str = "sg", table_shardings=None):
        import jax
        import jax.numpy as jnp

        def sg_loss(tables, batch):
            center, context, negatives = batch
            inv, outv = tables
            v_c = inv[center]                    # [B, D]
            v_o = outv[context]                  # [B, D]
            v_n = outv[negatives]                # [B, K, D]
            pos = jnp.sum(v_c * v_o, -1)
            neg = jnp.einsum("bd,bkd->bk", v_c, v_n)
            # SGNS objective: log σ(pos) + Σ log σ(-neg). SUM over the batch
            # so each pair's embedding rows receive a full word2vec-scale
            # update (classic per-pair SGD batched); mean would divide the
            # effective per-pair lr by the batch size.
            return -jnp.sum(
                jax.nn.log_sigmoid(pos) + jnp.sum(jax.nn.log_sigmoid(-neg), -1))

        def cbow_loss(tables, batch):
            # CBOW: mean of the context-window vectors predicts the center
            # word (↔ the reference's CBOW learning impl).
            contexts, mask, center, negatives = batch
            inv, outv = tables
            v_ctx = inv[contexts] * mask[..., None]          # [B, C, D]
            h = jnp.sum(v_ctx, 1) / jnp.maximum(
                jnp.sum(mask, 1, keepdims=True), 1.0)        # [B, D]
            pos = jnp.sum(h * outv[center], -1)
            neg = jnp.einsum("bd,bkd->bk", h, outv[negatives])
            return -jnp.sum(
                jax.nn.log_sigmoid(pos) + jnp.sum(jax.nn.log_sigmoid(-neg), -1))

        loss_fn = sg_loss if mode == "sg" else cbow_loss

        def step(tables, acc, batch, lr):
            loss, grads = jax.value_and_grad(loss_fn)(tables, batch)
            acc = jax.tree_util.tree_map(lambda a, g: a + g * g, acc, grads)
            new = jax.tree_util.tree_map(
                lambda t, g, a: t - lr * g / jnp.sqrt(a), tables, grads, acc)
            b = batch[0].shape[0]
            return new, acc, loss / b  # report per-example mean

        if table_shardings is not None:
            # P5 parameter-server role: embedding rows sharded on the mesh
            # model axis; GSPMD turns the gathers/scatter-adds of the same
            # step function into the cross-shard collectives the reference
            # routed through VoidParameterServer messages.
            rep = table_shardings[-1]
            self._step = jax.jit(
                step, donate_argnums=(0, 1),
                in_shardings=(table_shardings[:2], table_shardings[:2],
                              rep, rep),
                out_shardings=(table_shardings[:2], table_shardings[:2], rep))
        else:
            self._step = jax.jit(step, donate_argnums=(0, 1))

    def train_epochs(self, batches_fn: Callable[[], Iterable], *, epochs: int,
                     lr: float, lr_min: float, mode: str = "sg",
                     mesh=None) -> List[float]:
        """batches_fn() yields tuples of arrays matching `mode`'s loss:
        sg: (center, context, negatives); cbow: (contexts, mask, center,
        negatives). ``mesh``: shard the embedding tables across the mesh's
        'model' axis (SURVEY §2.6 P5 — the parameter-server-for-embeddings
        role); tables whose vocab doesn't divide the axis stay replicated.
        """
        import jax
        import jax.numpy as jnp

        shardings = None
        if mesh is not None:
            from deeplearning4j_tpu.nlp.sharding import replicated, row_sharding

            shardings = (row_sharding(mesh, self.in_vecs.shape),
                         row_sharding(mesh, self.out_vecs.shape),
                         replicated(mesh))
        step_key = (mode, None if shardings is None else tuple(
            str(s) for s in shardings))
        if getattr(self, "_step_key", None) != step_key:
            self._build_step(mode, table_shardings=shardings)
            self._step_key = step_key
        tables = (jnp.asarray(self.in_vecs), jnp.asarray(self.out_vecs))
        acc = tuple(jnp.asarray(a) for a in self._acc)
        if shardings is not None:
            tables = tuple(jax.device_put(t, s)
                           for t, s in zip(tables, shardings[:2]))
            acc = tuple(jax.device_put(a, s)
                        for a, s in zip(acc, shardings[:2]))
        history = []
        for e in range(epochs):
            cur_lr = lr - (lr - lr_min) * e / max(epochs - 1, 1)
            losses = []
            for batch in batches_fn():
                tables, acc, loss = self._step(
                    tables, acc, tuple(jnp.asarray(a) for a in batch),
                    jnp.float32(cur_lr))
                losses.append(loss)
            if losses:
                # Stack on device: one host fetch per epoch instead of one
                # per batch.
                history.append(float(np.mean(jax.device_get(jnp.stack(losses)))))
        self.in_vecs, self.out_vecs = (np.asarray(t) for t in tables)
        self._acc = tuple(np.asarray(a) for a in acc)
        return history


def _window_pairs(ids: Sequence[int], window: int, rng: np.random.Generator,
                  keep_probs: np.ndarray) -> List[Tuple[int, int]]:
    """Skip-gram training pairs with per-sentence random window shrink and
    frequency subsampling (Mikolov tricks, ↔ SkipGram.iterateSample)."""
    kept = [i for i in ids if keep_probs[i] >= 1.0 or rng.random() < keep_probs[i]]
    pairs = []
    for pos, center in enumerate(kept):
        b = rng.integers(1, window + 1)
        lo = max(0, pos - b)
        hi = min(len(kept), pos + b + 1)
        for j in range(lo, hi):
            if j != pos:
                pairs.append((center, kept[j]))
    return pairs


class Word2Vec:
    """↔ org.deeplearning4j.models.word2vec.Word2Vec (builder pattern kept
    as constructor kwargs).

    Usage::

        w2v = Word2Vec(vector_size=64, window=5, min_word_frequency=2)
        w2v.fit(sentences)                  # iterable of strings or token lists
        w2v.words_nearest("king", 5)
    """

    def __init__(self, *, vector_size: int = 100, window: int = 5,
                 min_word_frequency: int = 5, negative: int = 5,
                 subsample: float = 1e-3, learning_rate: float = 0.025,
                 min_learning_rate: float = 1e-4, epochs: int = 1,
                 batch_size: int = 2048, cbow: bool = False, seed: int = 0,
                 tokenizer: Optional[Callable] = None, mesh=None):
        self.vector_size = vector_size
        self.window = window
        self.min_word_frequency = min_word_frequency
        self.negative = negative
        self.subsample = subsample
        self.learning_rate = learning_rate
        self.min_learning_rate = min_learning_rate
        self.epochs = epochs
        self.batch_size = batch_size
        self.cbow = cbow
        self.seed = seed
        self.mesh = mesh  # P5: shard embedding tables over mesh 'model' axis
        self.tokenizer = tokenizer or DefaultTokenizerFactory(CommonPreprocessor())
        self.vocab: Optional[VocabCache] = None
        self._model: Optional[_SGNSModel] = None

    # -- training ----------------------------------------------------------

    def _tokenize_corpus(self, corpus) -> List[List[str]]:
        out = []
        for item in corpus:
            out.append(self.tokenizer(item) if isinstance(item, str) else list(item))
        return out

    def fit(self, corpus: Iterable) -> List[float]:
        sentences = self._tokenize_corpus(corpus)
        self.vocab = build_vocab(
            sentences, min_word_frequency=self.min_word_frequency,
            subsample=self.subsample)
        if len(self.vocab) < 2:
            raise ValueError("vocabulary too small (check min_word_frequency)")
        encoded = [self.vocab.encode(s) for s in sentences]
        encoded = [s for s in encoded if len(s) > 1]
        n = len(self.vocab)
        self._model = _SGNSModel(n, n, self.vector_size, self.seed)
        rng = np.random.default_rng(self.seed)

        if self.cbow:
            return self._fit_cbow(encoded, rng)

        def batches():
            pairs: List[Tuple[int, int]] = []
            for ids in encoded:
                pairs.extend(_window_pairs(ids, self.window, rng,
                                           self.vocab.keep_probs))
            arr = np.asarray(pairs, np.int32).reshape(-1, 2)
            for sel in fixed_shape_batches(len(arr), self.batch_size, rng,
                                           what="skip-gram pairs"):
                chunk = arr[sel]
                negs = self.vocab.sample_negatives(rng, (len(sel), self.negative))
                yield chunk[:, 0], chunk[:, 1], negs.astype(np.int32)

        return self._model.train_epochs(
            batches, epochs=self.epochs, lr=self.learning_rate,
            lr_min=self.min_learning_rate, mode="sg", mesh=self.mesh)

    def _fit_cbow(self, encoded, rng) -> List[float]:
        """CBOW samples: (padded context window, mask, center word)."""
        width = 2 * self.window

        def samples():
            ctxs, masks, centers = [], [], []
            for ids in encoded:
                kept = [i for i in ids
                        if self.vocab.keep_probs[i] >= 1.0
                        or rng.random() < self.vocab.keep_probs[i]]
                for pos, center in enumerate(kept):
                    b = int(rng.integers(1, self.window + 1))
                    window = (kept[max(0, pos - b):pos]
                              + kept[pos + 1:pos + b + 1])
                    if not window:
                        continue
                    row = np.zeros(width, np.int32)
                    m = np.zeros(width, np.float32)
                    row[:len(window)] = window
                    m[:len(window)] = 1.0
                    ctxs.append(row)
                    masks.append(m)
                    centers.append(center)
            return (np.asarray(ctxs, np.int32), np.asarray(masks, np.float32),
                    np.asarray(centers, np.int32))

        ctxs, masks, centers = samples()

        def batches():
            for sel in fixed_shape_batches(len(centers), self.batch_size, rng,
                                           what="CBOW samples"):
                negs = self.vocab.sample_negatives(rng, (len(sel), self.negative))
                yield ctxs[sel], masks[sel], centers[sel], negs.astype(np.int32)

        return self._model.train_epochs(
            batches, epochs=self.epochs, lr=self.learning_rate,
            lr_min=self.min_learning_rate, mode="cbow", mesh=self.mesh)

    # -- lookups (↔ WordVectors interface) ---------------------------------

    @property
    def vectors(self) -> np.ndarray:
        self._check_fit()
        return self._model.in_vecs

    def _check_fit(self):
        if self._model is None or self.vocab is None:
            raise RuntimeError("call fit() first")

    def has_word(self, w: str) -> bool:
        return self.vocab is not None and w in self.vocab

    def get_word_vector(self, w: str) -> np.ndarray:
        self._check_fit()
        return self._model.in_vecs[self.vocab.id_of(w)]

    def similarity(self, a: str, b: str) -> float:
        va, vb = self.get_word_vector(a), self.get_word_vector(b)
        return float(va @ vb / (np.linalg.norm(va) * np.linalg.norm(vb) + 1e-12))

    def words_nearest(self, word_or_vec, top_n: int = 10) -> List[str]:
        """↔ WordVectors.wordsNearest (cosine)."""
        self._check_fit()
        if isinstance(word_or_vec, str):
            vec = self.get_word_vector(word_or_vec)
            exclude = {self.vocab.id_of(word_or_vec)}
        else:
            vec = np.asarray(word_or_vec)
            exclude = set()
        m = self._model.in_vecs
        sims = (m @ vec) / (np.linalg.norm(m, axis=1) * np.linalg.norm(vec) + 1e-12)
        order = np.argsort(-sims)
        out = []
        for i in order:
            if int(i) in exclude:
                continue
            out.append(self.vocab.word_of(int(i)))
            if len(out) == top_n:
                break
        return out

    def analogy(self, a: str, b: str, c: str, top_n: int = 1) -> List[str]:
        """a is to b as c is to ? (king - man + woman ≈ queen)."""
        v = (self.get_word_vector(b) - self.get_word_vector(a)
             + self.get_word_vector(c))
        cands = self.words_nearest(v, top_n + 3)
        skip = {a, b, c}
        return [w for w in cands if w not in skip][:top_n]
