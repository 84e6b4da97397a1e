"""NLP tests (↔ deeplearning4j-nlp test coverage at the capability level):
tokenizers, vocab, word2vec similarity structure, glove, doc vectors,
serde round-trip. Corpus is synthetic with planted co-occurrence topics so
the similarity assertions are deterministic-ish and fast."""

import numpy as np
import pytest

from deeplearning4j_tpu.nlp import (
    CommonPreprocessor,
    DefaultTokenizerFactory,
    Glove,
    NGramTokenizerFactory,
    ParagraphVectors,
    Word2Vec,
    build_vocab,
    load_word_vectors,
    save_word_vectors,
)


def _topic_corpus(n=300, seed=0):
    """Two topics with disjoint vocab; words inside a topic co-occur."""
    rng = np.random.default_rng(seed)
    animals = ["cat", "dog", "horse", "cow", "sheep"]
    tech = ["cpu", "gpu", "ram", "disk", "cache"]
    sents = []
    for _ in range(n):
        topic = animals if rng.random() < 0.5 else tech
        sents.append(" ".join(rng.choice(topic, size=6)))
    return sents


class TestTokenization:
    def test_default_tokenizer(self):
        t = DefaultTokenizerFactory(CommonPreprocessor())
        assert t("Hello, World!  foo") == ["hello", "world", "foo"]

    def test_ngram(self):
        t = NGramTokenizerFactory(1, 2)
        assert t.tokenize("a b c") == ["a", "b", "c", "a_b", "b_c"]


class TestVocab:
    def test_build_and_prune(self):
        sents = [["a", "a", "b"], ["a", "c"]]
        v = build_vocab(sents, min_word_frequency=2)
        assert "a" in v and "b" not in v
        assert v.counts[v.id_of("a")] == 3

    def test_ordering_by_frequency(self):
        v = build_vocab([["x"], ["y", "y"], ["z", "z", "z"]])
        assert v.words[0] == "z" and v.words[-1] == "x"

    def test_negative_sampling_distribution(self):
        v = build_vocab([["a"] * 80 + ["b"] * 20])
        rng = np.random.default_rng(0)
        draws = v.sample_negatives(rng, 2000)
        frac_a = (draws == v.id_of("a")).mean()
        assert 0.55 < frac_a < 0.9  # ∝ count^0.75, softer than raw freq


class TestWord2Vec:
    @pytest.fixture(scope="class")
    def w2v(self):
        m = Word2Vec(vector_size=24, window=3, min_word_frequency=1,
                     negative=4, epochs=12, batch_size=1024, seed=1,
                     subsample=0.0)
        m.fit(_topic_corpus())
        return m

    def test_topic_similarity_structure(self, w2v):
        within = w2v.similarity("cat", "dog")
        across = w2v.similarity("cat", "gpu")
        assert within > across + 0.2, (within, across)

    def test_words_nearest(self, w2v):
        near = w2v.words_nearest("cpu", 4)
        assert set(near) <= {"gpu", "ram", "disk", "cache"}

    def test_get_vector_shape(self, w2v):
        assert w2v.get_word_vector("cat").shape == (24,)
        assert w2v.has_word("cat") and not w2v.has_word("zebra")

    def test_serde_roundtrip(self, w2v, tmp_path):
        p = tmp_path / "vecs.txt"
        save_word_vectors(p, w2v.vocab.words, w2v.vectors)
        words, vecs = load_word_vectors(p)
        assert words == w2v.vocab.words
        np.testing.assert_allclose(vecs, w2v.vectors, rtol=1e-4, atol=1e-4)

    def test_cbow_mode_trains(self):
        m = Word2Vec(vector_size=8, window=2, min_word_frequency=1,
                     epochs=2, cbow=True, seed=2)
        hist = m.fit(_topic_corpus(50))
        assert len(hist) == 2 and np.isfinite(hist).all()

    def test_unfit_raises(self):
        with pytest.raises(RuntimeError, match="fit"):
            Word2Vec().get_word_vector("x")


class TestGlove:
    def test_topic_structure(self):
        g = Glove(vector_size=16, window=3, min_word_frequency=1,
                  epochs=30, learning_rate=0.05, seed=3)
        hist = g.fit(_topic_corpus(200, seed=3))
        assert hist[-1] < hist[0]  # loss decreases
        within = g.similarity("cat", "dog")
        across = g.similarity("cat", "gpu")
        assert within > across, (within, across)


class TestParagraphVectors:
    def test_doc_topic_clustering(self):
        animals = ["cat dog horse cow", "dog sheep cat cow", "horse cat dog"]
        tech = ["cpu gpu ram disk", "gpu cache cpu ram", "disk cpu gpu"]
        pv = ParagraphVectors(vector_size=16, epochs=60, negative=4, seed=4,
                              batch_size=64)
        pv.fit(animals + tech,
               labels=[f"a{i}" for i in range(3)] + [f"t{i}" for i in range(3)])
        v_a = [pv.get_doc_vector(f"a{i}") for i in range(3)]
        v_t = [pv.get_doc_vector(f"t{i}") for i in range(3)]

        def cos(x, y):
            return float(x @ y / (np.linalg.norm(x) * np.linalg.norm(y) + 1e-12))

        within = np.mean([cos(v_a[0], v_a[1]), cos(v_t[0], v_t[1])])
        across = np.mean([cos(v_a[i], v_t[j]) for i in range(3) for j in range(3)])
        assert within > across, (within, across)

    def test_infer_vector_nearest_label(self):
        docs = ["cat dog cow horse sheep cat dog", "cpu gpu ram cache disk cpu gpu"]
        pv = ParagraphVectors(vector_size=16, epochs=150, negative=4, seed=5,
                              batch_size=32)
        pv.fit(docs, labels=["animals", "tech"])
        near = pv.nearest_labels("dog cat sheep", top_n=1)
        assert near == ["animals"]


class TestDistributedEmbeddings:
    """P5 parameter-server role: embedding tables
    sharded over the mesh 'model' axis must train to the SAME embeddings
    as the single-device path — GSPMD's collectives replace the reference's
    VoidParameterServer shard routing without changing the math."""

    def _mesh(self):
        import jax
        from jax.sharding import Mesh

        return Mesh(np.asarray(jax.devices()[:4]).reshape(4), ("model",))

    def _corpus12(self, n=120, seed=3):
        """12-word vocab — divisible by the 4-way model axis, so the tables
        REALLY shard (10 words would silently hit the replicate fallback)."""
        rng = np.random.default_rng(seed)
        a = ["cat", "dog", "horse", "cow", "sheep", "goat"]
        b = ["cpu", "gpu", "ram", "disk", "cache", "bus"]
        return [" ".join(rng.choice(a if rng.random() < 0.5 else b, size=6))
                for _ in range(n)]

    def test_word2vec_sharded_matches_single(self):
        corpus = self._corpus12()
        kw = dict(vector_size=16, window=3, min_word_frequency=1,
                  negative=4, epochs=2, batch_size=256, seed=11)
        single = Word2Vec(**kw)
        single.fit(corpus)
        sharded = Word2Vec(**kw, mesh=self._mesh())
        sharded.fit(corpus)

        assert sharded.vocab.words == single.vocab.words
        a = single._model.in_vecs
        b = sharded._model.in_vecs
        assert a.shape[0] % 4 == 0, "test vocab must divide the model axis"
        # the sharded jit really carried a row-sharding for the tables
        assert "model" in sharded._model._step_key[1][0]
        np.testing.assert_allclose(b, a, rtol=2e-4, atol=2e-5)

    def test_word2vec_cbow_sharded_runs(self):
        corpus = _topic_corpus(n=80, seed=4)
        m = Word2Vec(vector_size=16, window=2, min_word_frequency=1,
                     cbow=True, epochs=1, batch_size=128, seed=5,
                     mesh=self._mesh())
        hist = m.fit(corpus)
        assert hist and np.isfinite(hist[-1])

    def test_glove_sharded_matches_single(self):
        from deeplearning4j_tpu.nlp.glove import Glove

        corpus = self._corpus12(n=120, seed=6)
        kw = dict(vector_size=16, window=3, min_word_frequency=1,
                  epochs=3, batch_size=512, seed=7)
        single = Glove(**kw)
        single.fit(corpus)
        sharded = Glove(**kw, mesh=self._mesh())
        sharded.fit(corpus)
        np.testing.assert_allclose(sharded.vectors, single.vectors,
                                   rtol=2e-4, atol=2e-5)


class TestFastText:
    @pytest.fixture(scope="class")
    def ft(self):
        from deeplearning4j_tpu.nlp import FastText

        m = FastText(vector_size=24, window=3, min_word_frequency=1,
                     negative=4, epochs=12, batch_size=1024, seed=1,
                     subsample=0.0, minn=2, maxn=4, bucket=5000)
        m.fit(_topic_corpus())
        return m

    def test_topic_similarity_structure(self, ft):
        within = ft.similarity("cat", "dog")
        across = ft.similarity("cat", "gpu")
        assert within > across + 0.2, (within, across)

    def test_oov_lookup_via_subwords(self, ft):
        v = ft.get_word_vector("cats")  # OOV — shares <ca, cat, ats> etc.
        assert v.shape == (24,)
        assert np.linalg.norm(v) > 0
        # OOV morphological variant lands nearer its stem's topic than the
        # other topic's words
        assert ft.similarity("cats", "dog") > ft.similarity("cats", "gpu")

    def test_ngram_extraction(self):
        from deeplearning4j_tpu.nlp import char_ngrams

        grams = char_ngrams("cat", 3, 3)
        assert grams == ["<ca", "cat", "at>"]

    def test_words_nearest(self, ft):
        near = ft.words_nearest("cpu", 4)
        assert set(near) <= {"gpu", "ram", "disk", "cache"}


class TestWordPiece:
    """BertWordPieceTokenizerFactory pinned to the HuggingFace
    BertTokenizer oracle (↔ the reference's BertWordPieceTokenizerFactory,
    validated the way its tests validate against known encodings)."""

    VOCAB = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]", "the", "quick",
             "brown", "fox", "jump", "##s", "##ed", "##ing", "over", "lazy",
             "dog", "un", "##aff", "##able", "runn", "hello", "world", "!",
             ",", ".", "$", "2", "##0", "##2", "##4", "vex", "零", "一"]

    @pytest.fixture()
    def vocab_file(self, tmp_path):
        p = tmp_path / "vocab.txt"
        p.write_text("\n".join(self.VOCAB))
        return str(p)

    def test_tokenize_matches_huggingface(self, vocab_file):
        transformers = pytest.importorskip("transformers")
        hf = transformers.BertTokenizer(vocab_file, do_lower_case=True)
        from deeplearning4j_tpu.nlp import BertWordPieceTokenizerFactory

        ours = BertWordPieceTokenizerFactory(vocab_file)
        for text in [
            "The quick brown fox JUMPS over the lazy dog!",
            "unaffable, hello world.",
            "vexing jumps $2024 runn jumped",
            "héllo wörld 零一 the",          # accents + CJK isolation
            "supercalifragilistic the",      # uncomposable -> [UNK]
        ]:
            assert ours.tokenize(text) == hf.tokenize(text), text

    def test_pair_encoding_matches_huggingface(self, vocab_file):
        transformers = pytest.importorskip("transformers")
        hf = transformers.BertTokenizer(vocab_file, do_lower_case=True)
        from deeplearning4j_tpu.nlp import BertWordPieceTokenizerFactory

        ours = BertWordPieceTokenizerFactory(vocab_file)
        enc = ours.encode("the quick fox", "jumps over", max_len=16)
        want = hf(text="the quick fox", text_pair="jumps over",
                  max_length=16, padding="max_length",
                  truncation="longest_first")
        assert list(enc["token_ids"]) == want["input_ids"]
        assert list(enc["segment_ids"]) == want["token_type_ids"]
        assert [int(v) for v in enc["mask"]] == want["attention_mask"]

    def test_truncation_and_roundtrip(self, vocab_file):
        from deeplearning4j_tpu.nlp import BertWordPieceTokenizerFactory

        ours = BertWordPieceTokenizerFactory(vocab_file)
        enc = ours.encode("the quick brown fox jumps over the lazy dog",
                          "hello world hello world", max_len=12)
        assert enc["token_ids"].shape == (12,)
        assert float(enc["mask"].sum()) == 12.0  # fully used
        toks = ours.convert_ids_to_tokens(enc["token_ids"])
        assert toks[0] == "[CLS]" and toks.count("[SEP]") == 2

    def test_feeds_bert_model(self, vocab_file):
        """encode() output slots straight into models.bert apply."""
        import numpy as np

        from deeplearning4j_tpu.models.bert import bert_tiny
        from deeplearning4j_tpu.nlp import BertWordPieceTokenizerFactory

        ours = BertWordPieceTokenizerFactory(vocab_file)
        rows = [ours.encode("the quick fox", max_len=16),
                ours.encode("hello world !", max_len=16)]
        feats = {k: np.stack([r[k] for r in rows]) for k in rows[0]}
        model = bert_tiny(vocab_size=64, max_position=16)
        v = model.init(seed=0)
        h, _ = model.apply(v, feats)
        assert h.shape == (2, 16, 128)

    def test_special_tokens_survive_and_tie_truncation(self, vocab_file):
        transformers = pytest.importorskip("transformers")
        hf = transformers.BertTokenizer(vocab_file, do_lower_case=True)
        from deeplearning4j_tpu.nlp import BertWordPieceTokenizerFactory

        ours = BertWordPieceTokenizerFactory(vocab_file)
        # [MASK] embedded in raw text stays one token (never_split)
        text = "the [MASK] fox"
        assert ours.tokenize(text) == hf.tokenize(text) == \
            ["the", "[MASK]", "fox"]
        # equal-length pair over budget: ties truncate the SECOND sequence
        enc = ours.encode("the quick fox jumps over",
                          "hello world the lazy dog", max_len=13)
        want = hf(text="the quick fox jumps over",
                  text_pair="hello world the lazy dog", max_length=13,
                  padding="max_length", truncation="longest_first")
        assert list(enc["token_ids"]) == want["input_ids"]

    def test_decode_joins_wordpieces(self, vocab_file):
        from deeplearning4j_tpu.nlp import BertWordPieceTokenizerFactory

        ours = BertWordPieceTokenizerFactory(vocab_file)
        ids = ours.convert_tokens_to_ids(
            ["[CLS]", "un", "##aff", "##able", "jump", "##s", "[SEP]"])
        assert ours.decode(ids) == "unaffable jumps"
        assert ours.decode(ids, skip_special_tokens=False) == \
            "[CLS] unaffable jumps [SEP]"
        # padded encode round-trips cleanly
        enc = ours.encode("the quick fox", max_len=12)
        assert ours.decode(enc["token_ids"]) == "the quick fox"
