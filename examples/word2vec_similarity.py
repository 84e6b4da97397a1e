"""Word2Vec + FastText embeddings: train, query similarity/analogy.

↔ dl4j-examples Word2VecRawTextExample. Embedding training is batched
SGNS in one jitted step (the reference's parameter-server skip-gram path
collapsed to scatter-adds; see nlp/word2vec.py).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: F401,E402 - repo path + compile cache

import argparse

import numpy as np


def corpus(n=400, seed=0):
    rng = np.random.default_rng(seed)
    animals = ["cat", "dog", "horse", "cow", "sheep", "pig"]
    tech = ["cpu", "gpu", "ram", "disk", "cache", "bus"]
    return [" ".join(rng.choice(t, size=7))
            for t in (animals if rng.random() < 0.5 else tech
                      for _ in range(n))]


def main(quick: bool = False):
    from deeplearning4j_tpu.nlp import FastText, Word2Vec

    sents = corpus(200 if quick else 600)
    w2v = Word2Vec(vector_size=32, window=3, min_word_frequency=1,
                   epochs=6 if quick else 15, subsample=0.0, seed=1)
    w2v.fit(sents)
    print("w2v  sim(cat,dog) =", round(w2v.similarity("cat", "dog"), 3),
          " sim(cat,gpu) =", round(w2v.similarity("cat", "gpu"), 3))
    print("w2v  nearest(cpu):", w2v.words_nearest("cpu", 3))

    ft = FastText(vector_size=32, window=3, min_word_frequency=1,
                  epochs=6 if quick else 15, subsample=0.0, minn=2, maxn=4,
                  bucket=2000, seed=1)
    ft.fit(sents)
    print("ft   OOV 'cats' sim to dog vs gpu:",
          round(ft.similarity("cats", "dog"), 3),
          round(ft.similarity("cats", "gpu"), 3))
    return w2v.similarity("cat", "dog") - w2v.similarity("cat", "gpu")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    margin = main(ap.parse_args().quick)
    assert margin > 0.1, margin
