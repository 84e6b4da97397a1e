"""Sequence-to-sequence with a cross-attention vertex (encoder-decoder).

↔ the reference's AttentionVertex use case (ComputationGraph with an
attention vertex bridging an encoder sequence into a decoder): the toy
task is sequence reversal — input a random token sequence, output the
reversed sequence. A bidirectional-LSTM encoder produces the context; the
decoder side attends over it with CrossAttention (queries = position
embeddings) and classifies each output position. Whole graph is ONE
XLA program under jit — encoder, attention, decoder, loss.

Run: JAX_PLATFORMS=cpu python examples/seq2seq_attention.py --quick
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: F401,E402 - repo path + compile cache

import argparse

import jax
import numpy as np

from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn.config import (
    GraphConfig,
    GraphVertex,
    NeuralNetConfiguration,
)
from deeplearning4j_tpu.nn.layers.attention import CrossAttention
from deeplearning4j_tpu.nn.model import GraphModel
from deeplearning4j_tpu.train.trainer import Trainer
from deeplearning4j_tpu.train.updaters import Adam


def build(vocab: int, T: int, hidden: int) -> GraphModel:
    verts = {
        # encoder: embeds + biLSTM over the input sequence
        "embed": GraphVertex(kind="layer", inputs=["tokens"],
                             layer=L.Embedding(vocab_size=vocab,
                                               units=hidden)),
        "enc": GraphVertex(kind="layer", inputs=["embed"],
                           layer=L.Bidirectional(
                               L.LSTM(units=hidden // 2))),
        # decoder queries: one learned embedding per OUTPUT position,
        # duplicated across the batch via a positional-embedding layer on
        # a zero sequence
        "queries": GraphVertex(kind="layer", inputs=["qpos"],
                               layer=L.PositionalEmbedding(max_len=T)),
        # cross attention: decoder positions attend over encoder context
        "xatt": GraphVertex(kind="layer", inputs=["queries", "enc"],
                            layer=CrossAttention(num_heads=4,
                                                 out_size=hidden)),
        "out": GraphVertex(kind="layer", inputs=["xatt"],
                           layer=L.RnnOutputLayer(units=vocab,
                                                  activation="softmax",
                                                  loss="mcxent")),
    }
    cfg = GraphConfig(
        net=NeuralNetConfiguration(seed=0, updater=Adam(3e-3)),
        inputs=["tokens", "qpos"],
        input_shapes={"tokens": (T,), "qpos": (T, 64)},
        vertices=verts, outputs=["out"])
    return GraphModel(cfg)


def main(quick: bool = False):
    vocab, T = 12, 10
    hidden = 64
    n = 256 if quick else 1024
    steps = 120 if quick else 600

    rng = np.random.default_rng(0)
    tokens = rng.integers(2, vocab, size=(n, T)).astype(np.int32)
    targets = tokens[:, ::-1]  # task: emit the sequence reversed
    eye = np.eye(vocab, dtype=np.float32)
    qpos = np.zeros((n, T, 64), np.float32)  # carrier for PositionalEmbedding

    model = build(vocab, T, hidden)
    trainer = Trainer(model)
    ts = trainer.init_state()
    batch = {"features": {"tokens": tokens, "qpos": qpos},
             "labels": {"out": eye[targets]}}
    for i in range(steps):
        ts, m = trainer.train_step(ts, batch)
        if i % 50 == 0:
            print(f"step {i:4d} loss {float(m['loss']):.4f}")

    out = model.output(trainer.variables(ts),
                       {"tokens": tokens[:64], "qpos": qpos[:64]})["out"]
    pred = np.asarray(out).argmax(-1)
    acc = float((pred == targets[:64]).mean())
    print(f"reversal accuracy: {acc:.3f}")
    assert acc > (0.6 if quick else 0.9), "seq2seq failed to learn reversal"
    print("OK")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    main(ap.parse_args().quick)
