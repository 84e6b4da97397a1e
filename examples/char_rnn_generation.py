"""Char-RNN text generation with GravesLSTM.

↔ dl4j-examples GravesLSTMCharModellingExample + zoo TextGenerationLSTM
(BASELINE config #3): train on a corpus, sample with temperature. The
sampling loop is ONE compiled lax.scan (nn/generation.py), not a
step-per-dispatch host loop.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: F401,E402 - repo path + compile cache

import argparse

import jax
import numpy as np

from deeplearning4j_tpu.models.zoo.classic import text_generation_lstm_config
from deeplearning4j_tpu.nn.generation import generate
from deeplearning4j_tpu.nn.model import SequentialModel
from deeplearning4j_tpu.train.trainer import Trainer
from deeplearning4j_tpu.train.updaters import Adam

CORPUS = (
    "the quick brown fox jumps over the lazy dog. "
    "pack my box with five dozen liquor jugs. "
    "how vexingly quick daft zebras jump! "
) * 40


def main(quick: bool = False):
    chars = sorted(set(CORPUS))
    stoi = {c: i for i, c in enumerate(chars)}
    ids = np.array([stoi[c] for c in CORPUS], np.int32)
    vocab, T = len(chars), 48
    eye = np.eye(vocab, dtype=np.float32)
    starts = np.arange(0, len(ids) - T - 1, T // 2)
    windows = np.stack([ids[s:s + T + 1] for s in starts])
    batch = {"features": eye[windows[:, :-1]], "labels": eye[windows[:, 1:]]}

    model = SequentialModel(text_generation_lstm_config(
        vocab_size=vocab, hidden=64 if quick else 128, seq_len=T,
        updater=Adam(5e-3), seed=0))
    trainer = Trainer(model)
    ts = trainer.init_state()
    steps = 80 if quick else 400
    for i in range(steps):
        ts, m = trainer.train_step(ts, batch)
        if i % 40 == 0:
            print(f"step {i}: loss={float(m['total_loss']):.4f}")
    final = float(m["total_loss"])
    print(f"final loss: {final:.4f}")

    prime = np.array([stoi[c] for c in "the quick"], np.int32)
    out = generate(model, trainer.variables(ts), n_steps=120,
                   rng=jax.random.key(0), prime=prime, temperature=0.3)
    text = "".join(chars[i] for i in np.asarray(out[0]))
    print(f"sample: the quick{text!r}")
    return final


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    loss = main(ap.parse_args().quick)
    assert loss < 2.5, loss
