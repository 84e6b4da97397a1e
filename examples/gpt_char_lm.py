"""Char-level GPT: causal-LM training + compiled KV-cache sampling.

The transformer-era companion to char_rnn_generation.py (↔ the
reference's TextGenerationLSTM example, upgraded to the decoder-only
model in models/gpt.py): next-token training through the standard
Trainer, then autoregressive sampling where prefill AND the sample loop
compile into ONE lax.scan program — one device dispatch per sequence.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: F401,E402 - repo path + compile cache

import argparse

import jax
import numpy as np

from deeplearning4j_tpu.models.gpt import Gpt, GptConfig
from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
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
    itos = {i: c for c, i in stoi.items()}
    ids = np.array([stoi[c] for c in CORPUS], np.int32)
    T = 48
    starts = np.arange(0, len(ids) - T, T // 2)
    windows = np.stack([ids[s:s + T] for s in starts])

    model = Gpt(GptConfig(
        vocab_size=len(chars), hidden=64 if quick else 128,
        num_layers=2 if quick else 4, num_heads=4,
        intermediate=128 if quick else 512, max_position=128,
        dropout=0.0, attention_dropout=0.0,
        net=NeuralNetConfiguration(updater=Adam(3e-3), seed=0)))
    trainer = Trainer(model)
    ts = trainer.init_state()
    batch = {"features": {"token_ids": windows}}
    steps = 60 if quick else 300
    for i in range(steps):
        ts, m = trainer.train_step(ts, batch)
        if i % 30 == 0:
            print(f"step {i}: loss {float(jax.device_get(m['loss'])):.3f}")

    prime = "the quick "
    prime_ids = np.array([[stoi[c] for c in prime]], np.int32)
    toks = model.generate(
        trainer.variables(ts), prime_ids, n_steps=60,
        rng=jax.random.key(0), temperature=0.5)
    text = prime + "".join(itos[int(t)] for t in np.asarray(toks)[0])
    print("sample:", text)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    main(**vars(ap.parse_args()))
