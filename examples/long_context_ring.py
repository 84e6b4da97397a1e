"""Long-context training: ring-attention sequence parallelism + remat.

The capability the task brief makes first-class (SURVEY §5.7): train a
causal LM at a sequence length whose attention state would not fit one
device by shard­ing the SEQUENCE axis over a `seq` mesh axis — KV blocks
rotate around the ring via collective-permute while each shard computes
its queries' block (flash semantics, no [T,T] materialization anywhere).

Runs on the 8-virtual-CPU-device mesh exactly as it would on an ICI ring
(`XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu`);
on a real slice only the device list changes.
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
from deeplearning4j_tpu.parallel.sequence import sequence_mesh
from deeplearning4j_tpu.runtime.device import MeshSpec, build_mesh
from deeplearning4j_tpu.train.trainer import Trainer
from deeplearning4j_tpu.train.updaters import Adam


def main(quick: bool = False):
    if len(jax.devices()) < 8:
        raise SystemExit(
            "need 8 devices: XLA_FLAGS=--xla_force_host_platform_"
            "device_count=8 JAX_PLATFORMS=cpu")
    seq_len = 512 if quick else 4096
    mesh = build_mesh(MeshSpec(data=2, seq=4))
    model = Gpt(GptConfig(
        vocab_size=256, hidden=128, num_layers=2 if quick else 4,
        num_heads=4, intermediate=256, max_position=seq_len,
        dropout=0.0, attention_dropout=0.0,
        sequence_parallel="ring",   # KV rotation over the seq axis
        remat=True,                 # recompute blocks in backward
        net=NeuralNetConfiguration(updater=Adam(3e-3), seed=0)))
    trainer = Trainer(model)
    ts = trainer.init_state()

    rng = np.random.default_rng(0)
    base = rng.integers(1, 256, 64)
    ids = np.tile(base, (4, seq_len // 64 + 1))[:, :seq_len].astype(np.int32)
    batch = {"features": {"token_ids": ids}}

    steps = 12 if quick else 40
    # the SP layers capture the active mesh when the step is TRACED —
    # first call inside the context compiles the ring program
    with sequence_mesh(mesh):
        losses = []
        for i in range(steps):
            ts, m = trainer.train_step(ts, batch)
            if i % 4 == 0:
                loss = float(jax.device_get(m["loss"]))
                losses.append(loss)
                print(f"step {i}: loss {loss:.3f} (T={seq_len}, "
                      f"mesh data=2 x seq=4)")
    assert losses[-1] < losses[0], losses
    print("long-context ring-SP training converges:", losses)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    main(**vars(ap.parse_args()))
