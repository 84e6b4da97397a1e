"""LeNet-5 on MNIST: train, evaluate, checkpoint, resume.

↔ dl4j-examples LeNetMNIST — the reference's PR1 config (BASELINE config
#1). Runs on CPU or TPU; ~30s CPU with --quick.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: F401,E402 - repo path + compile cache

import argparse
import tempfile

from deeplearning4j_tpu.data import ArrayDataSetIterator, load_mnist
from deeplearning4j_tpu.evaluation import evaluate_model
from deeplearning4j_tpu.models.lenet import lenet
from deeplearning4j_tpu.serde.checkpoint import (
    latest_checkpoint,
    restore_checkpoint,
    save_checkpoint,
)
from deeplearning4j_tpu.train.listeners import ScoreIterationListener
from deeplearning4j_tpu.train.trainer import Trainer
from deeplearning4j_tpu.train.updaters import Adam


def main(quick: bool = False):
    n_train, n_test, epochs = (2048, 512, 5) if quick else (8192, 1024, 8)
    (xtr, ytr), (xte, yte), is_real = load_mnist(n_train=n_train, n_test=n_test)
    print(f"MNIST: {len(xtr)} train / {len(xte)} test (real={is_real})")

    model = lenet(updater=Adam(3e-3))
    trainer = Trainer(model)
    ts = trainer.init_state()
    ts = trainer.fit(ts, ArrayDataSetIterator(xtr, ytr, batch_size=256),
                     epochs=epochs, listeners=[ScoreIterationListener(every=8)])

    ev = evaluate_model(model, trainer.variables(ts),
                        ArrayDataSetIterator(xte, yte, batch_size=256,
                                             shuffle=False), num_classes=10)
    print(ev.stats())

    # checkpoint round-trip (↔ ModelSerializer)
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, ts, model=model)
        ckpt = latest_checkpoint(d)
        restored = restore_checkpoint(ckpt, ts)
        print(f"checkpoint saved+restored: step={int(restored.step)}")
    return ev.accuracy()


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    acc = main(ap.parse_args().quick)
    assert acc > 0.8, acc
