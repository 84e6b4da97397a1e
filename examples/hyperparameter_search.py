"""Hyperparameter search over a small classifier (↔ arbiter examples).

Random search over learning rate / width / activation, then a focused
grid around the winner; every trial is an ordinary compiled Trainer fit
scored on held-out accuracy.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: F401,E402 - repo path + compile cache

import argparse

import numpy as np

from deeplearning4j_tpu.data import ArrayDataSetIterator
from deeplearning4j_tpu.evaluation import evaluate_model
from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn.config import NeuralNetConfiguration, SequentialConfig
from deeplearning4j_tpu.nn.model import SequentialModel
from deeplearning4j_tpu.train.updaters import Adam
from deeplearning4j_tpu.tuning import (
    Choice,
    GridSearch,
    IntRange,
    LogUniform,
    RandomSearch,
    Tuner,
)


def main(quick: bool = False):
    r = np.random.default_rng(0)
    n, d, classes = 256, 12, 4
    centers = r.normal(size=(classes, d)) * 2.5
    labels = r.integers(0, classes, n)
    x = (centers[labels] + r.normal(size=(n, d))).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[labels]
    split = int(0.75 * n)
    train = ArrayDataSetIterator(x[:split], y[:split], batch_size=64)
    val = ArrayDataSetIterator(x[split:], y[split:], batch_size=64,
                               shuffle=False)

    def build(params):
        model = SequentialModel(SequentialConfig(
            net=NeuralNetConfiguration(seed=0, updater=Adam(params["lr"])),
            input_shape=(d,),
            layers=[L.Dense(units=params["units"],
                            activation=params["act"]),
                    L.OutputLayer(units=classes)]))
        return model, {}

    def scorer(model, variables):
        val.reset()
        return evaluate_model(model, variables, val,
                              num_classes=classes).accuracy()

    tuner = Tuner(build, scorer, mode="max")
    space = {"lr": LogUniform(1e-4, 1e-1), "units": IntRange(8, 64),
             "act": Choice(["relu", "tanh"])}
    best = tuner.fit(RandomSearch(space, n_trials=4 if quick else 12, seed=1),
                     train, epochs=6 if quick else 15)
    print(tuner.summary())
    print(f"\nrandom-search best: acc={best.score:.3f} params={best.params}")

    # Focused grid around the random winner (↔ GridSearchCandidateGenerator)
    lr = best.params["lr"]
    refine = {"lr": LogUniform(lr / 3, lr * 3),
              "units": Choice([best.params["units"]]),
              "act": Choice([best.params["act"]])}
    best2 = tuner.fit(GridSearch(refine, points_per_axis=3), train,
                      epochs=6 if quick else 15)
    print(f"grid-refined best: acc={best2.score:.3f} params={best2.params}")
    return max(best.score, best2.score)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    score = main(ap.parse_args().quick)
    assert score > 0.7, score
