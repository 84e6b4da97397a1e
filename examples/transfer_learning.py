"""Transfer learning: freeze a trained backbone, retrain a new head.

↔ dl4j-examples TransferLearning (EditLastLayerOthersFrozen): train a
LeNet on 10 classes, surgically replace the output layer for 5 classes,
freeze everything else, fine-tune. Frozen params stay bit-identical
(Trainer masks their gradients AND updater state).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: F401,E402 - repo path + compile cache

import argparse

import numpy as np

from deeplearning4j_tpu.data import ArrayDataSetIterator, load_mnist
from deeplearning4j_tpu.models.lenet import lenet
from deeplearning4j_tpu.train.trainer import Trainer
from deeplearning4j_tpu.train.transfer import FineTuneConfiguration, TransferLearning
from deeplearning4j_tpu.train.updaters import Adam


def main(quick: bool = False):
    n = 2048 if quick else 4096
    (xtr, ytr), _, _ = load_mnist(n_train=n, n_test=64)
    base = lenet(updater=Adam(3e-3))
    tr = Trainer(base)
    ts = tr.init_state()
    ts = tr.fit(ts, ArrayDataSetIterator(xtr, ytr, batch_size=256),
                epochs=4 if quick else 6)
    print("backbone trained")

    # keep only digits 0-4, new 5-way head
    mask5 = ytr[:, :5].sum(1) > 0
    x5, y5 = xtr[mask5], ytr[mask5][:, :5]

    surgery = (TransferLearning(base, tr.variables(ts))
               .fine_tune_configuration(FineTuneConfiguration(updater=Adam(1e-3)))
               .set_feature_extractor("2_conv2d")     # freeze up to+incl layer 2
               .n_out_replace(-1, 5))                 # new 5-class output
    new_model, new_vars, frozen = surgery.build()
    print(f"frozen layers: {frozen}")

    ft = Trainer(new_model, frozen_layers=frozen)
    fts = ft.init_state(variables=new_vars)
    before = {k: np.asarray(v["W"]).copy()
              for k, v in new_vars["params"].items() if "conv" in k and "W" in v}
    fts = ft.fit(fts, ArrayDataSetIterator(x5, y5, batch_size=128),
                 epochs=3 if quick else 4)
    after = ft.variables(fts)["params"]
    for k, w in before.items():
        np.testing.assert_array_equal(w, np.asarray(after[k]["W"]))
    print("frozen weights bit-identical after fine-tune ✓")
    from deeplearning4j_tpu.evaluation import evaluate_model
    ev = evaluate_model(new_model, ft.variables(fts),
                        ArrayDataSetIterator(x5, y5, batch_size=256,
                                             shuffle=False), num_classes=5)
    print(f"fine-tuned accuracy on 5-class subset: {ev.accuracy():.3f}")
    return ev.accuracy()


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    acc = main(ap.parse_args().quick)
    assert acc > 0.5, acc
