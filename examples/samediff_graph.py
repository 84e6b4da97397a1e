"""SameDiff-analogue graph building, autodiff, training, serde, StableHLO.

↔ the reference's SameDiff quickstart: placeholders + variables, op
namespaces, gradients, fit, save/load — but the graph compiles WHOLE
(one XLA program), not per-op through an interpreter.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: F401,E402 - repo path + compile cache

import argparse
import tempfile

import numpy as np

from deeplearning4j_tpu.autodiff.samediff import SameDiff, TrainingConfig


def main(quick: bool = False):
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(256, 4)).astype(np.float32)
    true_w = rng.normal(size=(4, 1)).astype(np.float32)
    ys = xs @ true_w + 0.05 * rng.normal(size=(256, 1)).astype(np.float32)

    sd = SameDiff.create()
    x = sd.placeholder("x", (None, 4), "float32")
    t = sd.placeholder("t", (None, 1), "float32")
    w = sd.var("w", np.zeros((4, 1), np.float32))
    b = sd.var("b", np.zeros((1,), np.float32))
    pred = x.mmul(w) + b
    loss = sd.loss.mse(pred, t)

    grads = sd.calculate_gradients({"x": xs, "t": ys}, loss.name)
    print("analytic grad shapes:", {k: v.shape for k, v in grads.items()})

    cfg = TrainingConfig(loss_variable=loss.name, feature_placeholders=["x"],
                         label_placeholders=["t"], updater="adam",
                         updater_args={"learning_rate": 0.05})
    data = [{"x": xs[i:i + 64], "t": ys[i:i + 64]} for i in range(0, 256, 64)]
    sd.fit(data, cfg, epochs=40 if quick else 150)
    err = float(np.max(np.abs(sd.get_value("w") - true_w)))
    print(f"max |w - w_true| after fit: {err:.4f}")

    with tempfile.TemporaryDirectory() as d:
        path = f"{d}/model.sdz"
        sd.save(path)
        sd2 = SameDiff.load(path)
        out = sd2.output({"x": xs[:4]}, [pred.name])[pred.name]
        print("restored-graph pred shape:", out.shape)

        hlo = sd.export_stablehlo([pred.name],
                                  {"x": ((4, 4), "float32")})
        print("stablehlo module bytes:", len(hlo))
    return err


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    err = main(ap.parse_args().quick)
    assert err < 0.15, err
