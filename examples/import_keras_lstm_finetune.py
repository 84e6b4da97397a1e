"""Import a frozen keras LSTM and FINE-TUNE it.

↔ the reference's import-then-train workflow (TFGraphMapper +
TransferLearning) for recurrent models. The keras While/TensorList loop
imports as a counter-bounded samediff while, which scan-lowers to
lax.scan — reverse-differentiable — so the imported weights can be
promoted to variables and trained. The whole fine-tune step (scan
included) compiles as ONE XLA program.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: F401,E402 - repo path + compile cache

import argparse

import numpy as np


def main(quick: bool = False):
    import tensorflow as tf
    from tensorflow import keras

    from deeplearning4j_tpu.autodiff.samediff import TrainingConfig
    from deeplearning4j_tpu.modelimport.tf import (
        freeze_tf_function,
        import_tf_graph,
    )

    T, D, H, N = 8, 3, 6, 32
    m = keras.Sequential([
        keras.layers.Input((T, D)),
        keras.layers.LSTM(H),
        keras.layers.Dense(1),
    ])

    # a target the pretrained-at-random model does NOT fit: mean of the
    # last two steps' first feature
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(N * 4, T, D)).astype(np.float32)
    ys = xs[:, -2:, 0].mean(axis=1, keepdims=True).astype(np.float32)

    from tensorflow.python.framework.convert_to_constants import (
        convert_variables_to_constants_v2,
    )

    conc = tf.function(lambda x: m(x, training=False)).get_concrete_function(
        tf.TensorSpec((N, T, D), tf.float32))
    frozen = convert_variables_to_constants_v2(conc,
                                               lower_control_flow=False)
    gd = frozen.graph.as_graph_def()
    in_name = frozen.inputs[0].name.split(":")[0]
    out_name = frozen.outputs[0].name.split(":")[0]

    sd, in_map, out_map = import_tf_graph(gd, outputs=[out_name])
    pred = sd.get_variable(out_map[out_name])

    # promote the imported weights (float consts) to trainable variables
    from deeplearning4j_tpu.autodiff.samediff import VariableType

    weights = [n for n, v in sd._vars.items()
               if v.var_type == VariableType.CONSTANT
               and np.asarray(sd._values[n]).dtype == np.float32
               and np.asarray(sd._values[n]).size > 4]
    for n in weights:
        sd.convert_to_variable(n)
    print(f"trainable tensors after promotion: {len(weights)}")

    t = sd.placeholder("target", (None, 1), "float32")
    loss = sd.loss.mse(pred, t)

    feeds = {in_map[in_name]: xs[:N], "target": ys[:N]}
    before = float(sd.output(feeds, [loss.name])[loss.name])

    cfg = TrainingConfig(loss_variable=loss.name,
                         feature_placeholders=[in_map[in_name]],
                         label_placeholders=["target"], updater="adam",
                         updater_args={"learning_rate": 1e-2})
    data = [{in_map[in_name]: xs[i:i + N], "target": ys[i:i + N]}
            for i in range(0, len(xs), N)]
    sd.fit(data, cfg, epochs=12 if quick else 60)

    after = float(sd.output(feeds, [loss.name])[loss.name])
    print(f"mse before fine-tune: {before:.4f}  after: {after:.4f}")
    assert after < before * 0.7, "fine-tuning should reduce the loss"
    return after


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    main(ap.parse_args().quick)
