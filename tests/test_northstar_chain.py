"""North-star #4 chain, end to end in ONE test path:

    TF checkpoint (frozen BERT-mini MLM graph, built and executed by REAL
    TensorFlow) → import into SameDiff → oracle parity → promote weights →
    full MLM TRAIN steps on the imported graph (loss drops) → StableHLO
    export of the tuned graph → run the exported program → parity with the
    in-graph execution → (gated) native PJRT runtime execute of the same
    MLIR.

ref: SURVEY §3.2 (the reference's BERT path: TF frozen graph → SameDiff
import → fit) and §7.4.1. Every seam is oracle-checked: TF itself at
import, the SameDiff execution after training, and jax/native execution of
the exported artifact.
"""

import os

import numpy as np
import pytest

os.environ.setdefault("CUDA_VISIBLE_DEVICES", "-1")

tf = pytest.importorskip("tensorflow")

from deeplearning4j_tpu.autodiff.samediff import TrainingConfig  # noqa: E402
from deeplearning4j_tpu.modelimport import import_tf_graph  # noqa: E402
from deeplearning4j_tpu.modelimport.tf import freeze_tf_function  # noqa: E402

N, T, H, I, V = 4, 8, 16, 32, 50  # batch, seq, hidden, ffn, vocab


def _build_tf_bert_mini(seed=0):
    """BERT-mini MLM graph from raw TF ops (embeddings + 1 transformer
    block + tied-decoder MLM head + masked CE loss), weights as constants —
    the shape a frozen checkpoint import sees."""
    rs = np.random.RandomState(seed)

    def w(*shape, s=0.1):
        return tf.constant(rs.randn(*shape).astype(np.float32) * s)

    word = w(V, H)
    pos = w(T, H)
    g = [tf.constant(np.ones(H, np.float32)) for _ in range(3)]
    b = [tf.constant(np.zeros(H, np.float32)) for _ in range(3)]
    wq, wk, wv, wo = w(H, H), w(H, H), w(H, H), w(H, H)
    w1, w2 = w(H, I), w(I, H)

    def ln(x, gi, bi):
        m = tf.reduce_mean(x, axis=-1, keepdims=True)
        v_ = tf.reduce_mean(tf.math.squared_difference(x, m), axis=-1,
                            keepdims=True)
        return (x - m) * tf.math.rsqrt(v_ + 1e-6) * gi + bi

    def proj(x, wm):  # [N,T,H] @ [H,O] via 2D matmul
        out_dim = wm.shape[-1]
        return tf.reshape(tf.matmul(tf.reshape(x, [-1, wm.shape[0]]), wm),
                          [N, T, out_dim])

    def encode(ids):
        x = tf.gather(word, ids) + tf.gather(pos, tf.range(T))
        x = ln(x, g[0], b[0])
        q, k, v_ = proj(x, wq), proj(x, wk), proj(x, wv)
        scores = tf.matmul(q, tf.transpose(k, [0, 2, 1])) / float(np.sqrt(H))
        x = ln(x + proj(tf.matmul(tf.nn.softmax(scores), v_), wo), g[1], b[1])
        x = ln(x + proj(tf.nn.relu(proj(x, w1)), w2), g[2], b[2])
        return x

    def logits_fn(ids):
        return tf.matmul(tf.reshape(encode(ids), [-1, H]), word,
                         transpose_b=True)  # [N*T, V] tied decoder

    def loss_fn(ids, labels_oh, mask):
        logp = tf.nn.log_softmax(logits_fn(ids))
        ce = -tf.reduce_sum(tf.reshape(labels_oh, [-1, V]) * logp, axis=-1)
        m = tf.reshape(mask, [-1])
        return tf.reduce_sum(ce * m) / tf.reduce_sum(m)

    return logits_fn, loss_fn


def _mlm_batch(seed=1):
    r = np.random.default_rng(seed)
    ids = r.integers(0, V, (N, T)).astype(np.int32)
    labels = np.eye(V, dtype=np.float32)[ids]
    mask = (r.random((N, T)) < 0.3).astype(np.float32)
    mask[0, 0] = 1.0  # never empty
    return ids, labels, mask


@pytest.fixture(scope="module")
def chain():
    """Run the whole chain once; individual tests assert each seam."""
    logits_fn, loss_fn = _build_tf_bert_mini()
    ids, labels, mask = _mlm_batch()

    # --- seam 1: freeze + import, TF is the oracle -----------------------
    gd, in_names, out_names = freeze_tf_function(
        loss_fn, tf.constant(ids), tf.constant(labels), tf.constant(mask))
    sd, in_map, out_map = import_tf_graph(
        gd,
        inputs={in_names[0]: (N, T), in_names[1]: (N, T, V),
                in_names[2]: (N, T)},
        outputs=out_names)
    feeds = {in_map[in_names[0]]: ids, in_map[in_names[1]]: labels,
             in_map[in_names[2]]: mask}
    loss_name = out_map[out_names[0]]
    tf_loss = float(loss_fn(tf.constant(ids), tf.constant(labels),
                            tf.constant(mask)).numpy())
    imported_loss = float(sd.output(feeds, [loss_name])[loss_name])

    # --- seam 2: promote weights, train on the imported graph ------------
    promoted = []
    for name, var in list(sd._vars.items()):
        val = sd._values.get(name)
        if var.var_type.value == "CONSTANT" and val is not None \
                and np.asarray(val).ndim >= 1 and np.asarray(val).size > H:
            sd.convert_to_variable(name)
            promoted.append(name)

    cfg = TrainingConfig(
        loss_variable=loss_name,
        feature_placeholders=[in_map[in_names[0]]],
        label_placeholders=[in_map[in_names[1]], in_map[in_names[2]]],
        updater="adam", updater_args={"lr": 3e-3})
    data = [{in_map[in_names[0]]: ids, in_map[in_names[1]]: labels,
             in_map[in_names[2]]: mask}]
    history = []
    for _ in range(50):
        sd.fit(data, cfg)
        history.append(float(sd.output(feeds, [loss_name])[loss_name]))

    # --- seam 3: export the TUNED graph, run it both ways ----------------
    tuned_loss = history[-1]
    specs = {in_map[in_names[0]]: ((N, T), "int32"),
             in_map[in_names[1]]: ((N, T, V), "float32"),
             in_map[in_names[2]]: ((N, T), "float32")}
    blob = sd.export_stablehlo([loss_name], specs)
    exported_out = sd.run_stablehlo(blob, feeds)[loss_name]
    mlir, arg_order = sd.export_stablehlo_text([loss_name], specs)

    return dict(tf_loss=tf_loss, imported_loss=imported_loss,
                promoted=promoted, history=history, tuned_loss=tuned_loss,
                exported_loss=float(exported_out), mlir=mlir,
                arg_order=arg_order, feeds=feeds)


class TestNorthStarChain:
    def test_import_matches_tf_oracle(self, chain):
        assert chain["imported_loss"] == pytest.approx(chain["tf_loss"],
                                                       rel=1e-4)

    def test_imported_graph_trains(self, chain):
        assert chain["promoted"], "no weight constants were promoted"
        h = chain["history"]
        assert h[-1] < chain["imported_loss"] * 0.5, h
        assert all(np.isfinite(x) for x in h)

    def test_exported_program_matches_tuned_graph(self, chain):
        assert chain["exported_loss"] == pytest.approx(chain["tuned_loss"],
                                                       rel=1e-5)

    def test_stablehlo_text_is_mlir(self, chain):
        assert "stablehlo" in chain["mlir"] or "mhlo" in chain["mlir"]
        assert len(chain["arg_order"]) == 3

    def test_native_runtime_executes_exported_mlir(self, chain):
        """Final seam: the exported MLIR runs on the PJRT native runtime.
        Opt-in like all live-plugin tests (the client claims the chip)."""
        if os.environ.get("DL4J_TPU_NATIVE_TESTS") != "1":
            pytest.skip("live-plugin execute is opt-in (DL4J_TPU_NATIVE_TESTS=1)")
        from deeplearning4j_tpu.runtime import native as nat

        if nat.default_plugin_path() is None:
            pytest.skip("no PJRT plugin on this machine")
        rt = nat.NativeRuntime()
        try:
            exe = rt.compile(chain["mlir"])
            args = [np.asarray(chain["feeds"][k]) for k in chain["arg_order"]]
            outs = exe.execute(args)
            assert float(outs[0]) == pytest.approx(chain["tuned_loss"],
                                                   rel=1e-2)
        finally:
            rt.close()
