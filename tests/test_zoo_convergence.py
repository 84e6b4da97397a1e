"""Zoo convergence sanity: every zoo entry must overfit 10 samples
(SURVEY §4 pattern 5 — a model that cannot memorize a tiny batch is
broken regardless of its shapes).

Models run at reduced input resolution (the configs are parametric) so the
whole suite stays CPU-feasible; architecture — blocks, skips, BN, pooling,
loss heads — is exercised unchanged.
"""

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.train.trainer import Trainer
from deeplearning4j_tpu.train.updaters import Adam

N = 10  # samples to memorize


def _image_batch(shape, classes, seed=0):
    r = np.random.default_rng(seed)
    x = r.normal(size=(N,) + shape).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[np.arange(N) % classes]
    return {"features": x, "labels": y}


def _overfit(model, batch, *, steps=60, min_drop=0.5, lr=None):
    if lr is not None:
        model.net.updater = Adam(lr)
    trainer = Trainer(model)
    ts = trainer.init_state(seed=0)
    first = None
    loss = None
    for _ in range(steps):
        ts, m = trainer.train_step(ts, batch)
        if first is None:
            first = float(jax.device_get(m["total_loss"]))
    loss = float(jax.device_get(m["total_loss"]))
    assert np.isfinite(loss), f"loss diverged: {loss}"
    assert loss < first * min_drop, (
        f"failed to overfit {N} samples: {first:.4f} -> {loss:.4f}")
    return first, loss


class TestSequentialZoo:
    def test_lenet(self):
        from deeplearning4j_tpu.models.lenet import lenet

        _overfit(lenet(updater=Adam(1e-3)),
                 _image_batch((28, 28, 1), 10))

    # Tier-1 budget relief (the PR 6/7 pattern, paying for the PR 17
    # replay/game-day suite): the 96x96 40-step alexnet overfit is
    # ~40 s of plain stacked-conv training; the architecture stays
    # wired in tier-1 via the forward-shape row (test_zoo.py::
    # test_sequential_zoo_forward_shapes[alexnet...]) and the
    # identical conv/pool overfit path runs every tier-1 in simplecnn.
    @pytest.mark.slow
    def test_alexnet(self):
        from deeplearning4j_tpu.models.zoo import alexnet

        _overfit(alexnet(num_classes=10, input_shape=(96, 96, 3),
                         updater=Adam(1e-4)),
                 _image_batch((96, 96, 3), 10), steps=40)

    # Tier-1 budget relief (the PR 6/7 pattern, paying for the PR 14
    # warm-start suite): vgg16 is the slowest remaining sequential
    # convergence run (~19 s of plain stacked-conv overfitting); its
    # architecture stays wired in tier-1 via the forward-shape row
    # (test_zoo.py::test_sequential_zoo_forward_shapes[vgg16...]) and
    # the identical conv/pool overfit path runs in simplecnn.
    @pytest.mark.slow
    def test_vgg16(self):
        from deeplearning4j_tpu.models.zoo import vgg16

        _overfit(vgg16(num_classes=10, input_shape=(64, 64, 3),
                       updater=Adam(1e-4)),
                 _image_batch((64, 64, 3), 10), steps=40)

    def test_simplecnn(self):
        from deeplearning4j_tpu.models.zoo import simplecnn

        _overfit(simplecnn(num_classes=10, updater=Adam(1e-3)),
                 _image_batch((48, 48, 3), 10), steps=40)

    # Tier-1 budget relief (ROADMAP item 5): darknet19 is the slowest
    # sequential-zoo convergence run (~31 s); its architecture stays
    # covered in tier-1 by the forward-shape test (test_zoo.py) and the
    # remaining sequential convergence runs (alexnet/vgg16/simplecnn)
    # exercise the same conv/BN/pool overfit path.
    @pytest.mark.slow
    def test_darknet19(self):
        from deeplearning4j_tpu.models.zoo import darknet19

        _overfit(darknet19(num_classes=10, input_shape=(64, 64, 3),
                           updater=Adam(1e-3)),
                 _image_batch((64, 64, 3), 10), steps=40)

    # Tier-1 budget relief (the PR 6/7 pattern, paying for the PR 20
    # autoscaler suite): ~3 s of 80-step char-LSTM overfitting; the
    # model stays wired in tier-1 via test_zoo.py::
    # test_text_generation_lstm_shapes and the LSTM cell/scan legs in
    # test_layers.py.
    @pytest.mark.slow
    def test_text_generation_lstm(self):
        from deeplearning4j_tpu.models.zoo.classic import text_generation_lstm

        vocab, t = 20, 16
        model = text_generation_lstm(vocab_size=vocab, hidden=32, seq_len=t,
                                     updater=Adam(1e-2))
        r = np.random.default_rng(0)
        ids = r.integers(0, vocab, (N, t + 1))
        eye = np.eye(vocab, dtype=np.float32)
        batch = {"features": eye[ids[:, :-1]], "labels": eye[ids[:, 1:]]}
        _overfit(model, batch, steps=80)


class TestGraphZoo:
    # Tier-1 budget relief (the PR 6/7 pattern, paying for the PR 14
    # warm-start suite): the 64x64 50-step resnet50 overfit is the
    # slowest test left in tier-1 (~35 s). The architecture stays
    # covered every tier-1 run by the forward-shape row (test_zoo.py::
    # test_graph_zoo_forward_shapes[resnet50...]) AND a real training
    # proxy (test_zoo.py::test_resnet50_trains_tiny — 3 steps at 16x16
    # prove the residual graph trains end-to-end); the skip-connection
    # overfit discipline continues via inception_resnet_v1.
    @pytest.mark.slow
    def test_resnet50(self):
        from deeplearning4j_tpu.models.zoo import resnet50

        _overfit(resnet50(num_classes=10, input_shape=(64, 64, 3),
                          updater=Adam(1e-3)),
                 _image_batch((64, 64, 3), 10), steps=50)

    # Tier-1 budget relief (the PR 6/7 pattern, paying for the PR 17
    # replay/game-day suite): ~22 s of 96x96 fire-module overfitting;
    # the graph stays wired in tier-1 via the forward-shape row
    # (test_zoo.py::test_graph_zoo_forward_shapes[squeezenet...]) and
    # the graph-zoo overfit discipline continues every tier-1 run via
    # inception_resnet_v1.
    @pytest.mark.slow
    def test_squeezenet(self):
        from deeplearning4j_tpu.models.zoo import squeezenet

        _overfit(squeezenet(num_classes=10, input_shape=(96, 96, 3),
                            updater=Adam(1e-3)),
                 _image_batch((96, 96, 3), 10), steps=60)

    # Tier-1 budget relief (ROADMAP item 5): xception is the single
    # slowest test in the whole suite (~74 s — separable convs at
    # 96x96); tier-1 keeps its graph wired via the forward-shape test
    # (test_zoo.py::test_graph_zoo_forward_shapes[xception...]) and the
    # same overfit discipline via the remaining graph-zoo runs.
    @pytest.mark.slow
    def test_xception(self):
        from deeplearning4j_tpu.models.zoo import xception

        _overfit(xception(num_classes=10, input_shape=(96, 96, 3),
                          updater=Adam(1e-3)),
                 _image_batch((96, 96, 3), 10), steps=40)

    # Tier-1 budget relief (the PR 6/7 pattern, paying for the PR 20
    # autoscaler suite): ~13 s of 64x64 residual-inception overfitting
    # was the slowest convergence leg left in tier-1. The graph stays
    # wired every tier-1 run via the inception_resnet_v1 forward-shape
    # row in test_zoo.py; the graph-zoo overfit discipline now rides
    # the slow tier wholesale (with resnet50/squeezenet/xception/
    # nasnet/unet).
    @pytest.mark.slow
    def test_inception_resnet_v1(self):
        from deeplearning4j_tpu.models.zoo import inception_resnet_v1

        _overfit(inception_resnet_v1(num_classes=10, width=8, blocks_a=1,
                                     blocks_b=1, input_shape=(64, 64, 3),
                                     dropout=0.0, updater=Adam(1e-3)),
                 _image_batch((64, 64, 3), 10), steps=60)

    # Tier-1 budget relief (ROADMAP item 5): ~29 s convergence run;
    # the graph stays wired in tier-1 via the nasnet forward-shape row
    # in test_zoo.py, and the remaining graph-zoo runs keep the overfit
    # discipline covered.
    @pytest.mark.slow
    def test_nasnet(self):
        from deeplearning4j_tpu.models.zoo import nasnet

        _overfit(nasnet(num_classes=10, input_shape=(64, 64, 3),
                        penultimate_filters=48, cells_per_stack=1,
                        dropout=0.0, updater=Adam(1e-3)),
                 _image_batch((64, 64, 3), 10), steps=60)

    # Tier-1 budget relief (the PR 6/7 pattern, paying for the PR 17
    # replay/game-day suite): the 60-step segmentation overfit is
    # ~60 s — the 2nd-slowest test left in tier-1; the encoder/decoder
    # graph stays wired via the forward-shape row (test_zoo.py::
    # test_graph_zoo_forward_shapes[unet...]) and the skip-connection
    # overfit discipline continues every tier-1 via
    # inception_resnet_v1.
    @pytest.mark.slow
    def test_unet(self):
        from deeplearning4j_tpu.models.zoo import unet

        model = unet(num_classes=1, input_shape=(32, 32, 3),
                     updater=Adam(1e-3))
        r = np.random.default_rng(0)
        x = r.normal(size=(N, 32, 32, 3)).astype(np.float32)
        # learnable target: mask = thresholded mean channel
        y = (x.mean(-1, keepdims=True) > 0).astype(np.float32)
        _overfit(model, {"features": x, "labels": y}, steps=60, min_drop=0.7)


class TestBert:
    # Tier-1 budget relief (the PR 6/7 pattern, paying for the PR 20
    # autoscaler suite): ~10 s of 60-step MLM overfitting; BERT
    # training stays proven every tier-1 run by test_attention_bert.py
    # ::test_bert_tiny_trains and ::test_bert_gathered_mlm_trains
    # (loss-decrease legs on the same tiny config).
    @pytest.mark.slow
    def test_bert_tiny_mlm(self):
        from deeplearning4j_tpu.models.bert import bert_tiny, make_mlm_batch
        from deeplearning4j_tpu.nn.config import NeuralNetConfiguration

        model = bert_tiny(net=NeuralNetConfiguration(updater=Adam(1e-3)))
        batch = make_mlm_batch(0, batch_size=N, seq_len=32,
                               vocab_size=model.config.vocab_size)
        batch = jax.device_put(batch)
        _overfit(model, batch, steps=60, min_drop=0.6)
