"""YOLOv2 family tests.

ref strategy: TestYolo2OutputLayer (loss computes, gradients flow, decode
round-trips) + YoloUtils tests. NMS is oracle-tested against a numpy
brute-force greedy implementation; decode is checked by planting one
synthetic box and recovering it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.zoo.yolo import (
    TINY_YOLO_ANCHORS,
    Yolo2OutputLayer,
    decode_predictions,
    make_yolo_labels,
    non_max_suppression,
    tiny_yolo,
    yolo2,
)
from deeplearning4j_tpu.train.trainer import Trainer
from deeplearning4j_tpu.train.updaters import Adam

C = 4  # classes in tests


def _grid_labels(n=2, gh=2, gw=2, seed=0):
    r = np.random.default_rng(seed)
    objects = []
    for _ in range(n):
        k = r.integers(1, 3)
        objs = [(float(r.uniform(0.1, 0.9)), float(r.uniform(0.1, 0.9)),
                 float(r.uniform(0.1, 0.4)), float(r.uniform(0.1, 0.4)),
                 int(r.integers(0, C))) for _ in range(k)]
        objects.append(objs)
    return make_yolo_labels(objects, grid=(gh, gw), num_classes=C)


class TestYolo2OutputLayer:
    def _layer(self):
        return Yolo2OutputLayer(anchors=TINY_YOLO_ANCHORS, num_classes=C)

    def test_shapes_and_loss_finite(self):
        layer = self._layer()
        b = len(TINY_YOLO_ANCHORS)
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(2, 2, 2, b * (5 + C))).astype(np.float32))
        labels = jnp.asarray(_grid_labels())
        out, _ = layer.apply({}, {}, x)
        assert out.shape == (2, 2, 2, b, 5 + C)
        loss = layer.compute_loss({}, {}, x, labels)
        assert np.isfinite(float(loss)) and float(loss) > 0

    def test_gradients_flow_and_loss_minimizable(self):
        layer = self._layer()
        b = len(TINY_YOLO_ANCHORS)
        r = np.random.default_rng(1)
        x0 = jnp.asarray(r.normal(size=(2, 2, 2, b * (5 + C))).astype(np.float32) * 0.1)
        labels = jnp.asarray(_grid_labels(seed=1))

        loss_fn = jax.jit(lambda x: layer.compute_loss({}, {}, x, labels))
        g = jax.grad(loss_fn)(x0)
        assert np.isfinite(np.asarray(g)).all()
        assert np.abs(np.asarray(g)).max() > 0
        # gradient descent directly on the feature map drives the loss down
        x = x0
        for _ in range(200):
            x = x - 0.05 * jax.grad(loss_fn)(x)
        assert float(loss_fn(x)) < 0.3 * float(loss_fn(x0))

    def test_empty_grid_only_noobj_term(self):
        layer = self._layer()
        b = len(TINY_YOLO_ANCHORS)
        x = jnp.zeros((1, 2, 2, b * (5 + C)), jnp.float32)
        labels = jnp.zeros((1, 2, 2, 5 + C), jnp.float32)
        # sigmoid(0)=0.5 → noobj loss = 0.5 * sum(0.25) over cells*anchors
        want = 0.5 * 0.25 * (2 * 2 * b)
        assert float(layer.compute_loss({}, {}, x, labels)) == pytest.approx(
            want, rel=1e-5)


class TestDecodeNMS:
    def test_decode_recovers_planted_box(self):
        b = len(TINY_YOLO_ANCHORS)
        gh = gw = 2
        feat = np.full((1, gh, gw, b, 5 + C), -8.0, np.float32)  # conf ~ 0
        # plant one confident box: cell (1,0), anchor 2, class 3
        anchor = 2
        feat[0, 1, 0, anchor, 0] = 0.0      # sigmoid -> x = 0.5 in cell
        feat[0, 1, 0, anchor, 1] = 0.0
        feat[0, 1, 0, anchor, 2:4] = 0.0    # wh = anchor prior
        feat[0, 1, 0, anchor, 4] = 8.0      # conf ~ 1
        feat[0, 1, 0, anchor, 5 + 3] = 8.0  # class 3
        layer = Yolo2OutputLayer(anchors=TINY_YOLO_ANCHORS, num_classes=C)
        decoded, _ = layer.apply({}, {}, jnp.asarray(
            feat.reshape(1, gh, gw, b * (5 + C))))
        boxes, scores, classes = decode_predictions(decoded, top_k=3)
        assert float(scores[0, 0]) > 0.9
        assert int(classes[0, 0]) == 3
        x1, y1, x2, y2 = np.asarray(boxes[0, 0])
        aw, ah = TINY_YOLO_ANCHORS[anchor]
        np.testing.assert_allclose((x1 + x2) / 2, 0.25, atol=1e-5)  # col 0
        np.testing.assert_allclose((y1 + y2) / 2, 0.75, atol=1e-5)  # row 1
        np.testing.assert_allclose(x2 - x1, aw / gw, rtol=1e-5)
        np.testing.assert_allclose(y2 - y1, ah / gh, rtol=1e-5)

    def test_nms_against_numpy_bruteforce(self):
        r = np.random.default_rng(3)
        k = 12
        centers = r.uniform(0.2, 0.8, (k, 2))
        sizes = r.uniform(0.1, 0.3, (k, 2))
        boxes = np.concatenate([centers - sizes / 2, centers + sizes / 2], -1)
        scores = r.uniform(0.1, 1.0, k).astype(np.float32)

        def np_nms(bx, sc, thr):
            order = np.argsort(-sc)
            keep = np.zeros(k)
            kept = []
            for i in order:
                ok = True
                for j in kept:
                    xx1 = max(bx[i, 0], bx[j, 0])
                    yy1 = max(bx[i, 1], bx[j, 1])
                    xx2 = min(bx[i, 2], bx[j, 2])
                    yy2 = min(bx[i, 3], bx[j, 3])
                    inter = max(0, xx2 - xx1) * max(0, yy2 - yy1)
                    a_i = (bx[i, 2] - bx[i, 0]) * (bx[i, 3] - bx[i, 1])
                    a_j = (bx[j, 2] - bx[j, 0]) * (bx[j, 3] - bx[j, 1])
                    if inter / (a_i + a_j - inter + 1e-9) > thr:
                        ok = False
                        break
                if ok:
                    keep[i] = 1
                    kept.append(i)
            return keep

        got = np.asarray(non_max_suppression(
            jnp.asarray(boxes[None].astype(np.float32)),
            jnp.asarray(scores[None]), iou_threshold=0.45))[0]
        want = np_nms(boxes, scores, 0.45)
        np.testing.assert_array_equal(got, want)


class TestYoloZooModels:
    def test_tiny_yolo_shapes(self):
        model = tiny_yolo(num_classes=C, input_shape=(64, 64, 3))
        assert model.shapes[-1] == (2, 2, len(TINY_YOLO_ANCHORS), 5 + C)
        variables = model.init(seed=0)
        x = np.random.default_rng(0).normal(size=(1, 64, 64, 3)).astype(np.float32)
        out, _ = model.apply(variables, jnp.asarray(x))
        assert out.shape == (1, 2, 2, len(TINY_YOLO_ANCHORS), 5 + C)

    def test_yolo2_passthrough_shapes(self):
        from deeplearning4j_tpu.models.zoo.yolo import YOLO2_ANCHORS

        model = yolo2(num_classes=C, input_shape=(64, 64, 3))
        # reorg(26x26-equivalent stage) concat head: channels 2048 + 1024
        assert model.shapes["route"][-1] == 512 * 4 + 1024
        assert model.shapes["yolo"] == (2, 2, len(YOLO2_ANCHORS), 5 + C)

    # Tier-1 budget relief (the PR 6/7 pattern, paying for the PR 17
    # replay/game-day suite): the 40-step 64x64 overfit is the single
    # slowest test in tier-1 (~74 s); the detection path stays wired
    # every tier-1 run via test_tiny_yolo_shapes (full forward) and
    # TestYoloLoss::test_gradients_flow_and_loss_minimizable (the same
    # loss decreasing under real gradient steps at grid scale).
    @pytest.mark.slow
    def test_tiny_yolo_overfits_tiny_batch(self):
        model = tiny_yolo(num_classes=C, input_shape=(64, 64, 3),
                          updater=Adam(1e-3))
        r = np.random.default_rng(0)
        x = r.normal(size=(4, 64, 64, 3)).astype(np.float32)
        labels = _grid_labels(n=4, gh=2, gw=2, seed=5)
        trainer = Trainer(model)
        ts = trainer.init_state(seed=0)
        batch = {"features": x, "labels": labels}
        first = None
        for _ in range(40):
            ts, m = trainer.train_step(ts, batch)
            if first is None:
                first = float(jax.device_get(m["total_loss"]))
        last = float(jax.device_get(m["total_loss"]))
        assert np.isfinite(last)
        assert last < first * 0.5, (first, last)
