"""ROC/AUC/calibration oracle tests.

ref strategy: nd4j ROCTest / EvaluationCalibrationTest — curves checked
against independently computed values. The oracle here recomputes every
operating point by brute force on the raw scores (predict positive iff
score >= k/B), which is exactly the thresholded-ROC definition the
device-side histograms implement, plus closed-form sanity cases
(perfect separation = 1.0, symmetric overlap ≈ 0.5).
"""

import numpy as np
import pytest

from deeplearning4j_tpu.evaluation import (
    ROC,
    EvaluationCalibration,
    ROCBinary,
    ROCMultiClass,
)

B = 200  # threshold steps used throughout


def _brute_roc(labels, scores, bins=B):
    """Oracle: TPR/FPR at thresholds k/bins, k=0..bins, by direct counting."""
    labels = np.asarray(labels, bool)
    scores = np.asarray(scores, np.float64)
    thr = np.arange(bins + 1) / bins
    tpr = np.array([(scores[labels] >= t).sum() for t in thr]) / max(labels.sum(), 1)
    fpr = np.array([(scores[~labels] >= t).sum() for t in thr]) / max((~labels).sum(), 1)
    return thr, fpr, tpr


def _scores(n, seed, sep=1.5):
    """Two overlapping score distributions in (0, 1)."""
    r = np.random.default_rng(seed)
    labels = r.integers(0, 2, n)
    raw = r.normal(loc=labels * sep, scale=1.0)
    scores = 1.0 / (1.0 + np.exp(-raw))
    # keep scores strictly inside bins (no threshold-boundary ties)
    scores = np.clip(np.round(scores * B - 0.5) / B + 0.5 / B, 0.0, 1.0 - 0.5 / B)
    return labels.astype(np.float32), scores.astype(np.float32)


class TestROC:
    def test_curve_matches_bruteforce(self):
        labels, scores = _scores(500, seed=0)
        roc = ROC(threshold_steps=B).eval(labels, scores)
        thr, fpr, tpr = roc.roc_curve()
        othr, ofpr, otpr = _brute_roc(labels, scores)
        np.testing.assert_allclose(thr, othr)
        np.testing.assert_allclose(fpr, ofpr, atol=1e-9)
        np.testing.assert_allclose(tpr, otpr, atol=1e-9)

    def test_auc_matches_bruteforce_trapezoid(self):
        labels, scores = _scores(500, seed=1)
        roc = ROC(threshold_steps=B).eval(labels, scores)
        _, ofpr, otpr = _brute_roc(labels, scores)
        oracle = -np.trapezoid(otpr, ofpr)
        assert roc.auc() == pytest.approx(oracle, abs=1e-9)
        # a separated mixture must score clearly above chance
        assert 0.75 < roc.auc() < 1.0

    def test_perfect_separation_auc_one(self):
        labels = np.array([0, 0, 0, 1, 1, 1], np.float32)
        scores = np.array([0.05, 0.1, 0.2, 0.8, 0.9, 0.95], np.float32)
        roc = ROC(threshold_steps=B).eval(labels, scores)
        assert roc.auc() == pytest.approx(1.0, abs=1e-6)
        assert roc.auc_pr() == pytest.approx(1.0, abs=1e-6)

    def test_random_scores_auc_half(self):
        r = np.random.default_rng(2)
        labels = r.integers(0, 2, 4000).astype(np.float32)
        scores = r.uniform(0, 1, 4000).astype(np.float32)
        roc = ROC(threshold_steps=B).eval(labels, scores)
        assert roc.auc() == pytest.approx(0.5, abs=0.05)

    def test_one_hot_two_column_input(self):
        labels, scores = _scores(200, seed=3)
        oh = np.stack([1 - labels, labels], axis=1)
        probs2 = np.stack([1 - scores, scores], axis=1)
        a = ROC(threshold_steps=B).eval(labels, scores).auc()
        b = ROC(threshold_steps=B).eval(oh, probs2).auc()
        assert a == pytest.approx(b, abs=1e-9)

    def test_incremental_equals_single_batch(self):
        labels, scores = _scores(300, seed=4)
        whole = ROC(threshold_steps=B).eval(labels, scores)
        parts = ROC(threshold_steps=B)
        for i in range(0, 300, 100):
            parts.eval(labels[i:i + 100], scores[i:i + 100])
        np.testing.assert_allclose(np.asarray(whole.pos), np.asarray(parts.pos))
        assert whole.auc() == pytest.approx(parts.auc(), abs=1e-12)

    def test_merge(self):
        labels, scores = _scores(300, seed=5)
        whole = ROC(threshold_steps=B).eval(labels, scores)
        a = ROC(threshold_steps=B).eval(labels[:150], scores[:150])
        b = ROC(threshold_steps=B).eval(labels[150:], scores[150:])
        assert a.merge(b).auc() == pytest.approx(whole.auc(), abs=1e-12)

    def test_auc_pr_matches_bruteforce(self):
        labels, scores = _scores(400, seed=6)
        roc = ROC(threshold_steps=B).eval(labels, scores)
        thr = np.arange(B + 1) / B
        lab = labels.astype(bool)
        tp = np.array([(scores[lab] >= t).sum() for t in thr], float)
        fp = np.array([(scores[~lab] >= t).sum() for t in thr], float)
        pred = tp + fp
        prec = np.divide(tp, pred, out=np.ones_like(tp), where=pred > 0)
        rec = tp / lab.sum()
        oracle = -np.trapezoid(prec, rec)
        assert roc.auc_pr() == pytest.approx(oracle, abs=1e-9)


class TestROCMultiClass:
    def test_per_class_matches_binary(self):
        r = np.random.default_rng(7)
        n, c = 400, 3
        labels = r.integers(0, c, n)
        logits = r.normal(size=(n, c)) + 2.0 * np.eye(c)[labels]
        probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
        probs = np.clip(np.round(probs * B - 0.5) / B + 0.5 / B,
                        0.0, 1.0 - 0.5 / B)

        mc = ROCMultiClass(num_classes=c, threshold_steps=B).eval(labels, probs)
        for k in range(c):
            solo = ROC(threshold_steps=B).eval(
                (labels == k).astype(np.float32), probs[:, k].astype(np.float32))
            assert mc.auc(k) == pytest.approx(solo.auc(), abs=1e-9)
            assert mc.auc(k) > 0.7  # informative scores
        assert mc.average_auc() == pytest.approx(
            np.mean([mc.auc(k) for k in range(c)]), abs=1e-12)

    def test_int_and_onehot_labels_agree(self):
        r = np.random.default_rng(8)
        labels = r.integers(0, 3, 100)
        probs = r.dirichlet(np.ones(3), 100)
        a = ROCMultiClass(3, threshold_steps=B).eval(labels, probs)
        b = ROCMultiClass(3, threshold_steps=B).eval(np.eye(3)[labels], probs)
        for k in range(3):
            assert a.auc(k) == pytest.approx(b.auc(k), abs=1e-12)


class TestROCBinaryMultiLabel:
    def test_independent_columns(self):
        l0, s0 = _scores(300, seed=9)
        l1, s1 = _scores(300, seed=10, sep=0.3)
        rb = ROCBinary(num_outputs=2, threshold_steps=B).eval(
            np.stack([l0, l1], 1), np.stack([s0, s1], 1))
        solo0 = ROC(threshold_steps=B).eval(l0, s0)
        solo1 = ROC(threshold_steps=B).eval(l1, s1)
        assert rb.auc(0) == pytest.approx(solo0.auc(), abs=1e-9)
        assert rb.auc(1) == pytest.approx(solo1.auc(), abs=1e-9)
        assert rb.auc(0) > rb.auc(1)  # column 0 is better separated


class TestEvaluationCalibration:
    def test_reliability_perfectly_calibrated(self):
        """Scores drawn so P(label=1 | score=s) = s: observed frequency per
        bin must track the bin center."""
        r = np.random.default_rng(11)
        n = 200_000
        scores = r.uniform(0, 1, n)
        labels = (r.uniform(0, 1, n) < scores).astype(np.float32)
        ec = EvaluationCalibration(num_classes=1, reliability_bins=10)
        ec.eval(labels[:, None], scores[:, None].astype(np.float32))
        centers, freq, count = ec.reliability_curve(0)
        assert count.sum() == n
        np.testing.assert_allclose(freq, centers, atol=0.02)
        assert ec.ece(0) < 0.02

    def test_overconfident_model_high_ece(self):
        """A model that always says 0.99 but is right half the time."""
        n = 2000
        labels = (np.arange(n) % 2).astype(np.float32)
        scores = np.full(n, 0.99, np.float32)
        ec = EvaluationCalibration(num_classes=1, reliability_bins=10)
        ec.eval(labels[:, None], scores[:, None])
        assert ec.ece(0) == pytest.approx(abs(0.5 - 0.95), abs=0.05)

    def test_probability_histogram_mass(self):
        r = np.random.default_rng(12)
        scores = r.uniform(0, 1, 5000).astype(np.float32)
        labels = r.integers(0, 2, 5000).astype(np.float32)
        ec = EvaluationCalibration(num_classes=1, histogram_bins=50)
        ec.eval(labels[:, None], scores[:, None])
        edges, counts = ec.probability_histogram(0)
        assert counts.sum() == 5000
        oracle, _ = np.histogram(scores, bins=edges)
        # uniform scores: every bin within sampling noise of n/bins
        np.testing.assert_allclose(counts, oracle, atol=1.0)

    def test_residual_plot_oracle(self):
        labels = np.array([1, 0, 1, 0], np.float32)
        scores = np.array([0.81, 0.81, 0.21, 0.21], np.float32)
        ec = EvaluationCalibration(num_classes=1, histogram_bins=50)
        ec.eval(labels[:, None], scores[:, None])
        centers, resid = ec.residual_plot(0)
        # bin of 0.81 (center 0.81): one pos |1-c| + one neg |c|
        b81 = int(0.81 * 50)
        b21 = int(0.21 * 50)
        assert resid[b81] == pytest.approx((1 - centers[b81]) + centers[b81])
        assert resid[b21] == pytest.approx((1 - centers[b21]) + centers[b21])
        assert resid.sum() == pytest.approx(2.0)


class TestShardedEvaluation:
    """Evaluation accumulates the confusion matrix on
    device (one jit'd step per batch, no host sync in the loop) and, under
    a mesh, psums across data shards to the same answer."""

    def test_sharded_matches_single_and_numpy_oracle(self):
        import jax
        from jax.sharding import Mesh

        from deeplearning4j_tpu.data import ArrayDataSetIterator
        from deeplearning4j_tpu.evaluation import Evaluation, evaluate_model
        from deeplearning4j_tpu.nn.config import (
            NeuralNetConfiguration,
            SequentialConfig,
        )
        from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
        from deeplearning4j_tpu.nn.model import SequentialModel

        model = SequentialModel(SequentialConfig(
            net=NeuralNetConfiguration(seed=0),
            layers=[Dense(units=16, activation="tanh"),
                    OutputLayer(units=3, activation="softmax",
                                loss="mcxent")],
            input_shape=(5,),
        ))
        variables = model.init(seed=0)
        r = np.random.default_rng(0)
        x = r.normal(size=(64, 5)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[r.integers(0, 3, 64)]

        it = lambda: ArrayDataSetIterator(x, y, batch_size=16, shuffle=False)  # noqa: E731
        single = evaluate_model(model, variables, it(), 3)

        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(4), ("data",))
        sharded = evaluate_model(model, variables, it(), 3, mesh=mesh)

        np.testing.assert_array_equal(single.confusion(), sharded.confusion())

        # independent numpy oracle for the confusion matrix
        logits = np.asarray(jax.device_get(model.output(variables, x)))
        pred = logits.argmax(1)
        lab = y.argmax(1)
        oracle = np.zeros((3, 3))
        for l, p in zip(lab, pred):
            oracle[l, p] += 1
        np.testing.assert_array_equal(single.confusion(), oracle)
        assert single.accuracy() == pytest.approx(
            (pred == lab).mean(), abs=1e-9)

    def test_sharded_eval_partial_tail_batch(self):
        """drop_last=False partial batches fall back to the unsharded step
        instead of crashing on a non-divisible shard (r3 review)."""
        import jax
        from jax.sharding import Mesh

        from deeplearning4j_tpu.data import ArrayDataSetIterator
        from deeplearning4j_tpu.evaluation import evaluate_model
        from deeplearning4j_tpu.nn.config import (
            NeuralNetConfiguration,
            SequentialConfig,
        )
        from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
        from deeplearning4j_tpu.nn.model import SequentialModel

        model = SequentialModel(SequentialConfig(
            net=NeuralNetConfiguration(seed=0),
            layers=[Dense(units=8, activation="tanh"),
                    OutputLayer(units=3, activation="softmax",
                                loss="mcxent")],
            input_shape=(5,),
        ))
        variables = model.init(seed=0)
        r = np.random.default_rng(1)
        x = r.normal(size=(22, 5)).astype(np.float32)  # 22 = 2*8 + 6 tail
        y = np.eye(3, dtype=np.float32)[r.integers(0, 3, 22)]

        it = lambda: ArrayDataSetIterator(x, y, batch_size=8, shuffle=False,  # noqa: E731
                                          drop_last=False)
        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(4), ("data",))
        single = evaluate_model(model, variables, it(), 3)
        sharded = evaluate_model(model, variables, it(), 3, mesh=mesh)
        np.testing.assert_array_equal(single.confusion(), sharded.confusion())
        assert sharded.confusion().sum() == 22


# --- EvaluationBinary (round 3) --------------------------------------------


def test_evaluation_binary_against_sklearn_style_oracle():
    """Per-output binary counts vs a hand-computed numpy oracle."""
    from deeplearning4j_tpu.evaluation import EvaluationBinary

    r = np.random.default_rng(0)
    probs = r.random((200, 3)).astype(np.float32)
    labels = (r.random((200, 3)) > 0.6).astype(np.float32)
    ev = EvaluationBinary(3)
    # two batches to exercise accumulation
    ev.eval(labels[:120], probs[:120])
    ev.eval(labels[120:], probs[120:])
    pred = (probs >= 0.5).astype(np.float32)
    for i in range(3):
        tp = float(((pred[:, i] == 1) & (labels[:, i] == 1)).sum())
        fp = float(((pred[:, i] == 1) & (labels[:, i] == 0)).sum())
        fn = float(((pred[:, i] == 0) & (labels[:, i] == 1)).sum())
        tn = float(((pred[:, i] == 0) & (labels[:, i] == 0)).sum())
        assert ev.true_positives()[i] == tp
        assert ev.false_positives()[i] == fp
        np.testing.assert_allclose(ev.accuracy(i), (tp + tn) / 200, rtol=1e-6)
        if tp + fp:
            np.testing.assert_allclose(ev.precision(i), tp / (tp + fp),
                                       rtol=1e-6)
        if tp + fn:
            np.testing.assert_allclose(ev.recall(i), tp / (tp + fn), rtol=1e-6)
    assert "label" in ev.stats()


def test_evaluation_binary_custom_thresholds_and_merge():
    from deeplearning4j_tpu.evaluation import EvaluationBinary

    probs = np.array([[0.3, 0.9], [0.6, 0.2]], np.float32)
    labels = np.array([[1, 1], [0, 0]], np.float32)
    ev = EvaluationBinary(2, thresholds=[0.25, 0.95])
    ev.eval(labels, probs)
    # col0 thr .25: preds 1,1 -> tp=1 fp=1; col1 thr .95: preds 0,0 -> fn=1 tn=1
    assert ev.true_positives()[0] == 1 and ev.false_positives()[0] == 1
    assert ev.false_negatives()[1] == 1 and ev.true_negatives()[1] == 1
    ev2 = EvaluationBinary(2, thresholds=[0.25, 0.95]).eval(labels, probs)
    ev.merge(ev2)
    assert ev.true_positives()[0] == 2


def test_evaluation_binary_1d_single_output():
    """[N]-shaped labels/probs with num_outputs=1 must work, not silently
    broadcast counts into [4,4] garbage (r3 review)."""
    from deeplearning4j_tpu.evaluation import EvaluationBinary

    ev = EvaluationBinary(1)
    ev.eval(np.array([1.0, 0.0, 1.0]), np.array([0.9, 0.1, 0.8]))
    assert ev.counts.shape == (4, 1)
    assert ev.true_positives()[0] == 2
    assert ev.true_negatives()[0] == 1
    with pytest.raises(ValueError, match="num_outputs"):
        ev.eval(np.zeros((4, 3)), np.zeros((4, 3)))


def test_evaluation_binary_macro_excludes_undefined():
    """Aggregate precision averages only defined outputs (like
    Evaluation's macro averaging of present classes)."""
    from deeplearning4j_tpu.evaluation import EvaluationBinary

    ev = EvaluationBinary(2)
    # output 0: one TP; output 1: never predicted positive & no positives
    # in labels -> precision undefined there
    ev.eval(np.array([[1.0, 0.0]]), np.array([[0.9, 0.1]]))
    assert ev.precision() == 1.0  # not dragged to 0.5 by undefined col


def test_evaluation_binary_label_shape_mismatch_raises():
    from deeplearning4j_tpu.evaluation import EvaluationBinary

    ev = EvaluationBinary(1)
    with pytest.raises(ValueError, match="labels shape"):
        ev.eval(np.zeros((4, 3)), np.array([0.9, 0.1, 0.8, 0.2]))


def test_eval_time_series_masked():
    """↔ Evaluation.evalTimeSeries: masked steps excluded; unmasked result
    equals flattening time into the batch."""
    from deeplearning4j_tpu.evaluation import Evaluation

    r = np.random.default_rng(0)
    preds = r.random((3, 5, 4)).astype(np.float32)
    lab_idx = r.integers(0, 4, (3, 5))
    labels = np.eye(4, dtype=np.float32)[lab_idx]

    ev = Evaluation(4)
    ev.eval(labels, preds)  # 3-D dispatches to eval_time_series
    flat = Evaluation(4)
    flat.eval(labels.reshape(-1, 4), preds.reshape(-1, 4))
    np.testing.assert_array_equal(ev.confusion(), flat.confusion())
    assert ev.confusion().sum() == 15

    mask = np.ones((3, 5), np.float32)
    mask[:, 3:] = 0.0
    evm = Evaluation(4)
    evm.eval_time_series(labels, preds, mask=mask)
    trunc = Evaluation(4)
    trunc.eval(labels[:, :3].reshape(-1, 4), preds[:, :3].reshape(-1, 4))
    np.testing.assert_array_equal(evm.confusion(), trunc.confusion())


def test_regression_eval_time_series_masked():
    from deeplearning4j_tpu.evaluation import RegressionEvaluation

    r = np.random.default_rng(0)
    preds = r.normal(size=(3, 5, 2)).astype(np.float32)
    targets = r.normal(size=(3, 5, 2)).astype(np.float32)

    ev = RegressionEvaluation(2)
    ev.eval(targets, preds)  # 3-D auto-dispatch
    flat = RegressionEvaluation(2)
    flat.eval(targets.reshape(-1, 2), preds.reshape(-1, 2))
    np.testing.assert_allclose(ev.mse(), flat.mse(), rtol=1e-6)

    mask = np.ones((3, 5), np.float32)
    mask[:, 2:] = 0.0
    evm = RegressionEvaluation(2)
    evm.eval_time_series(targets, preds, mask=mask)
    trunc = RegressionEvaluation(2)
    trunc.eval(targets[:, :2].reshape(-1, 2), preds[:, :2].reshape(-1, 2))
    np.testing.assert_allclose(evm.mse(), trunc.mse(), rtol=1e-6)
    np.testing.assert_allclose(evm.r2(), trunc.r2(), rtol=1e-5)


def test_top_n_accuracy():
    from deeplearning4j_tpu.evaluation import Evaluation

    probs = np.array([[0.5, 0.3, 0.2],   # true 1: top-1 miss, top-2 hit
                      [0.1, 0.2, 0.7],   # true 2: top-1 hit
                      [0.4, 0.35, 0.25],  # true 2: top-2 miss
                      [0.3, 0.4, 0.3]],  # true 0: top-2 hit
                     np.float32)
    labels = np.eye(3, dtype=np.float32)[[1, 2, 2, 0]]
    ev = Evaluation(3, top_n=2)
    ev.eval(labels[:2], probs[:2])
    ev.eval(labels[2:], probs[2:])
    np.testing.assert_allclose(ev.top_n_accuracy(), 3 / 4)
    assert ev.accuracy() == 1 / 4  # plain accuracy still from confusion
    with pytest.raises(ValueError, match="top_n"):
        Evaluation(3).top_n_accuracy()


def test_top_n_merge_and_time_series():
    from deeplearning4j_tpu.evaluation import Evaluation

    probs = np.array([[0.5, 0.3, 0.2], [0.1, 0.2, 0.7]], np.float32)
    labels = np.eye(3, dtype=np.float32)[[1, 2]]
    a = Evaluation(3, top_n=2).eval(labels, probs)
    b = Evaluation(3, top_n=2).eval(labels, probs)
    a.merge(b)
    np.testing.assert_allclose(a.top_n_accuracy(), 1.0)  # 4/4, both halves
    assert a._topn_total == 4

    # sequence inputs also accumulate top-N (every step counted)
    seq = Evaluation(3, top_n=2)
    seq.eval(labels.reshape(1, 2, 3), probs.reshape(1, 2, 3))
    np.testing.assert_allclose(seq.top_n_accuracy(), 1.0)
    assert seq._topn_total == 2


def test_top_n_masked_and_validation():
    from deeplearning4j_tpu.evaluation import Evaluation

    probs = np.array([[[0.5, 0.3, 0.2], [0.1, 0.2, 0.7]]], np.float32)
    labels = np.eye(3, dtype=np.float32)[[[1, 2]]]
    mask = np.array([[1.0, 0.0]], np.float32)  # second step padded
    ev = Evaluation(3, top_n=2)
    ev.eval_time_series(labels, probs, mask=mask)
    np.testing.assert_allclose(ev.top_n_accuracy(), 1.0)  # 1/1, not 2/2
    assert ev._topn_total == 1

    with pytest.raises(ValueError, match="top_n"):
        Evaluation(3, top_n=5)
    a, b = Evaluation(3, top_n=2), Evaluation(3, top_n=3)
    with pytest.raises(ValueError, match="merge"):
        a.merge(b)


class TestEvaluateHelpers:
    """evaluate_roc / evaluate_regression (↔ MultiLayerNetwork.evaluateROC
    / evaluateRegression iterator conveniences)."""

    def test_evaluate_roc_binary_and_multiclass(self):
        import numpy as np

        from deeplearning4j_tpu.data.iterators import ArrayDataSetIterator
        from deeplearning4j_tpu.evaluation import evaluate_roc
        from deeplearning4j_tpu.nn.config import (
            NeuralNetConfiguration,
            SequentialConfig,
        )
        from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
        from deeplearning4j_tpu.nn.model import SequentialModel

        rng = np.random.default_rng(0)
        x = rng.normal(size=(64, 5)).astype(np.float32)
        y2 = np.eye(2, dtype=np.float32)[(x[:, 0] > 0).astype(int)]
        model = SequentialModel(SequentialConfig(
            net=NeuralNetConfiguration(seed=0), input_shape=(5,),
            layers=[Dense(units=8, activation="tanh"),
                    OutputLayer(units=2)]))
        v = model.init(seed=0)
        roc = evaluate_roc(
            model, v, ArrayDataSetIterator(x, y2, batch_size=32,
                                           shuffle=False))
        assert 0.0 <= roc.auc() <= 1.0

        y3 = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 64)]
        model3 = SequentialModel(SequentialConfig(
            net=NeuralNetConfiguration(seed=0), input_shape=(5,),
            layers=[OutputLayer(units=3, activation="softmax")]))
        v3 = model3.init(seed=0)
        roc3 = evaluate_roc(
            model3, v3, ArrayDataSetIterator(x, y3, batch_size=32,
                                             shuffle=False),
            num_classes=3)
        assert 0.0 <= roc3.average_auc() <= 1.0

    def test_evaluate_regression(self):
        import numpy as np

        from deeplearning4j_tpu.data.iterators import ArrayDataSetIterator
        from deeplearning4j_tpu.evaluation import evaluate_regression
        from deeplearning4j_tpu.nn.config import (
            NeuralNetConfiguration,
            SequentialConfig,
        )
        from deeplearning4j_tpu.nn.layers import OutputLayer
        from deeplearning4j_tpu.nn.model import SequentialModel

        rng = np.random.default_rng(1)
        x = rng.normal(size=(48, 4)).astype(np.float32)
        y = rng.normal(size=(48, 2)).astype(np.float32)
        model = SequentialModel(SequentialConfig(
            net=NeuralNetConfiguration(seed=0), input_shape=(4,),
            layers=[OutputLayer(units=2, activation="identity",
                                loss="mse")]))
        v = model.init(seed=0)
        ev = evaluate_regression(
            model, v, ArrayDataSetIterator(x, y, batch_size=16,
                                           shuffle=False), n_columns=2)
        assert np.all(np.asarray(ev.mse()) >= 0)
        assert ev._h()["n"] == 48


class TestMultiOutputSelection:
    """select_output guard: multi-output graph models must not be scored
    against an arbitrary head (advisor r4 finding)."""

    class _TwoHead:
        def output(self, variables, feats):
            import jax.numpy as jnp

            n = feats.shape[0]
            return {"a": jnp.tile(jnp.asarray([[0.9, 0.1]]), (n, 1)),
                    "b": jnp.tile(jnp.asarray([[0.1, 0.9]]), (n, 1))}

    def _iter(self):
        import numpy as np

        x = np.zeros((8, 3), np.float32)
        y = np.eye(2, dtype=np.float32)[np.zeros(8, np.int64)]
        from deeplearning4j_tpu.data.iterators import ArrayDataSetIterator
        return ArrayDataSetIterator(x, y, batch_size=8, shuffle=False)

    def test_roc_raises_without_output_name(self):
        import pytest

        from deeplearning4j_tpu.evaluation import evaluate_roc

        with pytest.raises(ValueError, match="multiple outputs"):
            evaluate_roc(self._TwoHead(), {}, self._iter())

    def test_roc_selects_named_output(self):
        from deeplearning4j_tpu.evaluation import evaluate_roc

        import pytest

        # resolves without error for a valid name, refuses an unknown one
        evaluate_roc(self._TwoHead(), {}, self._iter(), output_name="a")
        with pytest.raises(KeyError, match="not found"):
            evaluate_roc(self._TwoHead(), {}, self._iter(), output_name="c")

    def test_evaluate_model_raises_without_output_name(self):
        import pytest

        from deeplearning4j_tpu.evaluation import evaluate_model

        with pytest.raises(ValueError, match="multiple outputs"):
            evaluate_model(self._TwoHead(), {}, self._iter(), 2)

    def test_evaluate_model_selects_named_output(self):
        from deeplearning4j_tpu.evaluation import evaluate_model

        ev_a = evaluate_model(self._TwoHead(), {}, self._iter(), 2,
                              output_name="a")
        ev_b = evaluate_model(self._TwoHead(), {}, self._iter(), 2,
                              output_name="b")
        assert ev_a.accuracy() == 1.0   # head a predicts class 0 = labels
        assert ev_b.accuracy() == 0.0
