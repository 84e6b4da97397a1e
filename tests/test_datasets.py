"""Dataset fetcher tests.

ref strategy: the reference's iterator tests assert shapes/classes/label
encoding per fetcher. Synthetic-fallback loaders must additionally be
LEARNABLE (the MNIST pattern) — a linear probe beats chance by a wide
margin — and the real-file parsers are oracle-tested against files we
write in the on-disk formats (CIFAR pickle, EMNIST idx, iris csv).
"""

import gzip
import pickle
import struct

import numpy as np
import pytest

from deeplearning4j_tpu.data import (
    load_cifar10,
    load_cifar100,
    load_emnist,
    load_iris,
    load_mnist,
    load_tiny_imagenet,
)


def _linear_probe_acc(x, y, xte, yte, *, steps=200, lr=0.5):
    """Tiny softmax regression in numpy — independent of the framework."""
    n, d = x.reshape(len(x), -1).shape
    c = y.shape[1]
    xf = x.reshape(n, -1)
    w = np.zeros((d, c))
    for _ in range(steps):
        p = np.exp(xf @ w - (xf @ w).max(1, keepdims=True))
        p /= p.sum(1, keepdims=True)
        w -= lr / n * xf.T @ (p - y)
    pte = xte.reshape(len(xte), -1) @ w
    return (pte.argmax(1) == yte.argmax(1)).mean()


class TestSyntheticFallbacks:
    def test_cifar10_shapes_and_learnable(self):
        (xtr, ytr), (xte, yte), is_real = load_cifar10(n_train=512, n_test=256)
        assert xtr.shape == (512, 32, 32, 3) and ytr.shape == (512, 10)
        assert xtr.dtype == np.float32 and 0.0 <= xtr.min() <= xtr.max() <= 1.0
        acc = _linear_probe_acc(xtr, ytr, xte, yte)
        assert acc > 0.5, f"fallback not learnable: {acc}"

    def test_cifar100_classes(self):
        (xtr, ytr), _, _ = load_cifar100(n_train=256, n_test=64)
        assert ytr.shape == (256, 100)
        assert set(np.unique(ytr)) == {0.0, 1.0}

    def test_emnist_splits(self):
        for split, classes in (("balanced", 47), ("letters", 26),
                               ("digits", 10)):
            (xtr, ytr), _, _ = load_emnist(split, n_train=128, n_test=32)
            assert xtr.shape == (128, 28, 28, 1)
            assert ytr.shape == (128, classes)
        with pytest.raises(ValueError, match="unknown EMNIST split"):
            load_emnist("nope")

    def test_tiny_imagenet_shapes(self):
        (xtr, ytr), _, _ = load_tiny_imagenet(n_train=64, n_test=16)
        assert xtr.shape == (64, 64, 64, 3) and ytr.shape == (64, 200)

    def test_iris_stratified_and_learnable(self):
        (xtr, ytr), (xte, yte), is_real = load_iris(test_frac=0.2)
        assert xtr.shape[1] == 4 and ytr.shape[1] == 3
        assert len(xtr) + len(xte) == 150
        # stratified: every class appears in both splits
        assert (ytr.sum(0) > 0).all() and (yte.sum(0) > 0).all()
        acc = _linear_probe_acc(xtr, ytr, xte, yte, steps=500, lr=0.1)
        assert acc > 0.7, f"iris probe only {acc}"

    def test_int_labels_mode(self):
        (xtr, ytr), _, _ = load_cifar10(n_train=32, n_test=8, one_hot=False)
        assert ytr.ndim == 1 and ytr.dtype.kind in "iu"

    def test_deterministic(self):
        a = load_cifar10(n_train=16, n_test=4)[0][0]
        b = load_cifar10(n_train=16, n_test=4)[0][0]
        np.testing.assert_array_equal(a, b)


class TestRealFileParsers:
    """Write files in the real on-disk formats and check the parsers."""

    def test_cifar10_pickle_batches(self, tmp_path, monkeypatch):
        from deeplearning4j_tpu.data import datasets as ds

        d = tmp_path / "cifar-10-batches-py"
        d.mkdir()
        r = np.random.default_rng(0)
        for i in range(1, 6):
            data = r.integers(0, 256, (20, 3072), dtype=np.uint8)
            with open(d / f"data_batch_{i}", "wb") as f:
                pickle.dump({b"data": data,
                             b"labels": list(r.integers(0, 10, 20))}, f)
        test = r.integers(0, 256, (10, 3072), dtype=np.uint8)
        with open(d / "test_batch", "wb") as f:
            pickle.dump({b"data": test, b"labels": list(range(10))}, f)

        monkeypatch.setattr(ds, "_search",
                            lambda names: d if "cifar-10-batches-py" in names[0]
                            else None)
        (xtr, ytr), (xte, yte), is_real = ds.load_cifar10()
        assert is_real
        assert xtr.shape == (100, 32, 32, 3) and xte.shape == (10, 32, 32, 3)
        # NCHW->NHWC transpose oracle on one pixel
        np.testing.assert_allclose(
            xte[0, 0, 0], test[0].reshape(3, 32, 32)[:, 0, 0] / 255.0)
        assert yte.argmax(1).tolist() == list(range(10))

    def test_emnist_idx_files(self, tmp_path, monkeypatch):
        from deeplearning4j_tpu.data import datasets as ds

        d = tmp_path / "emnist"
        d.mkdir()
        r = np.random.default_rng(0)

        def write_idx(path, arr):
            with gzip.open(path, "wb") as f:
                f.write(struct.pack(">I", (arr.ndim) | 0x0800))
                for s in arr.shape:
                    f.write(struct.pack(">I", s))
                f.write(arr.tobytes())

        xtr = r.integers(0, 256, (30, 28, 28), dtype=np.uint8)
        ytr = r.integers(1, 27, 30, dtype=np.uint8)  # letters: 1-indexed
        xte = r.integers(0, 256, (10, 28, 28), dtype=np.uint8)
        yte = r.integers(1, 27, 10, dtype=np.uint8)
        write_idx(d / "emnist-letters-train-images-idx3-ubyte.gz", xtr)
        write_idx(d / "emnist-letters-train-labels-idx1-ubyte.gz", ytr)
        write_idx(d / "emnist-letters-test-images-idx3-ubyte.gz", xte)
        write_idx(d / "emnist-letters-test-labels-idx1-ubyte.gz", yte)

        def search(names):
            for n in names:
                p = tmp_path / n
                if p.exists():
                    return p
            return None

        monkeypatch.setattr(ds, "_search", search)
        (x, y), _, is_real = ds.load_emnist("letters")
        assert is_real
        assert x.shape == (30, 28, 28, 1)
        assert y.shape == (30, 26)
        # labels rebased to 0..25
        assert y.argmax(1).min() >= 0 and y.argmax(1).max() <= 25
        # idx transpose oracle
        np.testing.assert_allclose(x[0, :, :, 0], xtr[0].T / 255.0)

    def test_iris_csv(self, tmp_path, monkeypatch):
        from deeplearning4j_tpu.data import datasets as ds

        rows = ["5.1,3.5,1.4,0.2,Iris-setosa",
                "7.0,3.2,4.7,1.4,Iris-versicolor",
                "6.3,3.3,6.0,2.5,Iris-virginica"] * 10
        p = tmp_path / "iris.csv"
        p.write_text("\n".join(rows))
        monkeypatch.setattr(ds, "_search",
                            lambda names: p if any("iris" in n for n in names)
                            else None)
        (xtr, ytr), (xte, yte), is_real = ds.load_iris(test_frac=0.3)
        assert is_real
        assert xtr.shape[1] == 4
        assert len(xtr) + len(xte) == 30
        assert ytr.shape[1] == 3


class TestTrainOnDataset:
    # Tier-1 budget relief (the PR 6/7 pattern, paying for the PR 20
    # autoscaler suite): the loader surface stays wired every tier-1
    # run via TestSyntheticFallbacks/TestRealFileParsers, and the
    # identical lenet train-and-evaluate path runs in test_lenet_e2e;
    # the fit-on-emnist convergence leg rides tier-2.
    @pytest.mark.slow
    def test_lenet_fits_emnist_digits(self):
        """End-to-end: a zoo model trains on a fetched dataset."""
        import jax

        from deeplearning4j_tpu.data import ArrayDataSetIterator
        from deeplearning4j_tpu.models.lenet import lenet
        from deeplearning4j_tpu.train.trainer import Trainer
        from deeplearning4j_tpu.train.updaters import Adam

        (xtr, ytr), _, _ = load_emnist("digits", n_train=256, n_test=32)
        model = lenet(updater=Adam(3e-3))
        trainer = Trainer(model)
        ts = trainer.init_state(seed=0)
        it = ArrayDataSetIterator(xtr, ytr, batch_size=32)
        losses = []

        class Cap:
            def on_fit_start(self, t, s):
                pass

            def on_epoch_start(self, e):
                pass

            def on_iteration(self, e, s, ts_, m):
                losses.append(float(jax.device_get(m["total_loss"])))
                return False

            def on_epoch_end(self, e, ts_):
                return False

            def on_fit_end(self, t, s):
                pass

        trainer.fit(ts, it, epochs=12, listeners=[Cap()])
        assert losses[-1] < losses[0] * 0.5
