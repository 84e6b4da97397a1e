"""Profiler + TensorBoard writer tests.

Oracles: event files are read back with REAL TensorFlow's summary_iterator
(independent reader — our writer can't be self-consistently wrong), and the
profiler's chrome trace is parsed from the actual jax.profiler capture.
"""

import glob
import os

import numpy as np
import pytest

from deeplearning4j_tpu.nn.config import NeuralNetConfiguration, SequentialConfig
from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
from deeplearning4j_tpu.nn.model import SequentialModel
from deeplearning4j_tpu.train.profiling import (
    ProfilingListener,
    analyze_trace,
    compare_traces,
)
from deeplearning4j_tpu.train.tensorboard import (
    TensorBoardListener,
    TensorBoardWriter,
    _masked_crc,
    crc32c,
)
from deeplearning4j_tpu.train.trainer import Trainer
from deeplearning4j_tpu.train.updaters import Adam


def _model():
    cfg = SequentialConfig(
        net=NeuralNetConfiguration(updater=Adam(1e-2), seed=0),
        layers=[Dense(units=16, activation="relu"),
                OutputLayer(units=2, activation="softmax", loss="mcxent")],
        input_shape=(8,),
    )
    return SequentialModel(cfg)


def _data(n=32):
    r = np.random.default_rng(0)
    x = r.normal(size=(n, 8)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[r.integers(0, 2, n)]
    return [{"features": x, "labels": y}]


def _read_events(log_dir):
    from tensorflow.python.summary.summary_iterator import summary_iterator

    files = glob.glob(os.path.join(log_dir, "events.out.tfevents.*"))
    assert files, f"no event file in {log_dir}"
    events = []
    for f in files:
        events.extend(summary_iterator(f))
    return events


class TestCRC32C:
    def test_known_vectors(self):
        # canonical CRC-32C check value + empty string
        assert crc32c(b"") == 0x0
        assert crc32c(b"123456789") == 0xE3069283

    def test_mask_roundtrip_is_deterministic(self):
        assert _masked_crc(b"hello") == _masked_crc(b"hello")
        assert _masked_crc(b"hello") != _masked_crc(b"hellp")


class TestTensorBoardWriter:
    def test_scalars_read_back_by_tensorflow(self, tmp_path):
        w = TensorBoardWriter(str(tmp_path))
        w.add_scalar("loss", 2.5, step=1, wall_time=123.0)
        w.add_scalar("loss", 1.25, step=2, wall_time=124.0)
        w.add_scalar("acc", 0.75, step=2)
        w.close()

        events = _read_events(str(tmp_path))
        assert events[0].file_version == "brain.Event:2"
        scalars = [(e.step, v.tag, v.simple_value)
                   for e in events for v in e.summary.value
                   if v.HasField("simple_value")]
        assert (1, "loss", 2.5) in scalars
        assert (2, "loss", 1.25) in scalars
        assert any(t == "acc" and abs(v - 0.75) < 1e-6
                   for _, t, v in scalars)
        # wall_time survives the round trip
        assert any(abs(e.wall_time - 123.0) < 1e-6 for e in events)

    def test_histogram_read_back_by_tensorflow(self, tmp_path):
        r = np.random.default_rng(0)
        values = r.normal(size=1000)
        w = TensorBoardWriter(str(tmp_path))
        w.add_histogram("weights", values, step=5)
        w.close()

        events = _read_events(str(tmp_path))
        histos = [(e.step, v.tag, v.histo)
                  for e in events for v in e.summary.value
                  if v.HasField("histo")]
        assert len(histos) == 1
        step, tag, h = histos[0]
        assert step == 5 and tag == "weights"
        assert h.num == pytest.approx(1000)
        assert h.min == pytest.approx(values.min())
        assert h.max == pytest.approx(values.max())
        assert h.sum == pytest.approx(values.sum(), rel=1e-6)
        assert sum(h.bucket) == pytest.approx(1000)
        assert len(h.bucket_limit) == len(h.bucket)

    def test_add_scalars_one_event(self, tmp_path):
        w = TensorBoardWriter(str(tmp_path))
        w.add_scalars({"a": 1.0, "b": 2.0}, step=3)
        w.close()
        events = _read_events(str(tmp_path))
        multi = [e for e in events if len(e.summary.value) == 2]
        assert len(multi) == 1 and multi[0].step == 3


class TestTensorBoardListener:
    def test_fit_writes_scalars_and_histograms(self, tmp_path):
        model = _model()
        trainer = Trainer(model)
        ts = trainer.init_state(seed=0)
        lst = TensorBoardListener(str(tmp_path), every=1,
                                  histogram_every_epochs=2)
        trainer.fit(ts, _data(), epochs=4, listeners=[lst])

        events = _read_events(str(tmp_path))
        tags = {v.tag for e in events for v in e.summary.value}
        assert "train/total_loss" in tags
        assert any(t.startswith("params/") for t in tags)
        losses = [v.simple_value for e in events for v in e.summary.value
                  if v.tag == "train/total_loss"]
        assert len(losses) == 4
        assert losses[-1] < losses[0]  # it trained


class TestProfilingListener:
    def test_trace_captured_and_analyzed(self, tmp_path):
        model = _model()
        trainer = Trainer(model)
        ts = trainer.init_state(seed=0)
        log_dir = str(tmp_path / "prof")
        lst = ProfilingListener(log_dir, start_step=2, end_step=4)
        trainer.fit(ts, _data(), epochs=6, listeners=[lst])

        rep = lst.report()
        assert rep["steps"] >= 2
        assert rep["p50_ms"] > 0

        rows = analyze_trace(log_dir)
        assert rows, "no events aggregated from trace"
        assert all({"name", "total_us", "count", "pct"} <= set(r) for r in rows)
        assert rows[0]["total_us"] >= rows[-1]["total_us"]

    def test_compare_traces(self, tmp_path):
        model = _model()
        for run in ("a", "b"):
            trainer = Trainer(model)
            ts = trainer.init_state(seed=0)
            lst = ProfilingListener(str(tmp_path / run), start_step=1,
                                    end_step=3)
            trainer.fit(ts, _data(), epochs=4, listeners=[lst])
        rows = compare_traces(str(tmp_path / "a"), str(tmp_path / "b"))
        assert rows and all("delta_us" in r for r in rows)


class TestModelStatsListener:
    """↔ StatsListener: per-layer mean magnitudes + update:param ratio."""

    def _fit(self, tmp_path, **kw):
        import jax.numpy as jnp
        from deeplearning4j_tpu.data import ArrayDataSetIterator
        from deeplearning4j_tpu.train.listeners import ModelStatsListener

        m = _model()
        tr = Trainer(m)
        ts = tr.init_state()
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(64, 8)).astype(np.float32))
        y = jnp.asarray(np.eye(2, dtype=np.float32)[rng.integers(0, 2, 64)])
        listener = ModelStatsListener(every=4, **kw)
        tr.fit(ts, ArrayDataSetIterator(x, y, batch_size=16), epochs=4,
               listeners=[listener])
        return m

    def test_jsonl_records_ratios_per_layer(self, tmp_path):
        import json as _json

        path = str(tmp_path / "stats.jsonl")
        m = self._fit(tmp_path, jsonl_path=path)
        rows = [_json.loads(l) for l in open(path)]
        assert rows, "no stats records written"
        layer_names = [n for n, _ in m.named_layers()]
        for row in rows:
            for name in layer_names:
                assert f"param_mm/{name}" in row
                assert f"update_mm/{name}" in row
                ratio = row[f"update_ratio/{name}"]
                # Adam with lr 1e-2 on a converging net: ratios are small
                # positive numbers; 0 would mean the diff saw no update
                assert 0 < ratio < 1.0

    def test_tensorboard_scalars_and_histograms(self, tmp_path):
        tf = pytest.importorskip("tensorflow")
        tb_dir = str(tmp_path / "tb")
        w = TensorBoardWriter(tb_dir)
        self._fit(tmp_path, tensorboard=w, histograms=True)
        w.close()
        events = glob.glob(os.path.join(tb_dir, "events.out.tfevents.*"))
        assert events
        tags = set()
        for e in tf.compat.v1.train.summary_iterator(events[0]):
            for v in e.summary.value:
                tags.add(v.tag)
        assert any(t.startswith("update_ratio/") for t in tags)
        assert any(t.startswith("params/") for t in tags)

    def test_nested_param_groups_bidirectional(self, tmp_path):
        """Bidirectional layers have {'fwd': {...}, 'bwd': {...}} params —
        the stats walk must traverse nested groups, not assume two dict
        levels."""
        import json as _json

        import jax.numpy as jnp
        from deeplearning4j_tpu.data import ArrayDataSetIterator
        from deeplearning4j_tpu.nn.config import SequentialConfig
        from deeplearning4j_tpu.nn.layers import (LSTM, Bidirectional,
                                                  RnnOutputLayer)
        from deeplearning4j_tpu.nn.model import SequentialModel
        from deeplearning4j_tpu.train.listeners import ModelStatsListener

        cfg = SequentialConfig(
            net=NeuralNetConfiguration(updater=Adam(1e-2), seed=0),
            input_shape=(6, 4),
            layers=[Bidirectional(LSTM(units=8)),
                    RnnOutputLayer(units=2, activation="softmax",
                                   loss="mcxent")])
        m = SequentialModel(cfg)
        tr = Trainer(m)
        rng = np.random.default_rng(0)
        x = np.asarray(rng.normal(size=(32, 6, 4)), np.float32)
        y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, (32, 6))]
        path = str(tmp_path / "bi.jsonl")
        tr.fit(tr.init_state(), ArrayDataSetIterator(jnp.asarray(x),
                                                     jnp.asarray(y),
                                                     batch_size=16),
               epochs=4, listeners=[ModelStatsListener(every=3,
                                                       jsonl_path=path)])
        rows = [_json.loads(l) for l in open(path)]
        assert rows
        bi_name = m.layer_names[0]
        assert any(f"update_ratio/{bi_name}" in r for r in rows)

    def test_reuse_across_fits_resets_snapshot(self, tmp_path):
        """A listener reused for a second fit must not diff across the two
        models' unrelated initializations."""
        import json as _json

        import jax.numpy as jnp
        from deeplearning4j_tpu.data import ArrayDataSetIterator
        from deeplearning4j_tpu.train.listeners import ModelStatsListener

        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(32, 8)).astype(np.float32))
        y = jnp.asarray(np.eye(2, dtype=np.float32)[rng.integers(0, 2, 32)])
        path = str(tmp_path / "r.jsonl")
        # every=4, 2 steps/epoch, 2 epochs -> 4 steps: snapshot at step 3,
        # fit ends with _prev set
        lis = ModelStatsListener(every=4, jsonl_path=path)
        for _ in range(2):
            m = _model()
            tr = Trainer(m)
            tr.fit(tr.init_state(), ArrayDataSetIterator(x, y, batch_size=16),
                   epochs=2, listeners=[lis])
        rows = [_json.loads(l) for l in open(path)]
        for row in rows:
            for k, v in row.items():
                if k.startswith("update_ratio/"):
                    assert v < 0.5, (
                        "cross-fit diff leaked into ratios: %r" % row)

    def test_tbptt_identical_params_not_reported_as_dead(self, tmp_path):
        """Under TBPTT, windows between batch updates see identical params;
        those must be skipped, not written as update_ratio=0."""
        import json as _json

        import jax.numpy as jnp
        from deeplearning4j_tpu.data import ArrayDataSetIterator
        from deeplearning4j_tpu.nn.config import SequentialConfig
        from deeplearning4j_tpu.nn.layers import LSTM, RnnOutputLayer
        from deeplearning4j_tpu.nn.model import SequentialModel
        from deeplearning4j_tpu.train.listeners import ModelStatsListener

        cfg = SequentialConfig(
            net=NeuralNetConfiguration(updater=Adam(1e-2), seed=0,
                                       backprop_type="tbptt",
                                       tbptt_length=4),
            input_shape=(16, 3),
            layers=[LSTM(units=8),
                    RnnOutputLayer(units=2, activation="softmax",
                                   loss="mcxent")])
        m = SequentialModel(cfg)
        tr = Trainer(m)
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(16, 16, 3)).astype(np.float32))
        y = jnp.asarray(np.eye(2, dtype=np.float32)[
            rng.integers(0, 2, (16, 16))])
        path = str(tmp_path / "tb.jsonl")
        tr.fit(tr.init_state(), ArrayDataSetIterator(x, y, batch_size=8),
               epochs=6,
               listeners=[ModelStatsListener(every=2, jsonl_path=path)])
        rows = [_json.loads(l) for l in open(path)]
        ratios = [v for r in rows for k, v in r.items()
                  if k.startswith("update_ratio/")]
        assert ratios, "no reports emitted at all under tbptt"
        assert all(v > 0 for v in ratios), "zero-update report leaked"


class TestOpCosts:
    """Static HLO cost analysis (↔ OpProfiler counters; profiling.op_costs)."""

    def test_matmul_flops_and_intensity(self):
        import jax.numpy as jnp

        from deeplearning4j_tpu.train.profiling import (
            arithmetic_intensity,
            op_costs,
        )

        def f(a, b):
            return jnp.tanh(a @ b).sum()

        c = op_costs(f, jnp.ones((64, 64), jnp.float32),
                     jnp.ones((64, 64), jnp.float32))
        # dominated by the 2*64^3 matmul; cost model may add elementwise
        assert c["flops"] >= 2 * 64**3
        ai = arithmetic_intensity(c)
        if ai is not None:  # CPU backend reports byte traffic
            assert 0 < ai < 1000

    def test_train_step_costs(self):
        from deeplearning4j_tpu.models.lenet import lenet
        from deeplearning4j_tpu.train.profiling import op_costs
        from deeplearning4j_tpu.train.trainer import Trainer

        model = lenet()
        tr = Trainer(model)
        ts = tr.init_state()
        import numpy as np

        batch = {"features": np.zeros((8, 28, 28, 1), np.float32),
                 "labels": np.zeros((8, 10), np.float32)}
        c = op_costs(tr.train_step, ts, batch)
        # fwd+bwd+Adam of LeNet at b8 is far beyond 1 MFLOP
        assert c["flops"] > 1e6


class TestActivationStatsListener:
    def test_jsonl_and_tensorboard(self, tmp_path):
        import json as _json

        from deeplearning4j_tpu.data.iterators import ArrayDataSetIterator
        from deeplearning4j_tpu.models.lenet import lenet
        from deeplearning4j_tpu.train.listeners import (
            ActivationStatsListener,
        )
        from deeplearning4j_tpu.train.tensorboard import TensorBoardWriter
        from deeplearning4j_tpu.train.trainer import Trainer

        rng = np.random.default_rng(0)
        x = rng.normal(size=(16, 28, 28, 1)).astype(np.float32)
        y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 16)]
        model = lenet()
        trainer = Trainer(model)
        ts = trainer.init_state()
        path = tmp_path / "acts.jsonl"
        tb = TensorBoardWriter(str(tmp_path / "tb"))
        lst = ActivationStatsListener(x[:4], every=2, jsonl_path=str(path),
                                      tensorboard=tb, histograms=True)
        ts = trainer.fit(ts, ArrayDataSetIterator(x, y, batch_size=8),
                         epochs=2, listeners=[lst])
        tb.close()
        rows = [_json.loads(l) for l in open(path)]
        assert rows, "no activation reports"
        keys = [k for k in rows[0] if k.startswith("activation_mm/")]
        assert len(keys) == len(model.layers)
        assert all(np.isfinite(r[k]) for r in rows for k in keys)

    def test_rejects_model_without_feed_forward(self):
        from deeplearning4j_tpu.train.listeners import (
            ActivationStatsListener,
        )

        class FakeTrainer:
            model = object()

        lst = ActivationStatsListener(np.zeros((1, 4), np.float32))
        import pytest

        with pytest.raises(TypeError, match="feed_forward"):
            lst.on_fit_start(FakeTrainer(), None)

    def test_graph_model_inputs_excluded(self, tmp_path):
        import json as _json

        from deeplearning4j_tpu.nn.config import (
            GraphConfig,
            GraphVertex,
            NeuralNetConfiguration,
        )
        from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
        from deeplearning4j_tpu.nn.model import GraphModel
        from deeplearning4j_tpu.train.listeners import (
            ActivationStatsListener,
        )
        from deeplearning4j_tpu.train.trainer import Trainer

        cfg = GraphConfig(
            net=NeuralNetConfiguration(),
            inputs=["in"], input_shapes={"in": (4,)},
            vertices={
                "h": GraphVertex(kind="layer", inputs=["in"],
                                 layer=Dense(units=8, activation="relu")),
                "out": GraphVertex(kind="layer", inputs=["h"],
                                   layer=OutputLayer(units=2)),
            },
            outputs=["out"])
        m = GraphModel(cfg)
        trainer = Trainer(m)
        ts = trainer.init_state()
        rng = np.random.default_rng(0)
        x = rng.normal(size=(8, 4)).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 8)]
        path = tmp_path / "g.jsonl"
        lst = ActivationStatsListener(x[:2], every=1,
                                      jsonl_path=str(path))
        from deeplearning4j_tpu.data.iterators import ArrayDataSetIterator

        trainer.fit(ts, ArrayDataSetIterator(x, y, batch_size=4),
                    epochs=1, listeners=[lst])
        rows = [_json.loads(l) for l in open(path)]
        keys = {k for r in rows for k in r if k.startswith("activation_mm/")}
        assert keys == {"activation_mm/h", "activation_mm/out"}  # no input
