"""train/profiling.py tests: the entry points the /debug/profile and
/debug/costs endpoints depend on, previously untested.

- ``analyze_trace`` device-lane filtering on a synthetic Chrome trace
  (host Python lanes must NOT dilute the device-op percentages) and the
  no-device-lane fallback (CPU backend);
- ``ProfilingListener`` on the CPU backend: a trace file is actually
  produced under the TensorBoard profile layout, ``report()`` returns
  the step-time stats;
- ``op_costs`` / ``arithmetic_intensity`` / ``normalize_cost_analysis``
  including the None-cost-analysis fallback.
"""

import gzip
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.train.profiling import (
    ProfilingListener,
    _find_trace_file,
    analyze_trace,
    arithmetic_intensity,
    compare_traces,
    normalize_cost_analysis,
    op_costs,
)

# ---------------------------------------------------------------------------
# synthetic Chrome traces


def _write_trace(path, events):
    with gzip.open(path, "wt") as fh:
        json.dump({"traceEvents": events}, fh)


def _mixed_lane_events():
    """pid 1 = device lane (XLA ops), pid 2 = host python lane."""
    return [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "process_name", "pid": 2,
         "args": {"name": "/host:CPU"}},
        {"ph": "X", "pid": 1, "tid": 1, "name": "fusion.1",
         "ts": 0, "dur": 300.0},
        {"ph": "X", "pid": 1, "tid": 1, "name": "fusion.1",
         "ts": 400, "dur": 100.0},
        {"ph": "X", "pid": 1, "tid": 1, "name": "copy.2",
         "ts": 600, "dur": 100.0},
        # host-side work, 10x the device time: must not appear
        {"ph": "X", "pid": 2, "tid": 9, "name": "python_dispatch",
         "ts": 0, "dur": 5000.0},
    ]


class TestAnalyzeTrace:
    def test_device_lane_filter(self, tmp_path):
        _write_trace(tmp_path / "a.trace.json.gz", _mixed_lane_events())
        rows = analyze_trace(str(tmp_path))
        names = {r["name"] for r in rows}
        assert "python_dispatch" not in names
        by_name = {r["name"]: r for r in rows}
        assert by_name["fusion.1"]["total_us"] == 400.0
        assert by_name["fusion.1"]["count"] == 2
        # pct computed against DEVICE time only (500 us), undiluted by
        # the 5000 us host lane
        assert by_name["fusion.1"]["pct"] == pytest.approx(80.0)
        assert by_name["copy.2"]["pct"] == pytest.approx(20.0)

    def test_fallback_without_device_lane(self, tmp_path):
        # CPU-backend-style capture: host lanes only
        events = [
            {"ph": "M", "name": "process_name", "pid": 2,
             "args": {"name": "/host:CPU"}},
            {"ph": "X", "pid": 2, "tid": 1, "name": "convolution",
             "ts": 0, "dur": 60.0},
            {"ph": "X", "pid": 2, "tid": 1, "name": "dot_general",
             "ts": 100, "dur": 40.0},
        ]
        _write_trace(tmp_path / "a.trace.json.gz", events)
        rows = analyze_trace(str(tmp_path))
        by_name = {r["name"]: r for r in rows}
        assert by_name["convolution"]["pct"] == pytest.approx(60.0)
        assert by_name["dot_general"]["pct"] == pytest.approx(40.0)

    def test_gpu_lane_matches(self, tmp_path):
        events = [
            {"ph": "M", "name": "process_name", "pid": 7,
             "args": {"name": "/device:GPU:0 (NVIDIA A100)"}},
            {"ph": "M", "name": "process_name", "pid": 2,
             "args": {"name": "python"}},
            {"ph": "X", "pid": 7, "tid": 1, "name": "gemm",
             "ts": 0, "dur": 10.0},
            {"ph": "X", "pid": 2, "tid": 1, "name": "host_stuff",
             "ts": 0, "dur": 90.0},
        ]
        _write_trace(tmp_path / "a.trace.json.gz", events)
        rows = analyze_trace(str(tmp_path))
        assert [r["name"] for r in rows] == ["gemm"]
        assert rows[0]["pct"] == pytest.approx(100.0)

    def test_compare_traces_delta(self, tmp_path):
        a, b = tmp_path / "a", tmp_path / "b"
        a.mkdir(), b.mkdir()
        _write_trace(a / "x.trace.json.gz", _mixed_lane_events())
        evs = _mixed_lane_events()
        evs[2]["dur"] = 900.0  # fusion.1 regressed
        _write_trace(b / "x.trace.json.gz", evs)
        rows = compare_traces(str(a), str(b))
        assert rows[0]["name"] == "fusion.1"
        assert rows[0]["delta_us"] == pytest.approx(600.0)

    def test_missing_trace_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            analyze_trace(str(tmp_path))


# ---------------------------------------------------------------------------
# ProfilingListener on the CPU backend


def _tiny_trainer():
    from deeplearning4j_tpu.nn.config import (
        NeuralNetConfiguration,
        SequentialConfig,
    )
    from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
    from deeplearning4j_tpu.nn.model import SequentialModel
    from deeplearning4j_tpu.train.trainer import Trainer

    model = SequentialModel(SequentialConfig(
        net=NeuralNetConfiguration(seed=0),
        layers=[Dense(units=8, activation="tanh"),
                OutputLayer(units=2, activation="softmax", loss="mcxent")],
        input_shape=(12,),
    ))
    return Trainer(model)


def _tiny_data(n=48, batch=8):
    from deeplearning4j_tpu.data import ArrayDataSetIterator

    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 12)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, n)]
    return ArrayDataSetIterator(x, y, batch_size=batch, shuffle=False)


class TestProfilingListener:
    def test_cpu_capture_produces_trace_and_report(self, tmp_path):
        log_dir = str(tmp_path / "profile")
        trainer = _tiny_trainer()
        lst = ProfilingListener(log_dir, start_step=2, end_step=4)
        trainer.fit(trainer.init_state(), _tiny_data(), epochs=1,
                    listeners=[lst])
        # a trace file landed under the TB profile plugin layout
        path = _find_trace_file(log_dir)
        assert os.path.getsize(path) > 0
        report = lst.report()
        # intervals are recorded only while the trace is active
        # (steps [start_step, end_step) => end - start samples)
        assert report["steps"] >= 1
        for key in ("mean_ms", "p50_ms", "min_ms", "max_ms"):
            assert report[key] >= 0.0
        assert report["min_ms"] <= report["p50_ms"] <= report["max_ms"]
        # the analyzer parses the real capture (host lanes on CPU: the
        # fallback path) and returns a non-empty breakdown
        rows = analyze_trace(log_dir)
        assert rows
        assert all(set(r) == {"name", "total_us", "count", "pct"}
                   for r in rows)

    def test_report_empty_before_steps(self, tmp_path):
        lst = ProfilingListener(str(tmp_path), start_step=2)
        assert lst.report() == {"steps": 0}


# ---------------------------------------------------------------------------
# op_costs / arithmetic_intensity / normalize_cost_analysis


class TestOpCosts:
    def test_cpu_backend_reports_flops(self):
        def fn(a, b):
            return jnp.tanh(a @ b).sum()

        a = jnp.ones((32, 64), jnp.float32)
        b = jnp.ones((64, 16), jnp.float32)
        costs = op_costs(fn, a, b)
        assert costs["flops"] > 0
        # matmul dominates: 2*M*N*K
        assert costs["flops"] >= 2 * 32 * 64 * 16
        assert all(isinstance(v, float) for v in costs.values())

    def test_train_step_costs(self):
        trainer = _tiny_trainer()
        ts = trainer.init_state()
        batch = {"features": np.zeros((8, 12), np.float32),
                 "labels": np.zeros((8, 2), np.float32)}
        costs = op_costs(trainer._raw_step, ts, batch)
        assert costs.get("flops", 0) > 0

    def test_arithmetic_intensity(self):
        assert arithmetic_intensity(
            {"flops": 100.0, "bytes accessed": 50.0}) == pytest.approx(2.0)
        # None when the backend omits byte traffic (some PJRT plugins)
        assert arithmetic_intensity({"flops": 100.0}) is None
        assert arithmetic_intensity({}) is None

    def test_normalize_cost_analysis_fallbacks(self):
        # None: backend implements no cost analysis
        assert normalize_cost_analysis(None) == {}
        # version-dependent 1-element list shape
        assert normalize_cost_analysis(
            [{"flops": 3, "label": "x"}]) == {"flops": 3.0}
        assert normalize_cost_analysis([]) == {}
        # plain dict: non-numeric values dropped, numerics floated
        out = normalize_cost_analysis({"flops": 7, "name": "prog"})
        assert out == {"flops": 7.0}

    def test_step_flops_background_analysis(self):
        """Trainer.step_flops fills its cache off-thread and the fit loop
        sets the analytic gauges (the /debug MFU story end to end)."""
        import time

        from deeplearning4j_tpu.observability import metrics as om

        om.reset_default_registry()
        om.set_enabled(True)
        try:
            trainer = _tiny_trainer()
            ts = trainer.init_state()
            batch = {"features": np.zeros((8, 12), np.float32),
                     "labels": np.zeros((8, 2), np.float32)}
            assert trainer.step_flops(ts, batch) is None  # kicked off
            deadline = time.monotonic() + 60
            flops = None
            while time.monotonic() < deadline and flops is None:
                time.sleep(0.05)
                flops = trainer.step_flops(ts, batch)
            assert flops and flops > 0
            # a fit now publishes the gauges from the cached analysis
            trainer.fit(ts, _tiny_data(), epochs=1)
            text = om.default_registry().render_text()
            assert "train_step_flops" in text
            # FLOPs over the host's dispatch time is no rate of the
            # device: the gauges derived from it are gone (PR 26)
            assert "train_flops_per_second" not in text
            assert "train_analytic_mfu" not in text
        finally:
            om.reset_default_registry()

    def test_step_flops_kill_switch(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_STEP_COST_ANALYSIS", "0")
        trainer = _tiny_trainer()
        ts = trainer.init_state()
        batch = {"features": np.zeros((8, 12), np.float32),
                 "labels": np.zeros((8, 2), np.float32)}
        assert trainer.step_flops(ts, batch) is None
        assert trainer._step_cost_cache == {}
