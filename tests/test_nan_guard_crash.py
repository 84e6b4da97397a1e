"""NaN-guard + crash-report tests.

ref strategy: Nd4j checkForNAN tests (inject a NaN, expect an exception
naming the operation) and CrashReportingUtil tests (dump file exists and
contains memory/config/iteration state).
"""

import json

import numpy as np
import pytest

from deeplearning4j_tpu.nn.config import NeuralNetConfiguration, SequentialConfig
from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
from deeplearning4j_tpu.nn.model import SequentialModel
from deeplearning4j_tpu.train.trainer import Trainer
from deeplearning4j_tpu.train.updaters import Adam
from deeplearning4j_tpu.utils.crash import (
    CrashReportingListener,
    last_crash_report,
    write_crash_report,
)


def _model():
    cfg = SequentialConfig(
        net=NeuralNetConfiguration(updater=Adam(1e-2), seed=0),
        layers=[Dense(units=8, activation="relu"),
                OutputLayer(units=2, activation="softmax", loss="mcxent")],
        input_shape=(4,),
    )
    return SequentialModel(cfg)


def _batch(nan=False):
    r = np.random.default_rng(0)
    x = r.normal(size=(8, 4)).astype(np.float32)
    if nan:
        x[3, 2] = np.nan
    y = np.eye(2, dtype=np.float32)[r.integers(0, 2, 8)]
    return {"features": x, "labels": y}


class TestNanGuard:
    def test_clean_step_passes(self):
        trainer = Trainer(_model(), check_nan=True)
        ts = trainer.init_state(seed=0)
        ts, metrics = trainer.train_step(ts, _batch())
        import jax

        assert np.isfinite(float(jax.device_get(metrics["total_loss"])))

    def test_nan_input_raises_with_op_name(self):
        trainer = Trainer(_model(), check_nan=True)
        ts = trainer.init_state(seed=0)
        with pytest.raises(Exception) as ei:
            ts, metrics = trainer.train_step(ts, _batch(nan=True))
            import jax

            jax.device_get(metrics["total_loss"])
        msg = str(ei.value)
        # checkify names the primitive that produced the first non-finite
        assert "nan" in msg.lower()

    def test_guard_off_by_default_and_nan_flows_through(self):
        trainer = Trainer(_model())
        assert trainer.check_nan is False
        ts = trainer.init_state(seed=0)
        ts, metrics = trainer.train_step(ts, _batch(nan=True))
        import jax

        assert not np.isfinite(float(jax.device_get(metrics["total_loss"])))

    def test_env_flag_enables_guard(self):
        from deeplearning4j_tpu.runtime.environment import (
            Environment,
            get_environment,
            set_environment,
        )

        old = get_environment()
        try:
            set_environment(Environment(check_numerics=True))
            trainer = Trainer(_model())
            assert trainer.check_nan is True
        finally:
            set_environment(old)

    def test_guarded_training_still_learns(self):
        trainer = Trainer(_model(), check_nan=True)
        ts = trainer.init_state(seed=0)
        batch = _batch()
        losses = []
        import jax

        for _ in range(20):
            ts, m = trainer.train_step(ts, batch)
            losses.append(float(jax.device_get(m["total_loss"])))
        assert losses[-1] < losses[0]


class TestCrashReport:
    def test_write_crash_report_contents(self, tmp_path):
        model = _model()
        try:
            raise MemoryError("RESOURCE_EXHAUSTED: out of HBM (simulated)")
        except MemoryError as e:
            path = write_crash_report(str(tmp_path), exception=e, model=model,
                                      step=123, recent_losses=[2.0, 1.5, 1.2])
        assert last_crash_report() == path
        with open(path) as fh:
            rep = json.load(fh)
        assert rep["step"] == 123
        assert rep["recent_losses"] == [2.0, 1.5, 1.2]
        assert rep["exception"]["type"] == "MemoryError"
        assert "RESOURCE_EXHAUSTED" in rep["exception"]["message"]
        assert rep["devices"], "device info missing"
        assert "platform" in rep["devices"][0]
        # config captured as structured JSON (layer list present)
        assert "layers" in json.dumps(rep.get("model_config", {}))

    def test_listener_dump_on_crash(self, tmp_path):
        model = _model()
        trainer = Trainer(model)
        ts = trainer.init_state(seed=0)

        class Boom:
            def __iter__(self):
                yield _batch()
                raise RuntimeError("data pipeline exploded")

        lst = CrashReportingListener(str(tmp_path))
        with pytest.raises(RuntimeError):
            try:
                trainer.fit(ts, Boom(), epochs=1, listeners=[lst])
            except RuntimeError as e:
                p = lst.dump(e, model=model)
                raise
        with open(p) as fh:
            rep = json.load(fh)
        assert rep["exception"]["message"] == "data pipeline exploded"
        assert rep["step"] >= 1  # one good iteration was recorded
        assert rep["recent_losses"]


class TestNanGuardSharded:
    def test_guard_preserves_mesh_shardings(self):
        """r3 review: enabling check_nan must not drop the pjit shardings.
        Small MLP + data-parallel mesh keeps the checkify+pjit compile
        cheap while still exercising the sharded-jit code path."""
        import jax

        from deeplearning4j_tpu.parallel.specs import data_parallel_plan
        from deeplearning4j_tpu.runtime.device import MeshSpec, build_mesh

        mesh = build_mesh(MeshSpec(data=-1), devices_=jax.devices()[:4])
        model = _model()
        ts_template = Trainer(model).init_state()
        ss, bs = data_parallel_plan(mesh)

        trainer = Trainer(model, mesh=mesh, state_sharding=ss,
                          batch_sharding=bs, check_nan=True)
        ts = jax.device_put(ts_template, ss)
        batch = jax.device_put(_batch(), bs)
        ts2, metrics = trainer.train_step(ts, batch)
        assert np.isfinite(float(jax.device_get(metrics["total_loss"])))
        assert int(jax.device_get(ts2.step)) == 1
        # and the guard still fires across shards
        with pytest.raises(Exception, match="(?i)nan"):
            ts3, m = trainer.train_step(ts2, jax.device_put(_batch(nan=True), bs))
            jax.device_get(m["total_loss"])


class TestNanGuardChained:
    def test_chained_step_keeps_guard(self):
        # make_chained_step must carry the checkify guard, not silently
        # drop it (a NaN inside the scan would otherwise only show up in
        # the returned losses)
        trainer = Trainer(_model(), check_nan=True)
        ts = trainer.init_state(seed=0)
        chained = trainer.make_chained_step(3)
        with pytest.raises(Exception):
            out_ts, losses = chained(ts, _batch(nan=True))
            import jax

            jax.device_get(losses)

    def test_chained_step_clean_passes(self):
        trainer = Trainer(_model(), check_nan=True)
        ts = trainer.init_state(seed=0)
        chained = trainer.make_chained_step(3)
        ts, losses = chained(ts, _batch())
        import jax

        assert np.isfinite(np.asarray(jax.device_get(losses))).all()
