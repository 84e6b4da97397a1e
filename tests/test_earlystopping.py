"""Early stopping tests.

ref strategy: deeplearning4j-core TestEarlyStopping — terminate on score
plateau with patience, best-checkpoint retention, invalid-score and
max-score iteration aborts, max-time and max-epochs conditions.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.config import NeuralNetConfiguration, SequentialConfig
from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
from deeplearning4j_tpu.nn.model import SequentialModel
from deeplearning4j_tpu.train.earlystopping import (
    EarlyStoppingConfig,
    EarlyStoppingTrainer,
    InvalidScoreIterationTermination,
    MaxEpochsTermination,
    MaxScoreIterationTermination,
    MaxTimeTermination,
    ScoreImprovementEpochTermination,
)
from deeplearning4j_tpu.train.trainer import Trainer
from deeplearning4j_tpu.train.updaters import Adam, Sgd


def _mlp(lr=1e-2, updater=None):
    cfg = SequentialConfig(
        net=NeuralNetConfiguration(updater=updater or Adam(lr), seed=0),
        layers=[
            Dense(units=16, activation="tanh"),
            OutputLayer(units=2, activation="softmax", loss="mcxent"),
        ],
        input_shape=(8,),
    )
    return SequentialModel(cfg)


def _data(n=32, seed=0):
    r = np.random.default_rng(seed)
    x = r.normal(size=(n, 8)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[(x.sum(-1) > 0).astype(int)]
    return [{"features": jnp.asarray(x), "labels": jnp.asarray(y)}]


def _val_loss_calculator(val_batch):
    def calc(trainer, ts):
        loss, _ = trainer.model.loss_fn(ts.params, ts.model_state, val_batch)
        return float(jax.device_get(loss))
    return calc


class TestConditions:
    def test_score_improvement_patience(self):
        c = ScoreImprovementEpochTermination(patience=2, min_improvement=0.0)
        assert not c.terminate(0, 1.0)   # improvement
        assert not c.terminate(1, 1.0)   # bad 1
        assert not c.terminate(2, 1.0)   # bad 2 == patience
        assert c.terminate(3, 1.0)       # bad 3 > patience
        c.initialize()
        assert not c.terminate(0, 5.0)   # reset works

    def test_max_epochs(self):
        c = MaxEpochsTermination(3)
        assert not c.terminate(1, 0.0)
        assert c.terminate(2, 0.0)

    def test_invalid_score(self):
        c = InvalidScoreIterationTermination()
        assert c.terminate(0, float("nan"))
        assert c.terminate(0, float("inf"))
        assert not c.terminate(0, 3.5)

    def test_max_score(self):
        c = MaxScoreIterationTermination(10.0)
        assert c.terminate(0, 11.0)
        assert not c.terminate(0, 9.0)


class TestEarlyStoppingTrainer:
    def test_terminates_on_plateau_and_returns_best(self):
        """Converging run plateaus; trainer stops via patience and hands back
        the best-scoring state, not the last."""
        model = _mlp()
        trainer = Trainer(model)
        ts = trainer.init_state(seed=0)
        data = _data()
        val = _data(seed=1)[0]

        seen = []
        calc = _val_loss_calculator(val)

        def tracking_calc(tr, state):
            s = calc(tr, state)
            seen.append(s)
            return s

        result = EarlyStoppingTrainer(trainer, EarlyStoppingConfig(
            score_calculator=tracking_calc,
            epoch_terminations=[
                ScoreImprovementEpochTermination(patience=3,
                                                min_improvement=1e-4)],
        )).fit(ts, data, max_epochs=500)

        assert result.termination_reason == "EpochTermination"
        assert result.termination_details == "ScoreImprovementEpochTermination"
        assert result.total_epochs < 500          # actually early-stopped
        assert result.best_epoch in result.score_history
        assert result.best_score == pytest.approx(min(seen))
        # best state reproduces the best score exactly
        assert calc(trainer, result.best_state) == pytest.approx(
            result.best_score, rel=1e-6)
        # ... and the plateau means later epochs were NOT better
        assert result.best_epoch <= result.total_epochs - 1

    def test_save_best_called_on_improvements(self, tmp_path):
        model = _mlp()
        trainer = Trainer(model)
        ts = trainer.init_state(seed=0)
        saved = []

        result = EarlyStoppingTrainer(trainer, EarlyStoppingConfig(
            score_calculator=_val_loss_calculator(_data(seed=1)[0]),
            epoch_terminations=[MaxEpochsTermination(5)],
            save_best=lambda state, score, epoch: saved.append((epoch, score)),
        )).fit(ts, _data(), max_epochs=50)

        assert result.termination_reason == "EpochTermination"
        assert result.total_epochs == 5
        assert saved  # at least the first evaluation improves on inf
        # saved scores are strictly improving
        scores = [s for _, s in saved]
        assert scores == sorted(scores, reverse=True)
        assert saved[-1][1] == pytest.approx(result.best_score)

    def test_invalid_score_aborts_fit(self):
        """A batch that produces a NaN loss trips the iteration guard
        instead of silently training on garbage to max_epochs."""
        model = _mlp()
        trainer = Trainer(model)
        ts = trainer.init_state(seed=0)

        class PoisonAfterFirst:
            """Healthy batch on epoch 1, NaN features from epoch 2 on."""

            def __init__(self):
                self.epochs = 0

            def __iter__(self):
                batch = dict(_data()[0])
                if self.epochs > 0:
                    batch["features"] = batch["features"] * jnp.nan
                self.epochs += 1
                return iter([batch])

        result = EarlyStoppingTrainer(trainer, EarlyStoppingConfig(
            score_calculator=_val_loss_calculator(_data(seed=1)[0]),
            iteration_terminations=[InvalidScoreIterationTermination()],
        )).fit(ts, PoisonAfterFirst(), max_epochs=200)

        assert result.termination_reason == "IterationTermination"
        assert result.termination_details == "InvalidScoreIterationTermination"
        assert result.total_epochs < 200

    def test_max_score_aborts_fit(self):
        model = _mlp(updater=Sgd(1e4))
        trainer = Trainer(model)
        ts = trainer.init_state(seed=0)

        result = EarlyStoppingTrainer(trainer, EarlyStoppingConfig(
            score_calculator=_val_loss_calculator(_data(seed=1)[0]),
            iteration_terminations=[MaxScoreIterationTermination(50.0),
                                    InvalidScoreIterationTermination()],
        )).fit(ts, _data(), max_epochs=200)

        assert result.termination_reason == "IterationTermination"
        assert result.termination_details in (
            "MaxScoreIterationTermination",
            # a clean NaN can race past the bound check numerically; either
            # abort is a correct outcome for a diverging run
            "InvalidScoreIterationTermination")

    def test_max_time(self):
        model = _mlp()
        trainer = Trainer(model)
        ts = trainer.init_state(seed=0)

        result = EarlyStoppingTrainer(trainer, EarlyStoppingConfig(
            score_calculator=_val_loss_calculator(_data(seed=1)[0]),
            epoch_terminations=[MaxTimeTermination(0.0)],
        )).fit(ts, _data(), max_epochs=100)

        assert result.termination_reason == "EpochTermination"
        assert result.termination_details == "MaxTimeTermination"
        assert result.total_epochs == 1

    def test_max_epochs_fallback_reason(self):
        model = _mlp()
        trainer = Trainer(model)
        ts = trainer.init_state(seed=0)

        result = EarlyStoppingTrainer(trainer, EarlyStoppingConfig(
            score_calculator=_val_loss_calculator(_data(seed=1)[0]),
        )).fit(ts, _data(), max_epochs=3)

        assert result.termination_reason == "MaxEpochs"
        assert result.total_epochs == 3
        assert math.isfinite(result.best_score)
