"""The training kind at a tiny size on the CPU: a whole run of the harness
without its look for a chip, sound and with the timed path broken
underneath, and the control put in the program's place."""

import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest

import benchmark_tiny
from benchmark.configs import reference_common as rc
from benchmark.harness import traffic, verdict
from benchmark.kinds import train

SEED = 2**31 + 99
CELLS = ["tiny_bert.tiny_mlm", "tiny_gpt.tiny_clm"]


def run(tmp_path, cell_name, **kw):
    cell = benchmark_tiny.load(str(tmp_path), cell_name)
    return train.run(cell, seed=SEED, seconds=1.0, trace=False,
                     t_start=time.perf_counter(), require_tpu=False,
                     scratch=str(tmp_path / "scratch"), **kw)


@pytest.mark.parametrize("cell_name", CELLS)
def test_a_sound_run_is_correct_and_prints_the_line(tmp_path, cell_name):
    result = run(tmp_path, cell_name)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 3
    assert set(result["metrics"]) == {"train_tok_s_chip", "setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert list(result)[-1] == "compared"
    for row in result["compared"]:
        if "limit" in row:
            assert row["value"] <= row["limit"]


def unchanged_state(build):
    def build_trainer(cell):
        trainer = build(cell)
        step = trainer.train_step

        def same(ts, batch):
            kept = jax.tree_util.tree_map(jnp.copy, ts)  # ts is donated
            _, metrics = step(ts, batch)
            return kept, metrics
        trainer.train_step = same
        return trainer
    return build_trainer


def half_left_out(next_batch):
    def __next__(self):
        batch = next_batch(self)
        return jax.tree_util.tree_map(lambda a: a[: a.shape[0] // 2], batch)
    return __next__


@pytest.mark.parametrize("cell_name", CELLS)
def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        tmp_path, monkeypatch, cell_name):
    monkeypatch.setattr(train, "build_trainer",
                        unchanged_state(train.build_trainer))
    result = run(tmp_path, cell_name)
    assert result["correct"] is False
    rows = {r["name"]: r for r in result["compared"]}
    assert rows["change_norm_gap"]["value"] == pytest.approx(1.0, abs=1e-6)


def a_fifth_faster(build):
    def build_trainer(cell):
        adam = dict(cell.config["training"]["adam"])
        adam["lr"] *= 1.2
        training = dict(cell.config["training"], adam=adam)
        return build(dataclasses.replace(
            cell, config=dict(cell.config, training=training)))
    return build_trainer


@pytest.mark.parametrize("cell_name", CELLS)
def test_a_learning_rate_a_fifth_off_is_not_correct(
        tmp_path, monkeypatch, cell_name):
    """Under Adam every leaf's change grows with the learning rate, so the
    median leaf's reads the fifth; no other number sees it."""
    monkeypatch.setattr(train, "build_trainer",
                        a_fifth_faster(train.build_trainer))
    result = run(tmp_path, cell_name)
    assert result["correct"] is False
    rows = {r["name"]: r for r in result["compared"]}
    assert rows["change_median_gap"]["value"] == pytest.approx(0.2, abs=0.02)
    assert rows["change_median_gap"]["ok"] is False
    assert rows["grad_share_gap"]["ok"] is True


@pytest.mark.parametrize("cell_name", CELLS)
def test_half_of_the_batch_left_out_is_not_correct(
        tmp_path, monkeypatch, cell_name):
    monkeypatch.setattr(train.Feed, "__next__",
                        half_left_out(train.Feed.__next__))
    result = run(tmp_path, cell_name)
    assert result["correct"] is False


@pytest.mark.parametrize("cell_name", CELLS)
def test_the_control_in_the_programs_place_is_not_correct(tmp_path, cell_name):
    """The reference computed in fp8, the precision below the bf16 that
    the configurations state, fails at least one number's limit."""
    cell = benchmark_tiny.load(str(tmp_path), cell_name)
    batches = traffic.generate(cell, SEED, 1.0)[:3]
    reference = train.follow_reference(cell, SEED, batches)
    control = train.follow_reference(cell, SEED, batches, "fp8")
    sizes = rc.leaf_sizes(cell.reference.param_shapes(cell.config))
    correct, rows = verdict.judge(
        verdict.training_numbers(control, reference, sizes),
        cell.workload["limits"])
    assert correct is False, rows
