"""The host's side of a training run on one clock
(``observability/trace.py::Timeline`` and ``IterationLegs``,
``observability/runtime.py::compile_events``): a fit's iterations as rows
under its root entry, the compilations with their time, stage and
function, and set-up's phases as spans."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.gpt import gpt_tiny
from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
from deeplearning4j_tpu.observability import metrics, runtime, trace, vocab
from deeplearning4j_tpu.resilience.recovery import FaultTolerantTrainer
from deeplearning4j_tpu.train.trainer import Trainer
from deeplearning4j_tpu.train.updaters import Adam


def _trainer():
    net = NeuralNetConfiguration(updater=Adam(1e-3))
    return Trainer(gpt_tiny(net=net))


def _batches(n):
    rng = np.random.default_rng(0)
    return [{"features": {"token_ids": rng.integers(
        0, 100, (4, 16)).astype(np.int32)}} for _ in range(n)]


def _plain_fit(trainer, ts, data, tmp_path):
    return trainer.fit(ts, data)


def _fault_tolerant_fit(trainer, ts, data, tmp_path):
    return FaultTolerantTrainer(trainer, str(tmp_path / "ckpt")).fit(ts, data)


@pytest.fixture
def timeline():
    tl = trace.get_timeline()
    tl.clear()
    yield tl
    tl.clear()


def _ordered_inside(rows, fit):
    for row in rows:
        marks = row[2:]
        assert list(marks) == sorted(marks), row
        assert fit.start <= marks[0] and marks[-1] <= fit.end


@pytest.mark.parametrize("fit", [_plain_fit, _fault_tolerant_fit])
def test_a_fit_leaves_a_row_a_batch_ordered_and_inside_the_fit(
        fit, timeline, tmp_path):
    trainer = _trainer()
    fit(trainer, trainer.init_state(), _batches(5), tmp_path)
    root, = timeline.fits()
    rows = timeline.rows(root)
    assert root.steps == len(rows) == 5
    assert [r[1] for r in rows] == [1, 2, 3, 4, 5]
    assert {r[0] for r in rows} == {root.id}
    assert root.thread == threading.current_thread().name
    _ordered_inside(rows, root)

    spans = timeline.spans()
    assert [s.name for s in spans[:6]] == [
        trace.FIT, trace.ITERATION, *trace.LEGS]
    assert len(spans) == 1 + 5 * 5
    assert {s.name for s in spans} <= vocab.HOST_SPANS
    assert {s.trace_id for s in spans} == {root.id}
    top = spans[0]
    assert top.parent_id is None and top.attrs["steps"] == 5
    steps = [s for s in spans if s.name == trace.ITERATION]
    assert [s.attrs["step"] for s in steps] == [1, 2, 3, 4, 5]
    assert {s.parent_id for s in steps} == {top.span_id}
    for leg in (s for s in spans if s.name in trace.LEGS):
        parent, = (s for s in steps if s.span_id == leg.parent_id)
        assert parent.start <= leg.start <= leg.end <= parent.end
    # the spans are made when asked for, into a ring of their own
    assert not [s for s in trace.get_tracer().spans() if s.name == trace.FIT]
    # and on the clock of trace.now()
    assert abs(top.start - trace.from_perf_counter(root.start)) < 1e-6
    assert top.end <= trace.now()


def test_a_second_fit_has_rows_of_its_own(timeline):
    trainer = _trainer()
    ts = trainer.fit(trainer.init_state(), _batches(3))
    trainer.fit(ts, _batches(2))
    first, second = timeline.fits()
    assert first.id != second.id and first.end <= second.start
    assert [r[1] for r in timeline.rows(first)] == [1, 2, 3]
    assert [r[1] for r in timeline.rows(second)] == [4, 5]
    assert (first.steps, second.steps) == (3, 2)
    _ordered_inside(timeline.rows(second), second)
    assert len(timeline.spans(first)) == 16
    assert len(timeline.spans()) == 11  # the last fit, unless told


def _drive(legs, steps, *, skip=(), fail=()):
    """A loop of the helper alone, as the two fit loops write it."""
    for n in steps:
        with legs.step(n):
            with legs.read:
                pass
            if n in skip:
                continue
            with legs.put:
                pass
            try:
                with legs.dispatch:
                    if n in fail:
                        raise FloatingPointError("nan")
            except FloatingPointError:
                continue
            with legs.listeners:
                pass
    legs.close()


def test_the_ring_drops_the_oldest_rows_beyond_its_bound():
    tl = trace.Timeline(rows_kept=4)
    legs = trace.IterationLegs(metrics.get_training_metrics(), timeline=tl)
    _drive(legs, range(1, 11))
    fit, = tl.fits()
    assert fit.steps == 10
    assert [r[1] for r in tl.rows(fit)] == [7, 8, 9, 10]
    assert len(tl.spans(fit)) == 1 + 4 * 5
    assert trace.get_timeline()._rows.maxlen == 16384


def test_only_an_iteration_that_dispatched_a_step_leaves_a_row():
    """The feed's end, a skipped batch and a step that raised (a
    rollback) write nothing and observe nothing."""
    tl = trace.Timeline()
    om = metrics.get_training_metrics()
    before = (om.step_seconds.summary()["count"],
              om.data_read_seconds.summary()["count"])
    legs = trace.IterationLegs(om, timeline=tl)
    _drive(legs, range(1, 7), skip={2}, fail={4})
    fit, = tl.fits()
    assert [r[1] for r in tl.rows(fit)] == [1, 3, 5, 6] and fit.steps == 4
    after = (om.step_seconds.summary()["count"],
             om.data_read_seconds.summary()["count"])
    assert (after[0] - before[0], after[1] - before[1]) == (4, 4)
    assert legs.read_s >= 0 and legs.step_s >= 0


def test_the_factory_of_the_annotations_can_be_a_tools_own():
    """``benchmark/tools/host_stalls.py`` times the legs its own way by
    replacing ``trainer._annotate``."""
    opened = []

    class Own:
        def __init__(self, name, **attrs):
            opened.append((name, attrs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    _drive(trace.IterationLegs(None, annotate=Own), [7])
    assert opened == [("train.step", {"step_num": 7}), ("train.read", {}),
                      ("train.put", {}), ("train.dispatch", {}),
                      ("train.listeners", {})]


def test_with_the_metrics_off_nothing_is_recorded_and_the_fit_is_the_same(
        timeline):
    trainer = _trainer()
    data = _batches(4)
    om = metrics.get_training_metrics()
    with_it = trainer.fit(trainer.init_state(seed=3), data)
    assert len(timeline.fits()) == 1
    count = om.step_seconds.summary()["count"]
    events = len(runtime.compile_events())
    metrics.set_enabled(False)
    try:
        without = trainer.fit(trainer.init_state(seed=3), data)
        jax.jit(lambda x: x * 3 + 1)(jnp.ones(3))
    finally:
        metrics.set_enabled(True)
    assert len(timeline.fits()) == 1 and len(timeline._rows) == 4
    assert om.step_seconds.summary()["count"] == count
    assert len(runtime.compile_events()) == events
    for a, b in zip(jax.tree_util.tree_leaves(with_it.params),
                    jax.tree_util.tree_leaves(without.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("fit", [_plain_fit, _fault_tolerant_fit])
def test_the_two_histograms_count_a_reading_a_step_as_before(
        fit, timeline, tmp_path):
    trainer = _trainer()
    om = metrics.get_training_metrics()
    before = {h: getattr(om, h).summary() for h in
              ("step_seconds", "data_read_seconds")}
    steps = om.steps_total.value()
    fit(trainer, trainer.init_state(), _batches(6), tmp_path)
    for name, was in before.items():
        now = getattr(om, name).summary()
        assert now["count"] - was["count"] == 6, name
        assert now["sum"] >= was["sum"]
    assert om.steps_total.value() - steps == 6
    # and they are the rows' own readings: each leg is timed once
    rows = timeline.rows(timeline.fits()[-1])
    read = sum(r[3] - r[2] for r in rows)
    step = sum(r[5] - r[4] for r in rows)
    assert om.data_read_seconds.summary()["sum"] - \
        before["data_read_seconds"]["sum"] == pytest.approx(read, abs=1e-9)
    assert om.step_seconds.summary()["sum"] - \
        before["step_seconds"]["sum"] == pytest.approx(step, abs=1e-9)


# -- compile events -------------------------------------------------------------

def _named(events, fun):
    return [e for e in events if e["fun_name"] and fun in e["fun_name"]]


def test_a_fresh_jit_yields_events_with_its_name_inside_the_span():
    runtime.watch_compiles()
    before = len(runtime.compile_events())

    def never_seen_before(x):
        for _ in range(40):  # long enough a trace to be kept
            x = jnp.sin(x) @ x
        return x

    with trace.span("a.phase", tracer=trace.Tracer()) as phase:
        jax.jit(never_seen_before)(jnp.ones((8, 8))).block_until_ready()
    mine = _named(runtime.compile_events()[before:], "never_seen_before")
    kinds = [e["kind"] for e in mine]
    assert kinds.count("backend_compile_duration") == 1
    assert kinds.count("jaxpr_to_mlir_module_duration") == 1
    assert "jaxpr_trace_duration" in kinds
    for e in mine:
        assert phase.start <= e["end"] - e["seconds"] and e["end"] <= phase.end
        assert e["thread"] == threading.current_thread().name
        assert e["seconds"] >= 0
    # the jnp functions a traced function calls are traces of their own,
    # microseconds each: they are not kept
    assert not [e for e in runtime.compile_events()[before:]
                if e["kind"] == "jaxpr_trace_duration"
                and e["seconds"] < 1e-3]


def test_a_background_threads_compile_is_on_its_own_line():
    runtime.watch_compiles()
    before = len(runtime.compile_events())
    t = threading.Thread(
        target=lambda: jax.jit(lambda x: jnp.cos(x) * 7)(jnp.ones(5)),
        name="a-compiling-thread")
    t.start()
    t.join(60)
    assert not t.is_alive()
    threads = {e["thread"] for e in runtime.compile_events()[before:]
               if e["kind"] == "backend_compile_duration"}
    assert threads == {"a-compiling-thread"}


def test_the_second_compile_of_a_program_counts_a_hit(tmp_path):
    from jax._src import compilation_cache as jax_cc

    runtime.watch_compiles()
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs,
           jax.config.jax_persistent_cache_min_entry_size_bytes)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cc"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax_cc.reset_cache()
    try:
        def cached_twice(x):
            return jnp.tanh(x) @ x + 11

        counts = runtime.cache_counts()
        before = len(runtime.compile_events())
        jax.jit(cached_twice)(jnp.ones((8, 8))).block_until_ready()
        jax.clear_caches()  # the process forgets; the directory does not
        jax.jit(cached_twice)(jnp.ones((8, 8))).block_until_ready()
        events = _named(runtime.compile_events()[before:], "cached_twice")
        compiles = [e for e in events
                    if e["kind"] == "backend_compile_duration"]
        assert [e["cache"] for e in compiles] == ["miss", "hit"]
        now = runtime.cache_counts()
        assert now["hit"] - counts["hit"] >= 1
        assert now["miss"] - counts["miss"] >= 1
        # the cache's own timings take the name of the compile they belong to
        assert [e["kind"] for e in events].count(
            "cache_retrieval_time_sec") == 1
    finally:
        jax.config.update("jax_compilation_cache_dir", was[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", was[1])
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", was[2])
        jax_cc.reset_cache()


def test_a_cache_read_under_the_program_tables_lock_does_not_wait_for_it(
        monkeypatch):
    """``program_table()`` fetches a pending program's text with its lock
    held, and on a machine with a persistent cache that fetch is a cache
    hit, reported on the same thread (the first traced run on the chip
    stood still here)."""
    monkeypatch.setattr(runtime, "_PROGRAMS", {})
    counts = runtime.cache_counts()

    def text():
        runtime._dispatch_cache_event("/jax/compilation_cache/cache_hits")
        runtime._dispatch_event(
            "/jax/core/compile/backend_compile_duration", 0.25,
            fun_name="jit(train_step)")
        return "HloModule jit_train_step\n"

    runtime.publish_program("jit_train_step", flops=1.0, text=text)
    done = []
    t = threading.Thread(target=lambda: done.append(runtime.program_table()),
                         name="reads-the-table")
    t.start()
    t.join(20)
    assert not t.is_alive() and "jit_train_step" in done[0]
    assert runtime.cache_counts()["hit"] == counts["hit"] + 1
    event = runtime.compile_events()[-1]
    assert (event["cache"], event["thread"]) == ("hit", "reads-the-table")
    resolve = _phase("program_table.resolve")[-1]
    assert resolve.start <= event["end"] <= resolve.end


def test_compiles_are_still_counted_and_timed_in_the_registry():
    collector = runtime.get_runtime_collector()
    x = jnp.ones(7)
    n = collector.jit_compiles_total.value()
    s = collector.jit_compile_seconds.summary()["count"]
    jax.jit(lambda x: x * 5 - 2)(x).block_until_ready()
    assert collector.jit_compiles_total.value() == n + 1
    assert collector.jit_compile_seconds.summary()["count"] == s + 1


# -- set-up's phases --------------------------------------------------------------

def _phase(name, since=0.0):
    return [s for s in trace.get_tracer().spans()
            if s.name == name and s.start >= since]


def test_set_ups_phases_are_spans_with_the_compiles_inside(timeline):
    since = trace.now()
    before = len(runtime.compile_events())
    trainer = _trainer()
    ts = trainer.init_state()
    ts = trainer.fit(ts, _batches(1))  # the first dispatch compiles
    for t in threading.enumerate():
        if t.name == "step-cost-analysis":
            t.join(120)
    init, = _phase("train.init_state", since)
    assert init.thread == threading.current_thread().name
    cost, = _phase("train.step_cost_analysis", since)
    assert cost.thread == "step-cost-analysis" and cost.duration > 0
    # the step compiled inside iteration 1's train.dispatch; whatever the
    # analysis compiled (nothing, where jax still holds the step it just
    # compiled for the same shapes) is inside its span, on its thread
    events = runtime.compile_events()[before:]
    step, = (e for e in _named(events, "train_step")
             if e["kind"] == "backend_compile_duration"
             and e["thread"] == threading.current_thread().name)
    row, = timeline.rows(timeline.fits()[-1])
    assert row[4] <= step["end"] - trace.from_perf_counter(0.0) <= row[5]
    for e in events:
        if e["thread"] == "step-cost-analysis":
            assert cost.start <= e["end"] <= cost.end
    assert trainer.step_description() is not None  # fetches the text
    resolve, = _phase("program_table.resolve", since)
    assert resolve.attrs["module"] == "jit_train_step"


def test_the_packages_import_is_a_span():
    span, = _phase("import.deeplearning4j_tpu")
    assert span.duration > 0 and span.end <= trace.now()
    assert span.name in vocab.HOST_SPANS


# -- what the helper costs --------------------------------------------------------

def test_the_helper_is_a_few_microseconds_an_iteration():
    """A coarse guard on a shared machine (the budget, 2 us over the
    parent's loop, is measured on the chip's host: ``PERF.md``): five legs,
    five clock readings, two histogram readings and a row."""
    tl = trace.Timeline()
    legs = trace.IterationLegs(metrics.get_training_metrics(), timeline=tl)
    n = 2000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        _drive(legs, range(n))
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 100e-6
