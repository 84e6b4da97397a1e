"""The host loop's readers (``benchmark/harness/host_readers.py``) on
hand-made timelines, their manifest entries, and on the timeline a tiny
``Trainer.fit`` leaves on the CPU."""

import json

import numpy as np
import pytest

from benchmark.harness import host_readers, manifest
from benchmark.harness.readers import Context
from deeplearning4j_tpu.observability import runtime, trace

METRICS = {"host_ms_per_step": ("ms", "program_span"),
           "host_stall_share_window": ("%", "program_span"),
           "compiles_in_fit": ("count", "program_counter")}
NOTES = {"fit", "host_stalls", "setup_phases", "compile_events"}
MS = 1e-3
# a quiet iteration: the feed's wait for the device, then the host's own
QUIET = {"train.read": 8 * MS, "train.put": 0.1 * MS,
         "train.dispatch": 1 * MS, "train.listeners": 0.9 * MS}


def hand_made(iterations, *, compiles=(), before=2.0, phases=()):
    """A timeline as ``host_readers._timeline`` hands it over: one fit of
    ``iterations`` (each a dict of leg seconds) that opens ``before``
    seconds after the process started at 1,000 s."""
    ring = trace.Tracer(capacity=1 + 5 * len(iterations))
    origin, fit = 1000.0, trace.new_id()
    start = at = origin + before
    end = start + sum(sum(legs.values()) for legs in iterations)
    root = trace.record_span(host_readers.FIT, start=start, end=end,
                             trace_id=fit, tracer=ring, thread="MainThread",
                             steps=len(iterations))
    for n, legs in enumerate(iterations, 1):
        step = trace.record_span(
            host_readers.STEP, start=at, end=at + sum(legs.values()),
            trace_id=fit, parent_id=root.span_id, tracer=ring,
            thread="MainThread", step=n)
        for name in host_readers.LEGS:
            trace.record_span(name, start=at, end=at + legs[name],
                              trace_id=fit, parent_id=step.span_id,
                              tracer=ring, thread="MainThread")
            at += legs[name]
    return {"fit": ring.spans(), "fits": [root], "phases": list(phases),
            "compiles": [dict(e, end=origin + e["end"]) for e in compiles],
            "cache": {"hit": 0, "miss": 0}, "origin": origin}


def compile_event(end, fun_name, *, seconds=0.5, cache=None,
                  kind=host_readers.COMPILED, thread="MainThread"):
    return {"kind": kind, "seconds": seconds, "end": end,
            "fun_name": fun_name, "thread": thread, "cache": cache}


def read_all(monkeypatch, timeline):
    monkeypatch.setattr(host_readers, "_timeline", lambda: timeline)
    ctx = Context(trace=None, counters={}, peaks={}, cell=None)
    values = {name: host_readers.window_metric(ctx, name=name)
              for name in METRICS}
    return values, ctx.notes


def with_one(slow):
    rows = [dict(QUIET) for _ in range(20)]
    rows[11] = dict(QUIET, **slow)
    return rows


def test_a_quiet_run_reads_nought(monkeypatch):
    values, notes = read_all(monkeypatch, hand_made(with_one({})))
    assert values["host_ms_per_step"] == pytest.approx(2.0)
    assert values["host_stall_share_window"] == 0.0
    assert values["compiles_in_fit"] == 0.0
    assert notes["fit"]["steps"] == 20
    assert notes["fit"]["seconds"] == pytest.approx(0.2)
    assert notes["fit"]["opens_at_s"] == pytest.approx(2.0)
    assert notes["fit"]["legs_ms"] == pytest.approx(
        {leg: 1e3 * s for leg, s in QUIET.items()})
    assert len(notes["host_stalls"]) == 10 and set(notes) == NOTES
    json.dumps(notes)  # the line prints them


@pytest.mark.parametrize("leg", ["train.read", "train.put",
                                 "train.dispatch"])
def test_an_iteration_of_ten_times_the_median_reads_its_excess(
        monkeypatch, leg):
    """Ten iterations' time in one, whichever leg outside the listeners
    holds it: the excess over twice the median of the time outside
    ``train.listeners`` (9.1 ms), as a share of the fit."""
    slow = {leg: QUIET[leg] + 90 * MS}
    values, notes = read_all(monkeypatch, hand_made(with_one(slow)))
    excess = (9.1 + 90) - 2 * 9.1
    assert values["host_stall_share_window"] == pytest.approx(
        100 * excess / 290)
    worst = notes["host_stalls"][0]
    assert worst["step"] == 12 and worst["seconds"] == pytest.approx(0.1)
    assert worst[leg] == pytest.approx(slow[leg])
    # the median keeps one iteration out of the host's usual time
    assert values["host_ms_per_step"] == pytest.approx(2.0)


def test_the_same_excess_inside_the_listeners_is_no_stall(monkeypatch):
    """The harness starts and stops its profiler in ``Probe.on_iteration``:
    the window's share leaves it out, the note names it."""
    slow = {"train.listeners": QUIET["train.listeners"] + 90 * MS}
    values, notes = read_all(monkeypatch, hand_made(with_one(slow)))
    assert values["host_stall_share_window"] == 0.0
    assert values["host_ms_per_step"] == pytest.approx(2.0)
    worst = notes["host_stalls"][0]
    assert worst["step"] == 12
    assert worst["train.listeners"] == pytest.approx(90.9 * MS)
    assert worst["train.read"] == pytest.approx(8 * MS)


def test_a_compile_inside_the_fit_counts_and_one_outside_does_not(
        monkeypatch):
    compiles = [
        compile_event(1.5, "jit(make_weights)", cache="hit"),   # set-up
        compile_event(2.05, "jit(train_step)", cache="miss"),   # the window
        compile_event(2.10, "jit(train_step)", seconds=0.02,
                      kind="jaxpr_trace_duration"),              # no compile
        compile_event(2.15, "jit(train_step)", thread="step-cost-analysis"),
        compile_event(1.5, "jit(make_weights)", seconds=30.0,
                      kind="compile_time_saved_sec"),            # not spent
        compile_event(9.0, "jit(reference)"),                    # after it
    ]
    values, notes = read_all(
        monkeypatch, hand_made(with_one({}), compiles=compiles))
    assert values["compiles_in_fit"] == 2.0
    assert notes["fit"]["compiled_in_fit"] == ["jit(train_step)"] * 2
    totals = notes["compile_events"]["totals"]
    assert (totals["compiles"], totals["hits"], totals["misses"]) == (3, 1, 1)
    assert totals["compile_s"] == pytest.approx(1.5)
    assert totals["trace_s"] == pytest.approx(0.02)
    assert totals["cache_saved_s"] == pytest.approx(30.0)
    listed = notes["compile_events"]["longest"]
    assert "jit(reference)" not in {e["fun_name"] for e in listed}
    assert "compile_time_saved_sec" not in {e["kind"] for e in listed}
    by_name = {(e["fun_name"], e["thread"]): e for e in listed
               if e["kind"] == host_readers.COMPILED}
    assert by_name[("jit(make_weights)", "MainThread")]["phase"] == "unowned"
    assert by_name[("jit(train_step)", "MainThread")]["phase"] == "train.fit"
    assert by_name[("jit(make_weights)", "MainThread")]["cache"] == "hit"
    assert totals["outside_any_phase"] == 0


def test_set_ups_account_reaches_from_the_start_to_the_windows_opening(
        monkeypatch):
    """The phases on the window's thread and what lies between them add up
    to the time the window opened at; another thread's phase is listed and
    fills no gap."""
    ring = trace.Tracer()
    spans = [trace.record_span(name, start=1000 + a, end=1000 + b,
                               trace_id="t", thread=thread, tracer=ring)
             for name, a, b, thread in (
                 ("import.deeplearning4j_tpu", 0.2, 0.9, "MainThread"),
                 ("train.init_state", 1.0, 1.1, "MainThread"),
                 ("train.step_cost_analysis", 1.2, 1.9,
                  "step-cost-analysis"))]
    first = trace.record_span(host_readers.FIT, start=1001.1, end=1001.7,
                              trace_id="f", thread="MainThread", tracer=ring)
    timeline = hand_made(with_one({}), phases=spans)
    timeline["fits"].insert(0, first)
    _, notes = read_all(monkeypatch, timeline)
    rows = notes["setup_phases"]
    assert [r["name"] for r in rows] == [
        "unowned", "import.deeplearning4j_tpu", "unowned",
        "train.init_state", "train.fit", "train.step_cost_analysis",
        "unowned", "train.fit"]
    on_thread = [r for r in rows[:-1] if r["thread"] == "MainThread"]
    assert sum(r["seconds"] for r in on_thread) == pytest.approx(
        notes["fit"]["opens_at_s"])
    assert rows[-1]["at_s"] == pytest.approx(2.0)


@pytest.mark.parametrize("timeline", [None, {"fit": []}])
def test_no_timeline_gives_nothing(monkeypatch, timeline):
    values, notes = read_all(monkeypatch, timeline)
    assert values == dict.fromkeys(METRICS) and notes == {}


def test_a_program_without_the_timeline_gives_nothing(monkeypatch):
    """A parent commit's ``observability/trace.py`` has no timeline."""
    monkeypatch.delattr(trace, "get_timeline")
    assert host_readers._timeline() is None
    monkeypatch.undo()
    monkeypatch.delattr(runtime, "compile_events")
    assert host_readers._timeline() is None


def test_no_fit_in_this_process_gives_nothing(monkeypatch):
    monkeypatch.setattr(trace, "_TIMELINE", trace.Timeline())
    ctx = Context(trace=None, counters={}, peaks={}, cell=None)
    assert host_readers.window_metric(ctx, name="host_ms_per_step") is None
    assert ctx.notes == {}


# -- the manifest -----------------------------------------------------------------

@pytest.fixture(scope="module")
def doc():
    return manifest.load_json(manifest.MANIFEST)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_entry_names_the_host_loop_and_the_five_cells_in_order(doc, name):
    entry, = (m for m in doc["per_layer"] if m["name"] == name)
    unit, source = METRICS[name]
    assert entry == {
        "name": name, "unit": unit, "better": "lower", "source": source,
        "layer": "host loop", "moves": "train_tok_s_chip",
        "workloads": [w["name"] for w in doc["workloads"]]}
    assert len(entry["workloads"]) == 5
    metric = manifest.load_metric(entry, manifest.BENCH_DIR + "/metrics")
    assert metric.reader is host_readers.window_metric
    assert metric.args == {"name": name}


def test_they_are_the_benchmarks_first_program_span_metrics(doc):
    spans = [m["name"] for m in doc["per_layer"]
             if m["source"] == "program_span"]
    assert spans == ["host_ms_per_step", "host_stall_share_window"]
    # appended: what stood before them stands as it stood
    assert [m["name"] for m in doc["per_layer"][-3:]] == [
        "host_ms_per_step", "host_stall_share_window", "compiles_in_fit"]


def test_every_cell_reports_the_three(doc):
    for w in doc["workloads"]:
        names = [m.name for m in manifest.load_cell(w["name"]).per_layer]
        assert names[-3:] == ["host_ms_per_step", "host_stall_share_window",
                              "compiles_in_fit"], w["name"]


# -- on a program's own timeline ----------------------------------------------------

def test_after_a_tiny_fit_the_readers_need_no_device_trace():
    from deeplearning4j_tpu.models.gpt import gpt_tiny
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.train.trainer import Trainer
    from deeplearning4j_tpu.train.updaters import Adam

    trainer = Trainer(gpt_tiny(net=NeuralNetConfiguration(updater=Adam(1e-3))))
    batch = {"features": {"token_ids": np.zeros((4, 16), np.int32)}}
    ts = trainer.fit(trainer.init_state(), [batch] * 3)   # set-up's steps
    compiled = len([e for e in runtime.compile_events()
                    if e["kind"] == host_readers.COMPILED])
    trainer.fit(ts, [batch] * 7)                          # the window
    cell = manifest.load_cell("bert_base.train_s128")
    ctx = Context(trace=None, counters={}, peaks={}, cell=cell)
    values = {m.name: m.reader(ctx, **m.args) for m in cell.per_layer
              if m.name in METRICS}
    assert set(values) == set(METRICS)
    assert values["host_ms_per_step"] > 0
    assert 0 <= values["host_stall_share_window"] < 100
    assert values["compiles_in_fit"] == 0.0
    assert NOTES <= set(ctx.notes)
    assert ctx.notes["fit"]["steps"] == 7
    assert ctx.notes["fit"]["seconds"] > 0
    assert [r["step"] for r in sorted(ctx.notes["host_stalls"],
                                      key=lambda r: r["step"])] == list(
        range(4, 11))
    names = [r["name"] for r in ctx.notes["setup_phases"]]
    assert names.count("train.fit") >= 2 and "train.init_state" in names
    assert "import.deeplearning4j_tpu" in names
    totals = ctx.notes["compile_events"]["totals"]
    assert totals["compiles"] >= compiled >= 1
    assert totals["outside_any_phase"] == 0
    # this trainer's step compiled in its first fit (an earlier test's
    # events, where the process ran one, lie before it)
    first = [r for r in ctx.notes["setup_phases"]
             if r["name"] == "train.fit"][-2]
    steps = [e for e in ctx.notes["compile_events"]["longest"]
             if e["kind"] == host_readers.COMPILED
             and "train_step" in (e["fun_name"] or "")
             and e["end_at_s"] >= first["at_s"]]
    assert "train.fit" in {e["phase"] for e in steps} <= {
        "train.fit", "train.step_cost_analysis"}
    json.dumps(ctx.notes)
