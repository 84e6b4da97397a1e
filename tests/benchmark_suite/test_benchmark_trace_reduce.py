"""The trace reducer, on hand-made intervals and on the recorded trace in
``benchmark/fixtures/`` (four train steps of a one-layer gpt2-width model
at 1 x 1024 on a TPU v5e, with a 20 ms sleep after the second)."""

import os

import pytest

import benchmark_tiny  # noqa: F401  (puts the repo's root on sys.path)
from benchmark.harness import manifest, trace_reduce as tr

FIXTURE = os.path.join(manifest.BENCH_DIR, "fixtures",
                       "gpt2_1layer_4steps.xplane.pb.gz")
MOSAIC = 'custom_call_target="tpu_custom_call"'


def test_merge_is_the_union():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == [
        (0, 4), (5, 7)]
    assert tr.length(tr.merge([(0, 10), (2, 3), (8, 12)])) == 12


def test_subtract_leaves_what_is_uncovered():
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [
        (0, 1), (2, 4), (6, 9)]
    assert tr.subtract([(0, 3), (5, 8)], [(2, 6)]) == [(0, 2), (6, 8)]
    assert tr.subtract([(0, 3)], []) == [(0, 3)]


def test_clip_cuts_events_to_the_window():
    events = [("a", 0, 5), ("b", 4, 12), ("c", 20, 30)]
    assert tr.clip(events, (3, 10)) == [("a", 3, 5), ("b", 4, 10)]


def test_op_name_keeps_the_custom_call_target():
    assert tr.op_name("%fusion.7 = bf16[8]{0} fusion(bf16[8] %x)") == "fusion.7"
    assert tr.op_name('%jvp__.1 = f32[2] custom-call(f32[2] %q), '
                      'custom_call_target="tpu_custom_call"') == (
        "jvp__.1[tpu_custom_call]")


@pytest.fixture(scope="module")
def sliced():
    return tr.reduce_trace(FIXTURE, step_module="jit_train_step")


def test_slice_runs_from_the_first_step_to_the_last(sliced):
    # four runs of the step program: three whole steps between their starts
    assert sliced.steps == 3
    assert len(sliced.devices) == 1
    assert sliced.window_s == pytest.approx(0.036695874, rel=1e-6)


def test_busy_union_and_idle_share(sliced):
    assert sliced.busy_s == pytest.approx(0.014763865, rel=1e-6)
    # three steps of 4.92 ms each are busy; the rest is the sleep
    assert sliced.busy_s / sliced.steps == pytest.approx(4.92e-3, rel=0.01)
    idle = 1 - sliced.busy_s / sliced.window_s
    assert idle == pytest.approx(0.5977, abs=1e-3)
    device = sliced.devices[0]
    assert tr.length(device.gaps()) / 1e9 == pytest.approx(
        sliced.window_s - sliced.busy_s, rel=1e-9)


def test_gap_list_names_the_sleep(sliced):
    gaps = sliced.idle_gaps()
    assert len(gaps) <= 10
    assert gaps[0][0] == "$time sleep"
    assert gaps[0][1] == pytest.approx(0.0219, abs=2e-4)
    assert all(a[1] >= b[1] for a, b in zip(gaps, gaps[1:]))


def test_kernel_event_sum(sliced):
    # one layer: the flash forward and two backward kernels, three steps
    seconds, count = sliced.matching_s(MOSAIC)
    assert count == 9
    assert seconds == pytest.approx(0.000757112, rel=1e-6)
    assert sliced.matching_s("no_such_operation") == (0.0, 0)


def test_device_ops_are_the_longest_first(sliced):
    ops = sliced.device_ops()
    assert len(ops) == 10
    assert ops[0][0] == "divide_add_fusion"
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))


def test_without_a_step_program_the_slice_is_all_operations():
    whole = tr.reduce_trace(FIXTURE)
    assert whole.steps is None
    assert whole.window_s == pytest.approx(0.041619082, rel=1e-6)
    assert whole.busy_s == pytest.approx(0.019685422, rel=1e-6)


def test_a_slice_needs_two_runs_of_the_step_program():
    with pytest.raises(ValueError, match="a slice needs two"):
        tr.reduce_trace(FIXTURE, step_module="jit_no_such_program")
