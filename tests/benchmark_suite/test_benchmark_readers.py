"""The general readers of per-layer metrics, on the recorded trace and on
hand-made counters: each returns its number, or nothing where it finds
nothing to read, and never 0 for a share of a peak."""

import os

import pytest

import benchmark_tiny  # noqa: F401  (puts the repo's root on sys.path)
from benchmark.harness import device, flops, manifest, peaks, readers
from benchmark.harness import trace_reduce as tr

FIXTURE = os.path.join(manifest.BENCH_DIR, "fixtures",
                       "gpt2_1layer_4steps.xplane.pb.gz")
MOSAIC = 'custom_call_target="tpu_custom_call"'
PEAKS = peaks.peaks_for("TPU v5 lite")
FLASH = {"pattern": MOSAIC, "cost": "flash_train_cost",
         "cost_args": {"rows": "rows", "heads": "heads", "seq_len": "seq_len",
                       "head_dim": "head_dim", "layers": "layers"}}
# the recorded model: one layer of gpt2's width at 1 x 1024
COUNTERS = {"flops_per_step": 3e11, "rows": 1, "heads": 12, "seq_len": 1024,
            "head_dim": 64, "layers": 1}


class Cell:
    chips = 1


def context(trace, counters):
    return readers.Context(trace=trace, counters=counters, peaks=PEAKS,
                           cell=Cell())


@pytest.fixture(scope="module")
def traced():
    return context(tr.reduce_trace(FIXTURE, step_module="jit_train_step"),
                   dict(COUNTERS))


def test_step_mfu_is_the_required_operations_over_the_slices_peak(traced):
    want = 100.0 * 3e11 * 3 / (traced.trace.window_s * 197e12)
    assert readers.step_mfu(traced) == pytest.approx(want)


def test_busy_time_and_idle_share_of_the_slice(traced):
    assert readers.busy_ms_per_step(traced) == pytest.approx(4.92, rel=0.01)
    assert readers.idle_share(traced) == pytest.approx(59.77, abs=0.1)


def test_kernel_roofline_is_the_least_time_over_the_kernels_time(traced):
    share = readers.kernel_roofline(traced, **FLASH)
    cost = flops.flash_train_cost(rows=1, heads=12, seq_len=1024,
                                  head_dim=64, layers=1)
    least = flops.roofline_seconds(cost["flops"], cost["bytes"], 197e12,
                                   819e9)
    assert share == pytest.approx(
        100.0 * least["seconds"] * 3 / 0.000757112, rel=1e-6)
    assert 0 < share < 100
    assert traced.notes[MOSAIC]["events"] == 9


@pytest.mark.parametrize("reader,args", [
    (readers.step_mfu, {}),
    (readers.busy_ms_per_step, {}),
    (readers.idle_share, {}),
    (readers.kernel_roofline, FLASH),
])
def test_without_a_trace_a_reader_of_the_trace_returns_nothing(reader, args):
    assert reader(context(None, dict(COUNTERS)), **args) is None


def test_a_kernel_that_never_ran_has_no_roofline(traced):
    args = dict(FLASH, pattern="no_such_kernel")
    assert readers.kernel_roofline(traced, **args) is None


@pytest.mark.parametrize("reader,args,counters,want", [
    (readers.percentile, {"counter": "lag", "q": 95, "scale": 1e3},
     {"lag": [0.003, 0.001, 0.002]}, 3.0),
    (readers.percentile, {"counter": "lag", "q": 50}, {"lag": [3, 1, 2]}, 2.0),
    (readers.percentile, {"counter": "lag", "q": 95}, {"lag": []}, None),
    (readers.percentile, {"counter": "lag", "q": 95}, {}, None),
    (readers.ratio, {"num": "tokens", "den": "steps"},
     {"tokens": 30, "steps": 4}, 7.5),
    (readers.ratio, {"num": "tokens", "den": "steps"},
     {"tokens": 30, "steps": 0}, None),
    (readers.counter, {"name": "compiles"}, {"compiles": 0}, 0.0),
    (readers.counter, {"name": "compiles"}, {}, None),
])
def test_readers_of_counters(reader, args, counters, want):
    assert reader(context(None, counters), **args) == want


class Chip:
    def __init__(self, **stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


def test_peak_bytes_is_the_fullest_chips_and_adds_the_scratch_when_told(
        monkeypatch):
    import jax

    chips = [Chip(peak_bytes_in_use=5, peak_bytes_reserved=20),
             Chip(peak_bytes_in_use=9, peak_bytes_reserved=1), Chip()]
    monkeypatch.setattr(jax, "local_devices", lambda: chips)
    assert device.peak_bytes(with_reserved=False) == 9
    assert device.peak_bytes(with_reserved=True) == 25
