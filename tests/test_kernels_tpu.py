"""On-TPU compiled kernel parity tests.

The interpret-mode suites (test_kernels_backward.py) validate kernel LOGIC
on CPU; these validate the COMPILED Pallas path on a real chip — the same
lowering the bench runs. Opt-in (DL4J_TPU_KERNEL_TESTS=1): a chip belongs
to one process at a time, so routine pytest never touches it. Once opted
in, a chip that cannot be reached is a FAILURE, not a skip.

NOTE: tests/conftest.py pins the CPU platform for the rest of the suite;
this module must re-point jax at the TPU, so it runs the checks in a
SUBPROCESS with a clean environment (the pytest process itself never
initialises a TPU backend, so the child finds the chip free).
"""

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.skipif(
    os.environ.get("DL4J_TPU_KERNEL_TESTS") != "1",
    reason="live-TPU kernel tests are opt-in (DL4J_TPU_KERNEL_TESTS=1)")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_ab():
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    code = (
        "import sys, json; sys.path.insert(0, %r); "
        "from kernels_ab import run_kernels_ab; "
        "print(json.dumps(run_kernels_ab({}, include_tune=False)))" % _REPO)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=1800, env=env, cwd=_REPO)
    assert out.returncode == 0, (
        f"opted-in chip run failed (TPU unavailable?): {out.stderr[-600:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def ab_result():
    return _run_ab()


def test_flash_attention_compiled_parity(ab_result):
    fa = ab_result["flash_attention"]
    assert "error" not in fa, fa
    assert fa["parity"], fa
    assert fa["fwd_max_rel_err"] < 2e-2
    assert fa["bwd_max_rel_err"] < 2e-2


def test_lstm_compiled_parity(ab_result):
    ls = ab_result["lstm_scan"]
    assert "error" not in ls, ls
    assert ls["parity"], ls


def test_speedups_recorded(ab_result):
    for k in ("flash_attention", "lstm_scan"):
        r = ab_result[k]
        assert "fwd_speedup" in r and "bwd_speedup" in r
    # The LSTM kernel must stay within striking distance of the XLA
    # scan on its bench shape.
    assert ab_result["lstm_scan"]["fwd_speedup"] > 0.8, ab_result["lstm_scan"]


def test_flash_attention_long_context_parity(ab_result):
    """The T=4096 causal config that justifies the dispatch crossover must
    itself be green (parity) when kernels run on the chip."""
    fl = ab_result.get("flash_attention_long")
    assert fl is not None, sorted(ab_result)
    assert "error" not in fl, fl
    assert fl["parity"], fl


def test_flash_attention_cell_shape_parity(ab_result):
    """``gpt2_small.train_s1024``'s own call, [16, 12, 1024, 64] bf16
    causal, at the geometry the dispatch chooses for it."""
    fc = ab_result.get("flash_attention_cell")
    assert fc is not None, sorted(ab_result)
    assert fc["parity"], fc
    assert fc["fwd_max_rel_err"] < 2e-2
    assert fc["bwd_max_rel_err"] < 2e-2


def test_gru_compiled_parity(ab_result):
    gs = ab_result["gru_scan"]
    assert "error" not in gs, gs
    assert gs["parity"], gs
    assert "fwd_speedup" in gs and "bwd_speedup" in gs
