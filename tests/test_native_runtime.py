"""C++ PJRT runtime binding tests (SURVEY §7.2 stage 0 substrate).

The native layer is exercised against the installed PJRT plugin (libtpu)
on a machine with a chip. Without a chip the tests assert the build + error
paths only. Oracle: numpy on the same inputs (SURVEY §4 "oracle testing"
pattern), with bf16-MXU tolerance on TPU per §7.4 item 6.

Why the compile/execute legs cannot run in default CI: they need a
dlopen-able PJRT **C-API plugin** .so AND the device behind it. jaxlib's CPU
backend is in-process, not a C-API plugin, so there is nothing CPU-side to
load. The live legs open their own PJRT client, which claims the chip: run
them in a process whose jax stays on CPU —
``JAX_PLATFORMS=cpu DL4J_TPU_NATIVE_TESTS=1 python -m pytest
tests/test_native_runtime.py`` — and alone on the chip.
"""

import os
import subprocess

import numpy as np
import pytest

from deeplearning4j_tpu.runtime import native as nat


def _plugin_available() -> bool:
    return nat.default_plugin_path() is not None


def test_native_lib_builds():
    path = nat.ensure_built()
    assert path.exists()
    out = subprocess.run(["nm", "-D", str(path)], capture_output=True, text=True)
    for sym in ("dl4j_pjrt_load", "dl4j_pjrt_compile", "dl4j_pjrt_execute",
                "dl4j_pjrt_buffer_from_host", "dl4j_pjrt_buffer_to_host"):
        assert sym in out.stdout


def test_missing_plugin_errors_cleanly(tmp_path):
    with pytest.raises(nat.NativeRuntimeError, match="client create failed|no PJRT"):
        nat.NativeRuntime(plugin_path=str(tmp_path / "nope.so"))


@pytest.fixture(scope="module")
def runtime():
    # Opt-in: a chip belongs to one process at a time, so routine pytest
    # must not open a PJRT client on it; set DL4J_TPU_NATIVE_TESTS=1 to run
    # the live-plugin tests. Once opted in, a client that cannot be created
    # is a failure, not a skip.
    if os.environ.get("DL4J_TPU_NATIVE_TESTS") != "1":
        pytest.skip("live-plugin tests are opt-in (DL4J_TPU_NATIVE_TESTS=1)")
    assert _plugin_available(), "libtpu is not installed"
    rt = nat.NativeRuntime()
    yield rt
    rt.close()


def _stablehlo(fn, *args):
    import jax

    return str(jax.jit(fn).lower(*args).compiler_ir("stablehlo"))


class TestAgainstPlugin:
    def test_device_enumeration(self, runtime):
        assert runtime.device_count() >= 1
        assert runtime.platform_name() != ""
        assert runtime.device_description(0) != ""
        major, minor = runtime.api_version()
        assert (major, minor) >= (0, 40)

    def test_compile_execute_matches_jax(self, runtime):
        import jax.numpy as jnp

        def f(x, w):
            return jnp.tanh(x @ w) * 2.0

        rs = np.random.RandomState(0)
        x = rs.randn(4, 8).astype(np.float32)
        w = rs.randn(8, 4).astype(np.float32)
        exe = runtime.compile(_stablehlo(f, x, w))
        assert exe.num_outputs == 1
        out, = exe.execute([x, w])
        want = np.tanh(x @ w) * 2.0
        # bf16 MXU tolerance (TPU); exact-ish elsewhere
        np.testing.assert_allclose(out, want, rtol=5e-2, atol=5e-2)
        exe.close()

    def test_multiple_outputs_and_dtypes(self, runtime):
        import jax.numpy as jnp

        def f(x):
            return jnp.sum(x).astype(jnp.float32), (x > 0), x.astype(jnp.int32)

        x = np.array([[-1.5, 2.5], [3.0, -4.0]], np.float32)
        exe = runtime.compile(_stablehlo(f, x))
        assert exe.num_outputs == 3
        s, pred, xi = exe.execute([x])
        np.testing.assert_allclose(s, x.sum(), rtol=1e-5)
        np.testing.assert_array_equal(pred, x > 0)
        np.testing.assert_array_equal(xi, x.astype(np.int32))
        exe.close()

    def test_compile_error_surfaces_message(self, runtime):
        with pytest.raises(nat.NativeRuntimeError, match="compile"):
            runtime.compile("this is not mlir")

    def test_repeated_execution_no_leak(self, runtime):
        import jax.numpy as jnp

        def f(x):
            return x * 2.0

        x = np.ones((128, 128), np.float32)
        exe = runtime.compile(_stablehlo(f, x))
        for _ in range(20):
            out, = exe.execute([x])
        np.testing.assert_allclose(out, x * 2.0)
        exe.close()
