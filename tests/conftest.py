"""Test configuration: force an 8-virtual-device CPU platform.

This is the TPU analogue of the reference's multi-node-without-cluster test
strategy (SURVEY §4: Spark local[N] + embedded Aeron media driver): all mesh
and pjit tests run against 8 fake CPU devices, so the identical SPMD
programs that run on a v5e slice are validated in CI with no TPU attached.
"""

import os
import re

flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
               os.environ.get("XLA_FLAGS", ""))
os.environ["XLA_FLAGS"] = (
    flags + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def as_on_tpu(monkeypatch):
    """The flash kernel's dispatch as on the chip, so that lowering (or
    compiling) for the TPU platform from this CPU host reaches Mosaic."""
    from deeplearning4j_tpu.kernels import flash_attention as fa

    for name in ("_use_pallas", "_on_tpu"):
        monkeypatch.setattr(fa, name, lambda: True)
    monkeypatch.setattr(fa, "_interpret", lambda: False)


# -- shared mixed predict+generation server ------------------------------------
#
# ONE tiny-GPT engine + one batched predict model behind one ModelServer,
# compiled once per module and shared by every test in that module. The
# replay/game-day modules both ride this instead of each compiling their
# own fleet (the PR 6/7 budget pattern, hoisted to conftest so the
# fixture exists exactly once).


@pytest.fixture(scope="module")
def mixed_server():
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.gpt import gpt_tiny
    from deeplearning4j_tpu.serving import (
        GenerationEngine,
        ModelRegistry,
        ModelServer,
        spec,
    )

    def fwd(v, x):
        return jnp.zeros((x.shape[0], 1), jnp.float32) + v["scale"]

    reg = ModelRegistry()
    reg.register("scale", fwd, {"scale": 2.0}, input_spec=spec((4,)),
                 mode="batched", max_batch_size=8,
                 devices=jax.devices()[:1])
    model = gpt_tiny()
    eng = GenerationEngine(
        model, model.init(seed=0), name="gpt", num_slots=2, max_len=32,
        max_new_tokens=24, min_kv_bucket=8, min_prompt_bucket=8,
        idle_wait_s=0.002, temperature=0.0, max_waiting=16, seed=0)
    srv = ModelServer(reg, port=0, sentinel=False,
                      generators={"gpt": eng})
    srv.start(warm=True)
    yield srv
    srv.stop()


# -- session thread-leak guard ------------------------------------------------
#
# Exporter/prober/evaluator shutdown bugs historically leaked non-daemon
# threads that kept CI processes alive past the last test. The guard
# snapshots live threads at session start and fails the run if the
# session ends with extra non-daemon threads still alive (after a grace
# window for in-flight joins). Named allowlist for infrastructure that
# legitimately outlives the session.

import sys  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402
from fnmatch import fnmatch  # noqa: E402

# thread-name patterns allowed to survive the session: executor pools
# are reclaimed by their atexit join, and pytest plugins may keep a
# watcher around
_THREAD_ALLOWLIST = (
    "ThreadPoolExecutor-*",
    "pytest-watcher*",
)


def _leaked_threads(initial):
    # `initial` holds the thread OBJECTS (not idents — CPython recycles
    # idents, so a leaked thread could inherit a session-start ident
    # and escape; the snapshot set keeps the objects alive, identity
    # can't be reused)
    cur = threading.current_thread()
    return [
        th for th in threading.enumerate()
        if th.is_alive() and not th.daemon and th is not cur
        and th not in initial
        and not any(fnmatch(th.name, pat) for pat in _THREAD_ALLOWLIST)
    ]


def pytest_sessionstart(session):
    session._initial_threads = set(threading.enumerate())


def pytest_sessionfinish(session, exitstatus):
    initial = getattr(session, "_initial_threads", None)
    if initial is None:
        return
    deadline = time.monotonic() + 3.0
    leaked = _leaked_threads(initial)
    while leaked and time.monotonic() < deadline:
        time.sleep(0.05)
        leaked = _leaked_threads(initial)
    if not leaked:
        return
    frames = sys._current_frames()
    tr = session.config.pluginmanager.get_plugin("terminalreporter")
    lines = ["", "=== thread-leak guard: non-daemon thread(s) leaked by "
                 "the test session ==="]
    import traceback
    for th in leaked:
        lines.append(f"  {th.name!r} (ident {th.ident})")
        frame = frames.get(th.ident)
        if frame is not None:
            lines.extend("    " + ln for ln in
                         "".join(traceback.format_stack(frame, limit=8))
                         .rstrip().splitlines())
    lines.append("fix the owning component's shutdown (or extend "
                 "tests/conftest.py _THREAD_ALLOWLIST with a reason)")
    text = "\n".join(lines)
    if tr is not None:
        tr.write_line(text, red=True)
    else:  # pragma: no cover - terminal plugin disabled
        print(text, file=sys.stderr)
    session.exitstatus = 1
