"""chip_smoke.py off the chip: it must fail fast and build nothing.

The script's real run needs a TPU (the driver runs it on one); what tier-1
can hold is the other half of its contract — with no accelerator the
default invocation exits non-zero within seconds, names the platform it
found, prints no result line, and never imports the package (no model is
built, no probe subprocess is started)."""

import os
import pathlib
import shutil
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, "-X", "importtime", "chip_smoke.py", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)
    return out, time.monotonic() - t0


def test_no_accelerator_is_a_fast_named_failure():
    out, wall = _run(ROOT)
    assert out.returncode not in (0, None), out.stdout
    assert wall < 60, wall
    assert "platform=cpu" in out.stdout
    assert "'cpu', not 'tpu'" in out.stderr
    # no result, no evidence, no summary: nothing ran
    assert '"ok"' not in out.stdout
    assert "summary" not in out.stdout and "cpu_evidence" not in out.stdout
    # -X importtime lists every module imported: the package (and so any
    # model) was never touched
    assert "deeplearning4j_tpu" not in out.stderr


def test_alone_in_a_directory_it_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo there is nothing to drive: non-zero, no result line."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    out, _ = _run(tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
