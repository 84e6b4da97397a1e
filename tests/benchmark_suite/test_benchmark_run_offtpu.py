"""Off the chip the command exits non-zero and prints no result line."""

import os
import subprocess
import sys

import pytest

import benchmark_tiny  # noqa: F401
from benchmark.harness import manifest


def run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("cell", ["bert_base.train_s128",
                                  "gpt2_small.train_s1024"])
def test_no_tpu_no_result(cell):
    done = run(["--workload", cell, "--seed", str(2**31 + 5),
                "--seconds", "1", "--trace", "0"], manifest.ROOT)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "TPU" in done.stderr


def test_unknown_workload_no_result():
    done = run(["--workload", "no_such.cell", "--seed", "1",
                "--seconds", "1", "--trace", "0"], manifest.ROOT)
    assert done.returncode != 0 and done.stdout.strip() == ""


def test_alone_with_the_manifest_it_prints_no_result(tmp_path):
    """In a directory that holds only ``BENCHMARK.json`` and the files
    under ``paths`` the program is missing: non-zero, no result line."""
    import shutil

    doc = manifest.load_json(manifest.MANIFEST)
    shutil.copy(manifest.MANIFEST, tmp_path / "BENCHMARK.json")
    for path in doc["paths"]:
        shutil.copytree(os.path.join(manifest.ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    done = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--workload", "bert_base.train_s128", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0 and done.stdout.strip() == ""
