"""Data-parallel training over a device mesh.

↔ ParallelWrapper / SharedTrainingMaster: the reference clones models per
GPU and exchanges gradients (averaging threads or Aeron UDP). Here the
SAME single-device train step is pjit-compiled over a Mesh — XLA inserts
exact all-reduces on ICI. Run on CPU with 8 virtual devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        JAX_PLATFORMS=cpu python examples/data_parallel_mesh.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: F401,E402 - repo path + compile cache

import argparse

import jax
import numpy as np

from deeplearning4j_tpu.data import ArrayDataSetIterator, load_mnist
from deeplearning4j_tpu.models.lenet import lenet
from deeplearning4j_tpu.parallel.specs import data_parallel_plan
from deeplearning4j_tpu.runtime.device import MeshSpec, build_mesh
from deeplearning4j_tpu.train.trainer import Trainer
from deeplearning4j_tpu.train.updaters import Adam


def main(quick: bool = False):
    mesh = build_mesh(MeshSpec(data=-1))  # every device on the data axis
    print(f"mesh: {mesh}")
    state_sh, batch_sh = data_parallel_plan(mesh)
    (xtr, ytr), _, _ = load_mnist(n_train=1024 if quick else 4096, n_test=64)

    model = lenet(updater=Adam(3e-3))
    trainer = Trainer(model, mesh=mesh, state_sharding=state_sh,
                      batch_sharding=batch_sh)
    ts = trainer.init_state()
    it = ArrayDataSetIterator(xtr, ytr, batch_size=256, drop_last=True)
    ts = trainer.fit(ts, it, epochs=1 if quick else 3)

    # parity: same seed, single-device
    single = Trainer(lenet(updater=Adam(3e-3)))
    ts1 = single.init_state()
    ts1 = single.fit(ts1, ArrayDataSetIterator(xtr, ytr, batch_size=256,
                                               drop_last=True),
                     epochs=1 if quick else 3)
    a = np.asarray(jax.device_get(
        jax.tree_util.tree_leaves(trainer.variables(ts)["params"])[0]))
    b = np.asarray(jax.device_get(
        jax.tree_util.tree_leaves(single.variables(ts1)["params"])[0]))
    err = float(np.max(np.abs(a - b)))
    print(f"sharded-vs-single max param delta: {err:.2e}")
    return err


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    err = main(ap.parse_args().quick)
    assert err < 5e-2, err
