"""Multi-process checkpoint round-trip across a TOPOLOGY CHANGE.

SURVEY §5.3-§5.4: the recovery story is
topology-independent restore — a job checkpointed on one mesh shape must
restore bitwise onto a different mesh and keep training. The in-process
tests pin this on one process; here it crosses real process boundaries:

  phase A: 2 processes x 2 devices, mesh ("data",)=4 — train 3 steps
           (data-parallel pjit), save a checkpoint from the replicated
           state, record the final loss + a param digest.
  phase B: fresh 2-process job, mesh ("data","model")=(2,2) — a different
           topology — restore, assert params are BITWISE identical to the
           phase-A save, train 2 more steps, assert the loss continues
           from (not above) phase A's.

Same real-gRPC-bootstrap pattern as tests/test_multihost.py; skips (not
fails) when the local environment can't handshake.
"""

import json
import os
import re
import socket
import subprocess
import sys
import textwrap

import pytest

_WORKER = textwrap.dedent("""
    import json, os, sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.runtime import distributed
    from deeplearning4j_tpu.serde import checkpoint as ckpt
    from deeplearning4j_tpu.nn.config import (NeuralNetConfiguration,
                                              SequentialConfig)
    from deeplearning4j_tpu.nn.layers.core import Dense
    from deeplearning4j_tpu.nn.layers.output import OutputLayer
    from deeplearning4j_tpu.nn.model import SequentialModel
    from deeplearning4j_tpu.train.trainer import Trainer, TrainState
    from deeplearning4j_tpu.train.updaters import Sgd

    phase, port, pid, workdir = (sys.argv[1], sys.argv[2], int(sys.argv[3]),
                                 sys.argv[4])
    distributed.initialize(f"127.0.0.1:{port}", num_processes=2,
                           process_id=pid)
    devs = np.array(jax.devices())
    assert devs.size == 4

    if phase == "A":
        mesh = Mesh(devs, ("data",))
        batch_spec = P("data")
    else:
        mesh = Mesh(devs.reshape(2, 2), ("data", "model"))
        batch_spec = P("data")

    def build():
        cfg = SequentialConfig(
            net=NeuralNetConfiguration(updater=Sgd(0.1), seed=7),
            input_shape=(8,),
            layers=[Dense(units=16, activation="tanh"),
                    OutputLayer(units=4, loss="mcxent",
                                activation="softmax")],
        )
        return SequentialModel(cfg)

    model = build()
    # data-parallel placement: replicated state (a single sharding is a
    # valid pytree prefix for the whole TrainState), batch split on "data"
    rep = NamedSharding(mesh, P())
    trainer = Trainer(model, mesh=mesh, state_sharding=rep,
                      batch_sharding=NamedSharding(mesh, batch_spec))

    r = np.random.default_rng(3)
    feats = r.normal(size=(8, 8)).astype(np.float32)
    labels = np.eye(4, dtype=np.float32)[r.integers(0, 4, 8)]
    from jax.experimental import multihost_utils
    n_local = 8 // 2
    lo = pid * n_local
    gfeats = multihost_utils.host_local_array_to_global_array(
        feats[lo:lo + n_local], mesh, batch_spec)
    glabels = multihost_utils.host_local_array_to_global_array(
        labels[lo:lo + n_local], mesh, batch_spec)
    batch = {"features": gfeats, "labels": glabels}

    ck = os.path.join(workdir, "ckpt")

    def digest(tree):
        import hashlib
        h = hashlib.sha256()
        for leaf in jax.tree_util.tree_leaves(tree):
            if isinstance(leaf, jax.Array) and jax.dtypes.issubdtype(
                    leaf.dtype, jax.dtypes.prng_key):
                leaf = jax.random.key_data(leaf)
            h.update(np.ascontiguousarray(np.asarray(jax.device_get(leaf)))
                     .tobytes())
        return h.hexdigest()

    if phase == "A":
        # build the replicated GLOBAL state inside jit: device_put cannot
        # target non-addressable (multi-process) shardings
        ts = jax.jit(lambda: trainer.init_state(), out_shardings=rep)()
        losses = []
        for _ in range(3):
            ts, m = trainer.train_step(ts, batch)
            losses.append(float(jax.device_get(m["total_loss"])))
        assert losses[-1] < losses[0], losses
        distributed.barrier("pre-save")
        if pid == 0:
            ckpt.save_state_tree(ck, ts, {"loss_last": losses[-1]})
            with open(os.path.join(workdir, "digest.json"), "w") as f:
                json.dump({"digest": digest(ts.params),
                           "loss_last": losses[-1]}, f)
        distributed.barrier("saved")
    else:
        template = trainer.init_state()
        ts = ckpt.load_state_tree(ck, template, sharding=rep)
        with open(os.path.join(workdir, "digest.json")) as f:
            saved = json.load(f)
        got = digest(ts.params)
        assert got == saved["digest"], (got, saved["digest"])
        losses = []
        for _ in range(2):
            ts, m = trainer.train_step(ts, batch)
            losses.append(float(jax.device_get(m["total_loss"])))
        # training continues from, not above, the phase-A loss
        assert losses[0] <= saved["loss_last"] + 1e-4, (
            losses, saved["loss_last"])
        assert losses[-1] < losses[0]

    distributed.barrier("done")
    print(f"phase{phase} proc{pid} ok", flush=True)
""")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_phase(phase, workdir):
    port = _free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=2").strip()
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WORKER, phase, str(port), str(i), workdir],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.skip("distributed handshake timed out in this environment")
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"phase {phase} proc{i} failed:\n{out[-3000:]}"
        assert f"phase{phase} proc{i} ok" in out


# Tier-1 budget relief (ROADMAP item 5): slow-marked (~8 s — two full
# 2-process gRPC bootstraps). The topology-change restore semantics stay
# in tier-1 via the single-process proxy below (same save-on-one-mesh /
# restore-on-another path over this process's 8 fake devices).
@pytest.mark.slow
def test_checkpoint_roundtrip_across_topology_change(tmp_path):
    wd = str(tmp_path)
    _run_phase("A", wd)
    assert (tmp_path / "ckpt" / "state.npz").exists()
    assert json.loads((tmp_path / "digest.json").read_text())["digest"]
    _run_phase("B", wd)


def test_checkpoint_topology_change_single_process(tmp_path):
    """Fast tier-1 proxy for the 2-process round-trip above: save a
    replicated state trained on mesh ``("data",)=8``, restore it BITWISE
    onto mesh ``("data","model")=(4,2)``, and keep training — all inside
    one process on the 8 fake CPU devices."""
    import hashlib

    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.nn.config import (
        NeuralNetConfiguration,
        SequentialConfig,
    )
    from deeplearning4j_tpu.nn.layers.core import Dense
    from deeplearning4j_tpu.nn.layers.output import OutputLayer
    from deeplearning4j_tpu.nn.model import SequentialModel
    from deeplearning4j_tpu.serde import checkpoint as ckpt
    from deeplearning4j_tpu.train.trainer import Trainer
    from deeplearning4j_tpu.train.updaters import Sgd

    def build():
        return SequentialModel(SequentialConfig(
            net=NeuralNetConfiguration(updater=Sgd(0.1), seed=7),
            input_shape=(8,),
            layers=[Dense(units=16, activation="tanh"),
                    OutputLayer(units=4, loss="mcxent",
                                activation="softmax")],
        ))

    def digest(tree):
        h = hashlib.sha256()
        for leaf in jax.tree_util.tree_leaves(tree):
            if isinstance(leaf, jax.Array) and jax.dtypes.issubdtype(
                    leaf.dtype, jax.dtypes.prng_key):
                leaf = jax.random.key_data(leaf)
            h.update(np.ascontiguousarray(
                np.asarray(jax.device_get(leaf))).tobytes())
        return h.hexdigest()

    devs = np.array(jax.devices())
    assert devs.size == 8
    r = np.random.default_rng(3)
    feats = r.normal(size=(8, 8)).astype(np.float32)
    labels = np.eye(4, dtype=np.float32)[r.integers(0, 4, 8)]

    # phase A: data-parallel mesh over all 8 devices
    mesh_a = Mesh(devs, ("data",))
    rep_a = NamedSharding(mesh_a, P())
    trainer_a = Trainer(build(), mesh=mesh_a, state_sharding=rep_a,
                        batch_sharding=NamedSharding(mesh_a, P("data")))
    ts = trainer_a.init_state()
    losses = []
    for _ in range(3):
        ts, m = trainer_a.train_step(
            ts, {"features": feats, "labels": labels})
        losses.append(float(jax.device_get(m["total_loss"])))
    assert losses[-1] < losses[0], losses
    ck = str(tmp_path / "ckpt")
    ckpt.save_state_tree(ck, ts, {"loss_last": losses[-1]})
    saved_digest = digest(ts.params)

    # phase B: a DIFFERENT topology — restore bitwise, keep training
    mesh_b = Mesh(devs.reshape(4, 2), ("data", "model"))
    rep_b = NamedSharding(mesh_b, P())
    trainer_b = Trainer(build(), mesh=mesh_b, state_sharding=rep_b,
                        batch_sharding=NamedSharding(mesh_b, P("data")))
    restored = ckpt.load_state_tree(ck, trainer_b.init_state(),
                                    sharding=rep_b)
    assert digest(restored.params) == saved_digest
    cont = []
    for _ in range(2):
        restored, m = trainer_b.train_step(
            restored, {"features": feats, "labels": labels})
        cont.append(float(jax.device_get(m["total_loss"])))
    assert cont[0] <= losses[-1] + 1e-4, (cont, losses)
    assert cont[-1] < cont[0]
