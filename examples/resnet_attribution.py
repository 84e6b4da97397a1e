"""ResNet-50 step-time attribution by differential timing.

Attribution by ablation: time the full train step and a forward-only
chain on the same chip with the min-of-3 chained-window methodology
bench.py uses. The delta attributes the step between {forward,
backward+update}.

Usage:  python examples/resnet_attribution.py [--batch 128] [--iters 10]
Prints one JSON line.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: F401,E402 - repo path + compile cache


def _timed_window(fn, state, batch, iters):
    """Min-of-3 chained windows, forced-materialization sync (bench.py)."""
    import jax
    import numpy as np

    t0 = time.perf_counter()
    state2, out = fn(state, batch)
    np.asarray(jax.device_get(out))
    compile_s = time.perf_counter() - t0
    dts = []
    for _ in range(3):
        t0 = time.perf_counter()
        state2, out = fn(state, batch)
        got = np.asarray(jax.device_get(out))
        leaf = jax.tree_util.tree_leaves(state2)[0]
        float(jax.device_get(jax.numpy.ravel(leaf)[0]))
        dts.append(time.perf_counter() - t0)
        if not np.isfinite(got).all():
            raise RuntimeError("non-finite output")
    return min(dts) / iters * 1000.0, compile_s


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.models.zoo import resnet50
    from deeplearning4j_tpu.train.trainer import Trainer
    from deeplearning4j_tpu.train.updaters import Adam

    b, iters = args.batch, args.iters
    r = np.random.default_rng(0)
    feats = r.normal(size=(b, 224, 224, 3)).astype(np.float32)
    labels = np.eye(1000, dtype=np.float32)[r.integers(0, 1000, b)]
    batch = jax.device_put({"features": feats, "labels": labels})

    out = {"batch": b, "iters": iters}

    def build():
        model = resnet50(num_classes=1000, updater=Adam(1e-3))
        model.net.mixed_precision = True
        return model

    # 1. full train step (reference point — matches bench.py resnet50 row)
    model = build()
    trainer = Trainer(model)
    ts = trainer.init_state()
    chained = trainer.make_chained_step(iters)
    ms, cs = _timed_window(lambda s, x: chained(s, x), ts, batch, iters)
    out["train_full_ms"] = round(ms, 2)
    print(f"train_full_ms={ms:.2f} (compile {cs:.1f}s)", file=sys.stderr)

    # 2. forward-only (train=False BN inference path, jit + scan chain)
    model2 = build()
    v = model2.init(seed=0)
    xb = jnp.asarray(feats)

    @jax.jit
    def fwd_chain(v_, x):
        def body(c, _):
            # Thread the carry INTO the input: a loop-invariant body would
            # be hoisted out of the while loop by XLA's invariant code
            # motion and the window would time ~1 forward, not `iters`.
            xc = x + (c * 1e-30).astype(x.dtype)
            y, _st = model2.apply(v_, xc.astype(jnp.bfloat16))
            return jnp.sum(y.astype(jnp.float32)), None

        acc, _ = jax.lax.scan(body, jnp.float32(0.0), None, length=iters)
        return v_, acc

    ms_f, cs_f = _timed_window(fwd_chain, v, xb, iters)
    print(f"forward_only_ms={ms_f:.2f} (compile {cs_f:.1f}s)",
          file=sys.stderr)
    out["forward_only_ms"] = round(ms_f, 2)
    out["backward_update_ms"] = round(out["train_full_ms"] - ms_f, 2)

    print(json.dumps(out))


if __name__ == "__main__":
    main()
