"""Record a small device trace on the chip and print what is in it.

Run on the chip: ``python benchmark/tools/record_fixture.py``. It traces a
few train steps of a one-layer ``gpt2_small``-width model at 1 x 1024 (so
the Pallas flash kernels are in the trace), writes the ``.xplane.pb`` to
``chiprun_out/fixture/`` and prints, for each plane and line, the event
names with their counts and summed durations. The trace kept in
``benchmark/fixtures/`` was recorded with this script.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import time
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    import jax
    import numpy as np
    from jax.profiler import ProfileData

    from deeplearning4j_tpu.models.gpt import Gpt, GptConfig
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.train.trainer import Trainer
    from deeplearning4j_tpu.train.updaters import Adam

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    layers = int(os.environ.get("FIXTURE_LAYERS", "1"))
    rows = int(os.environ.get("FIXTURE_ROWS", "1"))
    model = Gpt(GptConfig(
        num_layers=layers, dropout=0.0, attention_dropout=0.0,
        net=NeuralNetConfiguration(updater=Adam(1e-4), mixed_precision=True,
                                   rng_impl="rbg")))
    trainer = Trainer(model)
    ts = trainer.init_state()
    ids = np.random.default_rng(0).integers(
        0, 50257, (rows, 1024)).astype(np.int32)
    batch = {"features": {"token_ids": ids}}
    for _ in range(3):
        ts, m = trainer.train_step(ts, batch)
    jax.block_until_ready(ts.params)
    out = "chiprun_out/fixture"
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    t0 = time.perf_counter()
    jax.profiler.start_trace(out)
    t1 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench_slice"):
        for i in range(4):
            ts, m = trainer.train_step(ts, batch)
            if i == 1:
                jax.block_until_ready(ts.params)
                time.sleep(0.02)
        jax.block_until_ready(ts.params)
    t2 = time.perf_counter()
    jax.profiler.stop_trace()
    t3 = time.perf_counter()
    print(f"start_trace {t1 - t0:.3f}s  slice {t2 - t1:.3f}s  "
          f"stop_trace {t3 - t2:.3f}s")
    path = glob.glob(out + "/plugins/profile/*/*.xplane.pb")[0]
    print(path, os.path.getsize(path), "bytes")
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r} lines={len(lines)}")
        for line in lines:
            events = list(line.events)
            if not events:
                continue
            agg = defaultdict(lambda: [0, 0.0])
            for e in events:
                agg[e.name][0] += 1
                agg[e.name][1] += e.duration_ns
            t_lo = min(e.start_ns for e in events)
            t_hi = max(e.start_ns + e.duration_ns for e in events)
            print(f"  LINE {line.name!r} events={len(events)} "
                  f"span=[{t_lo:.0f},{t_hi:.0f}]ns")
            show = sorted(agg.items(), key=lambda kv: -kv[1][1])
            if not plane.name.startswith("/device"):
                show = show[:6]
            for name, (n, dur) in show[:40]:
                print(f"    {n:5d} {dur / 1e3:12.1f}us  {name[:110]}")
        if plane.name.startswith("/device:TPU:0"):
            for line in lines:
                for e in list(line.events)[:3]:
                    print("   STATS", line.name, e.name[:60],
                          dict(list(e.stats)[:12]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
