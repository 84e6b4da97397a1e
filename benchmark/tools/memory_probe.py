"""What the device's allocator reports against what the compiler plans:
``python benchmark/tools/memory_probe.py <training cell> ...`` on the chip
prints ``memory_stats()`` before and after two steps of each cell, and the
step program's ``memory_analysis()``."""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from benchmark.harness import manifest
    from benchmark.harness import traffic as traffic_mod
    from benchmark.harness.device import arm_compile_cache, check_devices
    from benchmark.kinds import train

    for name in (argv or sys.argv[1:]):
        cell = manifest.load_cell(name)
        check_devices(cell.chips, True)
        arm_compile_cache()
        trainer = train.build_trainer(cell)
        ts = trainer.init_state(seed=0)
        batch = traffic_mod.generate(cell, 1, 1.0)[0]
        device = jax.local_devices()[0]
        print(name, "before", device.memory_stats(), flush=True)
        for _ in range(2):
            ts, metrics = trainer.train_step(ts, batch)
        jax.block_until_ready(ts.params)
        print(name, "after", device.memory_stats(), flush=True)
        compiled = trainer.train_step.lower(ts, batch).compile()
        print(name, "analysis", compiled.memory_analysis(), flush=True)
        del ts, trainer, compiled
    return 0


if __name__ == "__main__":
    sys.exit(main())
