"""What every kind of cell asks of the machine: the chips, JAX's
persistent compilation cache at a fixed place, the peak of device memory,
and a line on standard error."""

from __future__ import annotations

import os
import sys
from typing import Any, Dict

from benchmark.harness import manifest


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def arm_compile_cache():
    """JAX's persistent compilation cache at a fixed place: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else inside the checkout."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        manifest.ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_devices(chips: int, require_tpu: bool) -> Dict[str, Any]:
    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        raise SystemExit(
            f"the cell needs {chips} TPU chip(s); jax found {len(devices)} "
            f"device(s) of platform {devices[0].platform!r}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def peak_bytes(*, with_reserved: bool) -> int:
    """The peak of device memory on the fullest chip: the buffers in use
    at their peak and, ``with_reserved``, what the runtime reserved at its
    peak. The TPU's allocator counts a running program's scratch (the
    compiler's ``temp`` bytes) under ``bytes_reserved`` and not under
    ``bytes_in_use``; a kind adds it only where ``memory_analysis()`` of
    its program has shown that the reservation is that scratch (PERF.md
    section 4 has both numbers for each cell)."""
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        reserved = stats.get("peak_bytes_reserved", 0) if with_reserved else 0
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(reserved))
    return peak
