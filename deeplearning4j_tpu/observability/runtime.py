"""Runtime collectors: device memory, live arrays, JIT compiles,
host↔device transfers — the "what is the process doing to the chip"
gauges the serving/training instruments don't see.

Three signal sources:

- **Sampled** (``collect()``, or a background thread via ``start()``):
  per-device HBM stats from PJRT (``device.memory_stats()``, the same
  numbers utils/crash.py dumps post-mortem — here continuously) and
  live jax array count/bytes (``jax.live_arrays()``) — the host-visible
  proxy for buffer leaks and donation failures.
- **Event-driven**: XLA compilations via ``jax.monitoring``'s
  ``backend_compile_duration`` events — count + wall time per
  recompile, so a serving warmup that misses a batch bucket (every miss
  is a fresh compile on the request path) is visible in the scrape
  rather than only as a latency outlier.
- **Explicit**: :func:`record_transfer` counters the instrumented hot
  paths call with the byte counts they move (Trainer.fit's batch
  device_put, ParallelInference's per-dispatch H2D/D2H, checkpoint
  snapshot D2H).

All instruments live on the process-global default registry; one module
-level jax.monitoring listener dispatches to whichever collector is
current, so registry resets (tests, bench) never stack listeners.
jax itself is imported lazily — importing this module costs nothing.
"""

from __future__ import annotations

import threading
from typing import Optional

from deeplearning4j_tpu.observability import metrics as _metrics

# memory_stats keys worth a gauge (present on TPU PJRT; CPU returns {}).
_MEMORY_STATS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                 "largest_alloc_size")


class RuntimeCollector:
    def __init__(self, registry: Optional[_metrics.MetricsRegistry] = None):
        r = registry if registry is not None else _metrics.default_registry()
        self.registry = r
        ns = "runtime"
        self.device_memory_bytes = r.gauge(
            "device_memory_bytes",
            "Per-device PJRT memory stats (labels: device id, stat key).",
            ("device", "stat"), namespace=ns)
        self.live_arrays = r.gauge(
            "live_arrays", "Live jax arrays held by this process.",
            namespace=ns)
        self.live_array_bytes = r.gauge(
            "live_array_bytes", "Total bytes of live jax arrays.",
            namespace=ns)
        self.jit_compiles_total = r.counter(
            "jit_compiles_total",
            "XLA backend compilations observed via jax.monitoring — "
            "a rising count in steady-state serving means bucket-miss "
            "recompiles on the request path.", namespace=ns)
        self.jit_compile_seconds = r.histogram(
            "jit_compile_seconds", "Wall time per XLA backend compile.",
            buckets=_metrics.COMPILE_BUCKETS, namespace=ns)
        self.transfers_total = r.counter(
            "transfers_total", "Host<->device transfers recorded by "
            "instrumented paths (direction: h2d | d2h).",
            ("direction",), namespace=ns)
        self.transfer_bytes_total = r.counter(
            "transfer_bytes_total",
            "Bytes moved host<->device by instrumented paths.",
            ("direction",), namespace=ns)
        self.collections_total = r.counter(
            "collections_total", "collect() sampling passes.", namespace=ns)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- event-driven --------------------------------------------------------

    def on_compile(self, duration_s: float):
        self.jit_compiles_total.inc()
        self.jit_compile_seconds.observe(float(duration_s))

    def record_transfer(self, direction: str, nbytes: int):
        if direction not in ("h2d", "d2h"):
            raise ValueError(f"direction must be h2d|d2h, got {direction!r}")
        self.transfers_total.inc(direction=direction)
        self.transfer_bytes_total.inc(float(nbytes), direction=direction)

    # -- sampled -------------------------------------------------------------

    def collect(self):
        """One sampling pass (never raises: a backend that exposes no
        memory stats just leaves those gauges untouched). No-op while
        ``metrics.set_enabled(False)`` — the kill switch must silence a
        running sampling thread like every other instrumented path."""
        if not _metrics.enabled():
            return
        import jax

        try:
            arrs = jax.live_arrays()
            self.live_arrays.set(len(arrs))
            self.live_array_bytes.set(
                sum(getattr(a, "nbytes", 0) or 0 for a in arrs))
        except Exception:  # noqa: BLE001 - deleted-buffer races, odd backends
            pass
        try:
            for d in jax.devices():
                stats = d.memory_stats() or {}
                for key in _MEMORY_STATS:
                    v = stats.get(key)
                    if isinstance(v, (int, float)):
                        self.device_memory_bytes.set(
                            float(v), device=str(d.id), stat=key)
        except Exception:  # noqa: BLE001 - backend-dependent
            pass
        self.collections_total.inc()

    def start(self, interval_s: float = 10.0) -> "RuntimeCollector":
        """Sample periodically on a daemon thread until ``stop()``."""
        if self._thread is not None and self._thread.is_alive():
            return self
        self._stop.clear()

        def loop():
            while not self._stop.wait(interval_s):
                self.collect()

        self._thread = threading.Thread(
            target=loop, daemon=True, name="runtime-collector")
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None


# -- module singleton + the one jax.monitoring listener ----------------------

_collector: Optional[RuntimeCollector] = None
_collector_lock = threading.Lock()
_listener_installed = False


def _dispatch_event(event: str, duration: float, **kw):
    c = _collector
    if (c is not None and _metrics.enabled()
            and event.endswith("backend_compile_duration")):
        try:
            c.on_compile(duration)
        except Exception:  # noqa: BLE001 - telemetry never breaks compiles
            pass


def _install_listener():
    """Register the module-level listener once per process. jax has no
    unregister, so the listener is a fixed dispatcher that forwards to
    the CURRENT collector — registry resets swap the target, never
    stack callbacks."""
    global _listener_installed
    if _listener_installed:
        return
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_dispatch_event)
    _listener_installed = True


def get_runtime_collector() -> RuntimeCollector:
    """The process collector on the default registry (created lazily,
    compile listener installed on first use)."""
    global _collector
    with _collector_lock:
        if _collector is None:
            _collector = RuntimeCollector()
            _install_listener()
    return _collector


def record_transfer(direction: str, nbytes: int):
    """Hot-path hook: count a host<->device transfer. No-op when
    instrumentation is disabled; never raises."""
    if not _metrics.enabled():
        return
    try:
        get_runtime_collector().record_transfer(direction, int(nbytes))
    except Exception:  # noqa: BLE001 - telemetry never fails the caller
        pass


def _reset():
    global _collector
    with _collector_lock:
        if _collector is not None:
            _collector.stop()
        _collector = None


_metrics.register_reset_hook(_reset)
