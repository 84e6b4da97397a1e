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
  rather than only as a latency outlier. The same listener keeps each
  event of a compilation's stages (trace, lowering, backend compile,
  cache read) with its end on ``trace.now()``'s clock, the function's
  name, the thread and whether the persistent cache held the program
  (:func:`compile_events`), so a span of the host timeline
  (``observability/trace.py``) can say what compiled inside it.
- **Explicit**: :func:`record_transfer` counters the instrumented hot
  paths call with the byte counts they move (Trainer.fit's batch
  device_put, ParallelInference's per-dispatch H2D/D2H, checkpoint
  snapshot D2H).

All instruments live on the process-global default registry; one module
-level jax.monitoring listener dispatches to whichever collector is
current, so registry resets (tests, bench) never stack listeners.
jax itself is imported lazily — importing this module costs nothing.

Beside the gauges sits one process-level table, **what each compiled
program is made of** (:func:`publish_program` / :func:`program_table`):
module name -> ``{"flops", "scopes": {HLO instruction: component scope},
"stale"}``,
published by whoever compiled the program (``Trainer.step_flops``) and
read by whoever holds a device trace of it — the trace names each
operation by its HLO instruction (``%fusion.13``), the table says which
part of the model step that instruction belongs to. It outlives the
trainer that filled it. The publisher hands over a way to get the
optimised module's text, not the text: the text of an executable that
was restored from the persistent compilation cache takes the TPU's
runtime seconds to produce, with the interpreter lock held (3.4-4.2 s
for the ``gpt2_small`` and ``bert_base`` steps, measured on a v5e), so
it is fetched and parsed when the table is first read, never while a
fit loop starts up.
"""

from __future__ import annotations

import re
import threading
from collections import deque
from typing import Callable, Dict, List, Optional

from deeplearning4j_tpu.observability import metrics as _metrics
from deeplearning4j_tpu.observability import trace as _trace
from deeplearning4j_tpu.observability.vocab import scope_of, subscope_of

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


# the stages of a compilation that jax times, by the event's last name
_COMPILE_STAGES = frozenset({
    "jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
    "backend_compile_duration", "cache_retrieval_time_sec",
    "compile_time_saved_sec"})
_COMPILE_EVENTS: deque = deque(maxlen=4096)
# tracing a model's step traces every jnp function it calls as a function
# of its own (3,744 such events in gpt2_small's step, microseconds each,
# all inside the step's own event, which counts their time): a trace
# shorter than this is not kept
_TRACE_FLOOR_S = 1e-3
_CACHE_VERDICTS = {"/jax/compilation_cache/cache_hits": "hit",
                   "/jax/compilation_cache/cache_misses": "miss"}
_CACHE_COUNTS = {"hit": 0, "miss": 0}
# a lock of its own, held for the count alone: a compile can start under
# any other lock of this module (program_table() fetches a program's text,
# a read of the cache, with _programs_lock held)
_cache_counts_lock = threading.Lock()
# per thread: the cache's verdict and the cache's own timings since the
# thread's last backend compile, which closes the compilation they belong
# to (jax reports them first, and without the function's name)
_compiling = threading.local()


def _dispatch_event(event: str, duration: float, **kw):
    if not _metrics.enabled():
        return
    kind = event.rsplit("/", 1)[-1]
    if kind not in _COMPILE_STAGES or (
            kind == "jaxpr_trace_duration" and duration < _TRACE_FLOOR_S):
        return
    row = {"kind": kind, "seconds": float(duration), "end": _trace.now(),
           "fun_name": kw.get("fun_name"),
           "thread": threading.current_thread().name}
    if kind == "backend_compile_duration":
        row["cache"] = _compiling.__dict__.pop("cache", None)
        for earlier in _compiling.__dict__.pop("rows", ()):
            earlier["fun_name"] = row["fun_name"]
        c = _collector
        if c is not None:
            try:
                c.on_compile(duration)
            except Exception:  # noqa: BLE001 - telemetry never breaks compiles
                pass
    elif row["fun_name"] is None:
        _compiling.__dict__.setdefault("rows", []).append(row)
    _COMPILE_EVENTS.append(row)


def _dispatch_cache_event(event: str, **kw):
    verdict = _CACHE_VERDICTS.get(event)
    if verdict is not None and _metrics.enabled():
        _compiling.cache = verdict
        with _cache_counts_lock:
            _CACHE_COUNTS[verdict] += 1


def watch_compiles():
    """Register the module-level listeners once per process (whoever
    compiles first calls this: a ``Trainer``, a collector). jax has no
    unregister, so they are fixed dispatchers that forward to the CURRENT
    collector — registry resets swap the target, never stack callbacks."""
    global _listener_installed
    if _listener_installed:
        return
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_dispatch_event)
    jax.monitoring.register_event_listener(_dispatch_cache_event)
    _listener_installed = True


def compile_events() -> List[dict]:
    """Every compilation stage jax timed since :func:`watch_compiles`, in
    the order they ended: ``{"kind", "seconds", "end", "fun_name",
    "thread"}`` with ``end`` on ``trace.now()``'s clock and ``kind`` one of
    ``jaxpr_trace_duration``, ``jaxpr_to_mlir_module_duration``,
    ``backend_compile_duration`` (which has ``cache``: ``"hit"``,
    ``"miss"`` or None where no persistent cache was asked),
    ``cache_retrieval_time_sec`` and ``compile_time_saved_sec``; traces
    under a millisecond (the jnp functions inside a traced function, whose
    own event holds their time) are left out. A bounded list that outlives
    whoever compiled, as the program table does."""
    return [dict(row) for row in list(_COMPILE_EVENTS)]


def cache_counts() -> Dict[str, int]:
    """How often the persistent compilation cache held a program asked of
    it (``hit``) and how often one was compiled and written to it
    (``miss``)."""
    with _cache_counts_lock:
        return dict(_CACHE_COUNTS)


def get_runtime_collector() -> RuntimeCollector:
    """The process collector on the default registry (created lazily,
    compile listener installed on first use)."""
    global _collector
    with _collector_lock:
        if _collector is None:
            _collector = RuntimeCollector()
            watch_compiles()
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


# -- what each compiled program is made of ------------------------------------

_PROGRAMS: Dict[str, dict] = {}
_programs_lock = threading.Lock()

_HLO_MODULE = re.compile(r"^HloModule ([^\s,]+)")
_HLO_COMPUTATION = re.compile(r"^(ENTRY )?%?([^\s(]+) \(.*\{$")
_HLO_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?([^\s=]+) = ")
_HLO_OPCODE = re.compile(r"(?:^|\s)([a-z][\w\-]*)\(")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
# the computations an instruction runs as programs of their own (a loop's
# body, a branch, a call); a fusion's ``calls=`` is read for its root only
_HLO_CALLED = re.compile(
    r"\b(?:body|condition|to_apply|calls|true_computation|"
    r"false_computation)=%?([^\s,)}]+)|branch_computations=\{([^}]*)\}")


def scopes_of_hlo(text: str, scope_of=scope_of):
    """``(module name, {instruction: component scope or None})`` of an
    optimised HLO module's text (``compiled.as_text()``): every
    instruction of the entry computation and of the computations it runs
    through loops, branches and calls — the instructions a device trace
    names. The scope is read from the instruction's own ``op_name``
    metadata (``observability.vocab.scope_of``); a fusion without one
    takes its root's, and where the root has none either, the scope most
    of the fused instructions carry. With ``scope_of=subscope_of`` the
    same walk gives every instruction's innermost sub-scope."""
    module, (scopes,) = _scopes_of_hlo(text, (scope_of,))
    return module, scopes


def _scopes_of_hlo(text: str, scope_fns):
    """One walk of the text for several readings of an ``op_name``:
    ``(module name, [{instruction: scope or None} for each function])``."""
    module = None
    comps: Dict[str, list] = {}   # computation -> [(name, line, is_root)]
    entry = None
    current = None
    for line in text.splitlines():
        if current is None:
            m = _HLO_COMPUTATION.match(line)
            if m is not None:
                current = comps.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
            elif module is None:
                m = _HLO_MODULE.match(line)
                if m is not None:
                    module = m.group(1)
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _HLO_INSTRUCTION.match(line)
        if m is not None:
            current.append((m.group(2), line, bool(m.group(1))))

    def called(line):
        for one, many in _HLO_CALLED.findall(line):
            if one:
                yield one
            for name in many.split(","):
                if name.strip():
                    yield name.strip().lstrip("%")

    nothing = (None,) * len(scope_fns)

    def own_scopes(line):
        m = _HLO_OP_NAME.search(line)
        if m is None:
            return nothing
        return tuple(fn(m.group(1)) for fn in scope_fns)

    def fused_scope(comp, i):
        """Of a fused computation: its root's scope, else the scope that
        most of its instructions carry."""
        body = comps.get(comp, ())
        root = next((own_scopes(ln)[i] for _, ln, is_root in body if is_root),
                    None)
        if root is not None:
            return root
        inner = [sc for sc in (own_scopes(ln)[i] for _, ln, _ in body) if sc]
        return max(inner, key=inner.count) if inner else None

    found: List[Dict[str, Optional[str]]] = [{} for _ in scope_fns]
    todo, seen = [entry], set()
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for name, line, _ in comps[comp]:
            scopes = own_scopes(line)
            opcode = _HLO_OPCODE.search(line.split(" = ", 1)[1])
            fusion = opcode is not None and opcode.group(1) == "fusion"
            if not fusion:
                todo.extend(called(line))
            for i, scope in enumerate(scopes):
                if fusion and scope is None:
                    scope = next(filter(None, (fused_scope(c, i)
                                               for c in called(line))), None)
                found[i][name] = scope
    return module, found


def publish_program(module: str, *, flops: Optional[float],
                    scopes: Optional[Dict[str, Optional[str]]] = None,
                    subscopes: Optional[Dict[str, Optional[str]]] = None,
                    text: Optional[Callable[[], str]] = None,
                    carries: Optional[str] = None):
    """Record what the compiled program ``module`` is made of; a later
    compile under the same name (another batch shape) replaces it.

    Either the ``scopes`` themselves (and the ``subscopes``, where the
    program has any), or ``text``: a function that returns the optimised
    module's text (``compiled.as_text()``), called once, when the table
    is first read. ``carries`` names a scope that
    the publisher knows the program to carry; a text without it marks the
    entry ``stale``: the executable's metadata are not this program's.
    jax keys its persistent compilation cache on the program *without*
    its metadata, so an entry written before a scope existed is read back
    with the names it was written with, until the entry is removed."""
    if (scopes is None) == (text is None):
        raise ValueError("publish_program takes scopes or text, one of them")
    entry = {"flops": flops, "stale": False}
    if scopes is not None:
        entry["scopes"] = dict(scopes)
        if subscopes is not None:
            entry["subscopes"] = dict(subscopes)
    else:
        entry["text"], entry["carries"] = text, carries
    with _programs_lock:
        _PROGRAMS[module] = entry


def _resolve(module: str, entry: dict):
    """Fetch and parse a pending entry's text, in place."""
    with _trace.span("program_table.resolve", module=module):
        text = entry["text"]()
        _, (scopes, subscopes) = _scopes_of_hlo(
            text, (scope_of, subscope_of))
    carries = entry["carries"]
    del entry["text"], entry["carries"]
    entry["scopes"] = scopes
    entry["subscopes"] = {k: v for k, v in subscopes.items() if v}
    entry["stale"] = carries is not None and carries not in scopes.values()
    if entry["stale"]:
        from deeplearning4j_tpu.observability.flightrecorder import (
            record_event,
        )

        record_event("compile_cache.stale_metadata", module=module)


def program_table() -> Dict[str, dict]:
    """Module name -> ``{"flops", "scopes", "subscopes", "stale"}`` of
    every program published in this process (``subscopes`` holds only
    the instructions that are in one). The first read after a program was
    published with its ``text`` fetches and parses it, which can take
    seconds (and a compile, where no compilation cache holds the
    program)."""
    with _programs_lock:
        for module, entry in list(_PROGRAMS.items()):
            if "text" in entry:
                try:
                    _resolve(module, entry)
                except Exception:  # noqa: BLE001 - a text that cannot be
                    del _PROGRAMS[module]  # had is no table, not a crash
        return dict(_PROGRAMS)


# -- counters of the last step of a fit -----------------------------------------

_STEP_COUNTERS: Dict[str, object] = {}


def publish_step_counters(values: Dict[str, object]):
    """Record the counters a step carries in its metrics
    (``observability.vocab.STEP_COUNTERS``), as ``Trainer.fit`` fetched
    them from its last step; they outlive the trainer."""
    with _programs_lock:
        _STEP_COUNTERS.update(values)


def step_counters() -> Dict[str, object]:
    """Counter name -> the value of the last fit's last step (a number,
    or nested lists of them)."""
    with _programs_lock:
        return dict(_STEP_COUNTERS)


def _reset():
    global _collector
    with _collector_lock:
        if _collector is not None:
            _collector.stop()
        _collector = None


_metrics.register_reset_hook(_reset)
