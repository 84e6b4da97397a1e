"""ModelServer: the HTTP front of the serving subsystem.

stdlib ``ThreadingHTTPServer`` over ``ModelRegistry`` +
``AdmissionController`` + ``ServingMetrics`` — no dependencies beyond
what the repo already ships. Endpoints:

- ``POST /v1/models/<name>:predict`` — body
  ``{"inputs": ..., "deadline_ms": <optional>}``; 200 returns
  ``{"model", "version", "outputs"}``; failures return the structured
  error envelope (errors.py) with 400/404/429/503/504 status.
- ``POST /v1/models/<name>:generate`` — the generative serving engine
  (serving/generation.py; ``generators={name: GenerationEngine}``):
  body ``{"prompt": [ids...], "max_new_tokens"?, "temperature"?,
  "eos_id"?, "stream"?: true}``. Streaming responses are chunked
  newline-delimited JSON (``{"token": id}`` per token, terminal
  ``{"done": ...}`` or typed ``{"error": ...}`` line);
  ``"stream": false`` collects server-side into one JSON body.
  ``GET /debug/generation`` renders live engine state.
- ``GET /models``   — registry contents (name, version, history, warmed).
- ``GET /healthz``  — process liveness, always 200 while serving.
- ``GET /readyz``   — 200 only after every registered model's warmup
  completed AND the server is not draining; 503 otherwise. While a
  warmup pass is in flight the 503 body carries progress —
  ``{warmed: k, total: n, retry_after_ms}`` plus a ``Retry-After``
  header — so the fleet router's prober treats a warming backend as
  alive-but-compiling (probe-neutral) and retrying clients back off by
  the estimate instead of a blind schedule. ``start(warm_async=True)``
  binds the port immediately and warms in the background (the
  restart-under-load shape); predicts against a still-cold model shed
  with a retryable 503 instead of sneaking a compile into the warmup.
- ``GET /metrics``  — Prometheus text format; ``?format=json`` for the
  JSON twin. Renders this server's serving bundle UNION the process-
  global default registry (observability/metrics.py), so the train /
  resilience / checkpoint / runtime-collector series of the same
  process ride the same scrape.

Diagnostics plane (``/debug/*`` — the operator-facing consumers of the
telemetry spine):

- ``GET /debug/health`` — SLO alert states + live burn rates from the
  server's :class:`~deeplearning4j_tpu.observability.slo.HealthEngine`
  (default rules: serving availability 99.9% + p99 latency; pass
  ``slo_rules=``/``slo_engine=`` to override). ``?format=text`` for the
  one-line-per-rule rendering.
- ``GET /debug/flightrecorder`` — the black-box event ring
  (``?seconds=N`` trims to the trailing window).
- ``POST /debug/profile?ms=N`` — capture ``jax.profiler`` of LIVE
  traffic for N ms; returns the Perfetto trace (gzipped, base64) plus
  the ``analyze_trace`` device-op breakdown. One capture at a time;
  a concurrent capture gets ``409`` with ``Retry-After`` + a precise
  ``retry_after_ms`` body field so client retry composes.
- ``GET /debug/costs`` — per-registered-model static XLA cost analysis
  (flops, bytes accessed, arithmetic intensity; ``?rows=N`` overrides
  the batch size analyzed).
- ``GET /debug/incidents`` — the anomaly sentinel's incident-bundle
  index; ``GET /debug/incidents/<id>`` fetches one full bundle
  (observability/incidents.py).
- ``GET /debug/requests`` — the always-on request ledger
  (observability/reqlog.py): one lifecycle record per request on both
  planes, filterable by ``outcome``/``tenant``/``model``/``plane``/
  ``min_latency_ms``; ``GET /debug/requests/<correlation-id>`` returns
  one request's record plus its tail-retained span tree
  (Chrome-format twin included). Tail sampling keeps span trees only
  for bad outcomes, latency outliers, and a deterministic 1-in-N
  sample — the ledger record itself exists for every request.

Anomaly sentinel (``sentinel=True``, the default): a rolling-baseline
detector engine (observability/sentinel.py) ticks alongside the SLO
evaluator — step-time / serving-p99 regressions, recompile storms,
queue buildup, data starvation, leak heuristics — each with an
ok→suspect→firing state machine. *Suspect* arms the always-on host
stack sampler's high-rate window; *firing* writes an incident bundle
(detector verdict, scrape, flight dump, span slice, host flames, and a
short live-traffic ``jax.profiler`` capture via the server's registered
profile hook) under bounded retention.

Predict requests propagate correlation IDs: ``X-Correlation-ID`` /
``X-Span-ID`` headers (minted when absent, echoed back) root the
server-side span tree request → admission → batch → dispatch
(observability/trace.py).

Graceful drain (``stop(drain=True)``): flip draining (readyz → 503, new
predicts shed with UNAVAILABLE), wait for in-flight requests to finish,
then stop the HTTP loop and shut the replica sets down (their FIFO
drain serves anything still queued).

**Overload management** (serving/overload.py, ``overload=
OverloadPolicy()``, None disables): predicts carry ``X-Priority``
(``critical``/``normal``/``batch``, validated) and ``X-Tenant``
headers; admission sheds lowest-class first against per-class
thresholds of an AIMD-adapted effective limit (``critical`` is never
shed while lower-class work is in flight), per-tenant token buckets
shed runaways with a distinct ``TENANT_QUOTA`` 429 whose Retry-After
is the exact refill wait, and sustained overload walks a brownout
ladder (shrink batch wait → shed ``batch`` class → hot-swap registered
fallback versions) with hysteresis, emitting ``serving.brownout``
flight events and the ``serving_brownout_*`` metric families.
``GET /debug/overload`` renders the manager's live state. Retry-After
hints everywhere scale with measured overshoot (in-flight over the
limit × the recent batch service EWMA) instead of a fixed 50 ms.

Per-model-version **circuit breaker** (serving/circuit.py,
``circuit_policy=``, None disables): a version failing at/above the
windowed rate sheds instantly with ``503 CIRCUIT_OPEN`` + Retry-After
(remaining open time) until half-open probes prove it healthy again —
failures are 500s and worker crashes, never 4xx, admission sheds, or
504s (deadlines are client-chosen and must not be weaponizable).
``serving_circuit_state`` / ``serving_circuit_transitions_total``
metrics + ``serving.circuit`` flight events trace every transition.
"""

from __future__ import annotations

import base64
import json
import os
import re
import threading
import time
from queue import Empty as _queue_Empty
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional, Sequence, Tuple
from urllib.parse import parse_qs

import jax
import numpy as np

from deeplearning4j_tpu.observability import incidents as _incidents
from deeplearning4j_tpu.observability import reqlog as _reqlog
from deeplearning4j_tpu.observability import sentinel as _sentinel
from deeplearning4j_tpu.observability import slo as _slo
from deeplearning4j_tpu.observability import timeseries as _timeseries
from deeplearning4j_tpu.observability import trace as _trace
from deeplearning4j_tpu.observability import usage as _usage
from deeplearning4j_tpu.observability.flightrecorder import (
    get_flight_recorder,
    record_event,
)
from deeplearning4j_tpu.observability.hostsampler import get_host_sampler
from deeplearning4j_tpu.observability.metrics import (
    CONTENT_TYPE_OPENMETRICS,
    CONTENT_TYPE_TEXT,
    default_registry,
    render_json_multi,
    render_text_multi,
    wants_openmetrics,
)
from deeplearning4j_tpu.parallel.inference import (
    InferenceDeadlineExpired,
    InferenceQueueFull,
    InferenceShutdown,
    WorkerCrashError,
)
from deeplearning4j_tpu.resilience.faults import get_fault_injector as _fault_injector
from deeplearning4j_tpu.runtime import compilecache as _compilecache
from deeplearning4j_tpu.serving import warmstart as _warmstart
from deeplearning4j_tpu.serving.admission import AdmissionController
from deeplearning4j_tpu.serving.cache import (
    ENV_CACHE,
    CacheMetrics,
    _env_flag,
    resolve_response_cache,
    response_cache_key,
)
from deeplearning4j_tpu.serving.circuit import (
    STATE_NUM,
    CircuitBreaker,
    CircuitPolicy,
)
from deeplearning4j_tpu.serving.errors import (
    BadRequestError,
    CircuitOpenError,
    DeadlineExceededError,
    DeadlineExpiredError,
    ModelNotFoundError,
    NotReadyError,
    QueueFullError,
    ServingError,
    TenantQuotaError,
    WorkerCrashedError,
)
from deeplearning4j_tpu.serving.generation import (
    GenerationEngine,
    token_brownout_rung,
)
from deeplearning4j_tpu.serving.metrics import ServingMetrics
from deeplearning4j_tpu.serving.overload import (
    BrownoutLadder,
    BrownoutRung,
    OverloadManager,
    OverloadPolicy,
    validate_priority,
)
from deeplearning4j_tpu.serving.registry import ModelRegistry

_PREDICT_RE = re.compile(r"^/v1/models/([\w.\-]+):predict$")
_GENERATE_RE = re.compile(r"^/v1/models/([\w.\-]+):generate$")

_SHED_REASONS = {
    QueueFullError: "queue_full",
    TenantQuotaError: "tenant_quota",
    DeadlineExceededError: "deadline",
    DeadlineExpiredError: "deadline_expired",
    NotReadyError: "draining",
    CircuitOpenError: "circuit_open",
    WorkerCrashedError: "worker_crash",
}

_MAX_TENANT_LEN = 128


def _payload_shape(features):
    """Shape descriptor for the ledger/trace-export plane: a list of
    ints for a single array, ``{name: shape}`` for dict features, None
    when the pytree is anything fancier — shapes only, never values."""
    try:
        if isinstance(features, dict):
            return {str(k): list(np.asarray(v).shape)
                    for k, v in features.items()}
        return list(np.asarray(features).shape)
    except Exception:  # noqa: BLE001 — telemetry never fails serving
        return None


class _CachedResponse(Exception):
    """Internal short-circuit: raised inside handle_predict's try block
    when the response cache answers, caught before the ServingError
    clause so the cached body rides the normal metrics/ledger tail
    without touching admission, the breaker, or a batch slot."""

    def __init__(self, body: dict, stale: bool):
        super().__init__("cached")
        self.body = body
        self.stale = stale


class ModelServer:
    def __init__(
        self,
        registry: Optional[ModelRegistry] = None,
        *,
        host: str = "127.0.0.1",
        port: int = 0,
        metrics: Optional[ServingMetrics] = None,
        admission: Optional[AdmissionController] = None,
        default_deadline_ms: float = 30000.0,
        slo_rules: Optional[Sequence["_slo.SLORule"]] = None,
        slo_engine: Optional["_slo.HealthEngine"] = None,
        slo_interval_s: float = 10.0,
        slo_time_scale: float = 1.0,
        max_profile_ms: float = 60000.0,
        circuit_policy: Optional[CircuitPolicy] = CircuitPolicy(),
        overload: Optional[OverloadPolicy] = None,
        generators: Optional[dict] = None,
        sentinel: bool = True,
        sentinel_detectors: Optional[Sequence] = None,
        sentinel_interval_s: float = 10.0,
        incident_dir: Optional[str] = None,
        incident_profile_ms: float = 250.0,
        warmup_manifest=None,
        compile_cache=None,
        cache=None,
        timeseries=None,
        usage=None,
    ):
        self.registry = registry if registry is not None else ModelRegistry()
        # Cold-start robustness (serving/warmstart.py + runtime/
        # compilecache.py): the warmup manifest records the live
        # (model, bucket) traffic mix and start() AOT-compiles exactly
        # those shapes before /readyz flips; the persistent compile
        # cache (integrity-verified, quarantining) makes each of those
        # compiles a disk read on restart. Both default from env
        # (DL4J_TPU_WARMUP_MANIFEST / JAX_COMPILATION_CACHE_DIR; the
        # elastic supervisor arms them per generation); pass False to
        # disable explicitly, a path or instance to configure directly.
        self.warm_manifest = _warmstart.resolve_warmup_manifest(
            warmup_manifest)
        if self.warm_manifest is not None:
            self.registry.attach_manifest(self.warm_manifest)
        self._compile_cache_disabled = compile_cache is False
        if compile_cache is False:
            self.compile_cache = None
        elif isinstance(compile_cache, _compilecache.CompileCache):
            self.compile_cache = compile_cache
        elif compile_cache is not None:
            self.compile_cache = _compilecache.CompileCache(compile_cache)
        else:
            self.compile_cache = None  # start() falls back to env
        self._warm_progress = _warmstart.WarmupProgress()
        self._warm_thread: Optional[threading.Thread] = None
        if metrics is not None:
            self.metrics = metrics
        elif getattr(self.registry, "_metrics", None) is not None:
            # adopt the bundle the registry was built with rather than
            # silently re-routing its worker-side metrics to a fresh one
            self.metrics = self.registry._metrics
        else:
            self.metrics = ServingMetrics()
        self.registry.attach_metrics(self.metrics)
        self.admission = admission if admission is not None else \
            AdmissionController(on_depth=self.metrics.queue_depth.set,
                                default_deadline_ms=default_deadline_ms)
        if getattr(self.admission, "on_class_depth", None) is None:
            self.admission.on_class_depth = (
                lambda cls, depth: self.metrics.class_in_flight.set(
                    depth, priority=cls))
        # worker batch service times feed the admission Retry-After
        # overshoot EWMA (satellite of the overload work: the shed hint
        # scales with how buried the server actually is)
        self.registry.attach_admission(self.admission)
        # Exact-match response cache (serving/cache.py): consulted in
        # handle_predict BEFORE admission, so a hit never takes a batch
        # slot. Tenant-scoped (X-Tenant), keyed on (model, version,
        # registry epoch, canonical payload); the registry invalidation
        # listener drops a model's entries the moment a hot-swap /
        # rollback activates different weights. None defers to the
        # DL4J_TPU_CACHE env knob; default OFF — identical-payload
        # traffic is the common case in tests and benches, and serving
        # it from memory there would be lying about the model path.
        self.cache_metrics: Optional[CacheMetrics] = None
        if (cache is not None and cache is not False) \
                or (cache is None and _env_flag(ENV_CACHE)):
            self.cache_metrics = CacheMetrics(self.metrics.registry)
        self.response_cache = resolve_response_cache(
            cache, metrics=self.cache_metrics, plane="serving")
        if self.response_cache is not None:
            self.registry.add_invalidation_listener(
                lambda name, version, epoch, reason:
                self.response_cache.invalidate_model(name, reason=reason))
        # Overload management (overload.py): priority-class admission +
        # tenant quotas are enforced inside the AdmissionController once
        # the manager attaches; the manager's tick adapts the in-flight
        # limit (AIMD over p99-vs-baseline) and walks the brownout
        # ladder (shrink batch wait → shed batch class → fallback
        # models). None = static admission, the historical behavior.
        self.overload: Optional[OverloadManager] = None
        if overload is not None:
            self.overload = OverloadManager(
                overload, metrics=self.metrics,
                registries=[self.metrics.registry])
            self.overload.bind_limit(self.admission.max_in_flight)
            self.overload.ladder = BrownoutLadder(
                self._default_brownout_rungs(),
                on_transition=self.overload._on_brownout_transition)
            self.admission.attach_overload(self.overload)
            self.metrics.effective_limit.set(self.overload.effective_limit)
            self.metrics.brownout_level.set(0)
        self._draining = False
        self._started = False
        self._serve_thread: Optional[threading.Thread] = None
        # Generative serving engines (serving/generation.py): continuous-
        # batching decode schedulers keyed by route name, served at
        # POST /v1/models/<name>:generate with streamed (chunked ndjson)
        # or collected responses. Each engine rides this server's metrics
        # bundle and — when overload management is on — its AIMD limit,
        # tenant quotas, batch-class brownout shed, and a dedicated
        # shrink-max_new_tokens brownout rung ahead of fallback hot-swap.
        self.generators: dict = {}
        for gname, engine in (generators or {}).items():
            self.add_generator(gname, engine)
        # Historical telemetry tier (observability/timeseries.py +
        # usage.py): the mini-TSDB sampler snapshots this server's
        # serving bundle UNION the process default registry into tiered
        # rings (GET /debug/timeseries); the usage meter attributes
        # requests/tokens (via the ledger finish sink) and device-batch-
        # seconds/FLOPs (via the registry batch listener) per
        # (tenant, model) and rolls up into the store (/debug/usage);
        # the capacity evaluator derives per-model headroom verdicts
        # from store rates vs the measured peak (/debug/capacity — the
        # autoscaler's input contract). None = on (the default);
        # False disables; an instance is adopted as-is.
        self.timeseries: Optional[_timeseries.TimeSeriesStore] = None
        if timeseries is not False:
            if isinstance(timeseries, _timeseries.TimeSeriesStore):
                self.timeseries = timeseries
                if self.timeseries._registries is None:
                    # an unbound store samples only the process default
                    # registry — bind it to this server's serving
                    # bundle too, or every serving_* family is invisible
                    self.timeseries._registries = [
                        self.metrics.registry, default_registry()]
            else:
                self.timeseries = _timeseries.TimeSeriesStore(
                    registries=[self.metrics.registry, default_registry()])
        self.usage: Optional[_usage.UsageMeter] = None
        self.capacity: Optional[_usage.CapacityEvaluator] = None
        if usage is not False:
            self.usage = (usage if isinstance(usage, _usage.UsageMeter)
                          else _usage.UsageMeter())
            self.usage.set_cost_resolver(self._entry_or_none)
            self.registry.add_batch_listener(self.usage.on_batch)
        if self.timeseries is not None:
            try:
                rollup_s = float(
                    os.environ.get(_usage.ENV_USAGE_ROLLUP_S) or 10.0)
            except ValueError:
                rollup_s = 10.0
            if self.usage is not None:
                self.timeseries.add_collector(self.usage.collect,
                                              every_s=rollup_s)
            self.capacity = _usage.CapacityEvaluator(
                self.timeseries, resolver=self._entry_or_none)
            self.timeseries.add_collector(self.capacity.collect,
                                          every_s=rollup_s)
        # Diagnostics plane: the health engine evaluates this server's
        # serving bundle UNION the process default registry, so train /
        # resilience series in the same process count toward rules too.
        # With the TSDB armed, the engine's burn-rate windows live in
        # store-owned deques and survive warm restarts with it.
        if slo_engine is not None:
            self.slo_engine = slo_engine
        else:
            self.slo_engine = _slo.HealthEngine(
                slo_rules if slo_rules is not None
                else _slo.default_serving_rules(),
                registries=[self.metrics.registry, default_registry()],
                interval_s=slo_interval_s, time_scale=slo_time_scale,
                store=self.timeseries)
        self.max_profile_ms = max_profile_ms
        self._profile_lock = threading.Lock()
        # when a capture holds the lock, the deadline it runs until —
        # the 409's Retry-After derives from it
        self._profile_busy_until = 0.0
        # Anomaly sentinel + incident pipeline (observability/sentinel.py,
        # incidents.py): detectors tick over the same registries the SLO
        # engine reads; firing writes an incident bundle whose device
        # profile comes from this server's live-traffic capture hook.
        self.incident_profile_ms = float(incident_profile_ms)
        self.incidents: Optional["_incidents.IncidentManager"] = None
        self.sentinel: Optional["_sentinel.Sentinel"] = None
        if sentinel:
            if incident_dir is not None:
                self.incidents = _incidents.IncidentManager(incident_dir)
            else:
                self.incidents = _incidents.get_incident_manager(create=True)
            self.sentinel = _sentinel.Sentinel(
                sentinel_detectors,
                registries=[self.metrics.registry, default_registry()],
                interval_s=sentinel_interval_s,
                incidents=self.incidents,
                sampler=get_host_sampler())
        # Per-request observability (observability/reqlog.py): the
        # process request ledger records one lifecycle record for EVERY
        # request either plane sees, and drives tail-based trace
        # sampling — only errors/sheds/preemptions/deadline-misses,
        # latency outliers, and a deterministic 1-in-N sample keep
        # their span trees in the tracer ring. Served at
        # GET /debug/requests[?outcome=&tenant=&model=&min_latency_ms=]
        # and GET /debug/requests/<correlation-id>.
        self.reqlog = _reqlog.get_request_ledger(create=True)
        # Per-(model, version) circuit breakers: a bad deploy's failures
        # open ITS version's circuit; the rollback target starts fresh.
        # None disables breaking entirely.
        self.circuit_policy = circuit_policy.validate() \
            if circuit_policy is not None else None
        self._circuits: dict = {}
        self._circuits_lock = threading.Lock()
        server = self

        class Handler(BaseHTTPRequestHandler):
            # HTTP/1.1 for chunked streaming responses (:generate); every
            # non-streamed response carries Content-Length (see _send),
            # which 1.1 keep-alive requires
            protocol_version = "HTTP/1.1"

            # quiet: per-request stderr lines are useless under load tests
            def log_message(self, *a):  # noqa: N802 - stdlib API
                pass

            def _send(self, status: int, body, content_type="application/json",
                      retry_after_ms=None, correlation_id=None):
                if retry_after_ms is None and isinstance(body, dict):
                    # every retryable error body carries a precise
                    # error.retry_after_ms; derive the Retry-After header
                    # from it here so each route doesn't repeat the lookup
                    err = body.get("error")
                    if isinstance(err, dict):
                        retry_after_ms = err.get("retry_after_ms")
                raw = (body if isinstance(body, bytes)
                       else json.dumps(body).encode())
                self.send_response(status)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(raw)))
                if correlation_id is not None:
                    self.send_header("X-Correlation-ID", correlation_id)
                if retry_after_ms is not None:
                    # HTTP Retry-After is integer seconds; the precise ms
                    # hint rides in the error body's retry_after_ms
                    self.send_header(
                        "Retry-After",
                        str(max(1, -(-int(retry_after_ms) // 1000))))
                self.end_headers()
                self.wfile.write(raw)

            def do_GET(self):  # noqa: N802 - stdlib API
                path, _, query = self.path.partition("?")
                if path == "/healthz":
                    self._send(200, {"status": "ok"})
                elif path == "/readyz":
                    body = server.readiness()
                    self._send(200 if body["ready"] else 503, body,
                               retry_after_ms=body.get("retry_after_ms"))
                elif path == "/models":
                    self._send(200, {"models": server.registry.describe()})
                elif path == "/metrics":
                    if "format=json" in query:
                        self._send(200, server.render_metrics_json())
                    else:
                        om = wants_openmetrics(self.headers.get("Accept"))
                        self._send(
                            200,
                            server.render_metrics_text(
                                openmetrics=om).encode(),
                            content_type=(CONTENT_TYPE_OPENMETRICS if om
                                          else CONTENT_TYPE_TEXT))
                elif path == "/debug/health":
                    if "format=text" in query:
                        self._send(200, server.render_health_text().encode(),
                                   content_type="text/plain")
                    else:
                        self._send(200, server.render_health())
                elif path == "/debug/flightrecorder":
                    q = parse_qs(query)
                    try:
                        seconds = (float(q["seconds"][0])
                                   if "seconds" in q else None)
                    except ValueError:
                        self._send(400, BadRequestError(
                            "seconds must be a number").to_json())
                        return
                    self._send(200, get_flight_recorder().dump(
                        last_seconds=seconds))
                elif path == "/debug/costs":
                    q = parse_qs(query)
                    try:
                        rows = int(q["rows"][0]) if "rows" in q else None
                    except ValueError:
                        rows = 0
                    if rows is not None and rows < 1:
                        self._send(400, BadRequestError(
                            "rows must be a positive integer").to_json())
                        return
                    self._send(200, server.render_costs(rows=rows))
                elif path == "/debug/overload":
                    if server.overload is None:
                        self._send(404, ServingError(
                            "overload management is disabled "
                            "(pass overload=OverloadPolicy())").to_json())
                    else:
                        self._send(200, server.overload.describe())
                elif path == "/debug/generation":
                    self._send(200, {"engines": {
                        name: eng.describe()
                        for name, eng in server.generators.items()}})
                elif path == "/debug/requests":
                    q = parse_qs(query)
                    try:
                        min_latency_ms = (float(q["min_latency_ms"][0])
                                          if "min_latency_ms" in q else None)
                        limit = int(q.get("limit", ["100"])[0])
                        window_s = (float(q["window_s"][0])
                                    if "window_s" in q else None)
                    except ValueError:
                        self._send(400, BadRequestError(
                            "min_latency_ms, window_s and limit must "
                            "be numbers").to_json())
                        return
                    if q.get("format", [None])[0] == "trace":
                        # payload-scrubbed replayable trace of the
                        # ledger window (resilience/replay.py consumes
                        # this directly)
                        self._send(200, server.render_trace(
                            plane=q.get("plane", [None])[0],
                            model=q.get("model", [None])[0],
                            window_s=window_s,
                            limit=(limit if "limit" in q else None)))
                        return
                    self._send(200, server.render_requests(
                        outcome=q.get("outcome", [None])[0],
                        tenant=q.get("tenant", [None])[0],
                        model=q.get("model", [None])[0],
                        plane=q.get("plane", [None])[0],
                        min_latency_ms=min_latency_ms, limit=limit))
                elif path.startswith("/debug/requests/"):
                    cid = path[len("/debug/requests/"):]
                    body = server.render_request(cid)
                    if body is None:
                        self._send(404, ServingError(
                            f"no request {cid!r} in the ledger or "
                            "tracer ring").to_json())
                    else:
                        self._send(200, body)
                elif path == "/debug/cache":
                    if server.response_cache is None \
                            and not any(
                                getattr(e, "prefix_cache", None) is not None
                                for e in server.generators.values()):
                        self._send(404, ServingError(
                            "caching is disabled (pass cache=True / a "
                            "ResponseCache, or set DL4J_TPU_CACHE=1; "
                            "prefix reuse via prefix_cache= on the "
                            "generation engine or DL4J_TPU_PREFIX_CACHE=1"
                            ").").to_json())
                    else:
                        self._send(200, server.render_cache())
                elif path == "/debug/timeseries":
                    q = parse_qs(query)
                    try:
                        window_s = (float(q["window"][0])
                                    if "window" in q else None)
                        step_s = (float(q["step"][0])
                                  if "step" in q else None)
                        quant = float(q["q"][0]) if "q" in q else None
                    except ValueError:
                        self._send(400, BadRequestError(
                            "window, step and q must be "
                            "numbers").to_json())
                        return
                    labels = {k[len("label."):]: v[0]
                              for k, v in q.items()
                              if k.startswith("label.")}
                    for shorthand in ("model", "tenant"):
                        if shorthand in q:
                            labels[shorthand] = q[shorthand][0]
                    status, body = server.render_timeseries(
                        family=q.get("family", [None])[0],
                        window_s=window_s, step_s=step_s,
                        op=q.get("op", ["range"])[0], q=quant,
                        labels=labels or None)
                    self._send(status, body)
                elif path == "/debug/usage":
                    status, body = server.render_usage()
                    self._send(status, body)
                elif path == "/debug/capacity":
                    q = parse_qs(query)
                    status, body = server.render_capacity(
                        evaluate=q.get("evaluate", ["0"])[0]
                        in ("1", "true"))
                    self._send(status, body)
                elif path == "/debug/incidents":
                    self._send(200, server.render_incidents())
                elif path.startswith("/debug/incidents/"):
                    iid = path[len("/debug/incidents/"):]
                    body = server.render_incident(iid)
                    if body is None:
                        self._send(404, ServingError(
                            f"no incident {iid!r}").to_json())
                    else:
                        self._send(200, body)
                else:
                    self._send(404, ServingError(
                        f"no route {path}").to_json())

            def do_POST(self):  # noqa: N802 - stdlib API
                path, _, query = self.path.partition("?")
                if path == "/debug/profile":
                    # drain the (unused) request body: closing the socket
                    # with unread request bytes makes Linux RST instead
                    # of FIN, which can discard the tail of the multi-MB
                    # profile response still in the send buffer
                    n = int(self.headers.get("Content-Length", 0))
                    if n:
                        self.rfile.read(n)
                    q = parse_qs(query)
                    try:
                        ms = float(q.get("ms", ["500"])[0])
                    except ValueError:
                        self._send(400, BadRequestError(
                            "ms must be a number").to_json())
                        return
                    status, body = server.handle_profile(ms)
                    self._send(status, body)
                    return
                m = _PREDICT_RE.match(path)
                g = _GENERATE_RE.match(path)
                if not m and not g:
                    self._send(404, ServingError(
                        f"no route {self.path}").to_json())
                    return
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    payload = json.loads(self.rfile.read(n)) if n else {}
                except Exception as e:  # noqa: BLE001 - client's bad JSON
                    self._send(400, BadRequestError(
                        f"invalid JSON body: {e}").to_json())
                    return
                # correlation propagation: adopt the client's trace id and
                # parent span, mint a trace id for headerless callers, and
                # echo the id back so either side can find the span tree
                cid = (self.headers.get("X-Correlation-ID")
                       or _trace.new_id())
                if g is not None:
                    self._do_generate(g.group(1), payload, cid)
                    return
                status, body = server.handle_predict(
                    m.group(1), payload, correlation_id=cid,
                    parent_span_id=self.headers.get("X-Span-ID"),
                    priority=self.headers.get("X-Priority"),
                    tenant=self.headers.get("X-Tenant"),
                    cache_bypass=bool(
                        self.headers.get("X-Cache-Bypass")))
                self._send(status, body, correlation_id=cid)

            def _do_generate(self, name: str, payload, cid: str):
                status, body, stream = server.handle_generate(
                    name, payload, correlation_id=cid,
                    parent_span_id=self.headers.get("X-Span-ID"),
                    priority=self.headers.get("X-Priority"),
                    tenant=self.headers.get("X-Tenant"))
                if stream is None:
                    self._send(status, body, correlation_id=cid)
                    return
                # streaming: chunked newline-delimited JSON, one event
                # per line — {"token": id}* then {"done": ...} or a
                # terminal {"error": {...}} the client re-raises typed
                self.send_response(200)
                self.send_header("Content-Type", "application/x-ndjson")
                self.send_header("Transfer-Encoding", "chunked")
                self.send_header("X-Correlation-ID", cid)
                self.end_headers()
                ts0 = _trace.now()
                n_lines = 0
                try:
                    for ev in stream.wire_events():
                        line = json.dumps(ev).encode() + b"\n"
                        self.wfile.write(b"%X\r\n" % len(line)
                                         + line + b"\r\n")
                        self.wfile.flush()
                        n_lines += 1
                    self.wfile.write(b"0\r\n\r\n")
                except (BrokenPipeError, ConnectionResetError, OSError):
                    # client went away mid-stream: free the decode slot
                    # instead of generating tokens nobody reads
                    stream.cancel()
                    return
                server._record_stream_leg(cid, stream, ts0, n_lines)

        self._httpd = ThreadingHTTPServer((host, port), Handler)

    # -- surface -------------------------------------------------------------

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    @property
    def url(self) -> str:
        host = self._httpd.server_address[0]
        return f"http://{host}:{self.port}"

    def readiness(self) -> dict:
        models = {e["name"]: e["warmed"] for e in self.registry.describe()}
        gens = {name: eng.warmed for name, eng in self.generators.items()}
        ready = (self._started and not self._draining
                 and all(models.values()) and all(gens.values()))
        body = {"ready": ready, "draining": self._draining, "models": models}
        if gens:
            body["generators"] = gens
        if self._warm_progress.active and not ready:
            # warmup in flight: report progress so the router prober and
            # retrying clients compose with it ({warmed: k, total: n,
            # retry_after_ms}; the /readyz 503 also carries Retry-After)
            body.update(self._warm_progress.snapshot())
        return body

    @property
    def draining(self) -> bool:
        return self._draining

    # -- circuit breakers ----------------------------------------------------

    def circuit_for(self, model: str, version: str) -> Optional[CircuitBreaker]:
        """The (model, version) breaker, created on first use (None when
        breaking is disabled). Transitions feed ``serving_circuit_state``
        / ``serving_circuit_transitions_total`` and ``serving.circuit``
        flight events."""
        if self.circuit_policy is None:
            return None
        key = (model, version)
        with self._circuits_lock:
            cb = self._circuits.get(key)
            if cb is None:
                # bound per-model breaker retention to the last 3
                # versions (incl. the one created below): versions
                # further back can never serve again (rollback reaches
                # one back), so a long-lived server under continuous
                # deploys must not grow a breaker per version forever.
                # The registry has no series-removal API, so the retired
                # version's gauge is pinned to closed — a breaker frozen
                # at "open" for a version that no longer exists must not
                # page anyone forever (its series objects do persist:
                # per-deploy label cardinality, operator-bounded).
                stale = [k for k in self._circuits if k[0] == model][:-2]
                for k in stale:
                    del self._circuits[k]
                    self.metrics.circuit_state.set(
                        STATE_NUM["closed"], model=k[0], version=k[1])
                def _on_transition(frm, to, _key=key):
                    self.metrics.circuit_state.set(
                        STATE_NUM[to], model=_key[0], version=_key[1])
                    self.metrics.circuit_transitions_total.inc(
                        model=_key[0], version=_key[1], to=to)
                    record_event("serving.circuit", model=_key[0],
                                 version=_key[1], frm=frm, to=to)

                cb = CircuitBreaker(self.circuit_policy,
                                    on_transition=_on_transition)
                self.metrics.circuit_state.set(
                    STATE_NUM[cb.state], model=model, version=version)
                self._circuits[key] = cb
        return cb

    # -- predict path (handler-independent for direct testing) ---------------

    @staticmethod
    def _validate_priority(priority) -> str:
        """``X-Priority`` → a known class (overload.validate_priority —
        shared with the fleet router so the two planes can never
        disagree on the class vocabulary)."""
        return validate_priority(priority)

    @staticmethod
    def _validate_tenant(tenant) -> Optional[str]:
        """``X-Tenant`` → a bounded opaque key (None when absent)."""
        if tenant is None:
            return None
        t = str(tenant).strip()
        if not t:
            return None
        if len(t) > _MAX_TENANT_LEN:
            raise BadRequestError(
                f"X-Tenant must be <= {_MAX_TENANT_LEN} chars")
        return t

    def handle_predict(self, name: str, payload, *,
                       correlation_id: Optional[str] = None,
                       parent_span_id: Optional[str] = None,
                       priority=None, tenant=None,
                       cache_bypass: bool = False) -> Tuple[int, dict]:
        t0 = time.monotonic()
        # Unknown model names are client-controlled: labeling metrics with
        # them would grow a permanent label set per scanned/typo'd URL.
        metric_model = name
        cid = correlation_id if correlation_id else _trace.new_id()
        cb = None  # the breaker this request must report back to
        cb_token = None
        # the always-on ledger record + tail-sampling staging for this
        # correlation id — opened before the root span so every span of
        # this request (admission, batch, dispatch) stages
        led = self.reqlog
        if led is not None:
            led.begin(cid, plane="predict", model=name)
        # Root of the server-side span tree: the client's span (X-Span-ID)
        # is the parent, admission nests inside via the thread-local stack,
        # and the batch/dispatch legs are recorded against req_span by the
        # ParallelInference worker (observability/trace.py).
        with _trace.span("serving.request", trace_id=cid,
                         parent_id=parent_span_id, model=name) as req_span:
            try:
                prio = self._validate_priority(priority)
                tenant = self._validate_tenant(tenant)
                if led is not None:
                    led.annotate(cid, priority=prio, tenant=tenant)
                inj = _fault_injector()
                if inj.enabled:
                    # resilience injection points: "serving.latency" (sleep
                    # arg seconds), "serving.overload" (the same sleep,
                    # named for sustained synthetic-overload chaos — armed
                    # with xTIMES it degrades p99 until the budget runs
                    # out, driving AIMD shrink → brownout → recovery), and
                    # "serving.error" (retryable 429 shed) — deterministic
                    # spikes for client-retry and SLO tests, armed via
                    # DL4J_TPU_FAULTS
                    inj.maybe_sleep("serving.latency")
                    inj.maybe_sleep("serving.overload")
                    p = inj.fire("serving.error")
                    if p is not None:
                        raise QueueFullError(
                            "injected overload (fault injection)",
                            retry_after_ms=(p.arg * 1000.0) if p.arg else None)
                entry = self.registry.get(name)
                if self._draining or not self._started:
                    raise NotReadyError("server is draining" if self._draining
                                        else "server not started")
                if not entry.warmed and self._warm_progress.active:
                    # warmup in flight (HTTP answers during it so /readyz
                    # can report progress): traffic must not reach the
                    # replica set — a live request coalescing with a
                    # warmup batch would skip buckets, and the request
                    # itself would eat a compile
                    snap = self._warm_progress.snapshot()
                    raise NotReadyError(
                        f"model '{name}' is warming up "
                        f"({snap['warmed']}/{snap['total']} shapes "
                        "compiled)",
                        retry_after_ms=snap["retry_after_ms"])
                if not isinstance(payload, dict) or "inputs" not in payload:
                    raise BadRequestError('body must be {"inputs": ...}')
                # Response-cache consult — BEFORE the breaker and BEFORE
                # admission: a hit must not consume a batch slot, count
                # against the AIMD in-flight limit, or burn a breaker
                # probe. Key includes the entry's swap epoch, so entries
                # minted against superseded weights miss structurally
                # even before the invalidation listener prunes them.
                ckey = None
                rc = self.response_cache
                if rc is not None:
                    if cache_bypass:
                        rc.note_bypass()
                        if led is not None:
                            led.annotate(cid, cache="bypass")
                        if req_span is not None:
                            req_span.attrs["cache"] = "bypass"
                    else:
                        ckey = response_cache_key(
                            name, entry.version, entry.epoch, payload)
                        if ckey is None:
                            # unserializable payload: uncacheable, and
                            # counted as such rather than a fake miss
                            rc.note_bypass()
                            if led is not None:
                                led.annotate(cid, cache="bypass")
                            if req_span is not None:
                                req_span.attrs["cache"] = "bypass"
                        else:
                            hit = rc.get(tenant, ckey)
                            if hit is not None:
                                raise _CachedResponse(hit.value, hit.stale)
                            if led is not None:
                                led.annotate(cid, cache="miss")
                            if req_span is not None:
                                req_span.attrs["cache"] = "miss"
                # circuit breaker: a version failing at/above the policy
                # rate sheds instantly with 503 + Retry-After instead of
                # paying the failure path per request
                cb = self.circuit_for(name, entry.version)
                if cb is not None:
                    allowed, retry_after_s, cb_token = cb.allow()
                    if not allowed:
                        cb = None  # denied: nothing to record back
                        raise CircuitOpenError(
                            f"circuit open for model '{name}' "
                            f"(recent failure rate over threshold)",
                            retry_after_ms=retry_after_s * 1000.0)
                # Admit before the body parse: over-cap traffic must shed
                # before paying the array-coercion cost, not after.
                with _trace.span("serving.admission", priority=prio):
                    timeout = self.admission.timeout_s(
                        payload.get("deadline_ms"))
                    ticket = self.admission.admit(priority=prio,
                                                  tenant=tenant,
                                                  correlation_id=cid)
                if led is not None:
                    led.annotate(cid, admission="admitted",
                                 deadline_s=timeout)
                # the absolute deadline anchors at admission: a request
                # still queued past it is dropped before dispatch
                deadline = time.monotonic() + timeout
                try:
                    features = entry.parse_inputs(payload["inputs"])
                    if led is not None:
                        # shape, never bytes: this is what export_trace
                        # ships and what replay synthesizes inputs from
                        led.annotate(cid,
                                     payload_shape=_payload_shape(features))
                    tctx = ((cid, req_span.span_id)
                            if req_span is not None else None)
                    try:
                        out, version = entry.predict_versioned(
                            features, timeout=timeout, trace=tctx,
                            deadline=deadline)
                    except InferenceDeadlineExpired as e:
                        # dropped pre-dispatch: distinct code + shed
                        # reason — the client learns it never ran
                        raise DeadlineExpiredError(
                            str(e) or "deadline expired before "
                            "dispatch") from e
                    except TimeoutError as e:
                        raise DeadlineExceededError(
                            str(e) or "deadline exceeded") from e
                    except InferenceQueueFull as e:
                        raise QueueFullError(str(e)) from e
                    except WorkerCrashError as e:
                        # the worker holding this batch died; it was
                        # respawned — retryable 503, counted as a circuit
                        # failure (a crash-looping version must open)
                        raise WorkerCrashedError(str(e)) from e
                    except InferenceShutdown as e:
                        if getattr(e, "workers_dead", False):
                            # NOT a drain: every worker died and the
                            # respawn budget is gone — a truthful,
                            # circuit-countable outage signal
                            raise WorkerCrashedError(str(e)) from e
                        # lost the race against stop()/deploy drain: a
                        # structured retryable 503, not an INTERNAL 500
                        raise NotReadyError("server is draining") from e
                    except RuntimeError as e:
                        if "shut down" in str(e):
                            raise NotReadyError("server is draining") from e
                        raise
                finally:
                    ticket.release()
                outputs = jax.tree_util.tree_map(
                    lambda a: np.asarray(a).tolist(), out)
                status, body = 200, {"model": name, "version": version,
                                     "outputs": outputs}
                if rc is not None and ckey is not None:
                    rc.put(tenant, ckey, body, model=name, version=version)
            except _CachedResponse as e:
                status = 200
                body = dict(e.body)
                body["cached"] = True
                if e.stale:
                    # brownout stale-serve: past-TTL entry returned
                    # while the ladder's cache_pressure rung is engaged
                    body["cache_stale"] = True
                outcome = "stale" if e.stale else "hit"
                if led is not None:
                    led.annotate(cid, cache=outcome)
                if req_span is not None:
                    req_span.attrs["cache"] = outcome
            except ServingError as e:
                status, body = e.http_status, e.to_json()
                if isinstance(e, ModelNotFoundError):
                    metric_model = "<unknown>"
                reason = _SHED_REASONS.get(type(e))
                if reason is not None:
                    if led is not None:
                        led.annotate(cid, admission=f"shed:{reason}")
                    self.metrics.shed_total.inc(model=metric_model,
                                                reason=reason)
                    extra = {}
                    if isinstance(e, TenantQuotaError):
                        # the counter is deliberately unlabeled (client-
                        # controlled keys = unbounded series); per-tenant
                        # attribution rides the bounded flight ring
                        self.metrics.tenant_shed_total.inc()
                        extra["tenant"] = tenant or ""
                    record_event("serving.shed", model=metric_model,
                                 reason=reason, status=status, **extra)
            except Exception as e:  # noqa: BLE001 — surface, never crash
                status = 500
                body = {"error": {"code": "INTERNAL",
                                  "message": str(e)[:300],
                                  "retryable": False}}
                record_event("serving.error", model=metric_model,
                             error=str(e)[:200])
            if req_span is not None:
                req_span.attrs["status"] = status
        if cb is not None:
            # model-health outcomes only: 200 succeeds; 500s and worker
            # crashes fail. 504s are NEUTRAL — deadline_ms is client-
            # chosen, so one client sending impossible deadlines must
            # not be able to open the circuit for everyone. Client
            # errors and admission/drain sheds likewise say nothing
            # about the version and return the probe slot.
            if status == 200:
                cb.record(True, token=cb_token)
            elif status == 500 or (isinstance(body, dict)
                    and body.get("error", {}).get("code")
                    == WorkerCrashedError.code):
                cb.record(False, token=cb_token)
            else:
                cb.record_neutral(token=cb_token)
        self.metrics.requests_total.inc(model=metric_model, code=str(status))
        # OpenMetrics-style exemplar: the latency bucket this request
        # landed in keeps its correlation id, so a slow bucket in the
        # scrape links straight to the offending trace
        self.metrics.request_latency.observe(time.monotonic() - t0,
                                             model=metric_model,
                                             exemplar_trace_id=cid)
        if led is not None:
            # finishing the record runs the tail sampler's retention
            # decision over every span this request staged
            led.finish(cid, outcome=self._predict_outcome(status, body),
                       status=status,
                       version=(body.get("version")
                                if status == 200 and isinstance(body, dict)
                                else None))
        return status, body

    @staticmethod
    def _predict_outcome(status: int, body) -> str:
        """Map one predict response to a ledger outcome. ``rejected``
        (client errors) is deliberately NOT in the tail sampler's keep
        set — a port scanner's 404s must not evict real post-mortems
        from the tracer ring — while sheds, deadline misses, and server
        failures are."""
        if status == 200:
            return "ok"
        code = (body.get("error", {}).get("code")
                if isinstance(body, dict) else None)
        if status in (400, 404):
            return "rejected"
        if code in ("DEADLINE_EXCEEDED", "DEADLINE_EXPIRED") \
                or status == 504:
            return "deadline"
        if code == WorkerCrashedError.code:
            return "failed"
        if status in (429, 503):
            return "shed"
        return "error"

    # -- generative serving ---------------------------------------------------

    def add_generator(self, name: str, engine: "GenerationEngine"
                      ) -> "GenerationEngine":
        """Attach a continuous-batching generation engine under ``name``
        (served at ``POST /v1/models/<name>:generate``). Wires the
        serving metrics bundle, the overload manager (AIMD slot clamp,
        tenant quotas, batch-class brownout shed), and — first generator
        only — slots the shrink-``max_new_tokens`` brownout rung into
        the default ladder ahead of the fallback hot-swap."""
        if name in self.generators:
            raise ValueError(f"generator '{name}' already registered")
        engine.name = name
        engine.attach_metrics(self.metrics)
        if self.warm_manifest is not None:
            engine.attach_manifest(self.warm_manifest)
        pstore = getattr(engine, "prefix_cache", None)
        if pstore is not None:
            # prefix-store hit/byte series join this server's scrape
            if self.cache_metrics is None:
                self.cache_metrics = CacheMetrics(self.metrics.registry)
            if pstore._metrics is None:
                pstore.attach_metrics(self.cache_metrics)
            pstore.model = name
        self.generators[name] = engine
        if self.overload is not None:
            engine.attach_overload(self.overload)
            self._ensure_generation_rung()
        if self._started:
            # live registration follows the deploy discipline: warm
            # first (readyz gates on every generator's warmed flag, and
            # traffic must never pay the bucket compiles), then start
            if not engine.warmed:
                engine.warm()
            if not engine.running:
                engine.start()
        return engine

    def _ensure_generation_rung(self):
        """Insert the generation token-brownout rung ahead of
        ``serve_fallback`` — once. ``BrownoutLadder.insert_rung`` is
        safe mid-walk; it refuses only while the fallback rung itself
        is engaged, in which case a transition listener retries as soon
        as the ladder moves."""
        ladder = getattr(self.overload, "ladder", None)
        if ladder is None:
            return
        rung = token_brownout_rung(lambda: list(self.generators.values()))
        if ladder.insert_rung(rung, before="serve_fallback"):
            return
        if getattr(self, "_gen_rung_retry_armed", False):
            return
        self._gen_rung_retry_armed = True
        done = []

        def retry(*_a):
            # one-shot: after the insert lands, every later transition
            # is a flag check, not a rung rebuild + locked name scan
            if not done and ladder.insert_rung(rung,
                                               before="serve_fallback"):
                done.append(True)

        ladder.add_transition_listener(retry)

    def _record_stream_leg(self, cid: str, stream, ts0: float,
                           n_lines: int) -> None:
        """The stream-write leg: how long the chunked ndjson write to
        THIS client took. Recorded post-hoc after the engine already
        finished the request, so it rides the ring only when the tail
        sampler retained the trace — a fast dropped request must not
        leak its stream span past the retention decision."""
        try:
            rec = self.reqlog.get(cid) if self.reqlog is not None else None
            if rec is None or not rec.get("trace_retained"):
                return
            root = None
            for s in _trace.get_tracer().spans(trace_id=cid):
                if s.name == "generation.request":
                    root = s.span_id
                    break
            _trace.record_span(
                "serving.stream", trace_id=cid, parent_id=root,
                start=ts0, end=_trace.now(), lines=n_lines,
                tracer=_trace.get_tracer())
        except Exception:  # noqa: BLE001 — telemetry never fails serving
            pass

    def handle_generate(self, name: str, payload, *,
                        correlation_id: Optional[str] = None,
                        parent_span_id: Optional[str] = None,
                        priority=None, tenant=None):
        """Validate + submit one generation request.

        Returns ``(status, body, stream)``: ``stream`` is the live
        :class:`GenerationStream` for streaming requests (the handler
        chunks its events), None when the response is complete —
        an error envelope, or the collected non-streaming body
        (``{"stream": false}``)."""
        cid = correlation_id if correlation_id else _trace.new_id()
        handle = None
        # open the ledger record (and span staging) before the root
        # span, exactly like predict — a shed's spans stage too, so a
        # kept shed trace explains itself
        if self.reqlog is not None:
            self.reqlog.begin(cid, plane="generation", model=name)
        try:
            with _trace.span("serving.generate", trace_id=cid,
                             parent_id=parent_span_id,
                             model=name) as gen_span:
                prio = self._validate_priority(priority)
                tenant = self._validate_tenant(tenant)
                engine = self.generators.get(name)
                if engine is None:
                    raise ModelNotFoundError(f"no generator named '{name}'")
                if self._draining or not self._started:
                    raise NotReadyError("server is draining"
                                        if self._draining
                                        else "server not started")
                if not engine.warmed and self._warm_progress.active:
                    snap = self._warm_progress.snapshot()
                    raise NotReadyError(
                        f"generator '{name}' is warming up "
                        f"({snap['warmed']}/{snap['total']} shapes "
                        "compiled)",
                        retry_after_ms=snap["retry_after_ms"])
                if not isinstance(payload, dict) or "prompt" not in payload:
                    raise BadRequestError(
                        'body must be {"prompt": [ids...]}')
                mnt = payload.get("max_new_tokens")
                if mnt is not None and (isinstance(mnt, bool)
                                        or not isinstance(mnt, int)):
                    raise BadRequestError(
                        "max_new_tokens must be an integer")
                temp = payload.get("temperature")
                if temp is not None and (
                        isinstance(temp, bool)
                        or not isinstance(temp, (int, float))):
                    raise BadRequestError("temperature must be a number")
                eos = payload.get("eos_id")
                if eos is not None and (isinstance(eos, bool)
                                        or not isinstance(eos, int)):
                    raise BadRequestError("eos_id must be an integer")
                stream_mode = payload.get("stream", True)
                # every validation — deadline included — happens BEFORE
                # submit: a 400 must never leave an orphaned stream
                # decoding tokens nobody will read. The deadline
                # semantics match predict: default_deadline_ms when
                # absent, clamped at max_deadline_ms — and they bound
                # STREAMING responses too (the stream ends with a
                # terminal DEADLINE_EXCEEDED line)
                timeout = self.admission.timeout_s(
                    payload.get("deadline_ms"))
                record_event("generation.request", model=name,
                             priority=prio, correlation_id=cid,
                             stream=bool(stream_mode))
                if self.reqlog is not None:
                    # BEFORE submit: the scheduler may finish (preempt,
                    # fail) the stream the instant it exists, and the
                    # deadline must already be on the record for the
                    # finish path's deadline-slack computation. The
                    # stream flag rides along so export_trace replays
                    # this request through the same wire mode.
                    self.reqlog.annotate(cid, deadline_s=timeout,
                                         stream=bool(stream_mode))
                handle = engine.submit(
                    payload["prompt"], max_new_tokens=mnt,
                    temperature=temp, eos_id=eos, priority=prio,
                    tenant=tenant, correlation_id=cid,
                    parent_span_id=(gen_span.span_id
                                    if gen_span is not None
                                    else parent_span_id))
            if stream_mode:
                handle._wire_timeout = timeout
                return 200, None, handle
            try:
                # total-budget deadline: result() converts it to an
                # absolute deadline, so a slow engine can't stretch it
                # one token at a time
                res = handle.result(timeout=timeout)
            except _queue_Empty:
                # outcome "deadline", not "cancelled": a server-side
                # 504 must burn the generation-availability rule
                handle._expire()
                raise DeadlineExceededError(
                    "generation did not finish before the deadline"
                    ) from None
            return 200, {"model": name, "version": engine.version,
                         "tokens": res["tokens"],
                         "n_tokens": len(res["tokens"]),
                         "finish_reason": res["finish_reason"]}, None
        except ServingError as e:
            if handle is not None:
                handle.cancel()  # idempotent; no-op on a finished stream
            status, body = e.http_status, e.to_json()
            if handle is None and self.reqlog is not None:
                # shed/rejected before any stream opened: finish the
                # record here so the admission outcome is still
                # answerable by correlation id (the engine never saw it)
                reason = _SHED_REASONS.get(type(e))
                self.reqlog.finish(
                    cid, outcome=self._predict_outcome(status, body),
                    status=status,
                    admission=(f"shed:{reason}" if reason is not None
                               else None))
            return status, body, None
        except Exception as e:  # noqa: BLE001 — surface, never crash
            if handle is not None:
                handle.cancel()
            record_event("generation.error", model=name,
                         error=str(e)[:200])
            if handle is None and self.reqlog is not None:
                self.reqlog.finish(cid, outcome="error", status=500)
            return 500, {"error": {"code": "INTERNAL",
                                   "message": str(e)[:300],
                                   "retryable": False}}, None

    # -- brownout ladder (default rungs) --------------------------------------

    def _default_brownout_rungs(self):
        """The default degradation ladder, shallowest first:

        0. ``cache_pressure`` (only when the response cache is on) —
           allow expired entries to be served stale and shed half the
           cache's memory footprint: under overload a slightly-stale
           answer that skips a batch slot beats a shed, and the cache
           is the cheapest RAM to give back.
        1. ``shrink_batch_wait`` — zero every entry's batch coalesce
           wait: latency headroom beats occupancy once overloaded.
        2. ``shed_batch_class`` — reject all ``batch``-priority
           requests at admission.
        3. ``serve_fallback`` — hot-swap every registered fallback
           version in (and back out on recovery) via the normal warmed
           deploy/rollback plumbing.
        """
        self._saved_batch_waits: dict = {}

        def shed_on():
            self.overload.shed_batch = True

        def shed_off():
            self.overload.shed_batch = False

        rungs = []
        if self.response_cache is not None:
            rc = self.response_cache

            def cache_pressure_on():
                rc.set_stale_serve(True)
                rc.pressure_evict()

            def cache_pressure_off():
                rc.set_stale_serve(False)

            rungs.append(BrownoutRung("cache_pressure",
                                      cache_pressure_on,
                                      cache_pressure_off))
        rungs += [
            BrownoutRung("shrink_batch_wait",
                         self._brownout_shrink_batch_wait,
                         self._brownout_restore_batch_wait),
            BrownoutRung("shed_batch_class", shed_on, shed_off),
            BrownoutRung("serve_fallback",
                         self._brownout_engage_fallbacks,
                         self._brownout_disengage_fallbacks),
        ]
        return rungs

    def _brownout_shrink_batch_wait(self):
        for e in self.registry.entries():
            if e.batch_wait_s > 0:
                self._saved_batch_waits[e.name] = e.batch_wait_s
                e.set_batch_wait(0.0)

    def _brownout_restore_batch_wait(self):
        saved, self._saved_batch_waits = self._saved_batch_waits, {}
        for name, wait in saved.items():
            try:
                self.registry.get(name).set_batch_wait(wait)
            except Exception:  # noqa: BLE001 — entry may be gone; recover rest
                pass

    def _brownout_engage_fallbacks(self):
        for name in self.registry.names():
            try:
                self.registry.engage_fallback(name)
            except Exception as e:  # noqa: BLE001 — one bad fallback must
                record_event("serving.fallback_error",  # not stop the rest
                             model=name, error=str(e)[:200])

    def _brownout_disengage_fallbacks(self):
        for name in self.registry.names():
            try:
                self.registry.disengage_fallback(name)
            except Exception as e:  # noqa: BLE001
                record_event("serving.fallback_error",
                             model=name, error=str(e)[:200])

    # -- metrics exposition ---------------------------------------------------

    def render_metrics_text(self, *, openmetrics: bool = False) -> str:
        """The /metrics document: this server's bundle UNION the
        process-global default registry (train / resilience / checkpoint /
        runtime collector series) — one scrape tells the whole story.
        ``openmetrics=True`` is the Accept-negotiated variant (exemplar
        suffixes + ``# EOF`` trailer); the default classic format never
        carries exemplars."""
        return render_text_multi([self.metrics.registry, default_registry()],
                                 openmetrics=openmetrics)

    def render_metrics_json(self) -> dict:
        return render_json_multi([self.metrics.registry, default_registry()])

    # -- diagnostics plane ----------------------------------------------------

    def render_health(self) -> dict:
        """Current SLO states + burn rates (a fresh tick, so /debug/health
        is never staler than one request)."""
        return self.slo_engine.tick()

    def render_health_text(self) -> str:
        self.slo_engine.tick()
        return self.slo_engine.render_text()

    def render_costs(self, rows: Optional[int] = None) -> dict:
        """Per-registered-model static XLA cost analysis — the roofline
        inputs (flops, bytes, arithmetic intensity) of what this server
        is actually serving. One entry failing (e.g. shut down mid-walk
        during a deploy) reports itself; the others still render."""
        out = []
        for e in self.registry.entries():
            try:
                out.append(e.cost_analysis(rows=rows))
            except Exception as exc:  # noqa: BLE001 — diagnostics never 500
                out.append({"model": e.name, "available": False,
                            "reason": str(exc)[:200]})
        return {"models": out}

    def _entry_or_none(self, name: str):
        """Guarded registry lookup for the usage meter / capacity
        evaluator cost resolvers (an unknown or shut-down model prices
        as unresolved, never raises)."""
        try:
            return self.registry.get(name)
        except Exception:  # noqa: BLE001 — pricing is best-effort
            return None

    def render_timeseries(self, *, family=None, window_s=None, step_s=None,
                          op="range", q=None, labels=None) -> Tuple[int, dict]:
        """GET /debug/timeseries: without ``family``, the store's
        describe() (tiers, families, memory); with one, the requested
        query (``op`` = range | rate | quantile | max; ``quantile``
        needs ``q``)."""
        store = self.timeseries
        if store is None:
            return 404, ServingError(
                "historical telemetry is disabled "
                "(pass timeseries=None/a TimeSeriesStore)").to_json()
        try:
            return 200, store.debug_query(family=family, window_s=window_s,
                                          step_s=step_s, op=op, q=q,
                                          labels=labels)
        except ValueError as e:
            return 400, BadRequestError(str(e)).to_json()

    def render_usage(self) -> Tuple[int, dict]:
        """GET /debug/usage: per-(tenant, model) accounts on both
        planes, per-model batch-seconds/FLOPs, reconciled against the
        ledger window."""
        if self.usage is None:
            return 404, ServingError(
                "usage metering is disabled "
                "(pass usage=None/a UsageMeter)").to_json()
        return 200, self.usage.describe(ledger=self.reqlog)

    def render_capacity(self, *, evaluate: bool = False) -> Tuple[int, dict]:
        """GET /debug/capacity: headroom verdict per model + backend
        (the autoscaler input contract). The sampler keeps the cached
        report fresh; ``evaluate=True`` (``?evaluate=1``) forces a
        pass now."""
        if self.capacity is None:
            return 404, ServingError(
                "capacity evaluation is disabled (it requires the "
                "timeseries store)").to_json()
        report = (self.capacity.evaluate() if evaluate
                  else self.capacity.report())
        return 200, report

    def render_cache(self) -> dict:
        """GET /debug/cache: response-cache occupancy/hit counters plus
        every generation engine's prefix-store view."""
        rc = self.response_cache
        prefixes = {}
        for gname, eng in self.generators.items():
            ps = getattr(eng, "prefix_cache", None)
            if ps is not None:
                prefixes[gname] = ps.describe()
        return {"response_cache": rc.describe() if rc is not None else None,
                "prefix_stores": prefixes}

    def render_requests(self, *, outcome=None, tenant=None, model=None,
                        plane=None, min_latency_ms=None,
                        limit: int = 100) -> dict:
        """The request-ledger list view (newest first, filtered)."""
        ledger = self.reqlog
        if ledger is None:
            return {"ledger": None, "count": 0, "records": []}
        records = ledger.query(
            outcome=outcome, tenant=tenant, model=model, plane=plane,
            min_latency_s=(min_latency_ms / 1000.0
                           if min_latency_ms is not None else None),
            limit=limit)
        return {"ledger": ledger.describe(), "count": len(records),
                "records": records}

    def render_request(self, cid: str) -> Optional[dict]:
        """One request by correlation id: ledger record + retained span
        tree (Chrome-format included); None when unknown."""
        return _reqlog.request_detail(cid)

    def render_trace(self, *, plane=None, model=None, window_s=None,
                     limit=None) -> dict:
        """The ledger window as a replayable payload-scrubbed trace
        (``GET /debug/requests?format=trace``)."""
        ledger = self.reqlog
        if ledger is None:
            return _reqlog.trace_from_records([])
        return ledger.export_trace(plane=plane, model=model,
                                   window_s=window_s, limit=limit)

    def render_incidents(self) -> dict:
        """The incident-bundle index + current detector verdicts (the
        sentinel's live view rides along so an empty index still answers
        "is anything suspect right now?")."""
        out: dict = {"incidents": (self.incidents.index()
                                   if self.incidents is not None else []),
                     "sentinel": None}
        if self.sentinel is not None:
            out["sentinel"] = self.sentinel.verdicts()
        return out

    def render_incident(self, incident_id: str) -> Optional[dict]:
        if self.incidents is None:
            return None
        return self.incidents.get(incident_id)

    def _incident_profile_hook(self) -> dict:
        """The sentinel's device-capture hook: a short live-traffic
        ``jax.profiler`` capture through the same serialized path as
        ``POST /debug/profile`` (the inline gzipped trace is dropped —
        the bundle references the on-disk trace file instead of
        embedding megabytes)."""
        status, body = self.handle_profile(self.incident_profile_ms)
        if status != 200:
            return {"available": False, "status": status,
                    "error": body.get("error") if isinstance(body, dict)
                    else None}
        body = dict(body)
        body.pop("trace_gz_b64", None)
        return {"available": True, "kind": "serving_live_traffic", **body}

    def handle_profile(self, ms: float) -> Tuple[int, dict]:
        """On-demand ``jax.profiler`` capture of live traffic for ``ms``
        milliseconds. Returns the Perfetto trace (gzipped trace file,
        base64) plus the ``analyze_trace`` op breakdown. Serialized: one
        capture at a time (jax has one global profiler session)."""
        import glob
        import os
        import tempfile

        from deeplearning4j_tpu.train.profiling import analyze_trace

        if not (0 < ms <= self.max_profile_ms):
            return 400, BadRequestError(
                f"ms must be in (0, {self.max_profile_ms:g}], "
                f"got {ms!r}").to_json()
        if not self._profile_lock.acquire(blocking=False):
            # how long the in-flight capture still runs, plus headroom
            # for its serialization/analysis tail — a precise ms hint in
            # the body and the integer-seconds Retry-After header both,
            # matching the admission/circuit 503 shape so ServingClient
            # retry composes
            remaining_ms = max(
                0.0, (self._profile_busy_until - time.monotonic()) * 1000.0)
            retry_after_ms = remaining_ms + 250.0
            return 409, {"error": {
                "code": "PROFILE_IN_PROGRESS",
                "message": "another /debug/profile capture is running",
                "retryable": True,
                "retry_after_ms": round(retry_after_ms, 1)}}
        try:
            self._profile_busy_until = time.monotonic() + ms / 1000.0
            log_dir = tempfile.mkdtemp(prefix="dl4j-tpu-profile-")
            t0 = time.monotonic()
            jax.profiler.start_trace(log_dir)
            try:
                time.sleep(ms / 1000.0)
            finally:
                jax.profiler.stop_trace()
            wall_ms = (time.monotonic() - t0) * 1000.0
            hits = sorted(
                glob.glob(os.path.join(log_dir, "**", "*.trace.json.gz"),
                          recursive=True), key=os.path.getmtime)
            if not hits:
                return 503, {"error": {
                    "code": "NO_TRACE",
                    "message": "profiler produced no trace file "
                               "(backend without profiling support?)",
                    "retryable": True}}
            trace_file = hits[-1]
            raw = open(trace_file, "rb").read()
            ops = analyze_trace(log_dir, top=25)
            record_event("debug.profile", ms=ms, trace_bytes=len(raw),
                         ops=len(ops))
            body = {"duration_ms": round(wall_ms, 1),
                    "trace_dir": log_dir, "trace_file": trace_file,
                    "trace_bytes": len(raw), "ops": ops}
            # the gzipped trace rides inline when it fits a JSON response
            if len(raw) <= 16 << 20:
                body["trace_gz_b64"] = base64.b64encode(raw).decode()
            return 200, body
        except Exception as e:  # noqa: BLE001 — diagnostics never crash
            return 500, {"error": {"code": "INTERNAL",  # the server
                                   "message": str(e)[:300],
                                   "retryable": False}}
        finally:
            self._profile_lock.release()

    # -- lifecycle ------------------------------------------------------------

    def warm_all(self) -> dict:
        """Warm every not-yet-warmed entry (and generation engine);
        {name: {rows: seconds}}. A freshly-warmed engine on an
        already-started server is started here — engines are never
        warmed while their scheduler runs (warm and the scheduler
        would race over the donated KV slabs)."""
        out = {e.name: e.warm()
               for e in self.registry.entries() if not e.warmed}
        for name, eng in self.generators.items():
            if not eng.warmed:
                out[name] = eng.warm()
                if self._started and not eng.running:
                    eng.start()
        return out

    def _warm_plan(self):
        """What a start-time warmup will compile: ``[(kind, target,
        shapes)]`` + the total shape count. Manifest-observed shapes
        when the warmup manifest has data for a model, the full closed
        vocabulary otherwise. Computed synchronously (no compiles) so
        the /readyz progress body knows its denominator before the
        first compile starts."""
        from deeplearning4j_tpu.serving.warmup import bucket_sizes

        manifest = self.warm_manifest
        plan, total = [], 0
        for e in self.registry.entries():
            if e.warmed:
                continue
            sizes = e._manifest_warm_sizes()
            # label by what actually happened, not by whether the
            # manifest had rows: a stale manifest whose buckets all
            # fell out of the vocabulary warmed the FULL set
            full = bucket_sizes(e.max_batch_size, e.mode)
            source = "manifest" if sizes != full else "full"
            plan.append(("entry", e, sizes, source))
            total += len(sizes)
        for eng in self.generators.values():
            if eng.warmed:
                continue
            p_list, pairs = eng.manifest_warm_plan(manifest)
            n_full = len(eng.prompt_buckets) + \
                len(eng.slot_buckets) * len(eng.kv_buckets)
            source = ("manifest" if len(p_list) + len(pairs) < n_full
                      else "full")
            plan.append(("engine", eng, (p_list, pairs), source))
            total += len(p_list) + len(pairs)
        return plan, total

    def _run_warm_plan(self, plan, *, raise_errors: bool):
        """Execute a warm plan, feeding per-shape progress; on success
        start the engines, seal the compile cache, and flush the
        manifest — the moment /readyz flips, the next restart's warm
        assets are already on disk."""
        t0 = time.monotonic()
        note = lambda _key, seconds: self._warm_progress.note(seconds)  # noqa: E731
        try:
            for kind, target, shapes, source in plan:
                if self._draining:
                    return
                if kind == "entry":
                    target.warm(sizes=shapes, progress=note,
                                source=source)
                else:
                    target.warm(prompt_buckets=shapes[0],
                                decode_pairs=shapes[1],
                                progress=note, source=source)
        except BaseException as e:
            record_event("serving.warmup_error", error=str(e)[:200])
            if raise_errors:
                raise
            return  # async warm racing stop(): readyz stays 503
        finally:
            self._warm_progress.finish()
        for eng in self.generators.values():
            if eng.warmed and not eng.running and self._started \
                    and not self._draining:
                eng.start()
        if self.compile_cache is not None:
            try:
                self.compile_cache.seal()
            except Exception:  # noqa: BLE001 — an unsealed cache only
                pass           # costs the NEXT restart its head start
        if self.warm_manifest is not None:
            self.warm_manifest.save()
        record_event("serving.warmup_complete",
                     shapes=self._warm_progress.snapshot()["warmed"],
                     seconds=round(time.monotonic() - t0, 3))

    def start(self, *, warm: bool = True,
              warm_async: bool = False) -> "ModelServer":
        """Serve. ``warm`` pre-compiles every registered model/engine
        (manifest-restricted when a warmup manifest has traffic data)
        before ``/readyz`` flips; ``warm_async=True`` returns
        immediately and warms on a background thread — HTTP answers
        throughout, ``/readyz`` 503s with ``{warmed, total,
        retry_after_ms}`` progress, and predicts shed retryably until
        their model is warm (the restart-under-load shape: the process
        binds its port at once, the router re-admits only on genuine
        warmth)."""
        if self._started:
            return self
        if self.compile_cache is None:
            if not self._compile_cache_disabled:
                # fall back to the env-armed process cache (the
                # supervisor sets JAX_COMPILATION_CACHE_DIR for worker
                # generations); compile_cache=False opted out
                # explicitly and stays out
                self.compile_cache = \
                    _compilecache.maybe_enable_compile_cache()
        elif not self.compile_cache.active:
            self.compile_cache.activate()
            _compilecache.set_compile_cache(self.compile_cache)
        if warm:
            # plan + progress BEFORE the HTTP thread exists: the
            # warming shed guard keys on _warm_progress.active, and a
            # request slipping in ahead of begin() would dispatch into
            # the replica queue and coalesce with a warmup batch
            plan, total = self._warm_plan()
            self._warm_progress.begin(total)
        self._serve_thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True,
            name="model-server")
        self._serve_thread.start()
        self._started = True
        if warm:
            if warm_async:
                self._warm_thread = threading.Thread(
                    target=self._run_warm_plan, args=(plan,),
                    kwargs={"raise_errors": False}, daemon=True,
                    name="server-warmup")
                self._warm_thread.start()
            else:
                try:
                    self._run_warm_plan(plan, raise_errors=True)
                except BaseException:
                    # failed sync start leaves NO running state (the
                    # historical contract: warm ran before anything
                    # started) — a retried start() must re-enter the
                    # warm path, not bounce off the _started guard
                    # into an unwarmed, engine-less server
                    self._httpd.shutdown()
                    self._serve_thread.join(timeout=10)
                    self._started = False
                    raise
        else:
            # only warmed engines get their scheduler: an unwarmed
            # engine's later warm_all() must never race a live scheduler
            # over the donated slabs (requests submitted meanwhile wait
            # in its queue)
            for eng in self.generators.values():
                if eng.warmed:
                    eng.start()
        self.slo_engine.start()
        if self.overload is not None:
            self.overload.start()
        if self.timeseries is not None:
            self.timeseries.start()
            if _timeseries.get_timeseries_store() is None:
                # zero-config history: the federation snapshot and
                # exporter read the process-default store
                _timeseries.set_timeseries_store(self.timeseries)
        if self.usage is not None:
            # the ledger finish sink feeds the meter on both planes;
            # one sink per process (mirrors the default-engine slot)
            if _reqlog.get_usage_sink() is None:
                _reqlog.set_usage_sink(self.usage.on_record)
            if _usage.get_usage_meter() is None:
                _usage.set_usage_meter(self.usage)
        if _slo.get_default_engine() is None:
            # zero-config visibility: UIServer's /health page renders the
            # process-default engine
            _slo.set_default_engine(self.slo_engine)
        if self.sentinel is not None:
            # always-on host flames + the detector engine; the server's
            # live-traffic capture becomes the incident device profile
            get_host_sampler(start=True)
            if _incidents.get_incident_manager() is None:
                # a server given its OWN incident_dir must still surface
                # in the federation snapshot (incident_index reads the
                # process-global manager): promote this manager while
                # the slot is free. Left registered on stop — bundles
                # outlive the server and stay readable in cohort views.
                _incidents.set_incident_manager(self.incidents)
            _incidents.register_profile_hook(
                "serving", self._incident_profile_hook)
            self.sentinel.start()
        record_event("serving.start", port=self.port,
                     models=self.registry.names())
        return self

    def stop(self, *, drain: bool = True, timeout: float = 30.0) -> bool:
        """Graceful shutdown; returns True if fully drained in time."""
        drained = True
        if self._started:
            self._draining = True
            record_event("serving.drain", port=self.port)
            if drain:
                # ONE timeout budget across the admission drain and
                # every engine drain — stop(timeout=30) must not block
                # (1 + n_engines) x 30 s
                deadline = time.monotonic() + timeout
                drained = self.admission.drain(timeout)
                for eng in self.generators.values():
                    drained = eng.drain(
                        max(0.0, deadline - time.monotonic())) and drained
            self._httpd.shutdown()
            if self._serve_thread is not None:
                self._serve_thread.join(timeout=10)
            self._started = False
            record_event("serving.stop", port=self.port, drained=drained)
        self.slo_engine.stop()
        if self.overload is not None:
            self.overload.stop()
        if self.sentinel is not None:
            self.sentinel.stop()
            # only unhook ourselves (a newer server's hook must survive);
            # the process host sampler stays running — it is the
            # always-on plane, not this server's
            _incidents.unregister_profile_hook(
                "serving", self._incident_profile_hook)
        if _slo.get_default_engine() is self.slo_engine:
            _slo.set_default_engine(None)
        if self.timeseries is not None:
            self.timeseries.stop()
            if _timeseries.get_timeseries_store() is self.timeseries:
                _timeseries.set_timeseries_store(None)
        if self.usage is not None:
            if _reqlog.get_usage_sink() == self.usage.on_record:
                _reqlog.set_usage_sink(None)
            if _usage.get_usage_meter() is self.usage:
                _usage.set_usage_meter(None)
        self._httpd.server_close()
        for eng in self.generators.values():
            eng.stop()
        self.registry.shutdown_all()
        # an async warm pass races stop(): the replica-set shutdown
        # above fails its next warm batch, so the short join below is a
        # compile's tail, not a full warmup
        if self._warm_thread is not None and self._warm_thread.is_alive():
            self._warm_thread.join(timeout=10)
        if self.warm_manifest is not None:
            # final flush: the traffic mix this run observed survives
            # the process — that is the whole point of the manifest
            self.warm_manifest.save()
        return drained

    def __enter__(self) -> "ModelServer":
        return self.start()

    def __exit__(self, *exc):
        self.stop()
