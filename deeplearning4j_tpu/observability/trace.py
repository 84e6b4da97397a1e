"""Tracing spans: nested, correlation-ID-linked, Perfetto-loadable.

The metrics registry answers "how much / how often"; spans answer
"where did THIS request's time go". A :class:`Span` is one named,
timed interval with a ``trace_id`` (the correlation ID every span of
one logical request shares), a ``span_id``, and a ``parent_id`` link
forming the tree. Producers:

- ``span("name")`` — context manager with thread-local nesting (a span
  opened inside another becomes its child automatically);
- ``record_span(...)`` — post-hoc recording with explicit timestamps,
  for work measured on another thread (ParallelInference workers record
  the batch/dispatch legs of a request after the fact).

Correlation propagation over HTTP uses two headers the serving layer
reads and writes: ``X-Correlation-ID`` (the trace id) and ``X-Span-ID``
(the caller's span, adopted as the server-side root's parent) — so one
served request yields a linked tree: client → request → admission /
batch → dispatch.

Finished spans land in a process-global bounded ring (:class:`Tracer`)
and export two ways: JSONL (one span per line — the same convention as
train/listeners.py records) and Chrome-trace JSON (``ph: "X"`` complete
events) loadable in Perfetto next to the XLA traces from
train/profiling.py. The two forms convert losslessly in both
directions: ids, parent links, and attributes ride in the Chrome
events' ``args``.

**Tail-based sampling** (:class:`TailSampler` + :class:`RetentionPolicy`):
at millions-of-requests scale the ring cannot hold every request's
spans, yet the requests worth explaining — errors, sheds, preemptions,
deadline blow-ups, p99.9 stragglers — are exactly the ones head
sampling would have discarded before knowing they mattered. The tail
sampler inverts the decision: a request registered via ``begin(cid)``
has its spans diverted into a per-request *staging buffer* as they
finish, and only at request completion does the retention policy decide
keep-vs-drop — keep on a bad outcome, keep when the request's latency
sits far above a rolling baseline (sentinel's ``RollingBaseline``
machinery), plus a deterministic 1-in-N baseline sample. Kept requests'
spans land in the bounded ring like any other span; dropped requests
cost only the staging append. The serving request ledger
(``observability/reqlog.py``) drives ``begin``/``finish`` for every
request on both serving planes.

**On the profiler's clock** (:func:`annotate`): the ring above is this
module's own and a device trace never sees it. ``annotate(name)`` opens a
``jax.profiler.TraceAnnotation`` instead, which a running profiler writes
onto the host plane of its own trace, beside the device's operations and
on their clock; ``span()`` opens one too, so every span recorded here is
also there. While no profiler runs an annotation costs a fraction of a
microsecond, and it is a no-op while ``jax`` has not been imported.

**The host's side of a training run** (:class:`Timeline`,
:class:`IterationLegs`): a fit loop runs thousands of iterations of five
legs each, too many for a :class:`Span` apiece on the hot path. The loop
opens its legs through one :class:`IterationLegs`, which annotates each
(as above), reads ``time.perf_counter`` once at each boundary, feeds the
``train_step_seconds`` / ``train_data_read_seconds`` histograms and keeps
one tuple an iteration in the process :class:`Timeline`, a bounded ring
under a root entry for each fit. :meth:`Timeline.spans` turns a fit's rows
into spans through :func:`record_span` when somebody asks (``train.fit``
-> ``train.step`` -> the four legs, one trace id a fit), and
``observability/runtime.compile_events()`` has the compilations on the
same clock.

Stdlib only at import; safe to import from any layer (the retention
policy's rolling baseline is imported lazily from
``observability.sentinel``, and ``annotate`` looks ``jax`` up in
``sys.modules`` and never imports it).
"""

from __future__ import annotations

import itertools
import json
import sys
import threading
import time
import uuid
from collections import OrderedDict, deque
from contextlib import contextmanager
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

# Wall-clock anchor + monotonic progression: timestamps are comparable
# across threads and meaningful as dates, but never go backwards the way
# raw time.time() can under NTP slew.
_T0 = time.time() - time.perf_counter()
_clock = time.perf_counter


def now() -> float:
    """Trace timestamp (seconds, wall-anchored monotonic)."""
    return _T0 + time.perf_counter()


def from_perf_counter(t: float) -> float:
    """A ``time.perf_counter`` reading as a trace timestamp."""
    return _T0 + t


# Span ids are minted on the serving hot path; uuid4 costs ~8 µs a call,
# so ids are a random-per-process 8-hex prefix + an atomic counter
# (itertools.count is GIL-atomic): unique across processes by the prefix,
# unique within one by the counter, ~0.3 µs a call.
_ID_PREFIX = uuid.uuid4().hex[:8]
_ID_COUNTER = itertools.count()


def new_id() -> str:
    """A fresh 16-hex-char correlation/span id."""
    return f"{_ID_PREFIX}{next(_ID_COUNTER) & 0xFFFFFFFF:08x}"


class Span:
    __slots__ = ("name", "trace_id", "span_id", "parent_id", "start", "end",
                 "thread", "attrs")

    def __init__(self, name: str, *, trace_id: str, span_id: str,
                 parent_id: Optional[str] = None, start: float = 0.0,
                 end: float = 0.0, thread: Optional[str] = None,
                 attrs: Optional[dict] = None):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.start = start
        self.end = end
        self.thread = thread
        self.attrs = attrs if attrs is not None else {}

    @property
    def duration(self) -> float:
        return max(0.0, self.end - self.start)

    def to_json(self) -> dict:
        return {"name": self.name, "trace_id": self.trace_id,
                "span_id": self.span_id, "parent_id": self.parent_id,
                "start": self.start, "end": self.end, "thread": self.thread,
                "attrs": dict(self.attrs)}

    @classmethod
    def from_json(cls, d: dict) -> "Span":
        return cls(d["name"], trace_id=d["trace_id"], span_id=d["span_id"],
                   parent_id=d.get("parent_id"), start=d.get("start", 0.0),
                   end=d.get("end", 0.0), thread=d.get("thread"),
                   attrs=dict(d.get("attrs", {})))

    def __repr__(self):
        return (f"Span({self.name!r}, trace={self.trace_id}, "
                f"id={self.span_id}, parent={self.parent_id}, "
                f"dur={self.duration * 1e3:.3f}ms)")


class Tracer:
    """Bounded ring of finished spans (oldest evicted first)."""

    def __init__(self, capacity: int = 4096):
        self._spans: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()

    def record(self, span: Span):
        with self._lock:
            self._spans.append(span)

    def spans(self, trace_id: Optional[str] = None) -> List[Span]:
        with self._lock:
            snap = list(self._spans)
        if trace_id is None:
            return snap
        return [s for s in snap if s.trace_id == trace_id]

    def clear(self):
        with self._lock:
            self._spans.clear()

    def export_jsonl(self, path: str, trace_id: Optional[str] = None) -> int:
        """Append spans as JSONL; returns the number written."""
        spans = self.spans(trace_id)
        with open(path, "a") as fh:
            for s in spans:
                fh.write(json.dumps(s.to_json()) + "\n")
        return len(spans)


_TRACER = Tracer()
_ENABLED = True
_tls = threading.local()


def get_tracer() -> Tracer:
    return _TRACER


# -- tail-based sampling ------------------------------------------------------


class RetentionPolicy:
    """The completion-time keep-vs-drop decision for one request's spans.

    ``decide()`` returns the retention *reason* (a short string the
    ledger records and the ``trace_retained_total`` counter labels) or
    None to drop:

    - ``keep_outcomes`` — any outcome in the set is kept outright
      (errors, sheds, preemptions, deadline misses: the requests a
      post-mortem needs most);
    - ``"slow"`` — the request's latency scores ``slow_score`` robust-z
      above a rolling median+MAD baseline of *dropped-ok* latencies AND
      exceeds the median by ``min_increase`` (the sentinel discipline:
      kept-slow samples never feed the baseline, so a sustained
      regression cannot teach itself into "normal");
    - ``"sampled"`` — a deterministic 1-in-``sample_every`` baseline
      sample of everything else, so healthy-path traces exist to
      compare the tail against.
    """

    def __init__(self, *, sample_every: int = 128, slow_score: float = 8.0,
                 min_increase: float = 0.5, baseline_window: int = 128,
                 min_history: int = 16,
                 keep_outcomes: Optional[Iterable[str]] = None):
        if sample_every < 1:
            raise ValueError(
                f"sample_every must be >= 1, got {sample_every}")
        from deeplearning4j_tpu.observability.sentinel import RollingBaseline

        self.sample_every = int(sample_every)
        self.slow_score = float(slow_score)
        self.min_increase = float(min_increase)
        self.min_history = int(min_history)
        self.keep_outcomes = frozenset(
            keep_outcomes if keep_outcomes is not None
            else ("error", "failed", "shed", "preempted", "deadline"))
        self._baseline = RollingBaseline(baseline_window)
        self._count = itertools.count()
        self._lock = threading.Lock()

    def decide(self, *, outcome: str = "ok",
               latency_s: Optional[float] = None) -> Optional[str]:
        """Retention reason for one completed request, or None (drop)."""
        if outcome in self.keep_outcomes:
            return outcome
        with self._lock:
            n = next(self._count)
            slow = False
            if latency_s is not None \
                    and len(self._baseline) >= self.min_history \
                    and not self._baseline.degenerate():
                med = self._baseline.median()
                slow = (self._baseline.score(latency_s) >= self.slow_score
                        and latency_s >= med * (1.0 + self.min_increase))
            if not slow and latency_s is not None:
                # only dropped-or-sampled OK latencies teach "normal" —
                # a kept-slow request is the anomaly, not the baseline
                self._baseline.add(latency_s)
        if slow:
            return "slow"
        if n % self.sample_every == 0:
            return "sampled"
        return None

    def describe(self) -> dict:
        with self._lock:
            return {"sample_every": self.sample_every,
                    "slow_score": self.slow_score,
                    "min_increase": self.min_increase,
                    "min_history": self.min_history,
                    "keep_outcomes": sorted(self.keep_outcomes),
                    "baseline": self._baseline.to_json()}


class TailSampler:
    """Per-request span staging + completion-time retention.

    ``begin(trace_id)`` registers a request; every span finishing with
    that trace id is diverted into its staging buffer instead of the
    ring (``offer`` — one dict lookup on the span-finish hot path for
    unregistered traces). ``finish(trace_id, outcome=, latency_s=)``
    pops the buffer and either records every staged span into the
    tracer ring (kept) or drops them all.

    Bounded both ways: at most ``max_staged`` requests stage at once
    (oldest evicted — a request that never finishes must not pin spans
    forever) and at most ``max_spans_per_request`` spans per request
    (newest dropped, eviction counted on the buffer).
    """

    def __init__(self, *, policy: Optional[RetentionPolicy] = None,
                 max_staged: int = 512, max_spans_per_request: int = 256,
                 dropped_memory: int = 512):
        if max_staged < 1:
            raise ValueError(f"max_staged must be >= 1, got {max_staged}")
        self.policy = policy if policy is not None else RetentionPolicy()
        self.max_staged = int(max_staged)
        self.max_spans_per_request = int(max_spans_per_request)
        self._lock = threading.Lock()
        self._staged: "OrderedDict[str, List[Span]]" = OrderedDict()
        # trace ids recently decided DROPPED: a straggler span closing
        # after the decision (the client-side span of an in-process
        # request, a worker's post-hoc leg) is swallowed instead of
        # leaking an orphan into the ring the retention just cleaned
        self._dropped: "OrderedDict[str, bool]" = OrderedDict()
        self.dropped_memory = int(dropped_memory)
        self.staging_evictions = 0  # whole requests evicted un-decided
        self.span_overflows = 0     # spans dropped over the per-request cap

    def begin(self, trace_id: str) -> None:
        """Register one request for staging (idempotent per trace id)."""
        with self._lock:
            if trace_id in self._staged:
                return
            # a retry reusing a previously-dropped id starts fresh
            self._dropped.pop(trace_id, None)
            while len(self._staged) >= self.max_staged:
                self._staged.popitem(last=False)
                self.staging_evictions += 1
            self._staged[trace_id] = []

    def watching(self, trace_id: str) -> bool:
        with self._lock:
            return trace_id in self._staged

    def staged_count(self) -> int:
        with self._lock:
            return len(self._staged)

    def offer(self, span: Span) -> bool:
        """Divert a finishing span into its request's staging buffer;
        False when the trace is not staged (caller records normally)."""
        with self._lock:
            buf = self._staged.get(span.trace_id)
            if buf is None:
                # late span of a dropped request: swallow it, or the
                # decision the sampler just made would leak an orphan
                return span.trace_id in self._dropped
            if len(buf) >= self.max_spans_per_request:
                self.span_overflows += 1
                return True  # consumed (dropped): the cap is the cap
            buf.append(span)
            return True

    def finish(self, trace_id: str, *, outcome: str = "ok",
               latency_s: Optional[float] = None,
               tracer: Optional[Tracer] = None
               ) -> Tuple[Optional[str], int]:
        """Decide retention for one completed request. Returns
        ``(reason, n_spans)`` — reason None means the staged spans were
        dropped; otherwise they were recorded into ``tracer`` (default:
        the process ring) and are queryable by trace id."""
        with self._lock:
            buf = self._staged.pop(trace_id, None)
            if buf is not None:
                # tentatively dropped from the same critical section
                # that un-stages: a span closing while the policy
                # deliberates below is swallowed, never an orphan in
                # the ring for a request the decision then drops. (The
                # flip side — a kept trace losing a span from that
                # microsecond window — is benign: every load-bearing
                # leg is recorded before finish() runs by design.)
                self._dropped[trace_id] = True
                while len(self._dropped) > self.dropped_memory:
                    self._dropped.popitem(last=False)
        if buf is None:
            return None, 0
        reason = self.policy.decide(outcome=outcome, latency_s=latency_s)
        if reason is None:
            return None, len(buf)
        with self._lock:
            self._dropped.pop(trace_id, None)
        t = tracer if tracer is not None else _TRACER
        for s in buf:
            t.record(s)
        return reason, len(buf)

    def discard(self, trace_id: str) -> int:
        """Drop a staged request without a retention decision (e.g. the
        ledger evicted its record); returns the span count dropped."""
        with self._lock:
            buf = self._staged.pop(trace_id, None)
        return len(buf) if buf is not None else 0


_TAIL_SAMPLER: Optional[TailSampler] = None


def get_tail_sampler(create: bool = False) -> Optional[TailSampler]:
    """The process tail sampler routing span finishes; ``create=True``
    installs one when none exists (the request ledger does this)."""
    global _TAIL_SAMPLER
    if _TAIL_SAMPLER is None and create:
        _TAIL_SAMPLER = TailSampler()
    return _TAIL_SAMPLER


def set_tail_sampler(sampler: Optional[TailSampler]) -> None:
    global _TAIL_SAMPLER
    _TAIL_SAMPLER = sampler


def _route(span: Span, tracer: Optional[Tracer]) -> None:
    """The one span-finish funnel: an explicit ``tracer`` always wins
    (tests and collectors that own a private ring bypass staging); a
    staged trace id diverts to the tail sampler; everything else lands
    in the process ring exactly as before."""
    if tracer is not None:
        tracer.record(span)
        return
    ts = _TAIL_SAMPLER
    if ts is not None and ts.offer(span):
        return
    _TRACER.record(span)


def set_tracing_enabled(flag: bool):
    global _ENABLED
    _ENABLED = bool(flag)


def tracing_enabled() -> bool:
    return _ENABLED


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def current_span() -> Optional[Span]:
    st = _stack()
    return st[-1] if st else None


class _NoAnnotation:
    """What :func:`annotate` returns while ``jax`` is not imported."""

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NO_ANNOTATION = _NoAnnotation()


def annotate(name: str, **attrs):
    """A context manager that puts ``name`` on the host plane of a
    running ``jax.profiler`` trace, on the device's clock: a
    ``TraceAnnotation`` (a ``StepTraceAnnotation`` when given
    ``step_num``) if ``jax`` is already imported, a no-op otherwise. The
    names in use are declared in ``observability/vocab.py``
    (``HOST_SPANS``)."""
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    if profiler is None:
        return _NO_ANNOTATION
    if "step_num" in attrs:
        return profiler.StepTraceAnnotation(name, **attrs)
    return profiler.TraceAnnotation(name, **attrs)


@contextmanager
def span(name: str, *, trace_id: Optional[str] = None,
         parent_id: Optional[str] = None, tracer: Optional[Tracer] = None,
         **attrs):
    """Open a span around a block. Nesting is thread-local: without an
    explicit ``trace_id``/``parent_id`` the current span (if any) is the
    parent and shares its trace. Yields the live Span (attrs mutable)
    or None when tracing is disabled. An exception in the block is
    recorded as an ``error`` attr and re-raised; the span always closes.
    The block is also an :func:`annotate` of the same name, so a running
    profiler sees it on the host plane of its trace.
    """
    if not _ENABLED:
        yield None
        return
    parent = current_span()
    if trace_id is None:
        trace_id = parent.trace_id if parent is not None else new_id()
    if parent_id is None and parent is not None:
        parent_id = parent.span_id
    s = Span(name, trace_id=trace_id, span_id=new_id(), parent_id=parent_id,
             start=now(), thread=threading.current_thread().name,
             attrs=dict(attrs))
    _stack().append(s)
    try:
        with annotate(name):
            yield s
    except BaseException as e:
        s.attrs.setdefault("error", type(e).__name__)
        raise
    finally:
        _stack().pop()
        s.end = now()
        _route(s, tracer)


def record_span(name: str, *, start: float, end: float, trace_id: str,
                parent_id: Optional[str] = None,
                span_id: Optional[str] = None, thread: Optional[str] = None,
                tracer: Optional[Tracer] = None, **attrs) -> Span:
    """Record a span with explicit timestamps (post-hoc, cross-thread).
    Returns the Span so callers can parent further spans to it."""
    s = Span(name, trace_id=trace_id,
             span_id=span_id if span_id is not None else new_id(),
             parent_id=parent_id, start=start, end=end,
             thread=(thread if thread is not None
                     else threading.current_thread().name),
             attrs=dict(attrs))
    _route(s, tracer)
    return s


# -- the host's side of a training run ------------------------------------------

FIT = "train.fit"
ITERATION = "train.step"
# the legs of an iteration, in the order they run; each ends where the
# next begins, so a row holds one clock reading a boundary
LEGS = ("train.read", "train.put", "train.dispatch", "train.listeners")
ROWS_KEPT = 16384  # iterations, over all fits; the longest window is ~1,550
FITS_KEPT = 256


class Fit:
    """A fit's root entry: ``start`` and ``end`` are ``perf_counter``
    readings (``end`` None while the loop runs), ``steps`` the rows it
    wrote, ``id`` the trace id its spans share."""

    __slots__ = ("id", "start", "end", "steps", "thread")

    def __init__(self):
        self.id = new_id()
        self.start = _clock()
        self.end: Optional[float] = None
        self.steps = 0
        self.thread = threading.current_thread().name


class Timeline:
    """Every fit loop's iterations, one row each: ``(fit id, step number,
    iteration start, end of train.read, of train.put, of train.dispatch,
    of train.listeners)``, ``perf_counter`` readings, in a ring that drops
    the oldest. Written by :class:`IterationLegs` without a lock (a deque's
    ``append`` is atomic); read through :meth:`fits` and :meth:`spans`."""

    def __init__(self, rows_kept: int = ROWS_KEPT):
        self._rows: deque = deque(maxlen=rows_kept)
        self._fits: deque = deque(maxlen=FITS_KEPT)

    def open_fit(self) -> Fit:
        fit = Fit()
        self._fits.append(fit)
        return fit

    def fits(self) -> List[Fit]:
        return list(self._fits)

    def rows(self, fit: Fit) -> List[tuple]:
        """The rows of ``fit`` that the ring still holds, oldest first."""
        return [r for r in list(self._rows) if r[0] == fit.id]

    def clear(self):
        self._rows.clear()
        self._fits.clear()

    def spans(self, fit: Optional[Fit] = None, *,
              tracer: Optional[Tracer] = None) -> List[Span]:
        """The spans of one fit (the last, unless told): ``train.fit``,
        under it a ``train.step`` for each row, under that the four legs,
        all with the fit's id as trace id. They are made here, through
        :func:`record_span`, into ``tracer``; without one they go to a
        ring of their own, made to hold them, and not to the process ring,
        which a window's nine thousand spans would flush."""
        if fit is None:
            if not self._fits:
                return []
            fit = self._fits[-1]
        rows = self.rows(fit)
        if tracer is None:
            tracer = Tracer(capacity=1 + 5 * len(rows))
        end = fit.end
        if end is None:  # still running
            end = rows[-1][6] if rows else _clock()
        root = record_span(
            FIT, start=_T0 + fit.start, end=_T0 + end, trace_id=fit.id,
            thread=fit.thread, tracer=tracer, steps=fit.steps)
        out = [root]
        for _, step, *marks in rows:
            it = record_span(
                ITERATION, start=_T0 + marks[0], end=_T0 + marks[4],
                trace_id=fit.id, parent_id=root.span_id, thread=fit.thread,
                tracer=tracer, step=step)
            out.append(it)
            for name, lo, hi in zip(LEGS, marks, marks[1:]):
                out.append(record_span(
                    name, start=_T0 + lo, end=_T0 + hi, trace_id=fit.id,
                    parent_id=it.span_id, thread=fit.thread, tracer=tracer))
        return out


_TIMELINE = Timeline()


def get_timeline() -> Timeline:
    return _TIMELINE


_annotate = annotate  # IterationLegs takes a parameter of the name


class _Leg:
    """One leg of the iteration as a context manager, made once a fit: an
    annotation around the block and, where the timeline is on, the clock
    at the block's end into ``marks[slot]``."""

    __slots__ = ("_name", "_annotate", "_marks", "_slot", "_open")

    def __init__(self, name: str, annotate, marks: Optional[list],
                 slot: int = 0):
        self._name, self._annotate = name, annotate
        self._marks, self._slot = marks, slot
        self._open = None

    def __enter__(self):
        self._open = self._annotate(self._name)
        self._open.__enter__()

    def __exit__(self, et, ev, tb):
        self._open.__exit__(et, ev, tb)
        if self._marks is not None:
            self._marks[self._slot] = _clock()
        return False


class _DispatchLeg(_Leg):
    """``train.dispatch``: its start is ``train.put``'s end; a dispatch
    that returns makes the iteration a step, with its two histogram
    readings."""

    __slots__ = ("_legs",)

    def __enter__(self):
        if self._marks is not None:
            self._marks[2] = _clock()
        self._open = self._annotate(self._name)
        self._open.__enter__()

    def __exit__(self, et, ev, tb):
        self._open.__exit__(et, ev, tb)
        marks = self._marks
        if marks is not None and et is None:
            # the listeners' mark too: a loop that opens no such leg, or
            # leaves it by an exception, still writes an ordered row
            marks[3] = marks[4] = t = _clock()
            legs = self._legs
            legs.read_s = read_s = marks[1] - marks[0]
            legs.step_s = step_s = t - marks[2]
            legs._om.data_read_seconds.observe(read_s)
            legs._om.step_seconds.observe(step_s)
            legs._stepped = True
        return False


class _IterationLeg(_Leg):
    """``train.step``, called with the step's number: the iteration's
    start on entry, its row on exit if a step was dispatched in it."""

    __slots__ = ("_legs", "_step")

    def __call__(self, step_num: int) -> "_IterationLeg":
        self._step = step_num
        return self

    def __enter__(self):
        self._open = self._annotate(self._name, step_num=self._step)
        self._open.__enter__()
        if self._marks is not None:
            self._marks[0] = _clock()

    def __exit__(self, et, ev, tb):
        self._open.__exit__(et, ev, tb)
        legs = self._legs
        if legs._stepped:
            legs._stepped = False
            legs._fit.steps += 1
            legs._append((legs._fit.id, self._step, *self._marks))
        return False


class IterationLegs:
    """What a fit loop opens its iteration and its legs through, made once
    a fit and shared by ``Trainer.fit`` and ``FaultTolerantTrainer.fit``::

        legs = IterationLegs(om)
        try:
            while ...:
                with legs.step(n):
                    with legs.read: ...
                    with legs.put: ...
                    with legs.dispatch: ...
                    with legs.listeners: ...
        finally:
            legs.close()

    Every leg is an ``annotate(name)`` (``HOST_SPANS``), so a running
    profiler shows it. With ``om`` (the training metrics bundle; None while
    ``metrics.enabled()`` is off) the boundaries between the legs are read
    off ``time.perf_counter`` once each: the iteration's start, the end of
    the read, the start and the end of the dispatch, the end of the
    listeners; ``train.put`` is what lies between the read and the
    dispatch. A dispatch that returns observes ``read_s`` and ``step_s``
    (kept here for the caller) in ``om.data_read_seconds`` and
    ``om.step_seconds``, and the iteration's exit then appends its row to
    the timeline; an iteration that dispatched nothing (the feed's end, a
    skipped batch, a step rolled back) leaves neither. No lock, no device
    sync, and nothing allocated beyond the annotations and the row.
    ``annotate`` is the factory of the annotations, for a tool that times
    the legs its own way."""

    def __init__(self, om, *, annotate=annotate,
                 timeline: Optional[Timeline] = None):
        self._om = om
        self._stepped = False
        self.read_s = self.step_s = 0.0
        if om is None:
            self._fit = marks = self._append = None
        else:
            timeline = timeline if timeline is not None else _TIMELINE
            self._fit = timeline.open_fit()
            marks = [self._fit.start] * 5
            self._append = timeline._rows.append
        leg = step = annotate
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if annotate is _annotate and profiler is not None:
            # what annotate() would look up again at every leg
            leg, step = profiler.TraceAnnotation, profiler.StepTraceAnnotation
        self.step = _IterationLeg(ITERATION, step, marks)
        self.read = _Leg(LEGS[0], leg, marks, 1)
        self.put = _Leg(LEGS[1], leg, None)
        self.dispatch = _DispatchLeg(LEGS[2], leg, marks)
        self.listeners = _Leg(LEGS[3], leg, marks, 4)
        self.step._legs = self.dispatch._legs = self

    def close(self):
        """The fit's end, from its ``finally``."""
        if self._fit is not None and self._fit.end is None:
            self._fit.end = _clock()


# -- JSONL / Chrome-trace conversion ----------------------------------------


def load_jsonl(path: str) -> List[Span]:
    spans = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if line:
                spans.append(Span.from_json(json.loads(line)))
    return spans


def to_chrome_trace(spans: Iterable[Span], *, pid: int = 1,
                    process_name: Optional[str] = None) -> dict:
    """Chrome-trace JSON (Perfetto-loadable). One ``"X"`` complete event
    per span; ids/attrs ride in ``args`` so :func:`from_chrome_trace`
    reconstructs the exact span set (nesting included). Threads map to
    tids with ``thread_name`` metadata events. ``pid``/``process_name``
    place the whole span set on one process lane — the cluster
    federation layer stitches per-worker traces into a single document
    by giving each worker its own pid (observability/federation.py)."""
    spans = list(spans)
    tids: Dict[str, int] = {}
    for s in spans:
        tids.setdefault(s.thread or "main", len(tids) + 1)
    events: List[dict] = []
    if process_name is not None:
        events.append({"ph": "M", "name": "process_name", "pid": pid,
                       "tid": 0, "args": {"name": process_name}})
    events.extend({"ph": "M", "name": "thread_name", "pid": pid,
                   "tid": tid, "args": {"name": tname}}
                  for tname, tid in tids.items())
    for s in spans:
        # attrs ride in their own sub-dict: a user attr named "span_id"
        # must not clobber the identity keys the round trip depends on
        args = {"trace_id": s.trace_id, "span_id": s.span_id,
                "parent_id": s.parent_id, "attrs": dict(s.attrs)}
        events.append({
            "ph": "X", "cat": "span", "name": s.name, "pid": pid,
            "tid": tids[s.thread or "main"],
            "ts": s.start * 1e6, "dur": s.duration * 1e6, "args": args})
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def from_chrome_trace(trace: dict) -> List[Span]:
    """Inverse of :func:`to_chrome_trace` for events it wrote (spans with
    ``span_id`` in args); foreign events without one — e.g. XLA ops in a
    merged profile — are skipped."""
    events = trace.get("traceEvents", [])
    # thread names are keyed per (pid, tid): a stitched multi-worker
    # document reuses tid 1 on every worker's pid lane
    tid_names = {(ev.get("pid"), ev.get("tid")):
                 ev.get("args", {}).get("name")
                 for ev in events
                 if ev.get("ph") == "M" and ev.get("name") == "thread_name"}
    spans = []
    for ev in events:
        if ev.get("ph") != "X":
            continue
        args = dict(ev.get("args", {}))
        if "span_id" not in args:
            continue
        start = float(ev.get("ts", 0.0)) / 1e6
        spans.append(Span(
            ev.get("name", "?"), trace_id=args.get("trace_id"),
            span_id=args.get("span_id"), parent_id=args.get("parent_id"),
            start=start, end=start + float(ev.get("dur", 0.0)) / 1e6,
            thread=tid_names.get((ev.get("pid"), ev.get("tid"))),
            attrs=dict(args.get("attrs", {}))))
    return spans


def stitch_named_lanes(lanes: Sequence[Tuple[str, Iterable[Span]]],
                       *, attr: str = "tier") -> dict:
    """One Perfetto document from several span sets, one pid lane per
    entry in order (client=0, router=1, backend=2 for a cross-tier
    request stitch). Each span is stamped ``attrs[attr] = lane name``
    so :func:`from_chrome_trace` round-trips the grouping, not just the
    spans — the federation layer's pid-lane idiom with named tiers
    instead of worker ids."""
    events: List[dict] = []
    for pid, (name, spans) in enumerate(lanes):
        stamped = []
        for s in spans:
            attrs = dict(s.attrs)
            attrs[attr] = name
            stamped.append(Span(
                s.name, trace_id=s.trace_id, span_id=s.span_id,
                parent_id=s.parent_id, start=s.start, end=s.end,
                thread=s.thread, attrs=attrs))
        events.extend(to_chrome_trace(
            stamped, pid=pid, process_name=name)["traceEvents"])
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def write_chrome_trace(path: str, spans: Iterable[Span]) -> int:
    spans = list(spans)
    with open(path, "w") as fh:
        json.dump(to_chrome_trace(spans), fh)
    return len(spans)
