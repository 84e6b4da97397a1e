"""Generative serving engine: continuous batching, bucketed KV slabs,
streaming decode.

The serving stack built so far (registry → admission → warmup buckets →
``ParallelInference``) only does fixed-shape one-shot predict; the
autoregressive path (``models/gpt.py``) compiled the WHOLE generation
loop into one program — great for offline sampling, useless for serving,
where requests arrive continuously and a per-request loop strands the
accelerator between dispatches. This module is the iteration-level
scheduler in between (↔ Orca/vLLM-style continuous batching, built on
the repo's own warmup-bucket discipline):

- **decode slots**: up to ``num_slots`` in-flight sequences share one
  batched decode step; requests JOIN the batch the step after their
  prefill and LEAVE it the step they finish — admission is per
  *iteration*, not per batch.
- **bucketed KV slabs**: every sequence's K/V cache lives in a
  preallocated slab row ``[num_slots+1, heads, max_len, head_dim]`` per
  layer (row ``num_slots`` is scratch for padded batch rows). Decode
  steps are compiled per ``(slot-count-bucket, kv-length-bucket)`` pair
  — powers of two, warmed at deploy — and attend only over the first
  ``kv_bucket`` positions, so short sequences never pay long-sequence
  attention and NO decode step ever recompiles after warmup.
- **prefill/decode split**: prefill is a separate compiled function per
  prompt-length bucket (one full-causal-attention matmul-shaped program
  writing the prompt's K/V into the slab, cf. the cuDNN batched-
  primitives framing) while decode is the memory-bound per-token step.
- **streaming**: tokens are pushed to a per-request queue the moment
  the device step returns; the server chunks them to the client as
  newline-delimited JSON; ``ServingClient.generate()`` yields them.
- **overload integration** (PR 10 plane, day one): priority classes
  preempt — a waiting ``critical`` request evicts the lowest-class
  active slot (its KV slab row is released and the victim fails
  retryably with ``SLOT_PREEMPTED`` + Retry-After); the AIMD effective
  limit clamps the live slot count; tenant token buckets and the
  brownout ``batch``-class shed apply at submit; and a dedicated
  brownout rung (:func:`token_brownout_rung`) shrinks the effective
  ``max_new_tokens`` under sustained overload.

Telemetry: ``generation_*`` metric families on the serving bundle
(tokens, TTFT + end-to-end latency histograms with correlation-id
exemplars, slot occupancy, preemptions, kv bytes, queue depth) and
``generation.join`` / ``generation.leave`` / ``generation.preempt`` /
``generation.shed`` flight events carrying the decode-step index AND
the correlation id — the post-mortem timeline shows exactly which
sequences shared which steps, and joins to the request ledger.

Per-request observability (PR 12): every accepted request opens a
ledger record (``observability/reqlog.py`` — queue wait, slot, TTFT,
prefill seconds, decode-step rollup, tokens, outcome, deadline slack)
and its spans accumulate in the tail sampler's staging buffer — a
post-hoc ``generation.request`` root, a ``generation.prefill`` leg,
*sampled* ``generation.decode_step`` legs (every
``decode_span_every``-th token plus the first two), and a
``generation.preempt`` marker — retained at completion only when the
retention policy keeps them (bad outcome, slow, or the 1-in-N sample),
so ``GET /debug/requests/<correlation-id>`` explains exactly the
requests worth explaining.

Threading: ONE scheduler thread owns the slabs and all device dispatch
(the single-writer discipline); submit/cancel only touch the waiting
queue and slot table under the engine lock. Host-side control flow per
step is a few hundred ns against a device step that is the actual
budget.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.generation import sample_token
from deeplearning4j_tpu.observability import reqlog as _reqlog
from deeplearning4j_tpu.observability import trace as _trace
from deeplearning4j_tpu.observability.flightrecorder import record_event
from deeplearning4j_tpu.serving.errors import (
    BadRequestError,
    NotReadyError,
    QueueFullError,
    SlotPreemptedError,
    TenantQuotaError,
)
from deeplearning4j_tpu.serving.overload import PRIORITIES, BrownoutRung
from deeplearning4j_tpu.serving.prefixkv import resolve_prefix_store
from deeplearning4j_tpu.serving.warmup import bucket_sizes

_PRIO_RANK = {p: i for i, p in enumerate(PRIORITIES)}  # critical first

_WAITING, _ACTIVE, _DONE = "waiting", "active", "done"


def _bucket(sizes: List[int], n: int) -> int:
    for s in sizes:
        if s >= n:
            return s
    return sizes[-1]


def _warmstart_metrics():
    from deeplearning4j_tpu.observability.metrics import (
        warmstart_metrics_or_none,
    )

    return warmstart_metrics_or_none()


class GenerationStream:
    """One generation request: the client-side stream handle AND the
    scheduler's per-sequence record. Single consumer: ``tokens()`` /
    ``result()`` / ``wire_events()`` drain the same queue."""

    def __init__(self, engine: "GenerationEngine", req_id: int,
                 prompt: np.ndarray, max_new_tokens: int,
                 temperature: float, eos_id: Optional[int],
                 priority: str, tenant: Optional[str], t_submit: float):
        self._engine = engine
        self.id = req_id
        self.prompt = prompt
        self.prompt_len = int(prompt.shape[0])
        self.max_new_tokens = max_new_tokens
        self.temperature = float(temperature)
        self.eos_id = eos_id
        self.priority = priority
        self.tenant = tenant
        self.t_submit = t_submit
        self.t_first: Optional[float] = None
        # per-request observability: correlation id (adopted from the
        # HTTP layer or minted), the pre-minted root span id every
        # post-hoc leg parents to, and the timing rollups the ledger
        # record carries
        self.cid: str = ""
        self.parent_span: Optional[str] = None
        self.root_span: str = ""
        self.traced = False          # ledger record open + spans staged
        self.prefill_s: Optional[float] = None
        self.decode_s = 0.0
        # scheduler state (engine lock)
        self.state = _WAITING
        self.slot: Optional[int] = None
        self.pos = 0            # next KV write position (= prompt_len once active)
        self.last_tok = 0       # sampled but not yet fed back
        self.generated = 0
        self.finish_reason: Optional[str] = None
        self.error: Optional[Exception] = None
        self._wire_timeout: Optional[float] = None  # set by the server
        self._q: "queue.SimpleQueue" = queue.SimpleQueue()

    # -- consumer side -------------------------------------------------------

    def tokens(self, timeout: Optional[float] = None):
        """Yield token ids as they are produced; raises the typed
        ``ServingError`` on preemption/failure, returns on completion.
        ``timeout`` bounds the wait per token (``queue.Empty`` on
        expiry)."""
        while True:
            kind, val = self._q.get(timeout=timeout)
            if kind == "token":
                yield val
            elif kind == "error":
                raise val
            else:  # done
                return

    def result(self, timeout: Optional[float] = None) -> dict:
        """Collect the whole stream: ``{"tokens", "finish_reason"}``.
        ``timeout`` is the TOTAL budget for the whole stream (an
        absolute deadline, not a per-token gap — a slow engine must not
        stretch a 1 s deadline by feeding one token per second);
        ``queue.Empty`` on expiry."""
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        toks = []
        while True:
            remaining = None
            if deadline is not None:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise queue.Empty()
            kind, val = self._q.get(timeout=remaining)
            if kind == "token":
                toks.append(val)
            elif kind == "error":
                raise val
            else:
                return {"tokens": toks,
                        "finish_reason": self.finish_reason}

    @staticmethod
    def _wire_error(e: Exception) -> dict:
        if hasattr(e, "to_json"):
            # ServingError owns the wire envelope — one definition,
            # shared with the predict plane's error bodies
            return e.to_json()
        return {"error": {"code": "INTERNAL", "message": str(e)[:300],
                          "retryable": False}}

    def wire_events(self, timeout: Optional[float] = None):
        """The HTTP streaming protocol: one dict per ndjson line —
        ``{"token": id}`` per token, then a ``{"done": ...}`` summary or
        ``{"error": {...}}`` terminal line. ``timeout`` (defaulting to
        the server-set ``_wire_timeout``, i.e. the request's
        ``deadline_ms``) is the TOTAL stream budget: on expiry the
        request is cancelled and the stream ends with a terminal
        ``DEADLINE_EXCEEDED`` line — a slow engine must not stretch the
        deadline one token at a time."""
        if timeout is None:
            timeout = self._wire_timeout
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        n = 0
        while True:
            remaining = None
            if deadline is not None:
                remaining = deadline - time.monotonic()
            try:
                if remaining is not None and remaining <= 0:
                    raise queue.Empty()
                kind, val = self._q.get(timeout=remaining)
            except queue.Empty:
                self._expire()
                yield {"error": {
                    "code": "DEADLINE_EXCEEDED",
                    "message": "generation did not finish before the "
                               "deadline",
                    "retryable": False}}
                return
            if kind == "token":
                n += 1
                yield {"token": val}
            elif kind == "error":
                yield self._wire_error(val)
                return
            else:
                yield {"done": True, "n_tokens": n,
                       "finish_reason": self.finish_reason}
                return

    def cancel(self):
        """Abort this request (client went away): frees the slot / drops
        the queue entry. Idempotent; a finished stream is untouched."""
        self._engine._cancel(self)

    def _expire(self):
        """Deadline-expired abort: same slot release as cancel, but the
        outcome is ``deadline`` — a SERVER-side failure the
        generation-availability rule must burn on, unlike a client
        disconnect."""
        self._engine._cancel(self, outcome="deadline")

    # -- scheduler side ------------------------------------------------------

    def _push_token(self, tok: int):
        self._q.put(("token", tok))

    def _push_done(self):
        self._q.put(("done", None))

    def _push_error(self, err: Exception):
        self._q.put(("error", err))


class GenerationEngine:
    """The continuous-batching decode scheduler for one ``Gpt`` model.

    Deploy shape: build, :meth:`warm` (compiles every prefill bucket and
    every (slot-bucket, kv-bucket) decode step), :meth:`start` (spawns
    the scheduler thread), then :meth:`submit` from any thread. The
    ``ModelServer`` does all of this when the engine rides its
    ``generators=`` mapping.
    """

    def __init__(self, model, variables, *, name: str = "model",
                 version: str = "v1", num_slots: int = 4,
                 max_len: Optional[int] = None, max_new_tokens: int = 64,
                 brownout_max_new_tokens: Optional[int] = None,
                 max_waiting: int = 64, min_kv_bucket: int = 8,
                 min_prompt_bucket: int = 8, idle_wait_s: float = 0.05,
                 temperature: float = 1.0, seed: int = 0,
                 decode_span_every: int = 8, prefix_cache=None,
                 metrics=None, clock: Callable[[], float] = time.monotonic):
        cfg = model.config
        self._model = model
        self._params = variables["params"]
        self.name = name
        self.version = version
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        self.num_slots = int(num_slots)
        L = max_len if max_len is not None else min(cfg.max_position, 1024)
        if not 2 <= L <= cfg.max_position:
            raise ValueError(
                f"max_len must be in [2, max_position={cfg.max_position}], "
                f"got {L}")
        self.max_len = int(L)
        self.max_prompt = self.max_len - 1  # at least one generated token
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        self.default_max_new_tokens = int(max_new_tokens)
        self._token_cap = int(max_new_tokens)
        self.brownout_max_new_tokens = (
            int(brownout_max_new_tokens) if brownout_max_new_tokens is not None
            else max(1, max_new_tokens // 4))
        self.max_waiting = int(max_waiting)
        self.default_temperature = float(temperature)
        self.idle_wait_s = float(idle_wait_s)
        # decode-step span sampling: per request, the first two tokens
        # and every Nth after that get a staged span — enough legs to
        # see the step cadence without a span per token
        self.decode_span_every = max(1, int(decode_span_every))
        self._clock = clock
        # bucket vocabularies — static, closed sets: runtime selection can
        # only ever pick a warmed program (the warmup.bucket_sizes
        # discipline the predict plane uses for batch buckets)
        self.slot_buckets = bucket_sizes(self.num_slots)
        self.kv_buckets = bucket_sizes(
            self.max_len, lo=min(min_kv_bucket, self.max_len))
        self.prompt_buckets = bucket_sizes(
            self.max_prompt, lo=min(min_prompt_bucket, self.max_prompt))
        # KV slab pool: one row per slot + a scratch row for padded batch
        # rows (duplicate pad writes land there, never on live state)
        self._scratch = self.num_slots
        self._alloc_slabs()
        self.kv_bytes = int(sum(a.nbytes for a in self._kslabs) * 2)
        self._base_key = jax.random.key(seed)
        self._prefill_fns: Dict[int, Any] = {}
        self._decode_fns: Dict[Tuple[int, int], Any] = {}
        # Prefix-KV reuse (serving/prefixkv.py): after a normal prefill
        # the slot's KV columns for the longest bucket-aligned prefix
        # are published as a shared immutable slab; a later request
        # with the same prefix grafts it (one compiled scatter per
        # prompt bucket, warmed in warm()) and feeds only its suffix
        # through the already-warmed single-row decode programs. None
        # defers to DL4J_TPU_PREFIX_CACHE; default OFF.
        self.prefix_cache = resolve_prefix_store(prefix_cache, model=name)
        self._graft_fns: Dict[int, Any] = {}
        self.warmed = False
        self.warm_stats: Dict[str, Dict[str, float]] = {}  # last warm()
        self.compiles_total = 0
        self.compiles_after_warm = 0
        # scheduler state
        self._cv = threading.Condition()
        self._waiting: List[GenerationStream] = []
        self._slots: List[Optional[GenerationStream]] = \
            [None] * self.num_slots
        self._seq = itertools.count(1)
        self._rng_step = 0
        self.steps = 0              # decode iterations dispatched
        self._stream_ewma_s: Optional[float] = None
        self._stopflag = False
        self._draining = False
        self._thread: Optional[threading.Thread] = None
        self._metrics = None
        self._overload = None
        self._manifest = None
        if metrics is not None:
            self.attach_metrics(metrics)

    def _alloc_slabs(self):
        """(Re)build the zeroed KV slab pool — construction and the
        post-failure recovery path must agree on the layout."""
        cfg = self._model.config
        hd = cfg.hidden // cfg.num_heads
        dtype = self._params["embeddings"]["word"].dtype
        shape = (self.num_slots + 1, cfg.num_heads, self.max_len, hd)
        self._kslabs = tuple(jnp.zeros(shape, dtype)
                             for _ in range(cfg.num_layers))
        self._vslabs = tuple(jnp.zeros(shape, dtype)
                             for _ in range(cfg.num_layers))

    # -- wiring --------------------------------------------------------------

    def attach_metrics(self, metrics):
        """Wire the ServingMetrics bundle (generation_* families)."""
        self._metrics = metrics
        if metrics is not None:
            metrics.generation_kv_bytes.set(self.kv_bytes, model=self.name)
            metrics.generation_max_new_tokens.set(self._token_cap,
                                                  model=self.name)
            metrics.generation_slot_limit.set(self._slot_limit(),
                                              model=self.name)

    def attach_overload(self, manager):
        """Install the PR 10 overload brain: its AIMD effective limit
        clamps the live slot count, its tenant buckets and brownout
        batch-shed flag gate :meth:`submit`."""
        self._overload = manager

    def attach_manifest(self, manifest):
        """Wire a warmup manifest (serving/warmstart.py): every
        dispatched prefill bucket and (slot, kv) decode pair feeds the
        live traffic mix a restarted process warms against."""
        self._manifest = manifest

    def _note_traffic(self, kind: str, *args):
        wm = self._manifest
        if wm is None:
            return
        try:
            if kind == "prefill":
                wm.note_prefill(self.name, args[0])
            else:
                wm.note_decode(self.name, args[0], args[1])
        except Exception:  # noqa: BLE001 — recording traffic never
            pass           # fails the scheduler

    # -- compiled programs ---------------------------------------------------

    def _donate(self) -> Tuple[int, ...]:
        # slab donation keeps decode zero-copy on accelerators; CPU's
        # donation support is spotty and only warns, so skip it there
        return () if jax.default_backend() == "cpu" else (1, 2)

    def _build_prefill(self):
        # one builder for every prompt bucket: the jit specializes on the
        # padded prompt's shape; per-bucket dict entries exist for the
        # compile bookkeeping, not per-bucket logic
        model = self._model
        nl = model.config.num_layers

        # the function's name is the program's on a trace's XLA Modules
        # line (jit_generation_prefill): observability/vocab.py
        def generation_prefill(params, kslabs, vslabs, base_key, step, slot,
                               prompt, t0, temp):
            logits, kvs = model.prefill_chunk(params, prompt[None, :])
            ks, vs = [], []
            for i in range(nl):
                ks.append(jax.lax.dynamic_update_slice(
                    kslabs[i], kvs[i]["k"].astype(kslabs[i].dtype),
                    (slot, 0, 0, 0)))
                vs.append(jax.lax.dynamic_update_slice(
                    vslabs[i], kvs[i]["v"].astype(vslabs[i].dtype),
                    (slot, 0, 0, 0)))
            last = logits[0, t0 - 1]
            key = jax.random.fold_in(base_key, step)
            tok = sample_token(last[None, :], key, temp[None])[0]
            return tuple(ks), tuple(vs), tok

        return jax.jit(generation_prefill, donate_argnums=self._donate())

    def _build_decode(self, b: int, kv: int):
        model = self._model
        nl = model.config.num_layers

        def generation_decode(params, kslabs, vslabs, base_key, step,
                              slot_idx, ids, pos, temps):
            caches = [{"k": kslabs[i][slot_idx, :, :kv, :],
                       "v": vslabs[i][slot_idx, :, :kv, :]}
                      for i in range(nl)]
            logits, new = model.decode_step_slots(params, caches, ids, pos)
            rows = jnp.arange(b)
            ks, vs = [], []
            for i in range(nl):
                # only the freshly-written column goes back to the slabs
                ks.append(kslabs[i].at[slot_idx, :, pos, :].set(
                    new[i]["k"][rows, :, pos, :]))
                vs.append(vslabs[i].at[slot_idx, :, pos, :].set(
                    new[i]["v"][rows, :, pos, :]))
            key = jax.random.fold_in(base_key, step)
            tok = sample_token(logits, key, temps)
            return tuple(ks), tuple(vs), tok

        return jax.jit(generation_decode, donate_argnums=self._donate())

    def _build_graft(self, P: int):
        # scatter a shared prefix slab (per-layer (heads, P, head_dim)
        # host arrays) into one slot's first P KV columns — the whole
        # prefill replaced by one copy when the prefix is cached
        nl = self._model.config.num_layers

        def generation_graft(kslabs, vslabs, pks, pvs, slot):
            ks, vs = [], []
            for i in range(nl):
                ks.append(jax.lax.dynamic_update_slice(
                    kslabs[i], pks[i][None].astype(kslabs[i].dtype),
                    (slot, 0, 0, 0)))
                vs.append(jax.lax.dynamic_update_slice(
                    vslabs[i], pvs[i][None].astype(vslabs[i].dtype),
                    (slot, 0, 0, 0)))
            return tuple(ks), tuple(vs)

        donate = () if jax.default_backend() == "cpu" else (0, 1)
        return jax.jit(generation_graft, donate_argnums=donate)

    def _get_graft_fn(self, P: int):
        fn = self._graft_fns.get(P)
        if fn is None:
            fn = self._graft_fns[P] = self._build_graft(P)
            self._note_compile("graft", str(P))
        return fn

    def _note_compile(self, kind: str, key: str):
        self.compiles_total += 1
        if self.warmed:
            # bucket sets are closed and (absent a manifest restriction)
            # warmed in full, so this should never fire — when it does,
            # it is the exact regression the recompile-storm detector
            # and the recompile-after-warmup burn rule page on
            self.compiles_after_warm += 1
            record_event("generation.compile", model=self.name, kind=kind,
                         key=key, after_warm=True)
            wm = _warmstart_metrics()
            if wm is not None:
                wm.recompiles_after_warm_total.inc(plane="generation")

    def _get_prefill_fn(self, p_bucket: int):
        fn = self._prefill_fns.get(p_bucket)
        if fn is None:
            fn = self._prefill_fns[p_bucket] = self._build_prefill()
            self._note_compile("prefill", str(p_bucket))
        return fn

    def _get_decode_fn(self, b: int, kv: int):
        fn = self._decode_fns.get((b, kv))
        if fn is None:
            fn = self._decode_fns[(b, kv)] = self._build_decode(b, kv)
            self._note_compile("decode", f"{b}x{kv}")
        return fn

    # -- warmup --------------------------------------------------------------

    def manifest_warm_plan(self, manifest=None) -> Tuple[
            List[int], List[Tuple[int, int]]]:
        """The (prompt buckets, decode pairs) a warm pass should
        compile: the manifest's observed shapes when it has data for
        this model, the full closed vocabulary otherwise. Observed
        shapes outside the vocabulary (a config change shrank the
        buckets) are dropped; an empty intersection falls back to
        full — a stale manifest must never yield a ZERO-shape warmup
        that declares a cold engine ready."""
        p_list = list(self.prompt_buckets)
        pairs = [(b, kv) for b in self.slot_buckets
                 for kv in self.kv_buckets]
        if manifest is None:
            manifest = self._manifest
        if manifest is not None:
            obs_p = manifest.prefill_buckets(self.name)
            if obs_p:
                keep = [p for p in p_list if p in set(obs_p)]
                if keep:
                    p_list = keep
            obs_d = manifest.decode_pairs(self.name)
            if obs_d:
                keep = [pr for pr in pairs if pr in set(obs_d)]
                if keep:
                    pairs = keep
        return p_list, pairs

    def warm(self, *, prompt_buckets: Optional[List[int]] = None,
             decode_pairs: Optional[List[Tuple[int, int]]] = None,
             progress=None, source: str = "full") -> dict:
        """Compile prefill buckets and (slot-bucket, kv-bucket) decode
        steps against the scratch slot, before any traffic — the
        generation twin of the predict plane's batch warmup. Defaults
        to the FULL closed vocabulary; pass ``prompt_buckets`` /
        ``decode_pairs`` (e.g. from :meth:`manifest_warm_plan`) to warm
        exactly the live traffic mix. ``progress`` is an optional
        ``(key, seconds)`` per-shape callback (the /readyz progress
        body). Returns {kind: {bucket: seconds}}."""
        if self.running:
            # the scheduler thread owns the slabs; warm() reassigning
            # them under a live decode loop would race (and on donating
            # backends hand an already-consumed buffer to one side)
            raise RuntimeError(
                "warm() must run before start() (or after stop())")
        if prompt_buckets is None:
            prompt_buckets = list(self.prompt_buckets)
        if decode_pairs is None:
            decode_pairs = [(b, kv) for b in self.slot_buckets
                            for kv in self.kv_buckets]
        wm = _warmstart_metrics()

        def note(key, seconds):
            if wm is not None:
                wm.warmup_shapes_total.inc(plane="generation",
                                           source=source)
                wm.warmup_seconds.observe(seconds, plane="generation")
            if progress is not None:
                progress(key, seconds)

        stats: Dict[str, Dict[str, float]] = {"prefill": {}, "decode": {}}
        t_all = time.monotonic()
        for p in prompt_buckets:
            t0 = time.monotonic()
            fn = self._get_prefill_fn(p)
            ks, vs, tok = fn(self._params, self._kslabs, self._vslabs,
                             self._base_key, np.int32(0),
                             np.int32(self._scratch),
                             np.zeros(p, np.int32), np.int32(p),
                             np.float32(0.0))
            self._kslabs, self._vslabs = ks, vs
            np.asarray(tok)
            stats["prefill"][str(p)] = round(time.monotonic() - t0, 4)
            note(str(p), stats["prefill"][str(p)])
        for b, kv in decode_pairs:
            t0 = time.monotonic()
            fn = self._get_decode_fn(b, kv)
            ks, vs, tok = fn(
                self._params, self._kslabs, self._vslabs,
                self._base_key, np.int32(0),
                np.full(b, self._scratch, np.int32),
                np.zeros(b, np.int32), np.zeros(b, np.int32),
                np.zeros(b, np.float32))
            self._kslabs, self._vslabs = ks, vs
            np.asarray(tok)
            stats["decode"][f"{b}x{kv}"] = round(
                time.monotonic() - t0, 4)
            note(f"{b}x{kv}", stats["decode"][f"{b}x{kv}"])
        if self.prefix_cache is not None:
            # the graft scatter is a compiled program per prompt bucket:
            # warm them all, or the first prefix hit after readiness is
            # a recompile-after-warmup
            stats["graft"] = {}
            hd = self._kslabs[0].shape[-1]
            heads = self._kslabs[0].shape[1]
            dtype = self._kslabs[0].dtype
            for p in prompt_buckets:
                t0 = time.monotonic()
                gfn = self._get_graft_fn(p)
                zero = tuple(np.zeros((heads, p, hd), dtype)
                             for _ in self._kslabs)
                ks, vs = gfn(self._kslabs, self._vslabs, zero, zero,
                             np.int32(self._scratch))
                self._kslabs, self._vslabs = ks, vs
                jax.block_until_ready(self._kslabs[0])
                stats["graft"][str(p)] = round(time.monotonic() - t0, 4)
                note(f"graft:{p}", stats["graft"][str(p)])
        self.warmed = True
        self.warm_stats = stats
        record_event("generation.warmup", model=self.name,
                     programs=self.compiles_total,
                     seconds=round(time.monotonic() - t_all, 3))
        return stats

    # -- submit path (any thread) --------------------------------------------

    def _shed(self, reason: str, priority: str,
              correlation_id: Optional[str] = None):
        m = self._metrics
        if m is not None:
            m.generation_requests_total.inc(model=self.name, outcome="shed")
        record_event("generation.shed", model=self.name, reason=reason,
                     priority=priority, correlation_id=correlation_id)

    def _retry_hint_ms(self, waiting: int) -> float:
        ewma = self._stream_ewma_s
        if ewma is None:
            return 100.0
        return round(min(30000.0, max(
            1.0, ewma * 1000.0 * (waiting + 1) / max(1, self.num_slots))), 1)

    def submit(self, prompt, *, max_new_tokens: Optional[int] = None,
               temperature: Optional[float] = None,
               eos_id: Optional[int] = None, priority: str = "normal",
               tenant: Optional[str] = None,
               correlation_id: Optional[str] = None,
               parent_span_id: Optional[str] = None) -> GenerationStream:
        """Queue one generation request; returns its stream handle.
        Sheds exactly like the predict plane: brownout ``batch`` shed
        and waiting-queue capacity sheds raise ``QueueFullError`` (only
        the latter feeds the AIMD shed-rate signal), tenant quota —
        checked LAST so a request the engine would shed anyway never
        burns a token — raises ``TenantQuotaError`` with the refill
        wait.

        ``correlation_id`` (minted when absent) keys this request's
        ledger record and staged span tree; ``parent_span_id`` (the
        server passes its ``serving.generate`` span) parents the
        post-hoc ``generation.request`` root so the client → server →
        scheduler legs form one tree."""
        if priority not in _PRIO_RANK:
            raise BadRequestError(
                f"priority must be one of {list(PRIORITIES)}, "
                f"got {priority!r}")
        try:
            raw = np.asarray(prompt).reshape(-1)
            if raw.dtype.kind == "f":
                # JSON floats arrive here: reject anything int64 would
                # silently truncate (463.7 must be a 400, not token 463)
                if not np.all(np.isfinite(raw)) \
                        or np.any(raw != np.trunc(raw)):
                    raise BadRequestError(
                        "prompt token ids must be whole numbers")
            elif raw.dtype.kind not in "iu":
                raise BadRequestError(
                    f"prompt token ids must be integers, got dtype "
                    f"{raw.dtype}")
            ids = raw.astype(np.int64)
        except BadRequestError:
            raise
        except (TypeError, ValueError) as e:
            raise BadRequestError(f"prompt must be a flat list of token "
                                  f"ids: {e}") from None
        if ids.size < 1:
            raise BadRequestError("prompt must hold at least one token")
        if ids.size > self.max_prompt:
            raise BadRequestError(
                f"prompt of {ids.size} tokens exceeds this engine's "
                f"max prompt length {self.max_prompt}")
        vocab = self._model.config.vocab_size
        if ids.min() < 0 or ids.max() >= vocab:
            raise BadRequestError(
                f"prompt token ids must be in [0, {vocab})")
        if max_new_tokens is None:
            max_new_tokens = self.default_max_new_tokens
        if max_new_tokens < 1:
            raise BadRequestError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if temperature is None:
            temperature = self.default_temperature
        if temperature < 0:
            raise BadRequestError(
                f"temperature must be >= 0, got {temperature}")
        if eos_id is not None and not 0 <= int(eos_id) < vocab:
            raise BadRequestError(f"eos_id must be in [0, {vocab})")
        ov = self._overload
        cid = correlation_id if correlation_id else _trace.new_id()
        with self._cv:
            if self._stopflag or self._draining:
                raise NotReadyError("generation engine is draining")
            waiting = len(self._waiting)
            if ov is not None and priority == "batch" and ov.shed_batch:
                self._shed("brownout_batch", priority, cid)
                raise QueueFullError(
                    "brownout: batch-class generation requests are shed",
                    retry_after_ms=self._retry_hint_ms(waiting))
            if waiting >= self.max_waiting:
                if ov is not None:
                    ov.note_shed()
                self._shed("queue_full", priority, cid)
                raise QueueFullError(
                    f"generation queue full ({waiting} waiting)",
                    retry_after_ms=self._retry_hint_ms(waiting))
            if ov is not None:
                ok, wait_s = ov.tenant_take(tenant)
                if not ok:
                    self._shed("tenant_quota", priority, cid)
                    raise TenantQuotaError(
                        f"tenant {(tenant or '<anonymous>')!r} is over "
                        "its request quota",
                        retry_after_ms=round(wait_s * 1000.0, 1))
            req = GenerationStream(
                self, next(self._seq), ids.astype(np.int32),
                int(max_new_tokens), float(temperature),
                None if eos_id is None else int(eos_id),
                priority, tenant, self._clock())
            req.cid = cid
            req.parent_span = parent_span_id
            req.root_span = _trace.new_id()
            # the always-on ledger record: one per accepted request,
            # whatever its fate — and the tail sampler starts staging
            # this trace id's spans the same moment
            led = _reqlog.get_request_ledger(create=True)
            rec = led.begin(
                cid, plane="generation", model=self.name,
                priority=priority, tenant=tenant,
                prompt_len=req.prompt_len,
                max_new_tokens=int(max_new_tokens),
                admission="admitted",
                req=req.id) if led is not None else None
            req.traced = rec is not None
            # priority-ordered insert, FIFO within a class
            rank = _PRIO_RANK[priority]
            at = len(self._waiting)
            for i, other in enumerate(self._waiting):
                if _PRIO_RANK[other.priority] > rank:
                    at = i
                    break
            self._waiting.insert(at, req)
            self._report_queue_locked()
            self._cv.notify_all()
        return req

    def _cancel(self, req: GenerationStream, outcome: str = "cancelled"):
        with self._cv:
            if req.state == _DONE:
                return
            if req.state == _WAITING and req in self._waiting:
                self._waiting.remove(req)
            elif req.state == _ACTIVE and req.slot is not None:
                self._slots[req.slot] = None
            req.state = _DONE
            req.finish_reason = outcome
            m = self._metrics
            if m is not None:
                m.generation_requests_total.inc(model=self.name,
                                                outcome=outcome)
            self._report_queue_locked()
        record_event("generation.leave", model=self.name, req=req.id,
                     slot=req.slot, step=self.steps, reason=outcome,
                     tokens=req.generated, correlation_id=req.cid)
        self._close_request(req, outcome)

    def _close_request(self, req: GenerationStream, outcome: str):
        """Terminal per-request observability, run exactly once per
        stream (every caller flips ``state`` to done under the lock
        first): the end-to-end latency histogram (correlation-id
        exemplar; client cancels excluded — the server never finished
        that stream), the post-hoc ``generation.request`` root span the
        staged legs parent to, and the ledger finish that triggers the
        tail sampler's keep-vs-drop decision."""
        dur = max(0.0, self._clock() - req.t_submit)
        m = self._metrics
        if m is not None and outcome != "cancelled":
            m.generation_latency.observe(dur, model=self.name,
                                         exemplar_trace_id=req.cid)
        if req.traced:
            # the root is recorded BEFORE the ledger finish pops the
            # staging buffer, so a retained tree always carries it
            t_end = _trace.now()
            _trace.record_span(
                "generation.request", trace_id=req.cid,
                span_id=req.root_span, parent_id=req.parent_span,
                start=t_end - dur, end=t_end, model=self.name,
                outcome=outcome, priority=req.priority,
                tokens=req.generated, slot=req.slot)
            led = _reqlog.get_request_ledger()
            if led is not None:
                ledger_outcome = "ok" if outcome == "completed" else outcome
                led.finish(
                    req.cid, outcome=ledger_outcome,
                    finish_reason=req.finish_reason, version=self.version,
                    tokens=req.generated,
                    decode_steps=max(0, req.generated - 1),
                    decode_s=round(req.decode_s, 6),
                    prefill_s=req.prefill_s,
                    preemptions=1 if outcome == "preempted" else 0,
                    slot=req.slot)

    # -- scheduler (single thread) -------------------------------------------

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def start(self) -> "GenerationEngine":
        if self.running:
            return self
        self._stopflag = False
        self._draining = False
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"generation-{self.name}")
        self._thread.start()
        return self

    def _loop(self):
        while True:
            with self._cv:
                while (not self._stopflag and not self._waiting
                       and all(s is None for s in self._slots)):
                    self._cv.wait(self.idle_wait_s)
                if self._stopflag:
                    break
            try:
                self._admit()
                self._decode_once()
            except Exception as e:  # noqa: BLE001 — the scheduler must
                # survive a bad program/step; fail the in-flight work
                # truthfully and keep serving (slabs rebuilt in case a
                # donated buffer was consumed by the failed call)
                record_event("generation.error", model=self.name,
                             error=str(e)[:200])
                self._fail_active(e)

    def _slot_limit(self) -> int:
        lim = self.num_slots
        ov = self._overload
        if ov is not None:
            lim = max(1, min(lim, ov.effective_limit))
        return lim

    def _report_queue_locked(self):
        m = self._metrics
        if m is not None:
            m.generation_queue_depth.set(len(self._waiting), model=self.name)
            m.generation_active_slots.set(
                sum(1 for s in self._slots if s is not None),
                model=self.name)
            m.generation_slot_limit.set(self._slot_limit(), model=self.name)

    def _admit(self):
        while True:
            req = None
            victim = None
            with self._cv:
                if not self._waiting:
                    return
                head = self._waiting[0]
                free = [i for i, s in enumerate(self._slots) if s is None]
                active_n = self.num_slots - len(free)
                if free and active_n < self._slot_limit():
                    self._waiting.pop(0)
                    head.slot = free[0]
                    head.state = _ACTIVE
                    self._slots[head.slot] = head
                    self._report_queue_locked()
                    req = head
                elif head.priority == "critical":
                    victim = self._preempt_locked()
                    if victim is None:
                        return
                else:
                    return
            if victim is not None:
                # the victim's telemetry close (ledger finish, span
                # promotion, flight event) runs OUTSIDE the engine
                # lock, like every other _close_request call site —
                # submitters and token pushes must not stall behind it
                self._finish_preempt(victim)
                continue  # a slot was freed; retry the admit
            self._prefill(req)

    def _preempt_locked(self) -> Optional[GenerationStream]:
        """Evict the lowest-class active slot for a waiting critical
        request. Victim = worst priority class, newest join within it
        (least sunk decode work). Never evicts critical. Caller holds
        the lock; returns the evicted stream (state already flipped to
        done, error set) for the caller to close outside the lock, or
        None when nothing was evictable."""
        victim = None
        for s in self._slots:
            if s is None or s.priority == "critical":
                continue
            if victim is None \
                    or _PRIO_RANK[s.priority] > _PRIO_RANK[victim.priority] \
                    or (_PRIO_RANK[s.priority] == _PRIO_RANK[victim.priority]
                        and s.id > victim.id):
                victim = s
        if victim is None:
            return None
        self._slots[victim.slot] = None
        victim.state = _DONE
        victim.finish_reason = "preempted"
        victim.error = SlotPreemptedError(
            f"decode slot preempted by a critical request after "
            f"{victim.generated} tokens",
            retry_after_ms=self._retry_hint_ms(len(self._waiting)))
        m = self._metrics
        if m is not None:
            m.generation_preemptions_total.inc(model=self.name,
                                               priority=victim.priority)
            m.generation_requests_total.inc(model=self.name,
                                            outcome="preempted")
        self._report_queue_locked()
        return victim

    def _finish_preempt(self, victim: GenerationStream):
        """Everything an eviction owes the victim that does not need
        the engine lock (its state is already done, so no other path
        can close it twice)."""
        record_event("generation.preempt", model=self.name,
                     victim=victim.id, slot=victim.slot, step=self.steps,
                     victim_priority=victim.priority,
                     tokens=victim.generated, correlation_id=victim.cid)
        if victim.traced:
            # a point-in-time leg: the preemption marker a retained
            # tree shows between the last decode step and the end
            t = _trace.now()
            _trace.record_span(
                "generation.preempt", trace_id=victim.cid,
                parent_id=victim.root_span, start=t, end=t,
                step=self.steps, slot=victim.slot,
                victim_priority=victim.priority, tokens=victim.generated)
        self._close_request(victim, "preempted")
        victim._push_error(victim.error)

    def _prefill(self, req: GenerationStream):
        t0v = req.prompt_len
        pc = self.prefix_cache
        if pc is not None:
            entry = pc.acquire(self.version, req.prompt,
                               self.prompt_buckets)
            if entry is not None:
                try:
                    self._prefill_from_prefix(req, entry)
                finally:
                    pc.release(entry)
                return
            led = _reqlog.get_request_ledger()
            if led is not None:
                led.annotate(req.cid, cache="miss")
        p = _bucket(self.prompt_buckets, t0v)
        self._note_traffic("prefill", p)
        fn = self._get_prefill_fn(p)
        prompt = np.zeros(p, np.int32)
        prompt[:t0v] = req.prompt
        self._rng_step += 1
        tp0 = _trace.now()
        with _trace.annotate("generation.prefill"):
            ks, vs, tok = fn(self._params, self._kslabs, self._vslabs,
                             self._base_key, np.int32(self._rng_step),
                             np.int32(req.slot), prompt, np.int32(t0v),
                             np.float32(req.temperature))
            self._kslabs, self._vslabs = ks, vs
            tok = int(np.asarray(tok))
        tp1 = _trace.now()
        if pc is not None:
            self._publish_prefix(req, t0v)
        with self._cv:
            # same cancel-race guard as the decode path: a client that
            # disconnected while the prefill ran gets no phantom TTFT
            # sample, token count, or join-after-leave flight event
            if req.state != _ACTIVE:
                return
            req.pos = t0v
            req.last_tok = tok
            req.generated = 1
            req.t_first = self._clock()
            req.prefill_s = round(tp1 - tp0, 6)
        ttft = req.t_first - req.t_submit
        m = self._metrics
        if m is not None:
            m.generation_ttft.observe(ttft, model=self.name,
                                      exemplar_trace_id=req.cid)
            m.generation_tokens_total.inc(model=self.name)
        if req.traced:
            _trace.record_span(
                "generation.prefill", trace_id=req.cid,
                parent_id=req.root_span, start=tp0, end=tp1,
                slot=req.slot, prompt_len=t0v, bucket=p)
            led = _reqlog.get_request_ledger()
            if led is not None:
                led.annotate(req.cid, slot=req.slot,
                             queue_wait_s=round(max(0.0, ttft
                                                    - (tp1 - tp0)), 6),
                             ttft_s=round(ttft, 6),
                             prefill_s=req.prefill_s,
                             prompt_bucket=p)
        record_event("generation.join", model=self.name, req=req.id,
                     slot=req.slot, step=self.steps, prompt_len=t0v,
                     priority=req.priority, correlation_id=req.cid)
        req._push_token(tok)
        self._maybe_finish(req, tok)

    def _prefill_from_prefix(self, req: GenerationStream, entry):
        """Prefix-hit prefill: graft the shared slab into the slot's
        first P KV columns, then force-feed the suffix tokens through
        the warmed single-row decode programs — each feed of
        ``prompt[j]`` at position ``j`` writes KV column ``j`` exactly
        as prefill would (the written column depends only on the input
        token and position); the last feed's sample IS the first
        generated token. Prefill FLOPs scale with the suffix, not the
        prompt."""
        t0v = req.prompt_len
        P = entry.length
        tp0 = _trace.now()
        gfn = self._get_graft_fn(P)
        pks = tuple(k for k, _ in entry.kvs)
        pvs = tuple(v for _, v in entry.kvs)
        with _trace.annotate("generation.graft"):
            ks, vs = gfn(self._kslabs, self._vslabs, pks, pvs,
                         np.int32(req.slot))
        self._kslabs, self._vslabs = ks, vs
        b = _bucket(self.slot_buckets, 1)
        tok = None
        for j in range(P, t0v):
            kv = _bucket(self.kv_buckets, min(j + 1, self.max_len))
            self._note_traffic("decode", b, kv)
            fn = self._get_decode_fn(b, kv)
            self._rng_step += 1
            slot_idx = np.full(b, self._scratch, np.int32)
            slot_idx[0] = req.slot
            ids = np.zeros(b, np.int32)
            ids[0] = req.prompt[j]
            pos = np.zeros(b, np.int32)
            pos[0] = j
            temps = np.zeros(b, np.float32)
            temps[0] = req.temperature
            with _trace.annotate("generation.decode"):
                ks, vs, toks = fn(self._params, self._kslabs, self._vslabs,
                                  self._base_key, np.int32(self._rng_step),
                                  slot_idx, ids, pos, temps)
            self._kslabs, self._vslabs = ks, vs
            tok = toks
        with _trace.annotate("generation.decode"):  # the last feed's sync
            tok = int(np.asarray(tok)[0])
        tp1 = _trace.now()
        with self._cv:
            if req.state != _ACTIVE:
                return
            req.pos = t0v
            req.last_tok = tok
            req.generated = 1
            req.t_first = self._clock()
            req.prefill_s = round(tp1 - tp0, 6)
        ttft = req.t_first - req.t_submit
        m = self._metrics
        if m is not None:
            m.generation_ttft.observe(ttft, model=self.name,
                                      exemplar_trace_id=req.cid)
            m.generation_tokens_total.inc(model=self.name)
        led = _reqlog.get_request_ledger()
        if led is not None:
            led.annotate(req.cid, cache="prefix_hit", prefix_len=P)
        if req.traced:
            _trace.record_span(
                "generation.prefill", trace_id=req.cid,
                parent_id=req.root_span, start=tp0, end=tp1,
                slot=req.slot, prompt_len=t0v, cache="prefix_hit",
                prefix_len=P)
            if led is not None:
                led.annotate(req.cid, slot=req.slot,
                             queue_wait_s=round(max(0.0, ttft
                                                    - (tp1 - tp0)), 6),
                             ttft_s=round(ttft, 6),
                             prefill_s=req.prefill_s)
        record_event("generation.join", model=self.name, req=req.id,
                     slot=req.slot, step=self.steps, prompt_len=t0v,
                     prefix_len=P, priority=req.priority,
                     correlation_id=req.cid)
        req._push_token(tok)
        self._maybe_finish(req, tok)

    def _publish_prefix(self, req: GenerationStream, t0v: int):
        """After a normal prefill: snapshot the slot's KV columns for
        the longest bucket-aligned prefix and publish them as a shared
        slab (host copies — immutable by construction, the slot row
        keeps decoding over its own copy)."""
        pc = self.prefix_cache
        # strictly shorter than the prompt: acquire() needs at least one
        # suffix token to feed, so a slab of the full prompt length
        # could only ever serve LONGER prompts — the shorter bucket
        # serves identical repeats too
        cands = [p for p in self.prompt_buckets
                 if p < t0v and p >= pc.min_tokens]
        if not cands:
            return
        P = max(cands)
        prefix = np.asarray(req.prompt[:P], dtype=np.int64)
        if pc.has(self.version, prefix):
            return
        kvs = [(np.asarray(self._kslabs[i][req.slot, :, :P, :]),
                np.asarray(self._vslabs[i][req.slot, :, :P, :]))
               for i in range(len(self._kslabs))]
        pc.insert(self.version, prefix, kvs)

    def _decode_once(self):
        with self._cv:
            active = [s for s in self._slots if s is not None]
        if not active:
            return
        b = _bucket(self.slot_buckets, len(active))
        kv = _bucket(self.kv_buckets,
                     min(max(r.pos for r in active) + 1, self.max_len))
        self._note_traffic("decode", b, kv)
        slot_idx = np.full(b, self._scratch, np.int32)
        ids = np.zeros(b, np.int32)
        pos = np.zeros(b, np.int32)
        temps = np.zeros(b, np.float32)
        for i, r in enumerate(active):
            slot_idx[i] = r.slot
            ids[i] = r.last_tok
            pos[i] = r.pos
            temps[i] = r.temperature
        fn = self._get_decode_fn(b, kv)
        self._rng_step += 1
        td0 = _trace.now()
        with _trace.annotate("generation.decode"):
            ks, vs, toks = fn(self._params, self._kslabs, self._vslabs,
                              self._base_key, np.int32(self._rng_step),
                              slot_idx, ids, pos, temps)
            self._kslabs, self._vslabs = ks, vs
            toks = np.asarray(toks)
        td1 = _trace.now()
        step_s = td1 - td0
        self.steps += 1
        m = self._metrics
        if m is not None:
            m.generation_decode_steps_total.inc(model=self.name)
            m.generation_slot_occupancy.observe(len(active) / b,
                                               model=self.name)
        pushed = 0
        for i, r in enumerate(active):
            tok = int(toks[i])
            with self._cv:
                if r.state != _ACTIVE:  # cancelled/preempted mid-step
                    continue
                r.pos += 1
                r.generated += 1
                r.last_tok = tok
                r.decode_s += step_s
                gen = r.generated
            if r.traced and (gen <= 3
                             or gen % self.decode_span_every == 0):
                # sampled decode-step legs: the first steps after join
                # plus every Nth token — the retained tree shows the
                # step cadence without a span per token
                _trace.record_span(
                    "generation.decode_step", trace_id=r.cid,
                    parent_id=r.root_span, start=td0, end=td1,
                    step=self.steps, slot=r.slot, token_index=gen,
                    batch=len(active), kv_bucket=kv)
            r._push_token(tok)
            pushed += 1
            self._maybe_finish(r, tok)
        # counted AFTER the per-row state check: only tokens actually
        # streamed (HELP contract), never a cancel-race phantom
        if m is not None and pushed:
            m.generation_tokens_total.inc(pushed, model=self.name)

    def _maybe_finish(self, req: GenerationStream, tok: int):
        reason = None
        if req.eos_id is not None and tok == req.eos_id:
            reason = "eos"
        elif req.generated >= min(req.max_new_tokens, self._token_cap):
            reason = "length"
        elif req.pos >= self.max_len:
            reason = "length"  # KV slab exhausted
        if reason is None:
            return
        with self._cv:
            if req.state != _ACTIVE:
                return
            req.state = _DONE
            req.finish_reason = reason
            self._slots[req.slot] = None
            dur = self._clock() - req.t_submit
            if self._stream_ewma_s is None:
                self._stream_ewma_s = dur
            else:
                self._stream_ewma_s += 0.3 * (dur - self._stream_ewma_s)
            m = self._metrics
            if m is not None:
                m.generation_requests_total.inc(model=self.name,
                                                outcome="completed")
            self._report_queue_locked()
        record_event("generation.leave", model=self.name, req=req.id,
                     slot=req.slot, step=self.steps, reason=reason,
                     tokens=req.generated, correlation_id=req.cid)
        self._close_request(req, "completed")
        req._push_done()

    def _fail_active(self, exc: Exception):
        """A device step blew up: rebuild the slabs (a donated input may
        be gone) and fail every active request truthfully."""
        self._alloc_slabs()
        failed = []
        with self._cv:
            for i, r in enumerate(self._slots):
                if r is not None:
                    self._slots[i] = None
                    r.state = _DONE
                    r.finish_reason = "failed"
                    r.error = exc
                    failed.append(r)
            m = self._metrics
            if m is not None:
                for _ in failed:
                    m.generation_requests_total.inc(model=self.name,
                                                    outcome="failed")
            self._report_queue_locked()
        for r in failed:
            self._close_request(r, "failed")
            r._push_error(RuntimeError(f"generation step failed: {exc}"))

    # -- token brownout (the generation rung) --------------------------------

    def engage_token_brownout(self):
        """Shrink the effective ``max_new_tokens`` — in-flight streams
        included (they finish with ``finish_reason="length"`` at the
        shrunken cap) — so sustained overload sheds *tokens* before it
        sheds *requests*."""
        self._token_cap = self.brownout_max_new_tokens
        m = self._metrics
        if m is not None:
            m.generation_max_new_tokens.set(self._token_cap, model=self.name)

    def disengage_token_brownout(self):
        self._token_cap = self.default_max_new_tokens
        m = self._metrics
        if m is not None:
            m.generation_max_new_tokens.set(self._token_cap, model=self.name)

    @property
    def token_cap(self) -> int:
        return self._token_cap

    # -- lifecycle / rendering ------------------------------------------------

    def drain(self, timeout: float = 30.0) -> bool:
        """Stop admitting, let in-flight streams finish; True if empty
        in time."""
        deadline = time.monotonic() + timeout
        with self._cv:
            self._draining = True
        while time.monotonic() < deadline:
            with self._cv:
                if not self._waiting \
                        and all(s is None for s in self._slots):
                    return True
            time.sleep(0.01)
        return False

    def stop(self):
        """Stop the scheduler; waiting AND active requests fail with a
        retryable ``NotReadyError`` (an honest drain is ``drain()``
        first, which ``ModelServer.stop`` does)."""
        with self._cv:
            self._stopflag = True
            self._draining = True
            victims = list(self._waiting) + \
                [s for s in self._slots if s is not None]
            self._waiting.clear()
            self._slots = [None] * self.num_slots
            for r in victims:
                r.state = _DONE
                r.finish_reason = "failed"
            m = self._metrics
            if m is not None:
                for _ in victims:
                    m.generation_requests_total.inc(model=self.name,
                                                    outcome="failed")
            self._report_queue_locked()
            self._cv.notify_all()
        for r in victims:
            self._close_request(r, "failed")
            r._push_error(NotReadyError("generation engine stopped"))
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None

    def describe(self) -> dict:
        with self._cv:
            return {
                "name": self.name,
                "version": self.version,
                "warmed": self.warmed,
                "num_slots": self.num_slots,
                "slot_limit": self._slot_limit(),
                "active": sum(1 for s in self._slots if s is not None),
                "waiting": len(self._waiting),
                "max_len": self.max_len,
                "max_prompt": self.max_prompt,
                "max_new_tokens": self.default_max_new_tokens,
                "token_cap": self._token_cap,
                "slot_buckets": list(self.slot_buckets),
                "kv_buckets": list(self.kv_buckets),
                "prompt_buckets": list(self.prompt_buckets),
                "kv_bytes": self.kv_bytes,
                "decode_steps": self.steps,
                "compiled_programs": self.compiles_total,
                "compiles_after_warm": self.compiles_after_warm,
                "stream_ewma_s": self._stream_ewma_s,
                "prefix_cache": (self.prefix_cache.describe()
                                 if self.prefix_cache is not None
                                 else None),
            }


def token_brownout_rung(engines: Callable[[], List[GenerationEngine]],
                        name: str = "shrink_generation_tokens"
                        ) -> BrownoutRung:
    """The generation brownout rung: shrink every engine's effective
    ``max_new_tokens`` (engage) and restore it (disengage). Takes a
    callable so the rung follows generators added after the ladder was
    built; ``ModelServer`` slots it into the default ladder ahead of the
    fallback hot-swap. Hysteresis and the ``serving.brownout`` flight
    event come from the :class:`BrownoutLadder` walking it."""

    def engage():
        for e in engines():
            e.engage_token_brownout()

    def disengage():
        for e in engines():
            e.disengage_token_brownout()

    return BrownoutRung(name, engage, disengage)


__all__ = [
    "GenerationEngine",
    "GenerationStream",
    "token_brownout_rung",
]
