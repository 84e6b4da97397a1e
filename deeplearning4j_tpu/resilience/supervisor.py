"""Elastic training supervisor: launch N workers, relaunch the cohort
on death or hang, resume from the latest verified checkpoint — and,
when a slot is *permanently* gone, keep training on the survivors.

The reference ran multi-worker training under ParallelWrapper /
SharedTrainingMaster, whose production value was surviving worker loss
(SURVEY §2.6, §5.3). jax has no supervisor — a SIGKILLed worker leaves
its peers stalled in the next collective until the watchdog
(resilience/cluster.py) times them out, and then *nothing restarts the
job*. This module is that missing process-level layer:

- :class:`ElasticSupervisor` launches ``num_workers`` subprocesses (one
  command per worker, parameterized by env: worker id, world size,
  generation, heartbeat dir), then monitors them:

  * a worker exiting non-zero (or being signal-killed) fails the
    *cohort* — SPMD training cannot continue minus one replica;
  * a worker whose heartbeat progress stamp goes stale is *hung*
    (stuck in a collective whose peer died, or livelocked) and fails
    the cohort the same way;
  * all workers exiting 0 completes the run.

- On cohort failure the survivors are terminated (SIGTERM, grace,
  SIGKILL), and after a capped full-jitter backoff
  (``resilience.retry.backoff_delays``) the whole cohort is relaunched
  as generation N+1 — bounded by ``max_restarts``, after which
  :class:`SupervisorGaveUp` surfaces the full exit history.

**Degraded mode** (``min_workers`` armed): relaunch-at-same-N assumes
every failure is transient, so a permanently lost slot (host gone, port
unbindable, crash loop) burns the whole restart budget and still ends
in :class:`SupervisorGaveUp`. With ``min_workers`` set the supervisor
instead *classifies* failures per slot — ``dead_slot_threshold``
consecutive immediate exits (younger than ``immediate_exit_s``) from
one slot, an explicit :meth:`ElasticSupervisor.mark_slot_dead`, or the
env-injectable ``supervisor.slot_dead`` fault — and on a dead slot
**shrinks to the survivors**: the cohort is torn down, worker ids are
compacted (slot identity rides along as ``DL4J_TPU_SLOT_ID``), the
per-generation env is re-derived for the smaller world
(``DL4J_TPU_NUM_WORKERS``, a fresh telemetry port base sized to the
survivor count, a fresh coordinator port via ``on_generation``), and
the cohort relaunches at N-k. Workers resume from the latest verified
checkpoint through the existing topology-independent restore, and the
data layer re-derives each worker's shard from the new ``(worker_id,
num_workers)`` under an explicit shrink policy
(``data.iterators.ShrinkPolicy``: preserve the global batch — each
survivor's share grows — or preserve the per-worker batch and accept
degraded throughput). A background **capacity probe** then retests the
dead slots on a jittered backoff (bind the slot's ports + an optional
user ``slot_healthy`` callback) and, once every dead slot probes
healthy, **re-expands to full N at the next checkpoint boundary**
(a new entry in ``checkpoint_dir``'s rotation index; immediately when
no ``checkpoint_dir`` is armed) so the planned teardown never loses a
step. Every topology transition writes a cluster crash dossier and is
observable: ``supervisor.shrink`` / ``supervisor.expand`` flight
events, ``cluster_workers_active`` / ``cluster_degraded`` gauges and
``supervisor_shrinks_total`` / ``supervisor_expands_total`` counters
federated through the cluster aggregator.

Recovery correctness is the *worker's* job: a worker that trains via
``FaultTolerantTrainer.fit(resume=True)`` (or
``PreemptionCheckpointer.resume``) restores the latest **verified**
checkpoint on relaunch, so the relaunched cohort resumes at the exact
rolled-back step — the supervisor only guarantees the relaunch happens,
with fresh coordination state per generation (``on_generation`` mints
per-generation env, e.g. a new coordinator port).

Everything is observable: ``supervisor.*`` flight-recorder events,
``resilience_supervisor_restarts_total`` on the shared registry, and
per-worker log files under ``log_dir``. Stdlib only.
"""

from __future__ import annotations

import dataclasses
import inspect
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path
from typing import (
    Callable,
    Dict,
    List,
    Optional,
    Sequence,
    Set,
    Union,
)

from deeplearning4j_tpu.analysis.lockcheck import make_lock
from deeplearning4j_tpu.resilience.cluster import (
    ENV_CRASH_DIR,
    ENV_HEARTBEAT_DIR,
    ENV_HEARTBEAT_INTERVAL,
    dead_peers,
)
from deeplearning4j_tpu.resilience.retry import backoff_delays

ENV_WORKER_ID = "DL4J_TPU_WORKER_ID"
ENV_NUM_WORKERS = "DL4J_TPU_NUM_WORKERS"
ENV_GENERATION = "DL4J_TPU_GENERATION"
# degraded-mode identity: the worker's PHYSICAL slot (stable across
# shrink/expand; worker ids are compacted per generation) and the
# cohort's full size, so the data layer can apply its shrink policy.
# data/iterators.py reads the same names (duplicated literals — this
# module must stay importable without jax, that one without this one).
ENV_SLOT_ID = "DL4J_TPU_SLOT_ID"
ENV_BASELINE_NUM_WORKERS = "DL4J_TPU_BASELINE_NUM_WORKERS"
ENV_SHRINK_POLICY = "DL4J_TPU_SHRINK_POLICY"
# cold-start robustness: armed for every generation when the supervisor
# is given a compile cache dir / warmup manifest, so a relaunch or a
# re-expanded cohort restores compiled artifacts + the traffic-derived
# shape mix instead of recompiling from scratch. Literals duplicated
# from runtime/compilecache.py + serving/warmstart.py — this module
# must stay importable without jax.
ENV_COMPILE_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
ENV_WARMUP_MANIFEST = "DL4J_TPU_WARMUP_MANIFEST"

# the rotation-index file serde/checkpoint.py maintains — watched (never
# parsed) for the expansion checkpoint boundary, so the supervisor needs
# no jax/numpy import to know a new checkpoint landed
_CKPT_INDEX = "checkpoint_index.json"


@dataclasses.dataclass
class WorkerExit:
    """One worker's terminal observation within a generation."""

    generation: int
    worker_id: int
    returncode: Optional[int]  # None = killed by the supervisor (hang)
    reason: str                # "exit" | "hang" | "cohort" | "shrink"
    #                            | "expand"
    log_path: Optional[str] = None
    slot: Optional[int] = None  # physical slot (== worker_id until a
    #                             shrink compacts the ids)


class SupervisorGaveUp(RuntimeError):
    """The restart budget is exhausted; carries the full exit history."""

    def __init__(self, msg: str, exits: List[WorkerExit]):
        super().__init__(msg)
        self.exits = exits


@dataclasses.dataclass
class SupervisorResult:
    """A completed run: how many generations it took and every exit
    observed along the way (empty when generation 1 just worked)."""

    generations: int
    restarts: int
    exits: List[WorkerExit]
    shrinks: int = 0
    expands: int = 0
    dead_slots: List[int] = dataclasses.field(default_factory=list)
    final_workers: int = 0


@dataclasses.dataclass
class _GenOutcome:
    """How one generation resolved (``_watch_cohort``'s verdict)."""

    kind: str                  # "ok" | "fail" | "expand"
    failure: Optional[str] = None
    worker: Optional[int] = None      # first failing worker index
    slot: Optional[int] = None        # ... and its physical slot
    reason: Optional[str] = None      # "exit" | "hang" | "shrink"
    lifetime_s: float = 0.0


def _flight(kind: str, **data):
    try:
        from deeplearning4j_tpu.observability.flightrecorder import (
            record_event,
        )

        record_event(kind, **data)
    except Exception:  # noqa: BLE001 — telemetry never fails supervision
        pass


class ElasticSupervisor:
    """Launch, watch, and relaunch a training-worker cohort.

    ``command``: the worker argv (one list used for every worker — the
    worker reads its identity from env), or a callable
    ``(worker_id, generation) -> argv``. Each worker's env carries
    ``DL4J_TPU_WORKER_ID`` / ``DL4J_TPU_NUM_WORKERS`` /
    ``DL4J_TPU_GENERATION`` (plus ``DL4J_TPU_SLOT_ID`` /
    ``DL4J_TPU_BASELINE_NUM_WORKERS`` / ``DL4J_TPU_SHRINK_POLICY`` for
    the degraded-mode data plane) and the heartbeat directory; workers
    that want hang detection call
    ``resilience.cluster.heartbeat_from_env()`` and ``touch()`` once per
    step (cheap — in-memory stamp). Workers without heartbeats are still
    supervised for exits, just not for hangs.

    ``on_generation``: optional hook returning extra env vars for a
    generation — the hook that mints a fresh coordinator port per
    relaunch (gRPC coordination state does not survive its processes).
    Signature ``(generation) -> dict`` or
    ``(generation, num_workers) -> dict`` — the two-argument form sees
    the *effective* (possibly shrunken) cohort size.

    Degraded mode: pass ``min_workers`` (the smallest cohort worth
    running) to allow shrink-to-survivors; see the module docstring for
    the classification/shrink/probe/expand lifecycle. ``checkpoint_dir``
    points at the workers' (shared) verified-checkpoint directory so
    re-expansion waits for the next checkpoint boundary instead of
    tearing down mid-step window.

    Usage::

        sup = ElasticSupervisor([sys.executable, "worker.py"],
                                num_workers=2, max_restarts=3,
                                workdir=run_dir, min_workers=1,
                                checkpoint_dir=ckpt_dir)
        result = sup.run()        # returns when all workers exit 0
    """

    def __init__(
        self,
        command: Union[Sequence[str], Callable[[int, int], Sequence[str]]],
        *,
        num_workers: int,
        max_restarts: int = 3,
        workdir: Optional[str | Path] = None,
        env: Optional[Dict[str, str]] = None,
        on_generation: Optional[Callable[..., Dict[str, str]]] = None,
        heartbeat_timeout_s: Optional[float] = None,
        heartbeat_interval_s: float = 0.25,
        poll_interval_s: float = 0.1,
        grace_s: float = 5.0,
        backoff_base_s: float = 0.25,
        backoff_max_s: float = 30.0,
        backoff_jitter: float = 0.5,
        seed: int = 0,
        telemetry: bool = False,
        telemetry_poll_interval_s: float = 1.0,
        cluster_server_port: Optional[int] = None,
        cluster_slo_rules: Optional[Sequence] = None,
        min_workers: Optional[int] = None,
        dead_slot_threshold: int = 3,
        immediate_exit_s: float = 5.0,
        shrink_policy: Optional[str] = None,
        checkpoint_dir: Optional[str | Path] = None,
        probe_interval_s: float = 5.0,
        probe_max_interval_s: float = 60.0,
        probe_jitter: float = 0.5,
        slot_healthy: Optional[Callable[[int], bool]] = None,
        slot_ports: Optional[Callable[[int], Sequence[int]]] = None,
        max_topology_changes: int = 16,
        compile_cache_dir: Optional[str | Path] = None,
        warmup_manifest: Optional[str | Path] = None,
    ):
        if num_workers < 1:
            raise ValueError(f"num_workers must be >= 1, got {num_workers}")
        if max_restarts < 0:
            raise ValueError(f"max_restarts must be >= 0, got {max_restarts}")
        if min_workers is not None and not 1 <= min_workers <= num_workers:
            raise ValueError(
                f"min_workers must be in [1, num_workers={num_workers}], "
                f"got {min_workers}")
        if dead_slot_threshold < 1:
            raise ValueError("dead_slot_threshold must be >= 1, got "
                             f"{dead_slot_threshold}")
        self.command = command
        self.num_workers = num_workers
        self.max_restarts = max_restarts
        self.workdir = Path(workdir) if workdir is not None else \
            Path(".") / "supervisor-run"
        self.env = dict(env) if env is not None else dict(os.environ)
        self.on_generation = on_generation
        self.heartbeat_timeout_s = heartbeat_timeout_s
        self.heartbeat_interval_s = heartbeat_interval_s
        self.poll_interval_s = poll_interval_s
        self.grace_s = grace_s
        self._delays = backoff_delays(
            base=backoff_base_s, cap=backoff_max_s, jitter=backoff_jitter,
            rng=random.Random(seed))
        self.exits: List[WorkerExit] = []
        self.generation = 0
        self._procs: List[subprocess.Popen] = []
        self._logs: List[Path] = []
        # -- degraded mode (shrink-to-survivors) -----------------------------
        self.min_workers = min_workers
        self.dead_slot_threshold = dead_slot_threshold
        self.immediate_exit_s = immediate_exit_s
        self.shrink_policy = shrink_policy
        self.checkpoint_dir = (Path(checkpoint_dir)
                               if checkpoint_dir is not None else None)
        self.probe_interval_s = probe_interval_s
        self.probe_max_interval_s = probe_max_interval_s
        self.probe_jitter = probe_jitter
        self.slot_healthy = slot_healthy
        self.slot_ports = slot_ports
        self.max_topology_changes = max_topology_changes
        # cold-start robustness: a workdir-relative default when True is
        # passed, any path used verbatim. Each generation's env carries
        # both, so relaunches AND re-expansions take traffic warm.
        if compile_cache_dir is True:
            compile_cache_dir = self.workdir / "compile_cache"
        if warmup_manifest is True:
            warmup_manifest = self.workdir / "warmup_manifest.json"
        self.compile_cache_dir = (Path(compile_cache_dir)
                                  if compile_cache_dir is not None else None)
        self.warmup_manifest = (Path(warmup_manifest)
                                if warmup_manifest is not None else None)
        self.dead_slots: Set[int] = set()
        self.shrinks = 0
        self.expands = 0
        self._fail_streak: Dict[int, int] = {}
        self._marked_dead: Set[int] = set()
        self._marked_lock = make_lock("ElasticSupervisor._marked_lock")
        self._gen_slots: List[int] = list(range(num_workers))
        self._launch_time = 0.0
        self._probe_thread: Optional[threading.Thread] = None
        self._probe_stop = threading.Event()
        self._expand_ready = threading.Event()
        self._ckpt_sig_at_ready = None
        self._probe_seed = seed + 1
        # probe-thread generation: a shrink supersedes any in-flight
        # probe pass, so a stale thread can never arm expansion for a
        # dead set it did not test. _probe_lock serializes the probe
        # state machine (dead-set mutation, epoch bump + ready-clear,
        # recheck + ready-set, and the shared backoff generator) across
        # the run thread and any number of probe threads.
        self._probe_epoch = 0
        self._probe_lock = make_lock("ElasticSupervisor._probe_lock")
        # ONE backoff schedule for the supervisor's lifetime: a slot
        # that flaps (probes healthy, crash-loops on expansion,
        # re-shrinks) keeps escalating toward probe_max_interval_s
        # instead of hammering on a fresh fast schedule every cycle
        self._probe_delays = None
        self._last_port_base: Optional[int] = None
        # -- cluster telemetry federation (observability/federation.py):
        # with telemetry=True each generation's workers get an exporter
        # port base + file-sink dir in env; the supervisor polls every
        # worker's snapshot each telemetry_poll_interval_s, serves the
        # federated view at /cluster/* (cluster_server_port: 0 =
        # ephemeral, None = no HTTP surface), runs a HealthEngine over
        # the federated registry (cluster_slo_rules: None = the default
        # worker-liveness rule), and buries the cohort's last-known
        # snapshots in a crash dossier on every teardown.
        self.telemetry = bool(telemetry)
        self.telemetry_poll_interval_s = float(telemetry_poll_interval_s)
        self.cluster_server_port = cluster_server_port
        self.cluster_slo_rules = cluster_slo_rules
        self._restart_count = 0
        self._aggregator = None
        self._cluster_server = None
        self._cluster_engine = None
        self._poll_thread: Optional[threading.Thread] = None
        self._poll_stop = threading.Event()

    # -- introspection -------------------------------------------------------

    @property
    def heartbeat_dir(self) -> Path:
        return self.workdir / "heartbeats"

    def worker_log(self, worker_id: int,
                   generation: Optional[int] = None) -> Path:
        gen = self.generation if generation is None else generation
        return self.workdir / f"gen{gen}_worker{worker_id}.log"

    @property
    def telemetry_dir(self) -> Path:
        return self.workdir / "telemetry"

    @property
    def aggregator(self):
        """The :class:`~deeplearning4j_tpu.observability.federation.
        ClusterAggregator` (None until the first telemetry-enabled
        launch)."""
        return self._aggregator

    @property
    def cluster_server(self):
        return self._cluster_server

    @property
    def cluster_url(self) -> Optional[str]:
        return (self._cluster_server.url
                if self._cluster_server is not None else None)

    @property
    def degraded(self) -> bool:
        """True while the cohort runs without its dead slots."""
        return bool(self.dead_slots)

    def active_slots(self) -> List[int]:
        """The physical slots the next (or current) generation runs —
        worker ids are their positions in this list."""
        return [s for s in range(self.num_workers)
                if s not in self.dead_slots]

    def mark_slot_dead(self, slot: int) -> None:
        """Classify ``slot`` permanently dead *now* (operator/scheduler
        knowledge the exit-history heuristic can't see: host
        decommissioned, maintenance drain). The watch loop tears the
        cohort down at its next poll and relaunches on the survivors.
        Requires degraded mode (``min_workers``), and refuses a mark
        that would take the cohort below the floor — silently consuming
        the operator's intent after a useless teardown would be worse
        than failing the call."""
        if not 0 <= slot < self.num_workers:
            raise ValueError(f"slot must be in [0, {self.num_workers}), "
                             f"got {slot}")
        if self.min_workers is None:
            raise RuntimeError(
                "mark_slot_dead requires degraded mode: construct the "
                "supervisor with min_workers=<floor> to allow shrinking")
        with self._marked_lock:
            survivors = [s for s in range(self.num_workers)
                         if s not in self.dead_slots
                         and s not in self._marked_dead and s != slot]
            if slot not in self.dead_slots \
                    and len(survivors) < self.min_workers:
                raise ValueError(
                    f"marking slot {slot} dead would leave "
                    f"{len(survivors)} worker(s), below "
                    f"min_workers={self.min_workers}")
            self._marked_dead.add(slot)

    # -- telemetry federation ------------------------------------------------

    def _pick_telemetry_port_base(self, n: Optional[int] = None
                                  ) -> Optional[int]:
        """A base port such that base..base+n-1 all bind right now
        (workers derive base + worker_id). ``n`` is the generation's
        *effective* cohort size — re-derived per generation so a
        shrunken cohort never inherits (or leaks) a dead slot's
        reservation. Racy by nature — a worker losing the race falls
        back to its file sink, which the aggregator reads anyway."""
        n = self.num_workers if n is None else n
        for _ in range(32):
            socks = []
            try:
                s0 = socket.socket()
                s0.bind(("127.0.0.1", 0))
                base = s0.getsockname()[1]
                socks.append(s0)
                ok = base + n <= 65535
                for i in range(1, n if ok else 0):
                    s = socket.socket()
                    try:
                        s.bind(("127.0.0.1", base + i))
                        socks.append(s)
                    except OSError:
                        ok = False
                        break
                if ok:
                    return base
            finally:
                for s in socks:
                    s.close()
        return None

    def _topology_info(self) -> dict:
        """What the aggregator publishes about the cohort's shape (the
        ``cluster_workers_active`` / ``cluster_degraded`` gauges and the
        time-in-degraded-mode counter feed from this)."""
        return {
            "workers_active": len(self.active_slots()),
            "workers_baseline": self.num_workers,
            "degraded": bool(self.dead_slots),
            "dead_slots": sorted(self.dead_slots),
            "shrinks": self.shrinks,
            "expands": self.expands,
        }

    def _arm_telemetry(self, env: Dict[str, str], n: int) -> None:
        """Per-generation telemetry env + aggregator (re)configuration;
        called from ``_launch_cohort`` before workers spawn. ``n`` is
        this generation's effective cohort size."""
        from deeplearning4j_tpu.observability.federation import (
            ENV_TELEMETRY_DIR,
            ENV_TELEMETRY_PORT_BASE,
            ClusterAggregator,
        )

        base = self._pick_telemetry_port_base(n)
        self._last_port_base = base
        self.telemetry_dir.mkdir(parents=True, exist_ok=True)
        if base is not None:
            env[ENV_TELEMETRY_PORT_BASE] = str(base)
        env[ENV_TELEMETRY_DIR] = str(self.telemetry_dir)
        if self._aggregator is None:
            # fresh run: a PREVIOUS run's sink files must not read as
            # this cohort's last-known state (they would defeat the
            # aggregator's startup grace and leak foreign snapshots
            # into the federated view/dossier). Cleared only here —
            # across THIS run's generations the files are the dead
            # workers' final states the dossier needs.
            for f in self.telemetry_dir.glob("worker_*.json"):
                try:
                    f.unlink()
                except OSError:
                    pass
            self._aggregator = ClusterAggregator(
                num_workers=n, port_base=base,
                sink_dir=self.telemetry_dir,
                heartbeat_dir=self.heartbeat_dir,
                restarts=lambda: self._restart_count,
                topology=self._topology_info,
                local_events=self._supervisor_events)
        else:
            # a shrink/expand changes the cohort size: re-derive the
            # polled worker-id range WITH the port base, or the
            # aggregator keeps polling (and failing on) dead slots'
            # stale reservations
            self._aggregator.set_cohort(n, port_base=base)

    def _cluster_m(self):
        """The aggregator's ClusterMetrics, or None without telemetry."""
        return (self._aggregator.metrics
                if self._aggregator is not None else None)

    def _supervisor_events(self) -> List[dict]:
        """This (supervisor) process's own ``supervisor.*`` flight
        events — merged into the cluster timeline so launches, shrinks
        and expansions appear next to the worker events they caused.
        Filtered to the supervisor namespace: the supervisor process's
        ring also carries unrelated local telemetry (tests, co-located
        training) that must not masquerade as cohort history."""
        try:
            from deeplearning4j_tpu.observability.flightrecorder import (
                get_flight_recorder,
            )

            return [e for e in get_flight_recorder().events()
                    if str(e.get("kind", "")).startswith("supervisor.")]
        except Exception:  # noqa: BLE001
            return []

    def _start_telemetry_surface(self) -> None:
        """Cluster HTTP surface + federated SLO engine (idempotent)."""
        if self._aggregator is None:
            return
        if self._cluster_engine is None:
            try:
                from deeplearning4j_tpu.observability.federation import (
                    default_cluster_rules,
                )
                from deeplearning4j_tpu.observability.slo import (
                    HealthEngine,
                )

                rules = (list(self.cluster_slo_rules)
                         if self.cluster_slo_rules is not None
                         else default_cluster_rules())
                self._cluster_engine = HealthEngine(
                    rules, registries=self._aggregator.registries(),
                    interval_s=max(1.0, self.telemetry_poll_interval_s))
                self._cluster_engine.start()
            except Exception:  # noqa: BLE001 — telemetry never fails
                self._cluster_engine = None  # supervision
        if self._cluster_server is None \
                and self.cluster_server_port is not None:
            try:
                from deeplearning4j_tpu.observability.federation import (
                    ClusterTelemetryServer,
                )

                self._cluster_server = ClusterTelemetryServer(
                    self._aggregator, port=self.cluster_server_port,
                    engine=self._cluster_engine,
                    max_staleness_s=self.telemetry_poll_interval_s)
                self._cluster_server.start()
            except Exception:  # noqa: BLE001
                self._cluster_server = None
        if self._poll_thread is None:
            # polling runs on its own thread: a wedged worker blocks a
            # fetch for fetch_timeout_s, and that must never delay the
            # watch loop's exit/hang detection
            self._poll_stop.clear()
            self._poll_thread = threading.Thread(
                target=self._poll_loop, daemon=True,
                name="supervisor-telemetry")
            self._poll_thread.start()

    def _poll_loop(self):
        while not self._poll_stop.wait(self.telemetry_poll_interval_s):
            try:
                self._aggregator.poll()
            except Exception:  # noqa: BLE001 — telemetry never fails
                pass           # supervision

    def _stop_telemetry_surface(self) -> None:
        self._poll_stop.set()
        if self._poll_thread is not None:
            self._poll_thread.join(timeout=10)
            self._poll_thread = None
        if self._aggregator is not None:
            try:
                self._aggregator.close()  # releases fetch-pool threads
            except Exception:  # noqa: BLE001
                pass
        if self._cluster_server is not None:
            try:
                self._cluster_server.stop()
            except Exception:  # noqa: BLE001
                pass
            self._cluster_server = None
        if self._cluster_engine is not None:
            try:
                self._cluster_engine.stop()
            except Exception:  # noqa: BLE001
                pass
            self._cluster_engine = None

    def _write_cluster_dossier(self, failure: str) -> Optional[str]:
        """On cohort teardown (failure OR planned topology transition):
        one final poll (the dead worker's file sink still holds its last
        pre-crash snapshot), then the whole last-known cluster view —
        worker table, merged timeline, every worker's final snapshot —
        into a crash report.

        Written WITHOUT ``utils.crash.write_crash_report``: that path
        imports jax and enumerates devices, and a supervisor that
        initializes an accelerator backend between generations would
        hold the very devices its relaunched workers need."""
        if self._aggregator is None:
            return None
        try:
            self._aggregator.poll()
        except Exception:  # noqa: BLE001
            pass
        try:
            import datetime
            import json

            crash_dir = Path(os.environ.get(ENV_CRASH_DIR,
                                            str(self.workdir)))
            crash_dir.mkdir(parents=True, exist_ok=True)
            dossier = self._aggregator.dossier()
            # incidents the cohort was carrying at teardown, hoisted to
            # the report's top level: the first question a post-mortem
            # asks is "was anything already firing when it died?"
            open_incidents = dossier.get("open_incidents", [])
            report = {
                "timestamp": datetime.datetime.now().isoformat(),
                "pid": os.getpid(),
                "kind": "supervisor_cluster_dossier",
                "open_incidents": open_incidents,
                "extra": {
                    "supervisor_failure": failure,
                    "generation": self.generation,
                    "topology": self._topology_info(),
                    "cluster_dossier": dossier,
                },
            }
            try:
                from deeplearning4j_tpu.observability.flightrecorder import (  # noqa: E501
                    get_flight_recorder,
                )

                report["flight_recorder"] = \
                    get_flight_recorder().dump(last_seconds=120.0)
            except Exception:  # noqa: BLE001
                pass
            # generation + microseconds uniquify: rapid launch-crash
            # loops (sub-second backoff) must not overwrite the
            # previous generation's dossier — the forensic artifact
            # this path exists to preserve
            stamp = datetime.datetime.now().strftime("%Y%m%d-%H%M%S-%f")
            path = crash_dir / (f"dl4j-tpu-crash-{stamp}-cluster-"
                                f"g{self.generation}-{os.getpid()}.json")
            path.write_text(json.dumps(report, indent=2, default=str))
            try:
                from deeplearning4j_tpu.observability import (
                    metrics as _obsm,
                )

                if _obsm.enabled():
                    _obsm.get_resilience_metrics() \
                         .crash_reports_total.inc()
            except Exception:  # noqa: BLE001
                pass
            _flight("supervisor.cluster_dossier",
                    generation=self.generation, path=str(path),
                    open_incidents=len(open_incidents))
            return str(path)
        except Exception:  # noqa: BLE001 — reporting never blocks the
            return None    # relaunch

    # -- cohort lifecycle ----------------------------------------------------

    def _argv(self, worker_id: int) -> List[str]:
        if callable(self.command):
            return list(self.command(worker_id, self.generation))
        return list(self.command)

    def _generation_env(self) -> Dict[str, str]:
        """The hook-minted extra env for this generation; the
        two-argument hook form also sees the effective cohort size."""
        if self.on_generation is None:
            return {}
        try:
            nparams = len(inspect.signature(
                self.on_generation).parameters)
        except (TypeError, ValueError):
            nparams = 1
        if nparams >= 2:
            return dict(self.on_generation(self.generation,
                                           len(self.active_slots())))
        return dict(self.on_generation(self.generation))

    def _launch_cohort(self, gen_env: Dict[str, str]):
        # heartbeats are per-generation: a stale beacon from the killed
        # previous cohort must not read as a dead peer of the new one
        hb = self.heartbeat_dir
        if hb.is_dir():
            for f in hb.glob("proc_*.json"):
                try:
                    f.unlink()
                except OSError:
                    pass
        hb.mkdir(parents=True, exist_ok=True)
        active = self.active_slots()
        n = len(active)
        if self.telemetry:
            self._arm_telemetry(gen_env, n)
        self._gen_slots = active
        self._procs, self._logs = [], []
        self._launch_time = time.monotonic()
        for wid, slot in enumerate(active):
            env = dict(self.env)
            env.update(gen_env)
            env[ENV_WORKER_ID] = str(wid)
            env[ENV_NUM_WORKERS] = str(n)
            env[ENV_GENERATION] = str(self.generation)
            env[ENV_SLOT_ID] = str(slot)
            env[ENV_BASELINE_NUM_WORKERS] = str(self.num_workers)
            if self.shrink_policy is not None:
                env[ENV_SHRINK_POLICY] = str(self.shrink_policy)
            env[ENV_HEARTBEAT_DIR] = str(hb)
            env[ENV_HEARTBEAT_INTERVAL] = str(self.heartbeat_interval_s)
            if self.compile_cache_dir is not None \
                    and not env.get(ENV_COMPILE_CACHE_DIR):
                # a directory the environment already names stands: the
                # cache lives in one place (runtime/compilecache.py)
                self.compile_cache_dir.mkdir(parents=True, exist_ok=True)
                env[ENV_COMPILE_CACHE_DIR] = str(self.compile_cache_dir)
            if self.warmup_manifest is not None:
                env[ENV_WARMUP_MANIFEST] = str(self.warmup_manifest)
            log_path = self.worker_log(wid)
            log = open(log_path, "w")
            try:
                proc = subprocess.Popen(
                    self._argv(wid), env=env, stdout=log,
                    stderr=subprocess.STDOUT,
                    start_new_session=True)  # one worker's SIGKILL storm
            finally:                         # never hits the supervisor
                log.close()
            self._procs.append(proc)
            self._logs.append(log_path)
        _flight("supervisor.launch", generation=self.generation,
                num_workers=n, slots=active, degraded=self.degraded,
                pids=[p.pid for p in self._procs])
        m = self._cluster_m()
        if m is not None:
            try:
                m.workers_active.set(float(n))
                m.degraded.set(1.0 if self.degraded else 0.0)
            except Exception:  # noqa: BLE001 — telemetry never fails
                pass

    def _hung_workers(self) -> List[int]:
        if self.heartbeat_timeout_s is None:
            return []
        try:
            # progress staleness, not beacon staleness: a worker stuck in
            # a collective still runs its beacon thread — the stamp its
            # train loop stopped touching is what goes stale
            return dead_peers(
                self.heartbeat_dir, timeout_s=self.heartbeat_timeout_s,
                progress_timeout_s=self.heartbeat_timeout_s)
        except OSError:
            return []

    @staticmethod
    def _signal_worker(p: subprocess.Popen, sig: int):
        """Signal the worker's whole process GROUP (each worker got its
        own session via start_new_session): a worker that wraps the real
        trainer in a shell/launcher must not leave grandchildren holding
        the coordinator port or heartbeat files past teardown."""
        try:
            os.killpg(p.pid, sig)
        except (ProcessLookupError, PermissionError, OSError):
            try:
                p.send_signal(sig)
            except OSError:
                pass

    def _terminate_cohort(self, reason: str, first: Optional[int] = None):
        for p in self._procs:
            if p.poll() is None:
                self._signal_worker(p, signal.SIGTERM)
        deadline = time.monotonic() + self.grace_s
        for p in self._procs:
            remaining = deadline - time.monotonic()
            try:
                p.wait(timeout=max(0.01, remaining))
            except subprocess.TimeoutExpired:
                self._signal_worker(p, signal.SIGKILL)
                p.wait()
        for wid, p in enumerate(self._procs):
            why = reason if wid == first or first is None else "cohort"
            self.exits.append(WorkerExit(
                generation=self.generation, worker_id=wid,
                returncode=p.returncode, reason=why,
                log_path=str(self._logs[wid]),
                slot=self._gen_slots[wid]))

    # -- degraded mode: classification / probe / expand ----------------------

    def _consume_marked(self) -> Set[int]:
        with self._marked_lock:
            marked, self._marked_dead = self._marked_dead, set()
        return marked

    def _classify_failure(self, out: _GenOutcome) -> Set[int]:
        """Which slots this failure proves permanently dead: K
        consecutive immediate exits from one slot, an external
        :meth:`mark_slot_dead`, or the ``supervisor.slot_dead``
        injectable fault (chaos testing the shrink path without a real
        crash loop)."""
        newly: Set[int] = set(self._consume_marked()) - self.dead_slots
        slot = out.slot
        if out.lifetime_s > self.immediate_exit_s:
            # the generation ran long before failing: EVERY slot was
            # healthy for a while, so nobody is crash-looping — isolated
            # immediate exits days apart must not accumulate into a
            # death sentence for a slot that ran fine in between
            self._fail_streak.clear()
        elif slot is not None and out.reason == "exit":
            self._fail_streak[slot] = self._fail_streak.get(slot, 0) + 1
            if self._fail_streak[slot] >= self.dead_slot_threshold:
                newly.add(slot)
        try:
            from deeplearning4j_tpu.resilience.faults import (
                get_fault_injector,
            )

            if get_fault_injector().fire("supervisor.slot_dead") is not None \
                    and slot is not None:
                newly.add(slot)
        except Exception:  # noqa: BLE001 — injection must never break
            pass           # real supervision
        return newly

    def _shrink(self, newly_dead: Set[int], failure: str) -> None:
        """Commit a topology shrink: record the dead slots, surface the
        transition (flight event + counters + dossier), and start the
        capacity probe that will earn the expansion back."""
        before = len(self.active_slots())
        with self._probe_lock:
            self.dead_slots |= newly_dead
        for s in newly_dead:
            self._fail_streak.pop(s, None)
        self.shrinks += 1
        after = len(self.active_slots())
        _flight("supervisor.shrink", generation=self.generation,
                dead_slots=sorted(newly_dead),
                all_dead_slots=sorted(self.dead_slots),
                from_workers=before, to_workers=after, cause=failure,
                policy=self.shrink_policy)
        m = self._cluster_m()
        if m is not None:
            try:
                m.shrinks_total.inc()
                m.degraded.set(1.0)
                m.workers_active.set(float(after))
            except Exception:  # noqa: BLE001
                pass
        self._start_probe()

    def _expand(self) -> None:
        """Commit the re-expansion: the probed-healthy slots rejoin and
        the cohort relaunches at full N from the checkpoint the boundary
        wait just observed."""
        before = len(self.active_slots())
        with self._probe_lock:
            healed = sorted(self.dead_slots)
            self.dead_slots.clear()
            self._expand_ready.clear()
        self.expands += 1
        _flight("supervisor.expand", generation=self.generation,
                healed_slots=healed, from_workers=before,
                to_workers=self.num_workers)
        m = self._cluster_m()
        if m is not None:
            try:
                m.expands_total.inc()
                m.degraded.set(0.0)
                m.workers_active.set(float(self.num_workers))
            except Exception:  # noqa: BLE001
                pass

    def _ckpt_signature(self):
        """Cheap identity of the newest checkpoint-index write (the
        expansion boundary detector — content is never parsed, so no
        jax/numpy enters the supervisor process)."""
        if self.checkpoint_dir is None:
            return None
        try:
            st = (self.checkpoint_dir / _CKPT_INDEX).stat()
            return (st.st_mtime_ns, st.st_size)
        except OSError:
            return None

    def _probe_slot(self, slot: int) -> bool:
        """One capacity retest of a dead slot: every port the slot needs
        must bind right now (``slot_ports`` when provided; else the
        slot's would-be telemetry port at the last armed base, skipped
        when that port sits inside the live survivors' range), and the
        user's ``slot_healthy`` callback (scheduler/host checks the
        supervisor can't see) must agree. With neither hook nor a
        telemetry base armed the probe degrades to a plain cooldown
        retry — expansion then leans on the escalating backoff and the
        ``max_topology_changes`` bound to contain a flapping slot."""
        ports: List[int] = []
        if self.slot_ports is not None:
            try:
                ports = [int(p) for p in self.slot_ports(slot)]
            except Exception:  # noqa: BLE001 — a broken hook reads as
                return False   # unhealthy, never as healthy
        elif self._last_port_base is not None:
            cand = self._last_port_base + slot
            if cand >= self._last_port_base + len(self._gen_slots):
                # outside the live survivors' port range: a squatter
                # (the slot's old tenant) still holding it means the
                # slot's resources are not back
                ports = [cand]
        ok = True
        for port in ports:
            s = socket.socket()
            try:
                s.bind(("127.0.0.1", port))
            except OSError:
                ok = False
            finally:
                s.close()
            if not ok:
                break
        if ok and self.slot_healthy is not None:
            try:
                ok = bool(self.slot_healthy(slot))
            except Exception:  # noqa: BLE001
                ok = False
        _flight("supervisor.probe", slot=slot, ok=ok, ports=ports)
        return ok

    def _start_probe(self) -> None:
        """(Re)arm the capacity probe for the CURRENT dead set. Always
        bumps the probe epoch and starts a fresh thread: an in-flight
        pass that was testing a smaller dead set is superseded — a
        stale thread must never arm expansion for slots it did not
        probe (the epoch check and the ready-set happen under one lock,
        so a superseded thread's arm is either rejected or already
        cleared here)."""
        with self._probe_lock:
            self._probe_epoch += 1
            self._expand_ready.clear()
        self._probe_stop.clear()
        self._probe_thread = threading.Thread(
            target=self._probe_loop, args=(self._probe_epoch,),
            daemon=True, name="supervisor-capacity-probe")
        self._probe_thread.start()

    def _next_probe_delay(self) -> float:
        """One delay off the supervisor-lifetime backoff schedule.
        Locked: a superseded probe thread may overlap the new one for
        one wakeup, and two threads calling next() on one generator
        concurrently is a ValueError."""
        with self._probe_lock:
            if self._probe_delays is None:
                self._probe_delays = backoff_delays(
                    base=self.probe_interval_s,
                    cap=self.probe_max_interval_s,
                    jitter=self.probe_jitter,
                    rng=random.Random(self._probe_seed))
            return next(self._probe_delays)

    def _probe_loop(self, epoch: int):
        """Retest dead slots on a capped full-jitter backoff; once EVERY
        dead slot probes healthy, arm the expansion (the watch loop
        executes it at the next checkpoint boundary). Partial healing
        keeps probing — re-expansion restores full N, not N-k+1. The
        backoff generator persists across probe restarts so a flapping
        slot keeps escalating instead of resetting to the fast end."""
        while not self._probe_stop.wait(self._next_probe_delay()):
            if epoch != self._probe_epoch:
                return  # superseded by a newer probe thread
            dead = sorted(self.dead_slots)
            if not dead:
                return
            if all(self._probe_slot(s) for s in dead):
                with self._probe_lock:
                    if epoch != self._probe_epoch \
                            or sorted(self.dead_slots) != dead:
                        continue  # a shrink landed mid-pass: retest all
                    # boundary baseline is captured NOW: only a
                    # checkpoint written after the heal releases the
                    # expansion, so the relaunched full cohort resumes
                    # from a post-heal save
                    self._ckpt_sig_at_ready = self._ckpt_signature()
                    self._expand_ready.set()
                _flight("supervisor.expand_ready", healed_slots=dead)
                return

    def _stop_probe(self) -> None:
        self._probe_stop.set()
        if self._probe_thread is not None:
            self._probe_thread.join(timeout=5)
            self._probe_thread = None

    def _expansion_due(self) -> bool:
        """The probe armed expansion AND the checkpoint boundary passed
        (a new rotation-index write since the probe passed; immediate
        when no ``checkpoint_dir`` is armed)."""
        if not self._expand_ready.is_set():
            return False
        if self.checkpoint_dir is None:
            return True
        return self._ckpt_signature() != self._ckpt_sig_at_ready

    # -- watch ---------------------------------------------------------------

    def _watch_cohort(self) -> _GenOutcome:
        """Block until the generation resolves: success (all workers
        exited 0), failure (exit/hang/marked-dead slot), or a due
        expansion (planned teardown at the checkpoint boundary)."""
        while True:
            codes = [p.poll() for p in self._procs]
            bad = next((i for i, c in enumerate(codes)
                        if c is not None and c != 0), None)
            if bad is not None:
                lifetime = time.monotonic() - self._launch_time
                _flight("supervisor.worker_exit",
                        generation=self.generation, worker=bad,
                        slot=self._gen_slots[bad], returncode=codes[bad],
                        lifetime_s=round(lifetime, 3))
                self._terminate_cohort("exit", first=bad)
                return _GenOutcome(
                    "fail",
                    failure=(f"worker {bad} (slot {self._gen_slots[bad]}) "
                             f"exited {codes[bad]}"),
                    worker=bad, slot=self._gen_slots[bad], reason="exit",
                    lifetime_s=lifetime)
            if all(c == 0 for c in codes):
                for wid, p in enumerate(self._procs):
                    self.exits.append(WorkerExit(
                        generation=self.generation, worker_id=wid,
                        returncode=0, reason="exit",
                        log_path=str(self._logs[wid]),
                        slot=self._gen_slots[wid]))
                return _GenOutcome("ok")
            marked = {s for s in self._consume_marked()
                      if s in self._gen_slots}
            if marked:
                first = self._gen_slots.index(sorted(marked)[0])
                # re-queue so classification (which consumes the marked
                # set again) still sees every marked slot
                with self._marked_lock:
                    self._marked_dead |= marked
                _flight("supervisor.slot_marked_dead",
                        generation=self.generation, slots=sorted(marked))
                self._terminate_cohort("shrink", first=first)
                return _GenOutcome(
                    "fail",
                    failure=f"slot(s) {sorted(marked)} marked dead",
                    worker=first, slot=self._gen_slots[first],
                    reason="shrink",
                    lifetime_s=time.monotonic() - self._launch_time)
            if self._expansion_due():
                self._terminate_cohort("expand")
                return _GenOutcome(
                    "expand",
                    failure=(f"planned expansion to {self.num_workers} "
                             "workers at checkpoint boundary"))
            hung = [w for w in self._hung_workers()
                    if w < len(codes) and codes[w] is None]
            if hung:
                _flight("supervisor.worker_hang",
                        generation=self.generation, workers=hung)
                self._terminate_cohort("hang", first=hung[0])
                return _GenOutcome(
                    "fail",
                    failure=(f"worker(s) {hung} hung (stale heartbeat "
                             "progress)"),
                    worker=hung[0], slot=self._gen_slots[hung[0]],
                    reason="hang",
                    lifetime_s=time.monotonic() - self._launch_time)
            time.sleep(self.poll_interval_s)

    # -- run -----------------------------------------------------------------

    def run(self) -> SupervisorResult:
        """Supervise until the cohort completes; relaunch on failure up
        to ``max_restarts`` times (consecutive failures at one topology
        — a shrink or expansion resets the streak: it changes the
        failure regime), then raise :class:`SupervisorGaveUp`."""
        self.workdir.mkdir(parents=True, exist_ok=True)
        restarts = 0
        streak = 0   # consecutive failures since the last topology change
        try:
            while True:
                self.generation += 1
                gen_env = self._generation_env()
                self._launch_cohort(gen_env)
                self._start_telemetry_surface()
                out = self._watch_cohort()
                if out.kind == "ok":
                    _flight("supervisor.complete",
                            generation=self.generation, restarts=restarts,
                            shrinks=self.shrinks, expands=self.expands)
                    return SupervisorResult(
                        generations=self.generation, restarts=restarts,
                        exits=self.exits, shrinks=self.shrinks,
                        expands=self.expands,
                        dead_slots=sorted(self.dead_slots),
                        final_workers=len(self.active_slots()))
                # cohort teardown: the aggregator's last-known view of
                # every worker (the dead one's final snapshot included)
                # becomes the crash dossier before anything relaunches.
                # Topology transitions commit FIRST so their dossier
                # carries the supervisor.shrink/expand event and the
                # post-transition topology — the forensic record of the
                # transition itself, not just the failure before it.
                if out.kind == "expand":
                    self._expand()
                    self._write_cluster_dossier(out.failure)
                    streak = 0
                    continue  # planned transition: no backoff, no budget
                newly_dead = (self._classify_failure(out)
                              if self.min_workers is not None else set())
                survivors = ([s for s in self.active_slots()
                              if s not in newly_dead]
                             if newly_dead else [])
                if newly_dead and len(survivors) >= self.min_workers \
                        and self.shrinks + self.expands \
                        < self.max_topology_changes:
                    self._shrink(newly_dead, out.failure)
                    self._write_cluster_dossier(
                        f"shrink to {len(survivors)} worker(s) after: "
                        f"{out.failure}")
                    restarts += 1
                    self._restart_count = restarts
                    streak = 0  # new topology, new failure regime
                    continue    # the failing slot is out: relaunch now
                if newly_dead:
                    # classification said dead but the floor / topology
                    # budget denies the shrink: surface it loudly — the
                    # intent is dropped here (relaunch at the same N),
                    # never silently
                    _flight("supervisor.shrink_denied",
                            generation=self.generation,
                            dead_slots=sorted(newly_dead),
                            survivors=len(survivors),
                            reason=("below min_workers"
                                    if len(survivors) < self.min_workers
                                    else "max_topology_changes reached"))
                self._write_cluster_dossier(out.failure)
                if streak >= self.max_restarts:
                    _flight("supervisor.gave_up",
                            generation=self.generation,
                            restarts=restarts, failure=out.failure)
                    raise SupervisorGaveUp(
                        f"cohort failed {streak + 1}x (restart budget "
                        f"{self.max_restarts}); last failure: "
                        f"{out.failure}",
                        self.exits)
                restarts += 1
                streak += 1
                self._restart_count = restarts
                delay = next(self._delays)
                _flight("supervisor.restart", generation=self.generation,
                        restarts=restarts, failure=out.failure,
                        backoff_s=round(delay, 3))
                try:
                    from deeplearning4j_tpu.observability import (
                        metrics as _obsm,
                    )

                    if _obsm.enabled():
                        _obsm.get_resilience_metrics() \
                             .supervisor_restarts_total.inc()
                except Exception:  # noqa: BLE001
                    pass
                time.sleep(delay)
        finally:
            self._stop_probe()
            self._stop_telemetry_surface()

    def stop(self):
        """Terminate any live workers (cleanup path for callers that
        abandon a run mid-flight)."""
        self._probe_stop.set()
        for p in self._procs:
            if p.poll() is None:
                self._signal_worker(p, signal.SIGTERM)
                try:
                    p.wait(timeout=self.grace_s)
                except subprocess.TimeoutExpired:
                    self._signal_worker(p, signal.SIGKILL)


def worker_identity() -> Dict[str, int]:
    """The supervisor-provided identity of this worker process
    (``{"worker_id", "num_workers", "generation"}``; zeros/ones when not
    running under a supervisor) — what a worker script reads to wire
    ``distributed.initialize(process_id=..., num_processes=...)``.
    Delegates to the observability layer's parser so every consumer
    (snapshots, crash reports, worker scripts) agrees on junk-env
    semantics (degrade to defaults, never raise)."""
    from deeplearning4j_tpu.observability.federation import (
        worker_identity as _identity,
    )

    return _identity()


def install_sigterm_teardown(sup: ElasticSupervisor) -> bool:
    """Install a SIGTERM handler that tears the cohort down with the
    supervisor (a systemd/k8s stop of the supervisor must not orphan
    workers); returns False off-main-thread where handlers cannot be
    installed. Opt-in — call it after constructing the supervisor."""
    def _handler(*_):
        sup.stop()
        sys.exit(143)

    try:
        signal.signal(signal.SIGTERM, _handler)
        return True
    except ValueError:  # non-main thread
        return False
