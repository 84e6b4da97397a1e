"""Central registry of every ``DL4J_TPU_*`` environment knob.

Before this registry existed the knob vocabulary lived in two drifting
places: ~39 env reads scattered across the package and a hand-written
subset (~20) in GUIDE.md. This module is now the single source of
truth:

- ``analysis/vocabpass.py`` statically requires every ``DL4J_TPU_*``
  string literal in the package to be registered here (and every
  registered knob to still appear in code — dead knobs rot too);
- the GUIDE.md knob table is *rendered* from this registry between
  ``knob-table`` markers; ``--check`` fails on any drift, and
  ``python -m deeplearning4j_tpu.analysis --write-knob-table``
  regenerates it.

Keep entries sorted by name within each plane; ``render_guide_table``
sorts globally by name regardless.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Tuple

_MARK_BEGIN = ("<!-- knob-table:begin — generated by "
               "deeplearning4j_tpu/analysis/knobs.py; do not edit by "
               "hand, run `python -m deeplearning4j_tpu.analysis "
               "--write-knob-table` -->")
_MARK_END = "<!-- knob-table:end -->"


@dataclasses.dataclass(frozen=True)
class Knob:
    name: str
    kind: str      # flag | int | float | str | path | list
    default: str   # display string; "unset" when absence is meaningful
    plane: str     # which subsystem reads it
    help: str


KNOBS: Tuple[Knob, ...] = (
    # -- runtime / numerics ---------------------------------------------------
    Knob("DL4J_TPU_ACCUM_DTYPE", "str", "float32", "runtime",
         "Gradient/optimizer accumulation dtype (runtime/environment.py)."),
    Knob("DL4J_TPU_CHECK_NUMERICS", "flag", "0", "runtime",
         "Arm per-step NaN/Inf guards on params, grads, and loss."),
    Knob("DL4J_TPU_COMPUTE_DTYPE", "str", "float32", "runtime",
         "Matmul/conv compute dtype."),
    Knob("DL4J_TPU_DEBUG", "flag", "0", "runtime",
         "Verbose runtime diagnostics (device table, dispatch traces)."),
    Knob("DL4J_TPU_PARAM_DTYPE", "str", "float32", "runtime",
         "Parameter storage dtype."),
    Knob("DL4J_TPU_PROFILING", "flag", "0", "runtime",
         "Arm the background profiler from process start."),
    Knob("DL4J_TPU_VERBOSE", "flag", "0", "runtime",
         "Chatty INFO logging across planes."),
    # -- kernels --------------------------------------------------------------
    Knob("DL4J_TPU_FLASH_BF16", "flag", "0", "kernels",
         "Allow bf16 flash-attention off-TPU (CPU testing)."),
    Knob("DL4J_TPU_FLASH_BLOCK_K", "int", "unset", "kernels",
         "Flash-attention KV-block tile size of all three kernels "
         "(unset: chosen per kernel from the shapes)."),
    Knob("DL4J_TPU_FLASH_BLOCK_Q", "int", "unset", "kernels",
         "Flash-attention query-block tile size of all three kernels "
         "(unset: chosen per kernel from the shapes)."),
    Knob("DL4J_TPU_FLASH_FP32", "flag", "0", "kernels",
         "Force fp32 flash-attention accumulation."),
    Knob("DL4J_TPU_FLASH_MIN_SEQ", "int", "1024", "kernels",
         "Sequence length at/above which dispatch picks the Pallas "
         "flash kernel."),
    Knob("DL4J_TPU_FORCE_PALLAS", "flag", "0", "kernels",
         "Force Pallas kernels regardless of per-shape dispatch."),
    # -- training -------------------------------------------------------------
    Knob("DL4J_TPU_STEP_COST_ANALYSIS", "flag", "1", "train",
         "Background XLA cost analysis of the train step; set 0 to "
         "skip the second compile."),
    # -- data pipeline --------------------------------------------------------
    Knob("DL4J_TPU_AUTO_PREFETCH", "flag", "0", "data",
         "Wrap training iterators in async prefetch when starvation "
         "is detected."),
    Knob("DL4J_TPU_IO_LIB", "path", "bundled native lib", "data",
         "Override path to the native CSV/IO shared library."),
    Knob("DL4J_TPU_PREFETCH_DEPTH", "int", "2", "data",
         "Ring depth for the async prefetch iterator."),
    # -- distributed / cluster identity --------------------------------------
    Knob("DL4J_TPU_BASELINE_NUM_WORKERS", "int", "unset", "resilience",
         "Full cohort size while running shrunk (degraded mode); "
         "divides the data shards."),
    Knob("DL4J_TPU_COLLECTIVE_TIMEOUT_S", "float", "300", "resilience",
         "Collective watchdog deadline; <= 0 disables."),
    Knob("DL4J_TPU_COORDINATOR_ADDRESS", "str", "unset", "runtime",
         "jax.distributed coordinator address (supervisor-minted)."),
    Knob("DL4J_TPU_COORDINATOR_PORT", "int", "unset", "runtime",
         "jax.distributed coordinator port (supervisor-minted)."),
    Knob("DL4J_TPU_GENERATION", "int", "1", "resilience",
         "Supervisor cohort generation stamped onto telemetry."),
    Knob("DL4J_TPU_NUM_WORKERS", "int", "1", "resilience",
         "Effective cohort size (compacted per generation)."),
    Knob("DL4J_TPU_SHRINK_POLICY", "str", "unset", "resilience",
         "Degraded-mode shard re-division policy, supervisor-armed."),
    Knob("DL4J_TPU_SLOT_ID", "int", "unset", "resilience",
         "Physical slot identity riding along during degraded mode."),
    Knob("DL4J_TPU_WORKER_ID", "int", "unset", "resilience",
         "Worker identity under a cluster supervisor."),
    # -- resilience: heartbeats / faults / crash dossiers ---------------------
    Knob("DL4J_TPU_CRASH_DIR", "path", ".", "resilience",
         "Directory crash reports (all-thread stacks + flight dump) "
         "are written to."),
    Knob("DL4J_TPU_FAULTS", "list", "empty", "resilience",
         "Fault-injection plan, e.g. train.step_nan@8;"
         "checkpoint.corrupt@2."),
    Knob("DL4J_TPU_FAULT_SEED", "int", "0", "resilience",
         "Seed for probabilistic fault-injection plans."),
    Knob("DL4J_TPU_HEARTBEAT_DIR", "path", "unset", "resilience",
         "Directory for per-worker heartbeat files (liveness + hang "
         "detection)."),
    Knob("DL4J_TPU_HEARTBEAT_INTERVAL_S", "float", "2", "resilience",
         "Heartbeat write cadence."),
    # -- resilience: traffic replay & game-days -------------------------------
    Knob("DL4J_TPU_GAMEDAY_REPORT_DIR", "path", "unset", "resilience",
         "Where game-day report artifacts (per-act verdicts, gate "
         "table, worst requests, incident index) are written."),
    Knob("DL4J_TPU_REPLAY_CLIENTS", "int", "4", "resilience",
         "Default client-thread count for the trace replay driver."),
    Knob("DL4J_TPU_REPLAY_SPEED", "float", "1", "resilience",
         "Default replay speed multiplier (1x-20x) when a driver "
         "isn't given one explicitly."),
    # -- observability --------------------------------------------------------
    Knob("DL4J_TPU_INCIDENT_DIR", "path", "per-process tmp dir",
         "observability",
         "Where incident bundles are persisted and served from."),
    Knob("DL4J_TPU_REQLOG_CAPACITY", "int", "2048", "observability",
         "Request-ledger ring capacity."),
    Knob("DL4J_TPU_TELEMETRY_DIR", "path", "unset", "observability",
         "Arms the file-sink exporter (worker_<id>.json snapshots)."),
    Knob("DL4J_TPU_TELEMETRY_PORT", "int", "unset", "observability",
         "Exact exporter port (wins over the port base)."),
    Knob("DL4J_TPU_TELEMETRY_PORT_BASE", "int", "unset", "observability",
         "Exporter port = base + worker_id (supervisor-armed)."),
    # -- fleet observability tier (serving/router.py) -------------------------
    Knob("DL4J_TPU_ROUTER_OBSERVABILITY", "flag", "1", "observability",
         "Arm the router's own observability spine: request ledger + "
         "span plane, fleet HealthEngine, mini-TSDB over the federated "
         "scrape, and the fleet sentinel (0 disables all four)."),
    Knob("DL4J_TPU_ROUTER_REQLOG_CAPACITY", "int", "2048",
         "observability",
         "Router request-ledger ring capacity (one lifecycle record "
         "per offered fleet request, sheds included)."),
    Knob("DL4J_TPU_ROUTER_TRACE_CAPACITY", "int", "4096",
         "observability",
         "Router span-ring capacity (router.request / router.pick / "
         "router.attempt / router.proxy spans retained for stitching)."),
    Knob("DL4J_TPU_ROUTER_OBS_INTERVAL_S", "float", "10",
         "observability",
         "Shared cadence of the router's fleet health ticks, TSDB "
         "samples and sentinel evaluations."),
    # -- historical telemetry tier (timeseries.py + usage.py) -----------------
    Knob("DL4J_TPU_TSDB_INTERVAL_S", "float", "finest tier step",
         "observability",
         "Mini-TSDB background sampler cadence."),
    Knob("DL4J_TPU_TSDB_MAX_SERIES", "int", "512", "observability",
         "Mini-TSDB series cardinality bound; overflow series are "
         "dropped and counted, never grown."),
    Knob("DL4J_TPU_TSDB_TIERS", "list", "1x600,10x720,60x1440",
         "observability",
         "Mini-TSDB retention tiers as <step_s>x<capacity> pairs "
         "(defaults: 1 s x 10 min, 10 s x 2 h, 60 s x 24 h)."),
    Knob("DL4J_TPU_USAGE_MAX_ACCOUNTS", "int", "256", "observability",
         "Usage-meter (tenant, model) account bound; overflow folds "
         "into the __other__ tenant."),
    Knob("DL4J_TPU_USAGE_ROLLUP_S", "float", "10", "observability",
         "Cadence at which usage accounts and capacity evaluations "
         "roll up into the mini-TSDB."),
    # -- request & prefix caching tier ----------------------------------------
    Knob("DL4J_TPU_CACHE", "flag", "0", "serving",
         "Arm the exact-match response cache on ModelServer (tenant-"
         "scoped, consulted before a batch slot is taken)."),
    Knob("DL4J_TPU_CACHE_CAPACITY", "int", "1024", "serving",
         "Response-cache entry bound (global LRU across tenants)."),
    Knob("DL4J_TPU_CACHE_MAX_BYTES", "int", "67108864", "serving",
         "Response-cache byte bound."),
    Knob("DL4J_TPU_CACHE_TTL_S", "float", "60", "serving",
         "Response-cache entry TTL; expired entries serve stale only "
         "while the cache_pressure brownout rung is engaged."),
    Knob("DL4J_TPU_PREFIX_CACHE", "flag", "0", "serving",
         "Arm prefix-KV reuse on GenerationEngine (shared immutable "
         "KV slabs grafted per prompt bucket; warmed at deploy)."),
    Knob("DL4J_TPU_PREFIX_CACHE_MAX_BYTES", "int", "268435456", "serving",
         "Prefix-KV store byte bound (LRU, pinned slabs skipped)."),
    # -- fleet autoscaler (serving/autoscaler.py) ------------------------------
    Knob("DL4J_TPU_AUTOSCALER_CLEAR_AFTER", "int", "2", "serving",
         "Consecutive calm ticks before the overload detector clears "
         "(hysteresis cool side)."),
    Knob("DL4J_TPU_AUTOSCALER_COOLDOWN_S", "float", "10", "serving",
         "Minimum seconds between scale decisions in the same "
         "direction (anti-flap)."),
    Knob("DL4J_TPU_AUTOSCALER_DRY_RUN", "flag", "0", "serving",
         "Record scale decisions in the ledger and flight recorder "
         "without executing them."),
    Knob("DL4J_TPU_AUTOSCALER_FIRE_AFTER", "int", "3", "serving",
         "Consecutive overloaded ticks before scale-out fires "
         "(hysteresis hot side; must be >= 2)."),
    Knob("DL4J_TPU_AUTOSCALER_IDLE_FIRE_AFTER", "int", "5", "serving",
         "Consecutive idle ticks before drain-and-retire fires."),
    Knob("DL4J_TPU_AUTOSCALER_MAX_BACKENDS", "int", "4", "serving",
         "Fleet size ceiling the autoscaler will not spawn past."),
    Knob("DL4J_TPU_AUTOSCALER_MIN_BACKENDS", "int", "1", "serving",
         "Fleet size floor scale-in will not retire below (scale-to-"
         "zero overrides the floor to 0)."),
    Knob("DL4J_TPU_AUTOSCALER_SCALE_TO_ZERO", "flag", "0", "serving",
         "Allow retiring the last idle backend; the next request "
         "parks under the retry budget while one respawns."),
    Knob("DL4J_TPU_AUTOSCALER_SHED_RATE", "float", "0.5", "serving",
         "Fleet shed rate (sheds/s) at/above which a tick counts as "
         "overloaded."),
    Knob("DL4J_TPU_AUTOSCALER_TICK_S", "float", "1", "serving",
         "Autoscaler control-loop tick interval."),
    # -- cold-start plane -----------------------------------------------------
    Knob("DL4J_TPU_WARMUP_MANIFEST", "path", "unset", "warmstart",
         "Traffic-derived warmup manifest: restarts AOT-compile "
         "observed shapes before /readyz flips."),
    # -- concurrency sanitizers (analysis plane) ------------------------------
    Knob("DL4J_TPU_SANITIZERS", "list", "empty", "analysis",
         "Comma-separated runtime sanitizers to arm; currently "
         "`lockorder` (instrumented locks: order-inversion + "
         "long-hold detection)."),
    Knob("DL4J_TPU_LOCKCHECK_HOLD_S", "float", "1.0", "analysis",
         "Lock-hold duration above which the lockorder sanitizer "
         "reports a long-hold violation."),
)


def registry() -> Dict[str, Knob]:
    out = {}
    for k in KNOBS:
        if k.name in out:
            raise ValueError(f"duplicate knob {k.name}")
        out[k.name] = k
    return out


def known_knob_names() -> frozenset:
    return frozenset(k.name for k in KNOBS)


def render_guide_table() -> str:
    """The markdown knob table, markers included — byte-for-byte what
    GUIDE.md must contain."""
    rows = ["| Knob | Type | Default | Plane | What it does |",
            "| --- | --- | --- | --- | --- |"]
    for k in sorted(KNOBS, key=lambda k: k.name):
        rows.append(f"| `{k.name}` | {k.kind} | {k.default} | {k.plane} "
                    f"| {k.help} |")
    return "\n".join([_MARK_BEGIN, *rows, _MARK_END])


_TABLE_RE = re.compile(
    re.escape(_MARK_BEGIN.split("—")[0]) + r".*?" + re.escape(_MARK_END),
    re.DOTALL)


def check_guide(guide_path: str) -> List[str]:
    """Drift errors between GUIDE.md's generated table and this
    registry (empty list = in sync)."""
    try:
        with open(guide_path, encoding="utf-8") as fh:
            text = fh.read()
    except OSError as e:
        return [f"cannot read {guide_path}: {e}"]
    m = _TABLE_RE.search(text)
    if m is None:
        return [f"{guide_path}: knob-table markers not found — run "
                f"--write-knob-table"]
    want = render_guide_table()
    if m.group(0) != want:
        have_names = set(re.findall(r"\|\s*`(DL4J_TPU_[A-Z0-9_]+)`",
                                    m.group(0)))
        want_names = known_knob_names()
        missing = sorted(want_names - have_names)
        stale = sorted(have_names - want_names)
        detail = []
        if missing:
            detail.append(f"missing from GUIDE.md: {missing}")
        if stale:
            detail.append(f"stale in GUIDE.md: {stale}")
        if not detail:
            detail.append("table text differs (row content/order)")
        return [f"{guide_path}: knob table drifted from "
                f"analysis/knobs.py ({'; '.join(detail)}) — run "
                f"--write-knob-table"]
    return []


def write_guide_table(guide_path: str) -> bool:
    """Regenerate the table in place; returns True when the file
    changed. Raises when the markers are absent (placement of the
    section is a human decision)."""
    with open(guide_path, encoding="utf-8") as fh:
        text = fh.read()
    if _TABLE_RE.search(text) is None:
        raise ValueError(f"{guide_path}: knob-table markers not found; "
                         "add them where the table should live")
    new = _TABLE_RE.sub(lambda _m: render_guide_table(), text, count=1)
    if new == text:
        return False
    with open(guide_path, "w", encoding="utf-8") as fh:
        fh.write(new)
    return True
