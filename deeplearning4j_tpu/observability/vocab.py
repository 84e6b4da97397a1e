"""Declared vocabularies for cross-plane string identifiers.

The flight recorder, the SLO evaluator, and the fleet debug endpoints
all key on *strings*: a flight event's ``kind``, a metric family name, a
``DL4J_TPU_*`` env knob. Strings drift — PR review history shows the
same defect class recurring (an event kind spelled two ways, a metric
family the rule file can't validate, knobs documented in GUIDE.md long
after the code grew them). This module is the single declaration point
for the flight-event ``kind`` vocabulary; ``analysis/vocabpass.py``
statically checks that every literal kind recorded anywhere in the
package appears here, so adding an event without declaring it is a
tier-1 failure, not a doc chore.

Grouped by producing plane. Keep the groups sorted; the analysis check
does not care, but reviewers diff this file.
"""

from __future__ import annotations

import re

# serving data plane (server.py / registry.py / warmup.py)
SERVING_KINDS = frozenset({
    "serving.admission_cap",
    "serving.brownout",
    "serving.circuit",
    "serving.deploy",
    "serving.drain",
    "serving.error",
    "serving.fallback",
    "serving.fallback_error",
    "serving.fallback_prewarm",
    "serving.fallback_prewarm_failed",
    "serving.recompile_after_warm",
    "serving.rollback",
    "serving.shed",
    "serving.start",
    "serving.stop",
    "serving.warmup_complete",
    "serving.warmup_error",
    "serving.worker_crash",
})

# generative serving engine (generation.py)
GENERATION_KINDS = frozenset({
    "generation.compile",
    "generation.error",
    "generation.join",
    "generation.leave",
    "generation.preempt",
    "generation.request",
    "generation.shed",
    "generation.warmup",
})

# fleet router tier (router.py)
ROUTER_KINDS = frozenset({
    "router.backend",
    "router.backend_added",
    "router.backend_removed",
    "router.backend_warming",
    "router.deploy",
    "router.drain",
    "router.park",
    "router.readmit",
    "router.retry",
    "router.retry_budget_exhausted",
    "router.shed",
    "router.start",
    "router.stop",
    "router.stream_broken",
})

# fleet autoscaler / self-healing control loop (serving/autoscaler.py)
AUTOSCALER_KINDS = frozenset({
    "autoscaler.gave_up",
    "autoscaler.page_in",
    "autoscaler.replace",
    "autoscaler.scale_in",
    "autoscaler.scale_out",
    "autoscaler.start",
    "autoscaler.stop",
})

# training + data pipeline (trainer.py / iterators.py)
TRAIN_KINDS = frozenset({
    "data.auto_prefetch",
    "data.starved",
    "train.data_recovered",
    "train.data_starvation",
    "train.epoch",
    "train.step",
})

# resilience: recovery hooks, elastic supervisor, fault injection
RESILIENCE_KINDS = frozenset({
    "checkpoint.quarantined",
    "checkpoint.verify_failed",
    "collective.timeout",
    "fault.injected",
    "resilience.checkpoint_skip",
    "resilience.lr_cut",
    "resilience.rollback",
    "resilience.skip_batch",
    "supervisor.cluster_dossier",
    "supervisor.complete",
    "supervisor.expand",
    "supervisor.expand_ready",
    "supervisor.gave_up",
    "supervisor.launch",
    "supervisor.probe",
    "supervisor.restart",
    "supervisor.shrink",
    "supervisor.shrink_denied",
    "supervisor.slot_marked_dead",
    "supervisor.worker_exit",
    "supervisor.worker_hang",
})

# cold-start plane (runtime/compilecache.py + serving/warmstart.py)
COMPILE_KINDS = frozenset({
    "compile_cache.activate",
    "compile_cache.quarantined",
    "compile_cache.sealed",
    "compile_cache.stale_metadata",
})

# kernels and operations with a backward rule of their own, at trace time
# (kernels/flash_attention.py, nn/layers/attention.py, nn/layers/moe.py,
# ops/loss.py)
KERNEL_KINDS = frozenset({
    "attention.dsa_select",
    "head.linear_cross_entropy",
    "kernel.flash_plan",
    "kernel.grouped_product",
})

# observability plane's own events (sentinel, SLO, profiling, recorder)
OBSERVABILITY_KINDS = frozenset({
    "anomaly.transition",
    "debug.profile",
    "incident.close",
    "incident.open",
    "metrics.snapshot",
    "slo.transition",
})

# concurrency/invariant sanitizers (analysis/lockcheck.py)
SANITIZER_KINDS = frozenset({
    "sanitizer.violation",
})

# request & prefix caching tier (serving/cache.py, serving/prefixkv.py,
# the router's fleet-level lookup)
CACHE_KINDS = frozenset({
    "cache.hit",
    "cache.invalidate",
    "cache.prefix_evict",
    "cache.prefix_insert",
    "cache.pressure",
    "cache.purge",
    "cache.stale_serve",
})

# traffic replay + scripted game-days (resilience/replay.py,
# resilience/gameday.py)
REPLAY_KINDS = frozenset({
    "gameday.act",
    "gameday.complete",
    "gameday.gate",
    "gameday.report",
    "gameday.start",
    "replay.complete",
    "replay.start",
})

# historical telemetry tier (observability/timeseries.py,
# observability/usage.py)
TELEMETRY_KINDS = frozenset({
    "capacity.verdict",
    "tsdb.restore",
    "tsdb.start",
    "tsdb.stop",
    "usage.overflow",
})

EVENT_KINDS = frozenset().union(
    SERVING_KINDS, GENERATION_KINDS, ROUTER_KINDS, TRAIN_KINDS,
    RESILIENCE_KINDS, COMPILE_KINDS, KERNEL_KINDS, OBSERVABILITY_KINDS,
    SANITIZER_KINDS, CACHE_KINDS, REPLAY_KINDS, TELEMETRY_KINDS,
    AUTOSCALER_KINDS)


def known_event_kinds() -> frozenset:
    """The full declared flight-event ``kind`` vocabulary."""
    return EVENT_KINDS


# -- names a profiler trace carries -------------------------------------------
#
# Three kinds of name reach a ``jax.profiler`` trace, each by the mechanism
# JAX already has. They are declared here once, used where the work is
# written, and read by the benchmark's per-layer metrics (PERF.md section 3
# has the table of which metric reads which name).

# ``jax.named_scope`` on the device side: the component of the model step an
# HLO instruction belongs to, found in its ``op_name`` metadata
# (``jit(train_step)/transpose(jvp(attn))/dot_general`` -> ``attn``).
SCOPE_EMBED = "embed"          # lookups, position/type add, embedding norm
SCOPE_ATTN = "attn"            # attention sub-layer with its norm + residual
SCOPE_MLP = "mlp"              # feed-forward sub-layer with its norm + residual
SCOPE_HEAD = "head"            # final norm, logits, the loss over them
SCOPE_OPTIMIZER = "optimizer"  # Trainer._finish_step + the master-weight cast
COMPONENT_SCOPES = (SCOPE_EMBED, SCOPE_ATTN, SCOPE_MLP, SCOPE_HEAD,
                    SCOPE_OPTIMIZER)

# Sub-scopes, opened inside a component scope by the layers that have
# parts worth timing apart; an instruction's sub-scope is the innermost
# one in its ``op_name`` (``subscope_of``), its component stays what
# ``scope_of`` says.
SCOPE_CCA_MIX = "cca_mix"          # in attn: CCA's projections, value shift,
                                   # convolutions, q-k mean, norm, rotary
SCOPE_MOE_ROUTE = "moe_route"      # in mlp: router, argmax, the chosen experts'
                                   # probabilities and places by compare and
                                   # select, sort, the rows' gather, weighting
                                   # and the sum by sorted segments
SCOPE_MOE_EXPERTS = "moe_experts"  # in mlp: the experts' grouped products
SCOPE_DSA_INDEX = "dsa_index"      # in attn: the indexer's projections, norm,
                                   # rotary, score product, relu, weighting
SCOPE_DSA_SELECT = "dsa_select"    # in attn: the threshold and the pair mask
# in attn, of a decoder whose layers differ in kind: the attention proper of
# a layer (rotary where it has positions, the repeat of k and v to the query
# heads, the three flash kernels forward and backward), by whether its
# queries reach back over a window or over everything before them
SCOPE_ATTN_WINDOW = "attn_window"
SCOPE_ATTN_GLOBAL = "attn_global"
SUB_SCOPES = (SCOPE_CCA_MIX, SCOPE_MOE_ROUTE, SCOPE_MOE_EXPERTS,
              SCOPE_DSA_INDEX, SCOPE_DSA_SELECT, SCOPE_ATTN_WINDOW,
              SCOPE_ATTN_GLOBAL)

# Metrics of the step that ``Trainer.fit`` fetches once as it returns (the
# last step's) and publishes in ``observability/runtime.step_counters``.
# ``moe.tokens_here`` counts token-expert pairs where a token has several
# experts.
COUNTER_MOE_TOKENS_HERE = "moe.tokens_here"        # [layers, experts held]
COUNTER_MOE_LOAD = "moe.load_max_over_mean"        # mean over the layers
COUNTER_MOE_PIECES_RUN = "moe.pieces_run"          # [layers], of the sorted rows
MOE_COUNTERS = (COUNTER_MOE_TOKENS_HERE, COUNTER_MOE_LOAD,
                COUNTER_MOE_PIECES_RUN)
COUNTER_DSA_PAIRS = "dsa.pairs_selected"           # [layers], over the batch
COUNTER_DSA_KEYS_MEAN = "dsa.keys_selected_mean"   # a query, over the layers
COUNTER_DSA_TILES_EMPTY = "dsa.tiles_empty_share"  # of flash_fwd's live tiles
DSA_COUNTERS = (COUNTER_DSA_PAIRS, COUNTER_DSA_KEYS_MEAN,
                COUNTER_DSA_TILES_EMPTY)
# Known while tracing (functions of the shapes and each layer's kind).
COUNTER_SWA_PAIRS_REQUIRED = "swa.pairs_required"  # [layers], over the batch:
                                                   # what the layer's kind asks
COUNTER_SWA_PAIRS_TOUCHED = "swa.pairs_touched"    # [layers], over the batch:
                                                   # what a kernel computes
SWA_COUNTERS = (COUNTER_SWA_PAIRS_REQUIRED, COUNTER_SWA_PAIRS_TOUCHED)
STEP_COUNTERS = MOE_COUNTERS + DSA_COUNTERS + SWA_COUNTERS

# ``name=`` of each Pallas kernel: the custom call reads ``jvp(flash_fwd)``
# where an unnamed one reads ``jvp()``.
KERNEL_NAMES = frozenset({
    "flash_fwd", "flash_bwd_dkv", "flash_bwd_dq",
    "lstm_scan_fwd", "lstm_scan_bwd", "gru_scan_fwd", "gru_scan_bwd",
    "segment_rows_sum",
})

# ``observability.trace.annotate`` on the host side: spans on the profiler's
# own clock (the host plane's ``python`` line), beside the device's. The
# ``train.*`` iteration and its legs are also rows of the host timeline
# (``observability/trace.py::Timeline``, under ``train.fit``), and the
# set-up phases are ``trace.span()``s: in the process ring and, while a
# profiler runs, on its host plane.
HOST_SPANS = frozenset({
    "train.fit",
    "train.step", "train.read", "train.put", "train.dispatch",
    "train.listeners",
    "import.deeplearning4j_tpu",   # the package's __init__, first to last line
    "train.init_state",            # Trainer.init_state
    "train.step_cost_analysis",    # Trainer.step_flops' thread: lower, compile,
                                   # cost_analysis
    "program_table.resolve",       # runtime.program_table: as_text() and parse
    "generation.prefill", "generation.decode", "generation.graft",
})

# jit names of the generation engine's three programs (``XLA Modules`` line)
GENERATION_PROGRAMS = ("generation_prefill", "generation_decode",
                       "generation_graft")

_TRANSFORM = re.compile(r"^[A-Za-z_]+\((.*)\)$")


def _bare(part: str) -> str:
    """A path element with its transform wrappers (``jvp(...)``,
    ``transpose(jvp(...))``) taken off."""
    while True:
        m = _TRANSFORM.match(part)
        if m is None:
            return part
        part = m.group(1)


def scope_of(op_name: str):
    """The component scope of an HLO ``op_name``, or None: the outermost
    path element that, bare, is one of ``COMPONENT_SCOPES``."""
    for part in op_name.split("/")[1:]:
        if _bare(part) in COMPONENT_SCOPES:
            return _bare(part)
    return None


def subscope_of(op_name: str):
    """The innermost path element of an HLO ``op_name`` that, bare, is one
    of ``SUB_SCOPES``, or None."""
    for part in reversed(op_name.split("/")[1:]):
        if _bare(part) in SUB_SCOPES:
            return _bare(part)
    return None
