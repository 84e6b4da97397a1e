"""Persistent compile cache with a checkpoint-style integrity layer.

Every process start — a supervisor relaunch, a re-expanded elastic
cohort, a restarted serving backend, a brownout fallback deploy —
re-traces and re-compiles every program from scratch; the sentinel's
recompile-storm detector can only watch the stall. This module makes
compiled artifacts *survive* the process (cf. PAPERS.md arxiv
1410.0759: compiled-primitive reuse is the precondition for cheap
topology changes): it arms jax's persistent compilation cache on a
configured directory, fronted by our own integrity layer in the
serde/checkpoint manifest style.

Why an integrity layer of our own: jax treats the cache directory as
trusted bytes. A truncated artifact (disk full mid-write), flipped bits
(the classic torn NFS story), or an artifact written by a different jax
version must never be *handed* to the runtime — `activate()` walks the
cache against ``cache_manifest.json`` (per-artifact SHA-256 + size +
the writing jax version), QUARANTINES anything that disagrees (moved to
``quarantine/``, counted in ``compile_cache_quarantined_total``, flight
event recorded), and only then arms the directory. A quarantined shape
simply compiles fresh — degraded, never poisoned. ``seal()`` (called
after warmup completes) re-digests the surviving + newly-written
artifacts into the manifest atomically.

Chaos points (resilience/faults.py): ``compile.cache_corrupt`` flips
bytes in one manifest-listed artifact right before the walk — the walk
must catch it; ``compile.cache_stall`` sleeps inside activation — a
hung cache filesystem must keep ``/readyz`` not-ready, not wedge the
process.

Where the cache lives is decided in ONE place, :func:`resolve_cache_dir`:
``JAX_COMPILATION_CACHE_DIR`` when it is set — jax reads that variable
itself at import, so this module then verifies and seals that directory
in place and never points jax anywhere else — otherwise the fixed
``<checkout>/.jax_cache``. The directory is part of jax's cache key, so
it is never a temporary name, a pid or a timestamp.

``enable_compile_cache()`` arms it explicitly (entry-point scripts:
``chip_smoke.py``, ``bench.py``, ``kernels_ab.py``, the examples).
``maybe_enable_compile_cache()`` is the one-liner ``Trainer.fit`` and
``ModelServer.start`` call: it arms only where the variable is set (the
supervisor sets it for every worker generation, so relaunches and
re-expansions land on a warm cache) — a library call must not start
writing every program of a test run to disk on its own.
"""

from __future__ import annotations

import json
import os
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = Path(__file__).resolve().parents[2]


def resolve_cache_dir() -> Path:
    """The compile cache's directory: ``JAX_COMPILATION_CACHE_DIR`` if
    set, else ``<checkout>/.jax_cache``."""
    env = os.environ.get(ENV_CACHE_DIR)
    return Path(env) if env else _CHECKOUT / ".jax_cache"

_CACHE_MANIFEST = "cache_manifest.json"
_QUARANTINE_DIR = "quarantine"
_MANIFEST_FORMAT = 1

REASON_CORRUPT = "corrupt"
REASON_TRUNCATED = "truncated"
REASON_VERSION_SKEW = "version_skew"


def _metrics():
    from deeplearning4j_tpu.observability.metrics import (
        warmstart_metrics_or_none,
    )

    return warmstart_metrics_or_none()


def _flight(kind: str, **data):
    try:
        from deeplearning4j_tpu.observability.flightrecorder import (
            record_event,
        )

        record_event(kind, **data)
    except Exception:  # noqa: BLE001 — telemetry never fails the cache
        pass


def _fault_injector():
    from deeplearning4j_tpu.resilience.faults import get_fault_injector

    inj = get_fault_injector()
    return inj if inj.enabled else None


class CompileCache:
    """One persistent-compile-cache directory + its integrity manifest.

    Lifecycle: ``activate()`` at process start (verify → quarantine →
    arm jax), ``seal()`` once warmup finished (record what the warm
    process wrote). Both are cheap next to a single XLA compile; both
    never raise on bad on-disk state — a broken cache degrades to cold
    compiles, it does not take the process down.
    """

    def __init__(self, directory: Optional[str | Path] = None):
        self.directory = (Path(directory) if directory is not None
                          else resolve_cache_dir())
        self.quarantine_dir = self.directory / _QUARANTINE_DIR
        self._lock = threading.Lock()
        self.active = False
        self.quarantined: List[dict] = []   # this process's verdicts

    # -- manifest ------------------------------------------------------------

    @property
    def manifest_path(self) -> Path:
        return self.directory / _CACHE_MANIFEST

    def _read_manifest(self) -> Optional[dict]:
        try:
            doc = json.loads(self.manifest_path.read_text())
        except FileNotFoundError:
            return None
        except Exception:  # noqa: BLE001 — a torn manifest = no manifest:
            return None    # artifacts re-seal on the next warm completion
        if not isinstance(doc, dict) or not isinstance(
                doc.get("entries"), dict):
            return None
        return doc

    def _artifact_files(self) -> List[Path]:
        """Cache artifacts worth protecting: regular files in the cache
        root, minus our own manifest/tmp litter and jax's ``-atime``
        access stamps (rewritten on every hit — hashing them would
        quarantine the whole cache each restart)."""
        if not self.directory.is_dir():
            return []
        out = []
        for p in sorted(self.directory.iterdir()):
            if not p.is_file():
                continue
            if p.name == _CACHE_MANIFEST or p.name.endswith(".tmp"):
                continue
            if p.name.endswith("-atime"):
                continue
            out.append(p)
        return out

    def _quarantine(self, path: Path, reason: str):
        self.quarantine_dir.mkdir(parents=True, exist_ok=True)
        target = self.quarantine_dir / path.name
        n = 0
        while target.exists():
            n += 1
            target = self.quarantine_dir / f"{path.name}.{n}"
        try:
            os.replace(path, target)
        except OSError:
            # same-fs rename failed (racing eviction?): drop the file
            # instead — an unverifiable artifact must not stay reachable
            try:
                path.unlink(missing_ok=True)
            except OSError:
                return
        self.quarantined.append({"artifact": path.name, "reason": reason})
        m = _metrics()
        if m is not None:
            m.cache_quarantined_total.inc(reason=reason)
        _flight("compile_cache.quarantined", artifact=path.name,
                reason=reason, quarantine=str(target))

    # -- verify / seal -------------------------------------------------------

    def verify(self) -> dict:
        """Walk manifest-listed artifacts; quarantine any that disagree
        (digest = corrupt, size = truncated, foreign jax version =
        version_skew). Artifacts on disk but not in the manifest are
        new since the last seal and pass through untouched — the next
        ``seal()`` adopts them. Returns a verdict summary."""
        import jax

        from deeplearning4j_tpu.serde.checkpoint import file_sha256

        t0 = time.perf_counter()
        doc = self._read_manifest()
        checked = quarantined = 0
        with self._lock:
            if doc is not None:
                skew = str(doc.get("jax", "")) != jax.__version__
                for name, rec in doc["entries"].items():
                    if not isinstance(rec, dict):
                        # foreign/hand-edited manifest row: no digests
                        # to trust = nothing to verify against, and the
                        # never-raise activation contract forbids
                        # crashing the process start over it
                        continue
                    p = self.directory / Path(name).name
                    if not p.is_file():
                        continue  # evicted out-of-band; drop at seal
                    checked += 1
                    if skew:
                        self._quarantine(p, REASON_VERSION_SKEW)
                        quarantined += 1
                        continue
                    size = p.stat().st_size
                    if rec.get("size") is not None and size != rec["size"]:
                        self._quarantine(p, REASON_TRUNCATED)
                        quarantined += 1
                        continue
                    if rec.get("sha256") and \
                            file_sha256(p) != rec["sha256"]:
                        self._quarantine(p, REASON_CORRUPT)
                        quarantined += 1
        m = _metrics()
        if m is not None:
            m.cache_op_seconds.observe(time.perf_counter() - t0,
                                       op="verify")
        return {"checked": checked, "quarantined": quarantined,
                "unlisted": max(0, len(self._artifact_files()) - (
                    checked - quarantined))}

    def seal(self) -> dict:
        """Atomically rewrite the manifest from what is on disk NOW —
        the post-warmup call that promotes this run's artifacts into
        the verified set the next process start trusts."""
        import jax

        from deeplearning4j_tpu.serde.checkpoint import (
            atomic_write_text,
            file_sha256,
        )

        t0 = time.perf_counter()
        entries: Dict[str, dict] = {}
        total_bytes = 0
        with self._lock:
            self.directory.mkdir(parents=True, exist_ok=True)
            for p in self._artifact_files():
                try:
                    size = p.stat().st_size
                    entries[p.name] = {"sha256": file_sha256(p),
                                       "size": size}
                    total_bytes += size
                except OSError:
                    continue  # evicted mid-walk; the next seal catches up
            atomic_write_text(self.manifest_path, json.dumps({
                "format": _MANIFEST_FORMAT,
                "jax": jax.__version__,
                "backend": jax.default_backend(),
                "written": time.time(),
                "entries": entries,
            }, indent=2))
        m = _metrics()
        if m is not None:
            m.cache_entries.set(float(len(entries)))
            m.cache_bytes.set(float(total_bytes))
            m.cache_op_seconds.observe(time.perf_counter() - t0, op="seal")
        _flight("compile_cache.sealed", entries=len(entries),
                bytes=total_bytes)
        return {"entries": len(entries), "bytes": total_bytes}

    # -- activation ----------------------------------------------------------

    def activate(self) -> dict:
        """Verify + quarantine, then arm jax's persistent compilation
        cache on the directory. Idempotent; never raises on bad cache
        state (the worst case is an empty cache = today's cold start).
        """
        import jax

        inj = _fault_injector()
        if inj is not None:
            inj.maybe_sleep("compile.cache_stall")
            if inj.fire("compile.cache_corrupt") is not None:
                self._chaos_corrupt_one()
        env_dir = os.environ.get(ENV_CACHE_DIR)
        if env_dir and Path(env_dir) != self.directory:
            raise ValueError(
                f"{ENV_CACHE_DIR}={env_dir} is set: the compile cache "
                f"lives there, not at {self.directory}")
        self.directory.mkdir(parents=True, exist_ok=True)
        verdict = self.verify()
        if not env_dir:
            # with the variable set jax already holds the directory (it
            # reads the variable at import) and nothing here moves it
            jax.config.update("jax_compilation_cache_dir",
                              str(self.directory))
            # jax binds its cache object to the FIRST directory it
            # initializes; re-activation onto a different directory
            # (tests) must drop that handle or the new dir is ignored
            from jax._src import compilation_cache as _jax_cc

            _jax_cc.reset_cache()
        # min-compile-time/entry-size floors dropped: serving buckets
        # are exactly the many-small-programs workload the defaults
        # (1 s / 4 KiB) would decline to cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        # cache faults degrade to fresh compiles, never crash serving
        jax.config.update("jax_raise_persistent_cache_errors", False)
        self.active = True
        m = _metrics()
        if m is not None:
            m.cache_active.set(1.0)
            doc = self._read_manifest()
            if doc is not None:
                m.cache_entries.set(float(len(doc["entries"])))
                m.cache_bytes.set(float(sum(
                    e.get("size", 0) for e in doc["entries"].values())))
        _flight("compile_cache.activate", directory=str(self.directory),
                **verdict)
        return verdict

    def _chaos_corrupt_one(self):
        """``compile.cache_corrupt``: flip bytes in the first
        manifest-listed artifact still on disk — the verify walk that
        follows must quarantine it."""
        doc = self._read_manifest()
        names = sorted(doc["entries"]) if doc is not None else \
            [p.name for p in self._artifact_files()]
        for name in names:
            p = self.directory / Path(name).name
            if p.is_file() and p.stat().st_size > 0:
                with open(p, "r+b") as f:
                    first = f.read(1)
                    f.seek(0)
                    f.write(bytes([first[0] ^ 0xFF]))
                return

    def describe(self) -> dict:
        doc = self._read_manifest()
        return {
            "directory": str(self.directory),
            "active": self.active,
            "manifest_entries": (len(doc["entries"])
                                 if doc is not None else 0),
            "manifest_jax": doc.get("jax") if doc is not None else None,
            "artifacts_on_disk": len(self._artifact_files()),
            "quarantined_this_process": list(self.quarantined),
        }


# -- process-wide activation --------------------------------------------------

_active_cache: Optional[CompileCache] = None
_active_lock = threading.Lock()


def get_compile_cache() -> Optional[CompileCache]:
    """The process's activated cache, or None (cold compiles)."""
    return _active_cache


def set_compile_cache(cache: Optional[CompileCache]):
    """Install (tests) or clear the process-wide cache handle. Does not
    un-arm jax's cache dir — jax has no clean disarm; pass a fresh
    CompileCache and activate() to re-point it."""
    global _active_cache
    _active_cache = cache


def enable_compile_cache() -> CompileCache:
    """Activate the process-wide persistent compile cache on
    :func:`resolve_cache_dir`, once. Subsequent calls return the
    already-active cache (one directory per process — jax has one
    global cache config)."""
    global _active_cache
    with _active_lock:
        if _active_cache is None:
            cache = CompileCache()
            cache.activate()
            _active_cache = cache
    return _active_cache


def maybe_enable_compile_cache() -> Optional[CompileCache]:
    """The implicit arm ``Trainer.fit`` and ``ModelServer.start`` call,
    so any entry point into compiled work picks the cache up without
    plumbing: the already-active cache if there is one, a fresh
    activation if ``JAX_COMPILATION_CACHE_DIR`` is set, else None (cold
    compiles, nothing written)."""
    if _active_cache is not None:
        return _active_cache
    if not os.environ.get(ENV_CACHE_DIR):
        return None
    return enable_compile_cache()
