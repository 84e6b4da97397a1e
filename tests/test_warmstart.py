"""Cold-start robustness tests (runtime/compilecache.py +
serving/warmstart.py): the compile-cache integrity matrix (flipped
byte / truncation / version skew -> quarantine + fresh-compile
fallback), warmup-manifest recording/restriction/persistence, /readyz
warmup progress, the zero-compile fallback engage regression, the
supervisor env arming, and THE restart-under-load chaos acceptance
(router + SIGKILLed backend restarted with warm cache + manifest).

Strategy mirrors the checkpoint corruption matrix (test_resilience):
integrity units run against hand-written artifact files (no jax compile
in the loop); one real persistent-cache round trip proves the jax
wiring; the chaos acceptance uses real subprocess backends behind a
FleetRouter with the test_router spawn idiom.
"""

import json
import os
import pathlib
import signal
import socket
import subprocess
import sys
import textwrap
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.observability import flightrecorder as fr
from deeplearning4j_tpu.observability import metrics as om
from deeplearning4j_tpu.resilience.faults import (
    FaultInjector,
    set_fault_injector,
)
from deeplearning4j_tpu.runtime import compilecache as cc
from deeplearning4j_tpu.serving import (
    ModelRegistry,
    ModelServer,
    NotReadyError,
    ServingClient,
    WarmupManifest,
    spec,
)

# ---------------------------------------------------------------------------
# fixtures / helpers


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    om.reset_default_registry()
    fr.set_flight_recorder(None)
    om.set_enabled(True)
    fr.set_recording(True)
    cc.set_compile_cache(None)
    yield
    cc.set_compile_cache(None)
    set_fault_injector(None)
    om.reset_default_registry()
    fr.set_flight_recorder(None)


def _wm():
    return om.get_warmstart_metrics()


def _fake_cache(tmp_path, n=3):
    """A cache dir with hand-written artifacts + a sealed manifest —
    the integrity layer is format-agnostic, so the corruption matrix
    needs no real compiles."""
    d = tmp_path / "cache"
    d.mkdir()
    for i in range(n):
        (d / f"jit_fn-{i:02d}abc-cache").write_bytes(
            bytes(range(40 + i)) * 20)
    cache = cc.CompileCache(d)
    cache.seal()
    return cache


def _quarantine_reasons():
    fam = _wm().cache_quarantined_total
    return {labels: v for labels, v in fam._data.items()}


def _scale_forward(v, x):
    import jax.numpy as jnp

    return jnp.zeros((x.shape[0], 1), jnp.float32) + v["scale"]


def _server(tmp_path=None, *, manifest=False, cache=False,
            max_batch=8, forward=_scale_forward, **kw):
    reg = ModelRegistry()
    reg.register("scale", forward, {"scale": np.float32(1.0)},
                 input_spec=spec((4,)), version="v1", mode="batched",
                 max_batch_size=max_batch)
    srv = ModelServer(reg, port=0, sentinel=False, slo_interval_s=3600.0,
                      warmup_manifest=manifest, compile_cache=cache, **kw)
    return srv, reg


def _get_json(url, path):
    with urllib.request.urlopen(url + path) as r:
        return json.loads(r.read())


def _count_compiles():
    """Process-wide XLA backend compiles via the runtime collector's
    counter (jax.monitoring-fed) — the oracle the zero-compile engage
    regression reads."""
    from deeplearning4j_tpu.observability.runtime import (
        get_runtime_collector,
    )

    return get_runtime_collector().jit_compiles_total.value()


# ---------------------------------------------------------------------------
# compile-cache integrity matrix (mirrors the checkpoint corruption tests)


class TestCompileCacheIntegrity:
    def test_seal_then_verify_clean(self, tmp_path):
        cache = _fake_cache(tmp_path)
        doc = json.loads(cache.manifest_path.read_text())
        assert len(doc["entries"]) == 3
        assert all(e["sha256"] and e["size"] for e in
                   doc["entries"].values())
        v = cache.verify()
        assert v == {"checked": 3, "quarantined": 0, "unlisted": 0}
        assert cache.quarantined == []

    def test_flipped_byte_quarantined_with_metric(self, tmp_path):
        cache = _fake_cache(tmp_path)
        victim = sorted(cache.directory.glob("*-cache"))[0]
        raw = bytearray(victim.read_bytes())
        raw[7] ^= 0xFF
        victim.write_bytes(raw)  # same size: only the digest catches it
        v = cache.verify()
        assert v["quarantined"] == 1 and v["checked"] == 3
        assert not victim.exists()
        assert (cache.quarantine_dir / victim.name).exists()
        assert cache.quarantined == [
            {"artifact": victim.name, "reason": "corrupt"}]
        assert _quarantine_reasons() == {("corrupt",): 1.0}

    def test_truncated_quarantined(self, tmp_path):
        cache = _fake_cache(tmp_path)
        victim = sorted(cache.directory.glob("*-cache"))[1]
        victim.write_bytes(victim.read_bytes()[:10])
        cache.verify()
        assert cache.quarantined == [
            {"artifact": victim.name, "reason": "truncated"}]
        assert _quarantine_reasons() == {("truncated",): 1.0}

    def test_version_skew_quarantines_all(self, tmp_path):
        cache = _fake_cache(tmp_path)
        doc = json.loads(cache.manifest_path.read_text())
        doc["jax"] = "0.0.0-somebody-else"
        cache.manifest_path.write_text(json.dumps(doc))
        v = cache.verify()
        assert v["quarantined"] == 3
        assert {q["reason"] for q in cache.quarantined} == {"version_skew"}
        assert _quarantine_reasons() == {("version_skew",): 3.0}
        # re-seal adopts nothing (dir is empty of artifacts now)
        assert cache.seal()["entries"] == 0

    def test_torn_manifest_treated_as_absent(self, tmp_path):
        cache = _fake_cache(tmp_path)
        cache.manifest_path.write_text('{"entries": [truncated')
        v = cache.verify()  # no manifest = nothing to distrust
        assert v["quarantined"] == 0
        assert cache.seal()["entries"] == 3  # re-sealed from disk

    def test_unlisted_artifacts_pass_through_and_seal(self, tmp_path):
        cache = _fake_cache(tmp_path)
        (cache.directory / "jit_new-ff-cache").write_bytes(b"x" * 64)
        v = cache.verify()
        assert v["quarantined"] == 0 and v["unlisted"] == 1
        assert cache.seal()["entries"] == 4

    def test_activate_arms_jax_and_survives_chaos_corrupt(self, tmp_path):
        """``compile.cache_corrupt`` armed: activation flips bytes in a
        cached artifact, the walk quarantines it, and the process
        degrades to a fresh compile — never a crash, never a poisoned
        executable (acceptance criterion)."""
        import jax
        import jax.numpy as jnp

        cache = _fake_cache(tmp_path)
        inj = FaultInjector()
        inj.plan("compile.cache_corrupt", at=1)
        set_fault_injector(inj)
        verdict = cache.activate()
        assert verdict["quarantined"] == 1
        assert cache.quarantined[0]["reason"] == "corrupt"
        assert jax.config.jax_compilation_cache_dir == str(cache.directory)
        assert cache.active
        # fresh compile fallback: compiled work still runs fine
        out = jax.jit(lambda x: (x * 2).sum())(jnp.ones(8))
        assert float(out) == 16.0
        evs = fr.get_flight_recorder().events(
            kinds=["compile_cache.quarantined"])
        assert len(evs) == 1 and evs[0]["data"]["reason"] == "corrupt"

    def test_cache_stall_fault_delays_activation(self, tmp_path):
        inj = FaultInjector()
        inj.plan("compile.cache_stall", at=1, arg=0.3)
        set_fault_injector(inj)
        cache = cc.CompileCache(tmp_path / "c")
        t0 = time.monotonic()
        cache.activate()
        assert time.monotonic() - t0 >= 0.3

    def test_real_persistent_cache_roundtrip(self, tmp_path):
        """The jax wiring end to end: activate -> compile -> artifacts
        on disk -> seal records them -> a fresh verify passes clean."""
        import jax
        import jax.numpy as jnp

        cache = cc.CompileCache(tmp_path / "cc")
        cache.activate()
        jax.jit(lambda x: (x @ x).sum() * 3)(
            jnp.ones((32, 32))).block_until_ready()
        sealed = cache.seal()
        assert sealed["entries"] >= 1 and sealed["bytes"] > 0
        fresh = cc.CompileCache(tmp_path / "cc")
        assert fresh.verify()["quarantined"] == 0
        assert _wm().cache_entries.value() >= 1.0

    def test_maybe_enable_from_env_is_idempotent(self, tmp_path,
                                                 monkeypatch):
        monkeypatch.setenv(cc.ENV_CACHE_DIR, str(tmp_path / "envcc"))
        c1 = cc.maybe_enable_compile_cache()
        c2 = cc.maybe_enable_compile_cache()
        assert c1 is c2 and c1.active
        assert c1.directory == tmp_path / "envcc"
        assert _wm().cache_active.value() == 1.0
        monkeypatch.delenv(cc.ENV_CACHE_DIR)
        cc.set_compile_cache(None)
        # the implicit arm (Trainer.fit / ModelServer.start) stays off
        # without the variable: no write-everything cache by default
        assert cc.maybe_enable_compile_cache() is None


class TestCacheDirResolver:
    """One resolver decides where the cache lives
    (runtime/compilecache.resolve_cache_dir)."""

    def test_variable_wins_else_the_checkout(self, tmp_path, monkeypatch):
        import tempfile

        monkeypatch.setenv(cc.ENV_CACHE_DIR, str(tmp_path / "placed"))
        assert cc.resolve_cache_dir() == tmp_path / "placed"
        assert cc.CompileCache().directory == tmp_path / "placed"
        monkeypatch.delenv(cc.ENV_CACHE_DIR)
        checkout = pathlib.Path(cc.__file__).resolve().parents[2]
        assert cc.resolve_cache_dir() == checkout / ".jax_cache"
        assert cc.CompileCache().directory == checkout / ".jax_cache"
        # a fixed place: never a temporary name (the path is part of
        # jax's cache key, a directory that moves never hits)
        assert not cc.resolve_cache_dir().is_relative_to(
            tempfile.gettempdir())
        assert cc.resolve_cache_dir() == cc.resolve_cache_dir()

    def test_variable_set_and_our_code_never_writes_the_dir(
            self, tmp_path, monkeypatch):
        """With JAX_COMPILATION_CACHE_DIR set jax holds the directory
        itself; no path through our code points it anywhere."""
        import jax

        monkeypatch.setenv(cc.ENV_CACHE_DIR, str(tmp_path / "placed"))
        writes = []
        real_update = jax.config.update

        def spy(name, value):
            writes.append(name)
            return real_update(name, value)

        monkeypatch.setattr(jax.config, "update", spy)
        cache = cc.enable_compile_cache()
        assert cache.active and cache.directory == tmp_path / "placed"
        cache.seal()
        assert (tmp_path / "placed" / "cache_manifest.json").is_file()
        assert "jax_compilation_cache_dir" not in writes
        # the integrity layer works in place; another directory is refused
        with pytest.raises(ValueError, match="JAX_COMPILATION_CACHE_DIR"):
            cc.CompileCache(tmp_path / "elsewhere").activate()
        assert "jax_compilation_cache_dir" not in writes

    def test_supervisor_keeps_a_directory_the_environment_names(
            self, tmp_path):
        from deeplearning4j_tpu.resilience.supervisor import (
            ElasticSupervisor,
        )

        dump = "import os; print(os.environ['JAX_COMPILATION_CACHE_DIR'])"
        env = dict(os.environ,
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path / "placed"))
        sup = ElasticSupervisor(
            [sys.executable, "-c", dump], num_workers=1, workdir=tmp_path,
            max_restarts=0, env=env, compile_cache_dir=tmp_path / "cc")
        sup.run()
        assert sup.worker_log(0).read_text().strip() == str(
            tmp_path / "placed")


# ---------------------------------------------------------------------------
# warmup manifest


class TestWarmupManifest:
    def test_note_save_load_roundtrip(self, tmp_path):
        p = tmp_path / "wm.json"
        m = WarmupManifest(p, autosave_every=10_000)
        m.note_batch("lenet", 8)
        m.note_batch("lenet", 8)
        m.note_prefill("gpt", 16)
        m.note_decode("gpt", 2, 64)
        assert m.save()
        assert not list(tmp_path.glob("*.tmp"))  # atomic, no litter
        m2 = WarmupManifest(p)
        assert m2.predict_buckets("lenet") == [8]
        assert m2.prefill_buckets("gpt") == [16]
        assert m2.decode_pairs("gpt") == [(2, 64)]
        assert m2.predict_buckets("nope") is None
        row = [e for e in m2.entries()
               if e["plane"] == "predict"][0]
        assert row["count"] == 2
        assert _wm().manifest_writes_total.value() >= 1.0

    def test_bounded_lru_eviction(self, tmp_path):
        m = WarmupManifest(max_entries=3)
        for i, b in enumerate([1, 2, 4, 8]):
            m.note_batch("m", b)
            time.sleep(0.002)  # distinct last_seen stamps
        assert len(m) == 3
        assert m.predict_buckets("m") == [2, 4, 8]  # bucket 1 was oldest

    def test_torn_file_loads_as_empty(self, tmp_path):
        p = tmp_path / "wm.json"
        p.write_text('{"entries": [{"plane": "predi')
        m = WarmupManifest(p)
        assert len(m) == 0

    def test_autosave_on_new_shape(self, tmp_path):
        p = tmp_path / "wm.json"
        m = WarmupManifest(p)
        m.note_batch("m", 4)  # a NEW shape saves immediately
        assert p.is_file()
        assert json.loads(p.read_text())["entries"][0]["shape"] == [4]


# ---------------------------------------------------------------------------
# server integration: progress-reporting readiness + manifest warmup


def _slow_forward(v, x):
    import jax.numpy as jnp

    time.sleep(0.12)  # trace-time cost: every bucket compile pays it
    return jnp.zeros((x.shape[0], 1), jnp.float32) + v["scale"]


class TestReadyzWarmupProgress:
    def test_readyz_503_carries_progress_then_flips(self):
        srv, reg = _server(forward=_slow_forward)
        try:
            srv.start(warm=True, warm_async=True)
            saw_warming = None
            saw_shed = None
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                try:
                    body = _get_json(srv.url, "/readyz")
                    break  # 200: warm
                except urllib.error.HTTPError as e:
                    b = json.loads(e.read())
                    if b.get("total"):
                        saw_warming = (b, e.headers.get("Retry-After"))
                        if saw_shed is None:
                            # a predict DURING warmup must shed
                            # retryably, never sneak a compile in
                            c = ServingClient(srv.url)
                            try:
                                c.predict("scale",
                                          np.zeros((1, 4), np.float32))
                                saw_shed = False
                            except NotReadyError as err:
                                saw_shed = err
                time.sleep(0.01)
            assert body["ready"] is True
            assert "warmed" not in body  # progress keys gone once ready
            assert saw_warming is not None, "never saw warming progress"
            prog, retry_after = saw_warming
            assert 0 <= prog["warmed"] < prog["total"] == 4
            assert prog["retry_after_ms"] >= 50.0
            assert retry_after is not None and int(retry_after) >= 1
            assert isinstance(saw_shed, NotReadyError), (
                "predict during warmup did not shed retryably")
            assert saw_shed.retryable
            # after warm: traffic flows
            out = ServingClient(srv.url).predict(
                "scale", np.zeros((2, 4), np.float32))
            assert out["version"] == "v1"
        finally:
            srv.stop()

    def test_manifest_restricts_warmup_and_detects_recompile(self):
        manifest = WarmupManifest()
        manifest.note_batch("scale", 2)
        srv, reg = _server(manifest=manifest)
        try:
            srv.start(warm=True)
            entry = reg.get("scale")
            assert entry.warmed_buckets == {2}
            fams = dict(_wm().warmup_shapes_total._data)
            assert fams[("predict", "manifest")] == 1.0
            # traffic inside the manifest: no recompile counted
            c = ServingClient(srv.url)
            c.predict("scale", np.zeros((2, 4), np.float32))
            assert _wm().recompiles_after_warm_total._data == {}
            # traffic OUTSIDE the warmed set: the recompile is counted
            # once and the flight ring names the bucket
            c.predict("scale", np.zeros((3, 4), np.float32))  # bucket 4
            assert _wm().recompiles_after_warm_total._data == {
                ("predict",): 1.0}
            c.predict("scale", np.zeros((3, 4), np.float32))
            assert _wm().recompiles_after_warm_total._data == {
                ("predict",): 1.0}  # counted once
            evs = fr.get_flight_recorder().events(
                kinds=["serving.recompile_after_warm"])
            assert [e["data"]["bucket"] for e in evs] == [4]
        finally:
            srv.stop()

    def test_live_traffic_recorded_and_persisted_on_stop(self, tmp_path):
        p = tmp_path / "wm.json"
        srv, reg = _server(manifest=str(p))
        with srv:
            c = ServingClient(srv.url)
            c.predict("scale", np.zeros((3, 4), np.float32))  # bucket 4
        doc = json.loads(p.read_text())
        rows = [(e["plane"], e["shape"]) for e in doc["entries"]]
        assert ("predict", [4]) in rows
        # a restart warms exactly the recorded mix
        srv2, reg2 = _server(manifest=str(p))
        with srv2:
            assert reg2.get("scale").warmed_buckets == {4}


# ---------------------------------------------------------------------------
# zero-compile fallback engage (the brownout satellite regression)


class TestFallbackPrewarm:
    def test_engage_fallback_causes_zero_compiles(self):
        srv, reg = _server(max_batch=4)
        try:
            srv.start(warm=True)
            entry = reg.get("scale")
            entry.set_fallback({"scale": np.float32(9.0)}, "v1-cheap")
            assert entry._fallback_pi is not None  # prewarmed + parked
            c = ServingClient(srv.url)
            before = _count_compiles()
            version = reg.engage_fallback("scale")
            out = c.predict("scale", np.zeros((2, 4), np.float32))
            assert version == "v1-cheap"
            assert out["version"] == "v1-cheap"
            assert out["outputs"][0][0] == 9.0
            assert _count_compiles() == before, (
                "engage_fallback compiled under overload — the exact "
                "storm prewarm exists to kill")
            assert entry.fallback_engaged
        finally:
            srv.stop()

    def test_disengage_reprewarms_for_the_next_cycle(self):
        srv, reg = _server(max_batch=2)
        try:
            srv.start(warm=True)
            entry = reg.get("scale")
            entry.set_fallback({"scale": np.float32(9.0)}, "v1-cheap")
            reg.engage_fallback("scale")
            assert entry._fallback_pi is None  # consumed by the engage
            restored = reg.disengage_fallback("scale")
            assert restored == "v1"
            deadline = time.monotonic() + 30
            while entry._fallback_pi is None \
                    and time.monotonic() < deadline:
                time.sleep(0.02)
            assert entry._fallback_pi is not None, (
                "background re-prewarm never completed")
            before = _count_compiles()
            assert reg.engage_fallback("scale") == "v1-cheap"
            assert _count_compiles() == before
        finally:
            srv.stop()

    def test_prewarm_false_keeps_lazy_engage(self):
        srv, reg = _server(max_batch=2)
        try:
            srv.start(warm=True)
            entry = reg.get("scale")
            entry.set_fallback({"scale": np.float32(9.0)}, "v1-cheap",
                               prewarm=False)
            assert entry._fallback_pi is None
            assert reg.engage_fallback("scale") == "v1-cheap"  # old path
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# generation engine: manifest-restricted warm + after-warm accounting


class TestGenerationManifestWarm:
    @pytest.fixture(scope="class")
    def gpt_model(self):
        from deeplearning4j_tpu.models.gpt import gpt_tiny

        model = gpt_tiny()
        return model, model.init(seed=0)

    def _engine(self, gpt_model):
        from deeplearning4j_tpu.serving import GenerationEngine

        model, variables = gpt_model
        return GenerationEngine(
            model, variables, name="gpt", num_slots=2, max_len=32,
            max_new_tokens=4, min_kv_bucket=16, min_prompt_bucket=8,
            idle_wait_s=0.005, temperature=0.0, seed=0)

    def test_manifest_plan_restricts_and_falls_back(self, gpt_model):
        eng = self._engine(gpt_model)
        full_pairs = [(b, kv) for b in eng.slot_buckets
                      for kv in eng.kv_buckets]
        # no manifest: full vocabulary
        p_list, pairs = eng.manifest_warm_plan(None)
        assert p_list == list(eng.prompt_buckets)
        assert pairs == full_pairs
        # observed subset: exactly that subset
        m = WarmupManifest()
        m.note_prefill("gpt", eng.prompt_buckets[0])
        m.note_decode("gpt", eng.slot_buckets[0], eng.kv_buckets[0])
        p_list, pairs = eng.manifest_warm_plan(m)
        assert p_list == [eng.prompt_buckets[0]]
        assert pairs == [(eng.slot_buckets[0], eng.kv_buckets[0])]
        # stale shapes outside the vocabulary: full fallback, never a
        # zero-shape warmup
        m2 = WarmupManifest()
        m2.note_prefill("gpt", 999)
        m2.note_decode("gpt", 999, 999)
        p_list, pairs = eng.manifest_warm_plan(m2)
        assert p_list == list(eng.prompt_buckets) and pairs == full_pairs

    def test_restricted_warm_counts_after_warm_compiles(self, gpt_model):
        eng = self._engine(gpt_model)
        m = WarmupManifest()
        m.note_prefill("gpt", eng.prompt_buckets[0])  # smallest bucket
        for kv in eng.kv_buckets:
            m.note_decode("gpt", eng.slot_buckets[0], kv)
        eng.attach_manifest(m)
        p_list, pairs = eng.manifest_warm_plan()
        eng.warm(prompt_buckets=p_list, decode_pairs=pairs,
                 source="manifest")
        assert eng.warmed
        assert eng.compiles_total == len(p_list) + len(pairs)
        assert eng.compiles_after_warm == 0
        try:
            eng.start()
            # a prompt in the warmed bucket: zero after-warm compiles
            h = eng.submit([1, 2, 3], max_new_tokens=2)
            h.result(timeout=30)
            assert eng.compiles_after_warm == 0
            # a LONG prompt outside the manifest: the prefill compile is
            # counted as after-warm and feeds the warmstart counter
            long_prompt = list(range(eng.prompt_buckets[0] + 1))
            h = eng.submit(long_prompt, max_new_tokens=2)
            h.result(timeout=30)
            assert eng.compiles_after_warm >= 1
            assert _wm().recompiles_after_warm_total.value(
                plane="generation") >= 1.0
            # and the live mix recorded what actually ran
            assert len(m.prefill_buckets("gpt")) == 2
        finally:
            eng.stop()


# ---------------------------------------------------------------------------
# supervisor arming


class TestSupervisorArming:
    def test_generation_env_carries_cache_and_manifest(self, tmp_path):
        from deeplearning4j_tpu.resilience.supervisor import (
            ElasticSupervisor,
        )

        dump = ("import os, json; print(json.dumps({k: v for k, v in "
                "os.environ.items() if 'COMPILATION_CACHE' in k or "
                "'WARMUP_MANIFEST' in k}))")
        sup = ElasticSupervisor(
            [sys.executable, "-c", dump], num_workers=1,
            workdir=tmp_path, max_restarts=0,
            compile_cache_dir=tmp_path / "cc",
            warmup_manifest=tmp_path / "wm.json")
        sup.run()
        env = json.loads(sup.worker_log(0).read_text().strip())
        assert env["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path / "cc")
        assert env["DL4J_TPU_WARMUP_MANIFEST"] == str(
            tmp_path / "wm.json")
        assert (tmp_path / "cc").is_dir()  # pre-created for the worker

    def test_unarmed_supervisor_leaves_env_alone(self, tmp_path):
        from deeplearning4j_tpu.resilience.supervisor import (
            ElasticSupervisor,
        )

        dump = ("import os, json; print(json.dumps([k for k in "
                "os.environ if 'COMPILATION_CACHE' in k or "
                "'WARMUP_MANIFEST' in k]))")
        env = {k: v for k, v in os.environ.items()
               if "COMPILATION_CACHE" not in k
               and "WARMUP_MANIFEST" not in k}
        sup = ElasticSupervisor([sys.executable, "-c", dump],
                                num_workers=1, workdir=tmp_path,
                                max_restarts=0, env=env)
        sup.run()
        assert json.loads(sup.worker_log(0).read_text().strip()) == []


# ---------------------------------------------------------------------------
# THE chaos acceptance: restart-under-load takes traffic warm


_BACKEND_SCRIPT = textwrap.dedent("""
    import sys, threading, time
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    from deeplearning4j_tpu.serving import (ModelRegistry, ModelServer,
                                            spec)
    port = int(sys.argv[1])

    def fwd(v, x):
        time.sleep(0.15)   # trace-time cost: makes warmup observable
        return jnp.zeros((x.shape[0], 1), jnp.float32) + v["scale"]

    reg = ModelRegistry()
    reg.register("scale", fwd, {"scale": float(sys.argv[2])},
                 input_spec=spec((4,)), version=sys.argv[3],
                 mode="batched", max_batch_size=8)
    srv = ModelServer(reg, port=port, sentinel=False,
                      slo_interval_s=3600.0)
    t0 = time.monotonic()
    srv.start(warm=True, warm_async=True)
    print("READY", srv.port, flush=True)   # port bound; still warming
    while not srv.readiness()["ready"]:
        time.sleep(0.01)
    print("WARMED", round(time.monotonic() - t0, 3), flush=True)
    while True:
        time.sleep(3600)
""")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_backend(port, scale, version, *, cache_dir, manifest,
                   faults=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir),
               DL4J_TPU_WARMUP_MANIFEST=str(manifest))
    if faults:
        env["DL4J_TPU_FAULTS"] = faults
    return subprocess.Popen(
        [sys.executable, "-c", _BACKEND_SCRIPT, str(port), str(scale),
         version],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)


def _await_line(proc, prefix, timeout_s=90.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if line.startswith(prefix):
            return line.split()
        if proc.poll() is not None:
            return None
    return None


def _wait(cond, timeout_s=10.0, interval_s=0.02):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval_s)
    return False


def _backend_metric(port, family):
    """Sum one counter family off a backend's classic /metrics scrape."""
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics") as r:
        text = r.read().decode()
    total = 0.0
    seen = False
    for line in text.splitlines():
        if line.startswith(family) and not line.startswith("#"):
            seen = True
            total += float(line.rsplit(" ", 1)[1])
    return total if seen else 0.0


class TestWarmRestartChaos:
    # Tier-1 budget relief (the PR 6/7 pattern, paying for the PR 20
    # autoscaler suite): subprocess chaos rides tier-2; the corrupt-
    # cache restart leg below keeps the degrade-clean path fast, and
    # the warm-count discipline runs every tier-1 via
    # TestGenerationManifestWarm.
    @pytest.mark.slow
    def test_sigkill_restart_with_warm_cache_takes_traffic_warm(
            self, tmp_path):
        """THE acceptance: 2 backends under router load, one SIGKILLed,
        restarted against the persistent cache + the manifest its own
        traffic wrote -> zero client-visible failures, /readyz flips
        only after manifest warmup, zero recompiles after the first
        post-restart request, re-admission measured."""
        from deeplearning4j_tpu.serving import FleetRouter, RouterPolicy

        cache_dir = tmp_path / "cc"
        cache_dir.mkdir()
        manifests = {i: tmp_path / f"wm{i}.json" for i in (0, 1)}
        ports = [_free_port() for _ in range(2)]
        procs = [_spawn_backend(ports[i], float(i + 1), "v1",
                                cache_dir=cache_dir,
                                manifest=manifests[i])
                 for i in (0, 1)]
        router = None
        try:
            warm_cold = {}
            for i, p in enumerate(procs):
                assert _await_line(p, "READY"), "backend failed to start"
                warmed = _await_line(p, "WARMED")
                assert warmed, "backend never flipped ready"
                warm_cold[i] = float(warmed[1])
            router = FleetRouter(
                [(f"b{i}", f"http://127.0.0.1:{ports[i]}")
                 for i in (0, 1)],
                policy=RouterPolicy(probe_interval_s=0.25,
                                    probe_timeout_s=0.5,
                                    reprobe_after_s=0.5)).start()
            assert _wait(lambda: router.backend("b1").routable,
                         timeout_s=10.0)

            served, failures = [], []
            lock = threading.Lock()
            stop_load = threading.Event()

            def load(tid):
                c = ServingClient(router.url, max_retries=3,
                                  backoff_base_s=0.05, retry_seed=tid)
                x = np.zeros((1, 4), np.float32)
                while not stop_load.is_set():
                    try:
                        out = c.predict("scale", x, deadline_ms=30000)
                        with lock:
                            served.append(out["outputs"][0][0])
                    except Exception as e:  # noqa: BLE001 — chaos
                        with lock:          # collects everything
                            failures.append(e)
                    time.sleep(0.02)

            threads = [threading.Thread(target=load, args=(i,))
                       for i in range(3)]
            for t in threads:
                t.start()
            # traffic flows (and writes both manifests + the cache)
            assert _wait(lambda: len(served) >= 20, timeout_s=20.0)

            victim = procs[1]
            victim.send_signal(signal.SIGKILL)
            t_kill = time.monotonic()
            victim.wait(timeout=10)
            assert _wait(lambda: not router.backend("b1").routable,
                         timeout_s=4.0, interval_s=0.01)

            # restart on the same port with the WARM assets
            procs[1] = _spawn_backend(ports[1], 2.0, "v2",
                                      cache_dir=cache_dir,
                                      manifest=manifests[1])
            assert _await_line(procs[1], "READY")
            # /readyz gates on warmup: while the child warms, direct
            # probes answer 503 with progress — the router must show
            # the backend as warming, not re-admit it early
            saw_warming = False
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                try:
                    _get_json(f"http://127.0.0.1:{ports[1]}", "/readyz")
                    break  # 200: warm
                except urllib.error.HTTPError as e:
                    b = json.loads(e.read())
                    if b.get("total"):
                        saw_warming = True
                        assert not router.backend("b1").routable, (
                            "router re-admitted a still-warming backend")
                except Exception:  # noqa: BLE001 — socket not up yet
                    pass
                time.sleep(0.01)
            assert saw_warming, "restart never reported warmup progress"
            warmed = _await_line(procs[1], "WARMED")
            assert warmed

            # re-admission to first post-restart success via the router
            assert _wait(lambda: router.backend("b1").routable,
                         timeout_s=15.0)
            c = ServingClient(router.url, max_retries=2)
            x = np.zeros((1, 4), np.float32)
            assert _wait(lambda: c.predict("scale", x)["outputs"][0][0]
                         == 2.0, timeout_s=10.0)
            mttr_s = time.monotonic() - t_kill
            stop_load.set()
            for t in threads:
                t.join(timeout=30)

            # zero client-visible failures across kill + restart
            assert failures == [], [repr(f) for f in failures[:3]]
            # zero recompiles after the restarted backend declared warm
            # (its manifest covered the live mix; machine-checked off
            # its own scrape)
            assert _backend_metric(
                ports[1], "warmup_recompiles_after_warm_total") == 0.0
            # the restarted process rode the sealed cache: its scrape
            # says the cache is active with entries
            assert _backend_metric(ports[1], "compile_cache_active") == 1.0
            # evidence trail for the bench gate (not asserted here: the
            # timing gate lives in bench.py warmstart where the host is
            # quiet): cold vs warm warmup seconds + MTTR
            print(f"warmstart-chaos: cold={warm_cold[1]:.2f}s "
                  f"warm={float(warmed[1]):.2f}s mttr={mttr_s:.2f}s")
        finally:
            stop_load_ev = locals().get("stop_load")
            if stop_load_ev is not None:
                stop_load_ev.set()
            if router is not None:
                router.stop()
            for p in procs:
                if p.poll() is None:
                    p.kill()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    pass

    def test_restart_with_corrupt_cache_degrades_clean(self, tmp_path):
        """compile.cache_corrupt armed on a restart: the backend still
        comes up warm (fresh compiles), quarantine is visible on its
        scrape, and traffic is served — never a crash."""
        cache_dir = tmp_path / "cc"
        cache_dir.mkdir()
        manifest = tmp_path / "wm.json"
        port = _free_port()
        p1 = _spawn_backend(port, 1.0, "v1", cache_dir=cache_dir,
                            manifest=manifest)
        try:
            assert _await_line(p1, "READY") and _await_line(p1, "WARMED")
            # one request so the manifest records a bucket
            c = ServingClient(f"http://127.0.0.1:{port}")
            c.predict("scale", np.zeros((1, 4), np.float32))
            p1.send_signal(signal.SIGKILL)
            p1.wait(timeout=10)
            p2 = _spawn_backend(port, 1.0, "v2", cache_dir=cache_dir,
                                manifest=manifest,
                                faults="compile.cache_corrupt@1")
        finally:
            if p1.poll() is None:
                p1.kill()
        try:
            assert _await_line(p2, "READY") and _await_line(p2, "WARMED")
            assert _backend_metric(
                port, "compile_cache_quarantined_total") >= 1.0
            out = ServingClient(f"http://127.0.0.1:{port}").predict(
                "scale", np.zeros((1, 4), np.float32))
            assert out["version"] == "v2"
        finally:
            if p2.poll() is None:
                p2.kill()
            try:
                p2.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
