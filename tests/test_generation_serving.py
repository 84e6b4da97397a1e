"""Generative serving engine tests (serving/generation.py + the Gpt
decode-step APIs): math parity against the whole-loop generator,
continuous batching over real HTTP (staggered join/leave proven via
flight events, zero recompiles after warmup across mixed prefix
lengths), priority preemption with client retry, the token brownout
rung, and the TTFT sentinel detector.

Strategy (the PR 6/7 budget pattern): scheduler decisions are exercised
white-box with manual ``_admit()`` calls (deterministic, no races); one
engine is compiled ONCE per module and shared; the sustained load /
overload-storm variants are ``@pytest.mark.slow`` behind these fast
proxies.
"""

import json
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.gpt import gpt_tiny
from deeplearning4j_tpu.nn.generation import sample_token
from deeplearning4j_tpu.observability import sentinel as sn
from deeplearning4j_tpu.observability import slo
from deeplearning4j_tpu.observability.flightrecorder import (
    get_flight_recorder,
)
from deeplearning4j_tpu.observability.runtime import get_runtime_collector
from deeplearning4j_tpu.serving import (
    BadRequestError,
    GenerationEngine,
    ModelServer,
    NotReadyError,
    OverloadPolicy,
    QueueFullError,
    ServingClient,
    SlotPreemptedError,
    TenantQuotaError,
)
from deeplearning4j_tpu.serving.metrics import ServingMetrics

# ---------------------------------------------------------------------------
# shared model + engine (compiled once per module; warm is the expensive part)


@pytest.fixture(scope="module")
def gpt_model():
    model = gpt_tiny()
    return model, model.init(seed=0)


@pytest.fixture(scope="module")
def engine(gpt_model):
    model, variables = gpt_model
    eng = GenerationEngine(
        model, variables, name="gpt", num_slots=3, max_len=48,
        max_new_tokens=40, min_kv_bucket=8, min_prompt_bucket=8,
        idle_wait_s=0.005, temperature=0.0, max_waiting=16, seed=0)
    eng.warm()
    return eng


def _events(kind, model="gpt"):
    return [e["data"] for e in get_flight_recorder().events(kinds=[kind])
            if e["data"].get("model") == model]


# ---------------------------------------------------------------------------
# model-level parity (the decode-capable Gpt step API)


class TestGptStepAPI:
    def test_slot_decode_matches_scalar_decode(self, gpt_model):
        model, variables = gpt_model
        params = variables["params"]
        caches = model.init_cache(2, 16)
        ids = jnp.asarray([3, 7], jnp.int32)
        for pos in range(3):
            lg_scalar, caches_scalar = model.decode_step(
                params, caches, ids, pos)
            lg_slots, caches = model.decode_step_slots(
                params, caches, ids, jnp.full(2, pos, jnp.int32))
            np.testing.assert_allclose(np.asarray(lg_slots),
                                       np.asarray(lg_scalar),
                                       atol=2e-5, rtol=1e-4)
            ids = jnp.argmax(lg_slots, axis=-1).astype(jnp.int32)
            for a, b in zip(caches, caches_scalar):
                np.testing.assert_allclose(np.asarray(a["k"]),
                                           np.asarray(b["k"]), atol=2e-5)

    def test_prefill_chunk_matches_decode_scan(self, gpt_model):
        model, variables = gpt_model
        params = variables["params"]
        prompt = jnp.asarray([[5, 9, 2, 11, 60]], jnp.int32)
        lg_seq, kvs = model.prefill_chunk(params, prompt)
        caches = model.init_cache(1, 5)
        scans = []
        for t in range(5):
            lg, caches = model.decode_step(params, caches, prompt[:, t], t)
            scans.append(lg)
        np.testing.assert_allclose(np.asarray(lg_seq),
                                   np.asarray(jnp.stack(scans, axis=1)),
                                   atol=2e-5, rtol=1e-4)
        for kv, cache in zip(kvs, caches):
            np.testing.assert_allclose(np.asarray(kv["k"]),
                                       np.asarray(cache["k"]), atol=2e-5)
            np.testing.assert_allclose(np.asarray(kv["v"]),
                                       np.asarray(cache["v"]), atol=2e-5)

    def test_sample_token_greedy_rows_and_sampled_rows(self):
        logits = jnp.asarray([[0.0, 5.0, 0.0], [9.0, 0.0, 0.0]])
        toks = sample_token(logits, jax.random.key(0),
                            jnp.asarray([0.0, 0.7]))
        assert int(toks[0]) == 1  # greedy row takes the argmax
        assert 0 <= int(toks[1]) < 3


# ---------------------------------------------------------------------------
# engine semantics (white-box: manual _admit, no scheduler races)


class TestEngineScheduling:
    def test_greedy_engine_matches_whole_loop_generate(self, gpt_model,
                                                       engine):
        model, variables = gpt_model
        engine.start()
        prime = np.asarray([5, 9, 2, 11], np.int32)
        res = engine.submit(prime, max_new_tokens=6,
                            temperature=0.0).result(timeout=30)
        ref = model.generate(variables, prime[None, :], n_steps=6,
                             rng=jax.random.key(0), temperature=0.0)
        assert res["tokens"] == np.asarray(ref)[0].tolist()
        assert res["finish_reason"] == "length"
        assert engine.compiles_after_warm == 0

    def test_eos_finishes_stream(self, engine):
        engine.start()
        # whatever greedy emits first from this prompt (its value depends
        # on the jax version's numerics): declaring it eos ends the
        # stream at once
        first = engine.submit([5, 9, 2, 11], max_new_tokens=1,
                              temperature=0.0).result(timeout=30)["tokens"][0]
        res = engine.submit([5, 9, 2, 11], max_new_tokens=6,
                            temperature=0.0, eos_id=first).result(timeout=30)
        assert res["finish_reason"] == "eos"
        assert res["tokens"] == [first]

    def test_submit_validation(self, engine):
        with pytest.raises(BadRequestError):
            engine.submit([])
        with pytest.raises(BadRequestError):
            engine.submit([1], priority="vip")
        with pytest.raises(BadRequestError):
            engine.submit([1], max_new_tokens=0)
        with pytest.raises(BadRequestError):
            engine.submit([1], temperature=-1.0)
        with pytest.raises(BadRequestError):
            engine.submit([10 ** 6])  # out-of-vocab id
        with pytest.raises(BadRequestError):
            engine.submit(np.zeros(4096, np.int32))  # over max_prompt
        with pytest.raises(BadRequestError):
            engine.submit([46.7])  # fractional id: rejected, not truncated
        engine.submit([46.0]).cancel()  # whole-number float is fine
        # the slabs belong to the live scheduler: no warm() mid-flight
        engine.start()
        with pytest.raises(RuntimeError):
            engine.warm()

    def test_critical_preempts_lowest_class_slot(self, engine):
        engine.stop()  # drive the scheduler by hand
        engine._stopflag = False
        engine._draining = False
        victims = [engine.submit([1, 2], priority="batch")
                   for _ in range(engine.num_slots)]
        engine._admit()
        assert all(v.state == "active" for v in victims)
        crit = engine.submit([3], priority="critical", max_new_tokens=2)
        engine._admit()
        assert crit.state == "active"
        preempted = [v for v in victims if v.finish_reason == "preempted"]
        assert len(preempted) == 1
        # newest batch join is the victim (least sunk decode work)
        assert preempted[0] is victims[-1]
        with pytest.raises(SlotPreemptedError) as ei:
            list(preempted[0].tokens(timeout=1))
        assert ei.value.retryable and ei.value.retry_after_ms is not None
        evs = _events("generation.preempt")
        assert evs and evs[-1]["victim_priority"] == "batch"
        # finish the survivors on the real scheduler
        engine.start()
        assert crit.result(timeout=30)["finish_reason"] == "length"
        for v in victims[:-1]:
            v.result(timeout=30)

    def test_queue_full_and_tenant_shed_paths(self, gpt_model):
        model, variables = gpt_model
        eng = GenerationEngine(model, variables, name="g2", num_slots=1,
                               max_len=16, max_waiting=1)

        class _Ov:  # the hot-path surface the engine consults
            shed_batch = False

            @staticmethod
            def tenant_take(tenant):
                return (tenant != "hog"), 0.25

            @staticmethod
            def note_shed():
                _Ov.sheds = getattr(_Ov, "sheds", 0) + 1

        eng.attach_overload(_Ov)
        # tenant quota checked while capacity remains (it is checked
        # LAST, so a request the queue would shed never burns a token)
        with pytest.raises(TenantQuotaError) as ei:
            eng.submit([1], tenant="hog")
        assert ei.value.retry_after_ms == 250.0
        eng.submit([1])  # fills the waiting queue (scheduler not running)
        with pytest.raises(QueueFullError):
            eng.submit([1])
        assert getattr(_Ov, "sheds", 0) == 1
        # with the queue full, even a quota-less tenant sheds on
        # capacity BEFORE the quota is consulted (no token burned)
        with pytest.raises(QueueFullError):
            eng.submit([1], tenant="hog")
        assert getattr(_Ov, "sheds", 0) == 2
        _Ov.shed_batch = True
        with pytest.raises(QueueFullError):
            eng.submit([1], priority="batch")
        eng.stop()
        with pytest.raises(NotReadyError):
            eng.submit([1])

    def test_token_brownout_trims_in_flight_streams(self, engine):
        engine.start()
        try:
            engine.engage_token_brownout()
            res = engine.submit([5, 9], max_new_tokens=40,
                                temperature=0.0).result(timeout=30)
            assert res["finish_reason"] == "length"
            assert len(res["tokens"]) == engine.brownout_max_new_tokens
        finally:
            engine.disengage_token_brownout()
        assert engine.token_cap == engine.default_max_new_tokens


# ---------------------------------------------------------------------------
# the e2e acceptance: staggered streaming requests share one decode batch
# over real HTTP, with jax.monitoring-counted compiles after warmup == 0


class TestHTTPStreaming:
    def test_staggered_streams_share_one_decode_batch(self, engine):
        server = ModelServer(port=0, sentinel=False,
                             generators={"gpt": engine})
        server.start(warm=True)
        try:
            collector = get_runtime_collector()
            compiles_before = collector.jit_compiles_total.value()
            steps_before = engine.steps
            # mixed prefix lengths across different prompt buckets
            # (longest + 20 new tokens still fits max_len=48). The
            # first stream decodes 40 tokens so the staggered joiners
            # land inside its decode window even on a fast host — a
            # fixed stagger against a uniform 20-token decode let a
            # quick machine finish each stream before the next client
            # arrived, serializing the batch and failing the overlap
            # assertion below.
            prompts = [[5, 9, 2], [1] * 9, [2] * 17, [3] * 27]
            want = {0: 40, 1: 20, 2: 20, 3: 20}
            results = {}
            lock = threading.Lock()

            def run(i):
                time.sleep(0.005 * i)  # staggered arrivals
                client = ServingClient(server.url)
                toks = list(client.generate(
                    "gpt", prompts[i], max_new_tokens=want[i],
                    temperature=0.7))
                with lock:
                    results[i] = toks

            threads = [threading.Thread(target=run, args=(i,))
                       for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
                assert not t.is_alive(), "streaming client hung"
            assert sorted(results) == [0, 1, 2, 3]
            assert all(len(v) == want[k] for k, v in results.items()), {
                k: len(v) for k, v in results.items()}
            # join/leave mid-decode: some request joined the batch at a
            # later decode step than another's join and before its leave
            joins = {e["req"]: e["step"]
                     for e in _events("generation.join")
                     if e["step"] >= steps_before}
            leaves = {e["req"]: e["step"]
                      for e in _events("generation.leave")
                      if e["step"] >= steps_before}
            assert len(joins) >= 4
            shared = [(a, b) for a in joins for b in joins
                      if a != b and joins[a] < joins[b] < leaves[a]]
            assert shared, (joins, leaves)
            # zero compiles after warmup across mixed prefix lengths
            assert collector.jit_compiles_total.value() \
                == compiles_before
            assert engine.compiles_after_warm == 0
            # occupancy > 1 slot proves actual batch sharing on-device
            occ = server.metrics.generation_slot_occupancy.summary(
                model="gpt")
            assert occ["count"] > 0
            ttft = server.metrics.generation_ttft.summary(model="gpt")
            assert ttft["count"] >= 4
        finally:
            server.stop()

    def test_chaos_critical_preempts_batch_and_client_retries(self, engine):
        policy = OverloadPolicy(min_in_flight=2, max_in_flight=8,
                                interval_s=60.0)
        server = ModelServer(port=0, sentinel=False, overload=policy,
                             generators={"gpt": engine})
        assert [r.name for r in server.overload.ladder.rungs] == [
            "shrink_batch_wait", "shed_batch_class",
            "shrink_generation_tokens", "serve_fallback"]
        server.start(warm=True)
        try:
            pre_before = server.metrics.generation_preemptions_total.value(
                model="gpt", priority="batch")
            results = {}
            lock = threading.Lock()

            def batch_run(i):
                client = ServingClient(server.url, max_retries=6,
                                       retry_seed=i)
                r = client.generate_tokens(
                    "gpt", [1 + i, 2], max_new_tokens=40, temperature=0.0,
                    priority="batch")
                with lock:
                    results[i] = r

            threads = [threading.Thread(target=batch_run, args=(i,))
                       for i in range(engine.num_slots)]
            for t in threads:
                t.start()
            # wait until every decode slot is held by a batch stream
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                if engine.describe()["active"] == engine.num_slots:
                    break
                time.sleep(0.002)
            assert engine.describe()["active"] == engine.num_slots
            client = ServingClient(server.url)
            r = client.generate_tokens("gpt", [7], max_new_tokens=3,
                                       temperature=0.0,
                                       priority="critical")
            assert r["n_tokens"] == 3
            for t in threads:
                t.join(timeout=60)
                assert not t.is_alive(), "batch client hung"
            # a batch slot WAS preempted, and the preempted client's
            # retry still completed its full request
            assert server.metrics.generation_preemptions_total.value(
                model="gpt", priority="batch") > pre_before
            assert sorted(results) == list(range(engine.num_slots))
            assert all(r["n_tokens"] == 40 for r in results.values())
        finally:
            server.stop()

    def test_nonstream_shed_maps_to_typed_http_error(self, gpt_model):
        model, variables = gpt_model
        eng = GenerationEngine(model, variables, name="g3", num_slots=1,
                               max_len=16, max_waiting=16)
        server = ModelServer(port=0, sentinel=False,
                             generators={"tiny": eng})
        # not started: the route sheds with a retryable 503
        status, body, stream = server.handle_generate(
            "tiny", {"prompt": [1]})
        assert status == 503 and stream is None
        assert body["error"]["code"] == "UNAVAILABLE"
        status, body, _ = server.handle_generate("nope", {"prompt": [1]})
        assert status == 404
        try:
            server.start(warm=False)  # bad payloads never reach the device
            status, body, _ = server.handle_generate("tiny", {"bad": 1})
            assert status == 400
            status, body, _ = server.handle_generate(
                "tiny", {"prompt": [1], "max_new_tokens": "many"})
            assert status == 400
            # deadline validated BEFORE submit — streaming included — so
            # a 400 never leaves an orphaned stream decoding into a
            # slot nobody reads
            for stream in (False, True):
                status, body, _ = server.handle_generate(
                    "tiny", {"prompt": [1], "stream": stream,
                             "deadline_ms": "bogus"})
                assert status == 400, (stream, body)
            d = eng.describe()
            assert d["waiting"] == 0 and d["active"] == 0
        finally:
            server.stop()

    def test_result_timeout_is_a_total_budget(self, gpt_model):
        import queue as _q

        model, variables = gpt_model
        eng = GenerationEngine(model, variables, name="g5", num_slots=1,
                               max_len=16)
        h = eng.submit([1])  # scheduler never started: no tokens come
        t0 = time.monotonic()
        with pytest.raises(_q.Empty):
            h.result(timeout=0.1)
        assert time.monotonic() - t0 < 5.0
        # the streaming wire protocol enforces the same total budget:
        # an expired deadline cancels the request and ends the stream
        # with a terminal DEADLINE_EXCEEDED line
        h2 = eng.submit([1])
        h2._wire_timeout = 0.05
        evs = list(h2.wire_events())
        assert evs[-1]["error"]["code"] == "DEADLINE_EXCEEDED"
        # server-side deadline miss: outcome "deadline" (burns the
        # generation-availability rule), NOT a client "cancelled"
        assert h2.finish_reason == "deadline"
        eng.stop()


# ---------------------------------------------------------------------------
# brownout rung + observability wiring (satellites)


class TestBrownoutAndObservability:
    def test_generation_rung_sits_ahead_of_fallback(self, gpt_model):
        model, variables = gpt_model
        eng = GenerationEngine(model, variables, name="g4", num_slots=1,
                               max_len=16, max_new_tokens=32,
                               brownout_max_new_tokens=4)
        policy = OverloadPolicy(min_in_flight=2, max_in_flight=8,
                                interval_s=60.0)
        server = ModelServer(port=0, sentinel=False, overload=policy,
                             generators={"g4": eng})
        ladder = server.overload.ladder
        names = [r.name for r in ladder.rungs]
        assert names.index("shrink_generation_tokens") \
            == names.index("serve_fallback") - 1
        for _ in range(3):
            ladder.step_down()
        assert eng.token_cap == 4
        assert server.metrics.generation_max_new_tokens.value(
            model="g4") == 4.0
        evs = [e["data"] for e in get_flight_recorder().events(
            kinds=["serving.brownout"])]
        assert any(e["rung"] == "shrink_generation_tokens"
                   and e["direction"] == "down" for e in evs)
        for _ in range(3):
            ladder.step_up()
        assert eng.token_cap == 32
        eng.stop()
        server.stop()

    def test_ttft_detector_fires_on_regression(self):
        det = next(d for d in sn.default_detectors(min_history=4)
                   if d.name == "generation_ttft_regression")
        m = ServingMetrics()
        families = lambda: slo._doc_map([m.registry])  # noqa: E731
        t = 0.0
        for _ in range(8):  # learn a fast-TTFT baseline
            for _ in range(4):
                m.generation_ttft.observe(0.01, model="gpt")
            det.observe(families(), t)
            t += 1.0
        assert det.state == "ok"
        for _ in range(4):  # sustained 100x TTFT regression
            for _ in range(4):
                m.generation_ttft.observe(1.0, model="gpt")
            det.observe(families(), t)
            t += 1.0
        assert det.state == "firing", det.verdict()

    def test_generation_metric_families_in_slo_vocabulary(self):
        known = slo.known_metric_names()
        for name in ("generation_requests_total", "generation_ttft_seconds",
                     "generation_tokens_total", "generation_slot_occupancy",
                     "generation_preemptions_total"):
            assert name in known, name


# ---------------------------------------------------------------------------
# heavy load / storm variants (slow-marked behind the proxies above)


@pytest.mark.slow
def test_streaming_load_tokens_flow_and_zero_recompiles(gpt_model):
    """Sustained streaming load: 8 closed-loop clients over HTTP for
    several rounds — every stream completes, recompiles stay 0, and the
    slot-occupancy histogram shows real batch sharing."""
    model, variables = gpt_model
    eng = GenerationEngine(model, variables, name="gpt", num_slots=4,
                           max_len=48, max_new_tokens=24,
                           min_prompt_bucket=8, idle_wait_s=0.002,
                           temperature=0.8, max_waiting=64)
    server = ModelServer(port=0, sentinel=False, generators={"gpt": eng})
    server.start(warm=True)
    try:
        collector = get_runtime_collector()
        compiles_before = collector.jit_compiles_total.value()
        done, broken = [], []
        lock = threading.Lock()

        def run(tid):
            rng = np.random.default_rng(tid)
            client = ServingClient(server.url, max_retries=4)
            for _ in range(6):
                prompt = rng.integers(0, 127,
                                      size=1 + int(rng.integers(0, 24)))
                try:
                    r = client.generate_tokens("gpt", prompt,
                                               temperature=0.8)
                    with lock:
                        done.append(r["n_tokens"])
                except Exception as e:  # noqa: BLE001 — any failure = bug
                    with lock:
                        broken.append(repr(e))

        threads = [threading.Thread(target=run, args=(t,))
                   for t in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not broken, broken[:3]
        assert len(done) == 48
        assert collector.jit_compiles_total.value() == compiles_before
        occ = server.metrics.generation_slot_occupancy.summary(model="gpt")
        assert occ["mean"] > 0.5  # real sharing, not 1-slot serial decode
    finally:
        server.stop()


@pytest.mark.slow
def test_generation_overload_storm_preempts_and_recovers(gpt_model):
    """Storm variant: a wall of batch streams over HTTP plus a stream of
    critical requests; critical availability stays 100% (preemption +
    priority queue), every preempted batch client eventually completes
    via retry, and the engine ends drained with zero recompiles."""
    model, variables = gpt_model
    eng = GenerationEngine(model, variables, name="gpt", num_slots=2,
                           max_len=48, max_new_tokens=32,
                           min_prompt_bucket=8, idle_wait_s=0.002,
                           temperature=0.0, max_waiting=64)
    policy = OverloadPolicy(min_in_flight=1, max_in_flight=8,
                            interval_s=60.0)
    server = ModelServer(port=0, sentinel=False, overload=policy,
                         generators={"gpt": eng})
    server.start(warm=True)
    try:
        crit_ok, crit_bad, batch_done, broken = [], [], [], []
        lock = threading.Lock()
        stop = threading.Event()

        def batch_run(tid):
            client = ServingClient(server.url, max_retries=8,
                                   retry_seed=tid)
            while not stop.is_set():
                try:
                    r = client.generate_tokens("gpt", [tid % 100, 2],
                                               priority="batch",
                                               temperature=0.0)
                    with lock:
                        batch_done.append(r["n_tokens"])
                except QueueFullError:
                    time.sleep(0.01)
                except Exception as e:  # noqa: BLE001
                    with lock:
                        broken.append(repr(e))

        threads = [threading.Thread(target=batch_run, args=(t,))
                   for t in range(6)]
        for t in threads:
            t.start()
        client = ServingClient(server.url, max_retries=4)
        for i in range(10):
            try:
                r = client.generate_tokens("gpt", [i], max_new_tokens=2,
                                           priority="critical",
                                           temperature=0.0)
                crit_ok.append(r["n_tokens"])
            except Exception as e:  # noqa: BLE001
                crit_bad.append(repr(e))
            time.sleep(0.05)
        stop.set()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive(), "batch client hung"
        assert not crit_bad, crit_bad[:3]
        assert len(crit_ok) == 10
        assert not broken, broken[:3]
        assert batch_done, "no batch stream ever completed"
        assert server.metrics.generation_preemptions_total.value(
            model="gpt", priority="batch") >= 1.0
        assert eng.compiles_after_warm == 0
    finally:
        server.stop()
