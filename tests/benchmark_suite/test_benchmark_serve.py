"""The serving kind at a tiny size on the CPU: a whole run of the harness
without its look for a chip. ``correct`` is a function of the tokens the
engine emitted and of nothing that timing decides, and it comes out false
when a token is altered where it is produced."""

import time

import pytest

import benchmark_tiny
from benchmark.harness import traffic, verdict
from benchmark.kinds import serve

CELL = "tiny_gpt_chat.tiny_chat"
SEED = 2**31 + 4242


def run(tmp_path, **kw):
    cell = benchmark_tiny.load(str(tmp_path), CELL)
    return serve.run(cell, seed=SEED, seconds=2.0, trace=False,
                     t_start=time.perf_counter(), require_tpu=False,
                     scratch=str(tmp_path / "scratch"), **kw)


def squeezed(family):
    """The same requests, all due within the first tenth of the window:
    another arrival schedule of the same seed."""
    def generate(cell, seed, seconds):
        requests = family(cell, seed, seconds)
        for r in requests:
            r.due_s *= 0.1
        return requests
    return generate


def test_the_verdict_is_the_same_under_two_arrival_schedules(
        tmp_path, monkeypatch):
    spread = run(tmp_path)
    monkeypatch.setattr(traffic, "replayed", squeezed(traffic.replayed))
    burst = run(tmp_path)
    for result in (spread, burst):
        assert result["failed"] == 0 and result["attempted"] == 20
        assert set(result["metrics"]) == {"itl_p95_ms", "serve_tok_s",
                                          "setup_s"}
        assert all(v["value"] > 0 for v in result["metrics"].values())
    assert spread["correct"] is True and burst["correct"] is True
    rows = {r["name"]: r for r in burst["compared"]}
    assert rows["served_tokens_missing"]["compared"] > 0
    assert list(burst)[-1] == "compared"
    # the burst fills the slots, so other programs served its steps
    assert burst["metrics"]["serve_tok_s"]["value"] != \
        spread["metrics"]["serve_tok_s"]["value"]


def test_a_token_altered_where_it_is_produced_is_not_correct(
        tmp_path, monkeypatch):
    import jax.numpy as jnp

    from deeplearning4j_tpu.serving import generation

    def least_likely(logits, key, temperature):
        return jnp.argmin(logits, axis=-1).astype(jnp.int32)

    monkeypatch.setattr(generation, "sample_token", least_likely)
    result = run(tmp_path)
    assert result["failed"] == 0
    assert result["correct"] is False
    rows = {r["name"]: r for r in result["compared"]}
    assert rows["served_logit_gap"]["ok"] is False
    assert rows["served_logit_gap"]["value"] > rows["served_logit_gap"]["limit"]


def test_requests_that_fail_go_to_failed_and_not_to_correct(
        tmp_path, monkeypatch):
    """A stream that the server cuts short is a failed request; the
    sampled requests that did finish still decide ``correct``."""
    from deeplearning4j_tpu.serving import generation

    finish = generation.GenerationEngine._maybe_finish

    def cut_short(self, req, tok):
        if req.id % 3 == 0 and req.generated >= 1:
            req.max_new_tokens = req.generated
        return finish(self, req, tok)

    monkeypatch.setattr(generation.GenerationEngine, "_maybe_finish",
                        cut_short)
    result = run(tmp_path)
    assert 0 < result["failed"] < result["attempted"]
    assert result["correct"] is True


def test_the_sample_is_drawn_from_the_seed_and_holds_the_longest(tmp_path):
    cell = benchmark_tiny.load(str(tmp_path), CELL)
    requests = traffic.generate(cell, SEED, 4.0)
    a = serve.sample_requests(requests, SEED, 4)
    assert a == serve.sample_requests(requests, SEED, 4)
    assert a != serve.sample_requests(requests, SEED + 1, 4)
    size = {r.index: len(r.prompt) + r.max_new_tokens for r in requests
            if r.due_s >= 0}  # the lead-in's requests are not compared
    assert size[a[0]] == max(size.values()) and len(set(a)) == 4
    assert set(a) <= set(size)


@pytest.mark.parametrize("stamps,ok,want", [
    ([0.5, 0.6, 0.9], True, 0.4),     # first token minus the due time
    ([0.5], False, 9.9),              # cut short: the worst value
    ([], False, 9.9),                 # never answered
])
def test_time_to_first_token_counts_from_the_due_time(stamps, ok, want):
    request = traffic.Request(index=0, due_s=0.1, prompt=[1, 2],
                              max_new_tokens=3)
    row = {"index": 0, "due_s": 0.1, "sent_s": 0.15, "token_s": stamps,
           "tokens": [7] * len(stamps), "done": ok, "error": None}
    seen = serve.window_numbers([row], [request], 1.0, 10.0)
    assert seen["ttft_s"] == [pytest.approx(want)]
    assert seen["failed"] == (0 if ok else 1)
    assert seen["send_lag_s"] == [pytest.approx(0.05)]


def test_the_control_in_the_programs_place_is_not_correct(tmp_path):
    """At each position of the same prompts and tokens, the token that the
    reference puts first when it is computed in fp8, the precision below
    the bf16 in which the configuration multiplies, lies further below
    the float32 reference's best than the limit allows. In bfloat16, the
    program's own precision, it does not."""
    import numpy as np

    cell = benchmark_tiny.load(str(tmp_path), CELL)
    rng = np.random.default_rng(7)
    rows = [(i, rng.integers(0, 211, 8).tolist(),
             rng.integers(0, 211, 16).tolist()) for i in range(16)]
    numbers = serve.served_numbers(cell, SEED, rows,
                                   controls=("bfloat16", "fp8"))
    limits = cell.workload["limits"]

    def judged(name):
        """``correct`` with that precision's tokens in the program's place."""
        return verdict.judge(
            {"served_logit_gap": numbers[f"control_{name}_logit_gap"],
             "served_tokens_missing": numbers["served_tokens_missing"]},
            limits)[0]

    assert judged("fp8") is False
    assert judged("bfloat16") is True
    # tokens drawn at random are no greedy tokens either
    served = {k: numbers[k] for k in limits}
    assert verdict.judge(served, limits)[0] is False
    assert numbers["served_tokens_missing"]["compared"] == 16 * 16
