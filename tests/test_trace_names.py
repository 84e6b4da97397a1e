"""The names a profiler trace carries (observability/vocab.py): component
scopes on the compiled step's instructions and in the table the program
publishes, the kernels' and the engine programs' names in lowered text,
and host spans on the profiler's own clock."""

import glob
import os
import re
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.bert import bert_tiny, make_mlm_batch
from deeplearning4j_tpu.models.gpt import gpt_tiny
from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
from deeplearning4j_tpu.observability import runtime, trace, vocab
from deeplearning4j_tpu.train.trainer import Trainer
from deeplearning4j_tpu.train.updaters import Adam

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _net():
    return NeuralNetConfiguration(updater=Adam(1e-3), mixed_precision=True)


def _gpt():
    batch = {"features": {"token_ids": np.zeros((4, 16), np.int32)}}
    return Trainer(gpt_tiny(net=_net())), batch


def _bert():
    batch = make_mlm_batch(0, 4, 16, 1000, max_predictions=4)
    return Trainer(bert_tiny(net=_net(), dropout=0.0,
                             attention_dropout=0.0)), batch


def _join_cost_analysis():
    for t in threading.enumerate():
        if t.name == "step-cost-analysis":
            t.join()


# -- scope_of and the parse of an optimised module's text ----------------------

@pytest.mark.parametrize("op_name,want", [
    ("jit(train_step)/transpose(jvp(attn))/dot_general", "attn"),
    ("jit(train_step)/jvp(mlp)/mul", "mlp"),
    ("jit(train_step)/optimizer/sub", "optimizer"),
    ("jit(train_step)/jvp(checkpoint)/jvp(head)/while/body/add", "head"),
    ("jit(f)/shard_map/jvp(attn)/jvp(flash_fwd)/pallas_call", "attn"),
    ("jit(train_step)/jvp()/dot_general", None),
    ("jit(attn)/add", None),          # a program's name is not a scope
    ("jit(f)/attention/add", None),   # only whole path elements count
])
def test_scope_of_an_op_name(op_name, want):
    assert vocab.scope_of(op_name) == want


HLO = """HloModule jit_step, is_scheduled=true

FileNames
1 "x.py"

%fused_computation (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(step)/jvp(mlp)/mul"}
  ROOT %add.1 = f32[8]{0} add(%mul.1, %p0), metadata={op_name="jit(step)/jvp(head)/add"}
}

%fused_computation.1 (p0.1: f32[8]) -> f32[8] {
  %p0.1 = f32[8]{0} parameter(0)
  %convert.1 = bf16[8]{0} convert(%p0.1), metadata={op_name="jit(step)/jvp(optimizer)/convert_element_type"}
  ROOT %convert.2 = f32[8]{0} convert(%convert.1)
}

%body (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%arg), index=1
  %tanh.7 = f32[8]{0} tanh(%gte.1), metadata={op_name="jit(step)/jvp(attn)/while/body/tanh"}
  ROOT %tuple.2 = (s32[], f32[8]{0}) tuple(%gte.1, %tanh.7)
}

%cond (arg.1: (s32[], f32[8])) -> pred[] {
  %arg.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt.3 = pred[] constant(false)
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.13 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation
  %fusion.14 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/transpose(jvp(attn))/mul"}
  %convert_fusion = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %flash_fwd.2 = (bf16[8]{0:T(8,128)(2,1)S(1)}, f32[8]{0}) custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(attn)/jvp(flash_fwd)/pallas_call"}
  %while.1 = (s32[], f32[8]{0}) while(%x), condition=%cond, body=%body
  ROOT %copy.5 = f32[8]{0} copy(%fusion.13)
}
"""


def test_the_parse_gives_each_traced_instruction_its_scope():
    module, scopes = runtime.scopes_of_hlo(HLO)
    assert module == "jit_step"
    assert scopes == {
        "x": None,
        "fusion.13": "head",         # no metadata of its own: its root's
        "fusion.14": "attn",         # its own wins
        "convert_fusion": "optimizer",  # root has none: most instructions
        "flash_fwd.2": "attn",
        "while.1": None,
        "copy.5": None,
        # the loop's body and condition run as programs of their own
        "arg": None, "gte.1": None, "tanh.7": "attn", "tuple.2": None,
        "arg.1": None, "lt.3": None,
    }


@pytest.mark.parametrize("op_name,want", [
    ("jit(train_step)/jvp(attn)/jvp(cca_mix)/dot_general", "cca_mix"),
    ("jit(train_step)/transpose(jvp(attn))/transpose(jvp(cca_mix))/mul",
     "cca_mix"),
    ("jit(train_step)/jvp(mlp)/moe_experts/jit(gmm)/pallas_call",
     "moe_experts"),
    ("jit(train_step)/jvp(mlp)/jvp(moe_route)/sort", "moe_route"),
    ("jit(train_step)/jvp(attn)/jvp(flash_fwd)/pallas_call", None),
    ("jit(train_step)/jvp(mlp)/dot_general", None),
    ("jit(moe_route)/mul", None),  # the program's own name is no scope
])
def test_subscope_of_an_op_name(op_name, want):
    assert vocab.subscope_of(op_name) == want
    if want is not None:  # the component is still what scope_of says
        assert vocab.scope_of(op_name) in ("attn", "mlp")


def test_the_parse_gives_the_innermost_subscope_beside_the_scope():
    text = HLO.replace("jit(step)/transpose(jvp(attn))/mul",
                       "jit(step)/transpose(jvp(attn))/jvp(cca_mix)/mul")
    _, scopes = runtime.scopes_of_hlo(text)
    _, subscopes = runtime.scopes_of_hlo(text, vocab.subscope_of)
    assert scopes == runtime.scopes_of_hlo(HLO)[1]  # read as before
    assert {k for k, v in subscopes.items() if v} == {"fusion.14"}
    assert subscopes["fusion.14"] == "cca_mix"
    runtime.publish_program("jit_sub", flops=None, text=lambda: text,
                            carries="optimizer")
    entry = runtime.program_table()["jit_sub"]
    assert entry["subscopes"] == {"fusion.14": "cca_mix"}
    assert entry["scopes"]["fusion.14"] == "attn" and not entry["stale"]


def test_the_table_keeps_the_last_program_published_under_a_name():
    runtime.publish_program("jit_t", flops=1.0, scopes={"a": "attn"})
    runtime.publish_program("jit_t", flops=2.0, scopes={"b": None})
    assert runtime.program_table()["jit_t"] == {
        "flops": 2.0, "scopes": {"b": None}, "stale": False}


# -- the compiled step of a tiny Gpt and a tiny Bert --------------------------

@pytest.mark.parametrize("make", [_gpt, _bert], ids=["gpt", "bert"])
def test_the_step_describes_itself_with_every_scope(make):
    trainer, batch = make()
    assert trainer.step_description() is None
    ts = trainer.fit(trainer.init_state(), [batch, batch])
    _join_cost_analysis()
    desc = trainer.step_description()
    assert desc["module"] == "jit_train_step"
    assert desc["flops"] == trainer.step_flops(ts, batch) > 0
    assert set(vocab.COMPONENT_SCOPES) <= set(desc["scopes"].values())
    assert desc["stale"] is False
    entry = runtime.program_table()["jit_train_step"]
    assert entry["scopes"] == desc["scopes"]
    assert entry["flops"] == desc["flops"]


def test_the_table_is_read_after_the_trainer_is_gone(monkeypatch):
    """As the benchmark reads it: the state freed, jax's caches cleared,
    and the step's text not yet fetched."""
    import gc

    monkeypatch.setattr(runtime, "_PROGRAMS", {})
    trainer, batch = _gpt()
    trainer.fit(trainer.init_state(), [batch])
    _join_cost_analysis()
    assert "text" in runtime._PROGRAMS["jit_train_step"]  # not fetched
    del trainer
    gc.collect()
    jax.clear_caches()
    entry = runtime.program_table()["jit_train_step"]
    assert set(vocab.COMPONENT_SCOPES) <= set(entry["scopes"].values())
    assert entry["flops"] > 0 and entry["stale"] is False


@pytest.mark.parametrize("make", [_gpt, _bert], ids=["gpt", "bert"])
def test_every_scope_is_on_the_forward_and_the_backward_pass(make):
    trainer, batch = make()
    text = jax.jit(trainer._raw_step).lower(
        jax.eval_shape(trainer.init_state), batch).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in (vocab.SCOPE_EMBED, vocab.SCOPE_ATTN, vocab.SCOPE_MLP,
                  vocab.SCOPE_HEAD):
        assert any(f"/jvp({scope})/" in n for n in names), scope
        assert any(f"/transpose(jvp({scope}))/" in n for n in names), scope
    assert any(f"/{vocab.SCOPE_OPTIMIZER}/" in n for n in names)
    # the cast of the master weights, inside the differentiated function
    assert any(f"/jvp({vocab.SCOPE_OPTIMIZER})/" in n for n in names)


# -- the head's loss, an operation with a backward rule of its own ------------

def _zaya():
    from deeplearning4j_tpu.models.zaya import zaya_tiny

    batch = {"features": {"token_ids": np.zeros((2, 16), np.int32)}}
    return Trainer(zaya_tiny(net=_net())), batch


# the operation's three products carry their einsum's subscripts in their
# ``op_name``: the logits, the hidden state's and the weight's gradient
_HEAD_PRODUCTS = ("/jvp(head)/...h,vh->...v/",
                  "/transpose(jvp(head))/...v,vh->...h/",
                  "/transpose(jvp(head))/...v,...h->vh/")


@pytest.mark.parametrize("make", [_gpt, _zaya], ids=["gpt", "zaya"])
def test_the_heads_operation_is_under_head_forward_and_backward(make):
    trainer, batch = make()
    trainer.fit(trainer.init_state(), [batch])
    _join_cost_analysis()
    scopes = trainer.step_description()["scopes"]
    text = jax.jit(trainer._raw_step).lower(
        jax.eval_shape(trainer.init_state), batch).compile().as_text()
    traced = re.findall(
        r'^\s*(?:ROOT )?%?([\w.\-]+) = .*op_name="([^"]*)"', text, re.M)
    for product in _HEAD_PRODUCTS:
        found = [name for name, op_name in traced if product in op_name]
        assert found, product
        assert any(scopes.get(name) == vocab.SCOPE_HEAD for name in found)
        for name in found:  # in the table, or inside a fusion that is
            assert scopes.get(name, vocab.SCOPE_HEAD) == vocab.SCOPE_HEAD
    # the rule's own exp (the softmax from the stored logits) is in the
    # backward pass under ``head``, and no log-softmax is left in the step
    backward = {op_name.rsplit("/", 1)[-1] for _, op_name in traced
                if "/transpose(jvp(head))/" in op_name}
    assert {"exp", "dot_general"} <= backward
    assert not any("log_softmax" in op_name for _, op_name in traced)
    # and none of the rule's primitives fell out of the scope: an exp, an
    # iota or a concatenate of the backward pass with no component
    assert not [op_name for _, op_name in traced
                if op_name.rsplit("/", 1)[-1] in ("exp", "iota", "concatenate")
                and "transpose(" in op_name
                and vocab.scope_of(op_name) is None]


@pytest.mark.parametrize("make,bias,vocabulary,rows", [
    (_gpt, True, 128, 4 * 15), (_zaya, False, 96, 2 * 15)],
    ids=["gpt", "zaya"])
def test_one_flight_event_a_trace_says_which_head_the_step_holds(
        make, bias, vocabulary, rows):
    from deeplearning4j_tpu.observability.flightrecorder import (
        FlightRecorder,
        get_flight_recorder,
        set_flight_recorder,
    )

    trainer, batch = make()
    before = get_flight_recorder()
    flight = set_flight_recorder(FlightRecorder())
    try:
        jax.jit(trainer._raw_step).lower(
            jax.eval_shape(trainer.init_state), batch)
    finally:
        set_flight_recorder(before)
    event, = flight.events(kinds=["head.linear_cross_entropy"])
    assert event["data"] == {
        "rows": rows, "vocabulary": vocabulary, "logits_dtype": "bfloat16",
        "bias": bias, "logsumexp": "max_then_sum_float32"}
    assert "head.linear_cross_entropy" in vocab.known_event_kinds()


# -- names in lowered text -----------------------------------------------------

def test_the_flash_kernels_are_called_by_name(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_FORCE_PALLAS", "1")  # interpret mode
    from deeplearning4j_tpu.kernels import flash_attention as fa

    q = jnp.ones((1, 2, 128, 16), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True, scale=0.25,
                                          block_q=128, block_k=128))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).as_text(debug_info=True)
    for kernel in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"):
        assert kernel in vocab.KERNEL_NAMES
        assert re.search(rf"\b{kernel}\)*/pallas_call", text), kernel


def test_every_pallas_call_of_the_package_has_a_declared_name():
    found = set()
    for path in glob.glob(os.path.join(ROOT, "deeplearning4j_tpu", "kernels",
                                       "*.py")):
        with open(path, encoding="utf-8") as f:
            source = f.read()
        calls = source.count("pl.pallas_call(")
        names = re.findall(r'^\s+name="(\w+)",$', source, re.M)
        assert len(names) == calls, path
        found.update(names)
    assert found == vocab.KERNEL_NAMES


@pytest.mark.parametrize("program", ["generation_decode",
                                     "generation_prefill"])
def test_the_engines_programs_have_their_own_module_names(program):
    from deeplearning4j_tpu.serving import GenerationEngine

    model = gpt_tiny()
    engine = GenerationEngine(model, model.init(), num_slots=2, max_len=16,
                              max_new_tokens=4, min_prompt_bucket=8)
    e, p, b, kv = engine, 8, 1, 16
    slabs = (e._params, e._kslabs, e._vslabs, e._base_key, np.int32(0))
    heads, hd = e._kslabs[0].shape[1], e._kslabs[0].shape[-1]
    zero = tuple(np.zeros((heads, p, hd), e._kslabs[0].dtype)
                 for _ in e._kslabs)
    lowered = {
        "generation_prefill": e._get_prefill_fn(p).lower(
            *slabs, np.int32(0), np.zeros(p, np.int32), np.int32(p),
            np.float32(0.0)),
        "generation_decode": e._get_decode_fn(b, kv).lower(
            *slabs, np.zeros(b, np.int32), np.zeros(b, np.int32),
            np.zeros(b, np.int32), np.zeros(b, np.float32)),
        "generation_graft": e._get_graft_fn(p).lower(
            e._kslabs, e._vslabs, zero, zero, np.int32(0)),
    }
    assert tuple(lowered) == vocab.GENERATION_PROGRAMS
    for name, low in lowered.items():
        assert f"module @jit_{name}" in low.as_text(), name
    # and the block, walked with the cache's attend, carries the component
    # scopes in the programs that run the model
    text = lowered[program].as_text(debug_info=True)
    for scope in (vocab.SCOPE_EMBED, vocab.SCOPE_ATTN, vocab.SCOPE_MLP,
                  vocab.SCOPE_HEAD):
        assert f"jit({program})/{scope}/" in text, scope


# -- host spans on the profiler's clock ---------------------------------------

def test_annotate_without_jax_is_a_no_op_and_trace_stays_stdlib_only():
    code = (
        "import sys\n"
        "before = set(sys.modules)\n"
        "import importlib.util as u\n"
        "spec = u.spec_from_file_location('trace_alone', sys.argv[1])\n"
        "m = u.module_from_spec(spec); spec.loader.exec_module(m)\n"
        "third = sorted(n for n in set(sys.modules) - before\n"
        "               if n.split('.')[0] not in sys.stdlib_module_names\n"
        "               and n != 'trace_alone')\n"
        "assert third == [], third\n"
        "assert 'jax' not in sys.modules\n"
        "with m.annotate('train.read') as a:\n"
        "    assert a is None\n"
        "with m.annotate('train.step', step_num=3) as a:\n"
        "    assert a is None\n"
        "assert m.annotate('x') is m.annotate('y')\n"
        "assert 'jax' not in sys.modules\n"
        "print('ok')\n")
    path = os.path.join(ROOT, "deeplearning4j_tpu", "observability",
                        "trace.py")
    out = subprocess.run([sys.executable, "-c", code, path],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def _host_events(log_dir):
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return [(e.name, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines if line.name == "python"
            for e in line.events]


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_spans_and_the_fit_loop_are_on_the_profilers_host_plane(tmp_path):
    trainer, batch = _gpt()
    ts = trainer.fit(trainer.init_state(), [batch])  # compiles
    with jax.profiler.trace(str(tmp_path)):
        with trace.span("serving.request"):
            with trace.annotate("generation.decode"):
                pass
        trainer.fit(ts, [batch, batch, batch])
    events = _host_events(str(tmp_path))
    names = [n for n, _ in events]
    assert "serving.request" in names and "generation.decode" in names
    steps = [s["step_num"] for n, s in events if n == "train.step"]
    # three steps and the iteration that found the data at its end
    assert steps == [2, 3, 4, 5]
    for leg in ("train.read", "train.dispatch", "train.listeners"):
        assert leg in vocab.HOST_SPANS
        assert names.count(leg) >= 3, leg
    assert names.count("train.read") == 4
    assert "train.put" not in names  # no batch sharding: nothing is put


def test_the_fault_tolerant_loop_emits_the_same_spans(tmp_path):
    from deeplearning4j_tpu.resilience.recovery import FaultTolerantTrainer

    trainer, batch = _gpt()
    ft = FaultTolerantTrainer(trainer, str(tmp_path / "ckpt"))
    ts = ft.fit(trainer.init_state(), [batch])
    log_dir = tmp_path / "trace"
    with jax.profiler.trace(str(log_dir)):
        ft.fit(ts, [batch, batch], epochs=2)
    events = _host_events(str(log_dir))
    steps = [s["step_num"] for n, s in events if n == "train.step"]
    assert steps == sorted(steps) and len(set(steps)) >= 2
    names = {n for n, _ in events}
    assert {"train.read", "train.dispatch", "train.listeners"} <= names


def test_a_text_is_fetched_at_the_first_read_and_only_then(monkeypatch):
    monkeypatch.setattr(runtime, "_PROGRAMS", {})
    calls = []

    def text():
        calls.append(1)
        return HLO

    runtime.publish_program("jit_step", flops=7.0, text=text,
                            carries="attn")
    assert calls == []
    entry = runtime.program_table()["jit_step"]
    assert entry["flops"] == 7.0 and entry["stale"] is False
    assert entry["scopes"]["fusion.13"] == "head"
    assert runtime.program_table()["jit_step"] == entry and calls == [1]
    with pytest.raises(ValueError):
        runtime.publish_program("jit_step", flops=None)


def test_a_text_without_the_scope_it_should_carry_is_stale(monkeypatch):
    """What a persistent-cache entry written by an older program gives
    back: this program's instructions under that program's metadata."""
    from deeplearning4j_tpu.observability import flightrecorder

    monkeypatch.setattr(runtime, "_PROGRAMS", {})
    runtime.publish_program("jit_step", flops=None, text=lambda: HLO,
                            carries="optimizer_of_a_newer_program")
    assert runtime.program_table()["jit_step"]["stale"] is True
    kinds = [e["kind"] for e in flightrecorder.get_flight_recorder().events()]
    assert "compile_cache.stale_metadata" in kinds


def test_a_text_that_cannot_be_had_is_no_entry(monkeypatch):
    monkeypatch.setattr(runtime, "_PROGRAMS", {})

    def text():
        raise RuntimeError("no backend")

    runtime.publish_program("jit_step", flops=None, text=text)
    assert runtime.program_table() == {}


def test_every_annotated_span_of_the_package_is_declared():
    """Every literal ``annotate("...")``, the timeline's own names (the
    fit, the iteration, its legs) and the set-up phases, which are
    ``span()``s and one ``record_span()`` under the prefixes of the
    training side."""
    found = {trace.FIT, trace.ITERATION, *trace.LEGS}
    for path in glob.glob(os.path.join(ROOT, "deeplearning4j_tpu", "**",
                                       "*.py"), recursive=True):
        with open(path, encoding="utf-8") as f:
            text = f.read()
        found.update(re.findall(r'annotate\(\s*"([\w.]+)"', text))
        found.update(re.findall(
            r'span\(\s*"((?:train|import|program_table)\.[\w.]+)"', text))
    assert found == vocab.HOST_SPANS
