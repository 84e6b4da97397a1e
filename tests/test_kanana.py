"""kanana-2's decoder at a tiny size on the CPU, seeded random weights,
float32: the program (``models/kanana.py``, ``latent_attention`` with its
interleaved rotary pairs and its 24-wide query-key heads over 16-wide
values, the dense first layer, ``RoutedExperts`` at top-3 of 8 experts
behind a sigmoid router with a non-zero correction bias beside two shared
experts) against its plain reference
(``benchmark/configs/kanana2_reference.py``), over three training steps
through ``Trainer.fit`` too, and what the cut to a chip's share of the
experts must keep true."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.configs import kanana2_reference as ref
from benchmark.configs import reference_common as rc
from deeplearning4j_tpu.models.kanana import kanana2_30b_a3b, kanana_tiny
from deeplearning4j_tpu.nn.layers import attention as attn
from deeplearning4j_tpu.nn.layers.moe import RoutedExperts
from deeplearning4j_tpu.observability import vocab
from deeplearning4j_tpu.observability.flightrecorder import (
    FlightRecorder,
    get_flight_recorder,
    set_flight_recorder,
)

ROWS, SEQ = 2, 32
ALL = tuple(range(8))
SCALE = 0.5  # of the projections that write into the residual stream
THETA = 1e6


def bias_rows(seed=9, spread=0.1):
    """One row of 8 a layer with experts, seeded: large enough to move
    some of the choices."""
    return np.asarray(spread * jax.random.normal(jax.random.key(seed),
                                                 (2, 8))).tolist()


def tiny_cfg(held=ALL, bias="seeded"):
    return {
        "hidden_size": 64, "num_attention_heads": 4, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "n_routed_experts_total": 8, "n_routed_experts": len(held),
        "experts_held": list(held), "num_experts_per_tok": 3,
        "n_shared_experts": 2, "num_hidden_layers": 3,
        "first_k_dense_replace": 1, "vocab_size": 96, "rms_norm_eps": 1e-6,
        "rope_theta": THETA, "routed_scaling_factor": 2.448,
        "initializer_range": 0.2, "residual_init_scale": SCALE,
        "correction_bias": bias_rows() if bias == "seeded" else bias,
    }


def program(cfg, **kw):
    return kanana_tiny(experts_held=tuple(cfg["experts_held"]),
                       residual_init_scale=SCALE,
                       correction_bias=cfg["correction_bias"], **kw)


def seeded(cfg, seed=7):
    """Every leaf random, the ones too, so that no term of the model
    drops out of the comparison."""
    shapes = ref.param_shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[1], str))
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        cfg["initializer_range"] * jax.random.normal(key, shape)
        + (1.0 if kind == "ones" else 0.0)
        for key, (shape, kind) in zip(keys, leaves)])


def batch(cfg, seed=3):
    rng = np.random.default_rng(seed)
    return ref.make_batch(cfg, rng, {"rows": ROWS, "seq_len": SEQ})


def reference_loss(cfg, params, rows):
    parts = ref.loss_parts(cfg, params, rows, rc.Matmul("float32"))
    return parts["lm"] / ref.part_weights(rows)["lm"]


@pytest.fixture(scope="module", autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def assert_trees_close(got, want, rtol=2e-4, atol_share=1e-5):
    flat_want = jax.tree_util.tree_flatten_with_path(want)[0]
    flat_got = jax.tree_util.tree_leaves(got)
    scale = max(float(jnp.max(jnp.abs(w))) for _, w in flat_want)
    for (path, w), g in zip(flat_want, flat_got):
        assert g.shape == w.shape, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=rtol, atol=atol_share * scale,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("held", [ALL, (0, 1, 2, 3), (1, 6)])
def test_loss_and_every_gradient_match_the_reference(held):
    cfg = tiny_cfg(held)
    params = seeded(cfg)
    rows = batch(cfg)
    model = program(cfg)
    assert (jax.tree_util.tree_structure(model.init(0)["params"])
            == jax.tree_util.tree_structure(params))
    want, want_grads = jax.value_and_grad(
        lambda p: reference_loss(cfg, p, rows))(params)
    got, got_grads = jax.value_and_grad(
        lambda p: model.loss_fn(p, {}, rows)[0])(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert_trees_close(got_grads, want_grads)
    for path, g in jax.tree_util.tree_flatten_with_path(got_grads)[0]:
        assert np.any(np.asarray(g)), jax.tree_util.keystr(path)


def test_three_training_steps_through_fit_follow_the_reference():
    """``Trainer.fit`` in float32, Adam, three batches: each step's loss
    and every leaf's change over the three steps are the reference's."""
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.train.listeners import TrainingListener
    from deeplearning4j_tpu.train.trainer import Trainer
    from deeplearning4j_tpu.train.updaters import Adam

    cfg = tiny_cfg((0, 2, 5, 7))
    params = seeded(cfg)
    feed = [batch(cfg, seed) for seed in (1, 2, 3)]
    adam = {"lr": 3e-3, "beta1": 0.9, "beta2": 0.999, "eps": 1e-8}
    def copy():  # both sides donate what they are given
        return jax.tree_util.tree_map(jnp.copy, params)

    want = rc.follow_training(
        lambda p, rows: ref.loss_parts(cfg, p, rows, rc.Matmul("float32")),
        ref.part_weights, copy(), feed, adam=adam, row_block=ROWS)

    model = program(cfg, net=NeuralNetConfiguration(updater=Adam(**adam)))
    trainer = Trainer(model)
    ts = trainer.init_state(variables={"params": copy(), "state": {}})

    class Keep(TrainingListener):
        losses = []

        def on_iteration(self, epoch, step, ts, metrics):
            self.losses.append(float(metrics["total_loss"]))
            return False

    ts = trainer.fit(ts, feed, listeners=[Keep()])
    np.testing.assert_allclose(Keep.losses, want["losses"], rtol=1e-5)
    change = {k: float(v) for k, v in rc.leaf_norms(jax.tree_util.tree_map(
        jnp.subtract, ts.params, params)).items()}
    assert set(change) == set(want["change_norms"])
    for leaf, norm in want["change_norms"].items():
        assert change[leaf] == pytest.approx(norm, rel=1e-3, abs=1e-7), leaf


def test_init_has_the_references_leaves():
    cfg = tiny_cfg((0, 1, 2))
    model = program(cfg)
    made = model.init(0)["params"]
    sizes = rc.leaf_sizes(ref.param_shapes(cfg))
    got = {jax.tree_util.keystr(p): leaf.size for p, leaf in
           jax.tree_util.tree_flatten_with_path(made)[0]}
    assert got == sizes
    assert model.num_params({"params": made}) == sum(sizes.values())
    assert "mlp" in made["layer_0"] and "moe" not in made["layer_0"]
    assert "moe" in made["layer_2"] and "mlp" not in made["layer_2"]
    moe = made["layer_1"]["moe"]
    assert moe["gate"].shape == (3, 64, 32) and moe["Wg"].shape == (64, 8)
    assert moe["shared_gate"].shape == (64, 64)  # two experts of 32
    assert sorted(made["layer_0"]["attn"]) == [
        "Wkva", "Wkvb", "Wo", "Wq", "kv_norm", "norm"]
    assert made["head"]["out"] is not made["embeddings"]["word"]  # untied


def test_the_published_sizes_and_the_cut():
    """The factory's defaults are the published config; the cell's cut
    holds 575,955,456 parameters."""
    c = kanana2_30b_a3b().config
    assert (c.hidden, c.num_layers, c.first_dense, c.num_heads, c.nope_dim,
            c.rope_dim, c.v_dim, c.kv_lora_rank, c.intermediate,
            c.experts_total, c.experts_per_token, c.expert_units,
            c.shared_experts, c.routed_scaling, c.rope_theta, c.eps,
            c.vocab_size) == (
        2048, 48, 1, 32, 128, 64, 128, 512, 6144, 128, 6, 768, 2, 2.448,
        1e6, 1e-6, 128256)
    cut = kanana2_30b_a3b(num_layers=5, experts_held=tuple(range(16)),
                          vocab_size=16032)
    shapes = jax.eval_shape(cut.init, 0)["params"]
    assert sum(leaf.size for leaf in
               jax.tree_util.tree_leaves(shapes)) == 575_955_456
    with pytest.raises(ValueError, match="sigmoid"):
        RoutedExperts(router="softmax")


# -- latent attention ----------------------------------------------------------

def test_interleaved_rotary_turns_each_pair_by_its_angle():
    x = jax.random.normal(jax.random.key(0), (1, 5, 3, 8))
    got = attn._rotary(x, 100.0, 1.0, interleaved=True)
    pos = np.arange(5)[:, None, None]
    angle = pos * 100.0 ** (-np.arange(0, 8, 2) / 8)  # [5, 1, 4]
    a, b = np.asarray(x)[..., 0::2], np.asarray(x)[..., 1::2]
    want = np.empty(x.shape, np.float32)
    want[..., 0::2] = a * np.cos(angle) - b * np.sin(angle)
    want[..., 1::2] = b * np.cos(angle) + a * np.sin(angle)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)
    # the reference's form is the same vector, the pairs gathered to halves
    np.testing.assert_allclose(
        np.asarray(ref.rotary_interleaved(x, 100.0)),
        np.concatenate([want[..., 0::2], want[..., 1::2]], -1),
        rtol=1e-5, atol=1e-6)
    # the half-pair form the other models use is untouched
    half = attn._rotary(x, 100.0, 1.0)
    assert not np.allclose(np.asarray(half), want, atol=1e-3)


def attention_inputs(seed=5):
    cfg = tiny_cfg()
    p = seeded(cfg, seed)["layer_1"]["attn"]
    y = jax.random.normal(jax.random.key(seed + 1), (ROWS, SEQ, 64))
    return cfg, p, y


def program_attention(p, y):
    return attn.latent_attention(
        p, y, num_heads=4, nope_dim=16, rope_dim=8, v_dim=16,
        rope_theta=THETA, eps=1e-6)


@pytest.mark.parametrize("flash", [False, True])
def test_latent_attention_is_the_references(flash, monkeypatch):
    """Output and every gradient, through XLA's attention and through the
    three flash kernels (interpreted) at 24 over 16."""
    if flash:
        monkeypatch.setenv("DL4J_TPU_FORCE_PALLAS", "1")
    cfg, p, y = attention_inputs()
    mm = rc.Matmul("float32")
    want = ref.attention(cfg, mm, y, p)
    got = program_attention(p, y)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)
    g = jax.random.normal(jax.random.key(3), want.shape)
    want_grads = jax.grad(lambda p, y: jnp.sum(
        ref.attention(cfg, mm, y, p) * g), argnums=(0, 1))(p, y)
    got_grads = jax.grad(lambda p, y: jnp.sum(
        program_attention(p, y) * g), argnums=(0, 1))(p, y)
    assert_trees_close(got_grads, want_grads)


def test_the_rotary_key_is_one_for_all_heads_and_positions_count():
    """Moving the keys a query sees changes what it gets (positions), and
    the 64... here 8 rotary dimensions of a key are one vector a position
    whatever the head: a head's q_pe against another head's k_pe reads
    the same key."""
    cfg, p, y = attention_inputs()
    perm = np.concatenate([np.arange(SEQ - 1)[::-1], [SEQ - 1]])
    last = np.asarray(program_attention(p, y)[:, -1])
    moved = np.asarray(program_attention(p, y[:, perm])[:, -1])
    assert not np.allclose(last, moved, rtol=1e-2, atol=1e-4)
    assert p["Wkva"].shape == (64, 32 + 8)  # one rotary key, not one a head


def test_the_flight_event_says_the_widths():
    before = get_flight_recorder()
    flight = set_flight_recorder(FlightRecorder())
    try:
        _, p, y = attention_inputs()
        jax.eval_shape(program_attention, p, y)
    finally:
        set_flight_recorder(before)
    event, = [e["data"] for e in flight.events(kinds=["attention.mla"])]
    assert event == {"heads": 4, "qk_head_dim": 24, "v_head_dim": 16,
                     "kv_lora_rank": 32, "qk_rope_head_dim": 8,
                     "rope_pairs": "interleaved",
                     "up_projection_recomputed": False}


# -- the expert sub-layer -----------------------------------------------------

def sublayer_inputs(seed=11):
    cfg = tiny_cfg()
    p = seeded(cfg, seed)["layer_2"]["moe"]
    z = jax.random.normal(jax.random.key(seed + 1), (ROWS, SEQ, 64))
    return cfg, p, z


def share_of(p, held):
    held = np.asarray(held)
    return dict(p, gate=p["gate"][held], up=p["up"][held],
                down=p["down"][held])


def layer(held):
    return RoutedExperts(experts_total=8, experts_held=tuple(held), units=32,
                         top_k=3, router="sigmoid", scaling=2.448,
                         shared_units=64)


def program_sublayer(p, z, held, bias):
    """The sub-layer's share with the correction bias fed in as the
    router's ``bias``, as ``Kanana`` feeds it."""
    p = {k: v for k, v in share_of(p, held).items() if k != "norm"}
    return layer(held).apply(dict(p, bias=jnp.asarray(bias)), {}, z)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One expert a chip, eight chips: the eight partial outputs, with the
    shared experts that every chip computes alike counted once, sum to the
    uncut reference's whole layer; the router is computed alike in all and
    the weights are normalised over all three of a token's experts
    wherever they are held."""
    cfg, p, z = sublayer_inputs()
    mm = rc.Matmul("float32")
    bias = np.asarray(cfg["correction_bias"][0], np.float32)
    whole = ref.expert_sublayer(cfg, mm, z, p, jnp.asarray(bias))
    shared = ref.gated(mm, z, p["shared_gate"], p["shared_up"],
                       p["shared_down"])
    parts, states = zip(*[program_sublayer(p, z, (e,), bias) for e in ALL])
    np.testing.assert_allclose(
        np.asarray(sum(parts) - (len(ALL) - 1) * shared), np.asarray(whole),
        rtol=1e-4, atol=1e-5)
    landed = np.concatenate([np.asarray(s["tokens_here"]) for s in states])
    assert landed.sum() == 3 * ROWS * SEQ  # every pair landed on one chip
    assert np.count_nonzero(landed) >= 6, landed
    for held in ((0, 1, 2, 3), (4, 5, 6, 7), (1, 6)):
        cut = dict(cfg, experts_held=list(held))
        want = ref.expert_sublayer(cut, mm, z, share_of(p, held),
                                   jnp.asarray(bias))
        got, _ = program_sublayer(p, z, held, bias)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)


def test_the_sigmoid_router_chooses_by_p_plus_b_and_weighs_by_p():
    cfg, p, z = sublayer_inputs()
    bias = np.asarray(cfg["correction_bias"][0], np.float32)
    tokens = z.reshape(-1, 64)
    _, chosen, share = layer(ALL).route(dict(p, bias=jnp.asarray(bias)),
                                        tokens, None)
    _, unbiased, _ = layer(ALL).route(p, tokens, None)
    prob = np.asarray(jax.nn.sigmoid(jnp.matmul(
        tokens, p["Wg"], precision=jax.lax.Precision.HIGHEST)))
    want = np.argsort(-(prob + bias), axis=-1)[:, :3]
    assert (np.sort(np.asarray(chosen), -1) == np.sort(want, -1)).all()
    # the bias moved some token's choice, and the weights are p's alone
    assert (np.sort(np.asarray(unbiased), -1) != np.sort(want, -1)).any()
    picked = np.take_along_axis(prob, np.asarray(chosen), -1)
    np.testing.assert_allclose(
        np.asarray(share), picked / picked.sum(-1, keepdims=True) * 2.448,
        rtol=1e-5)
    np.testing.assert_allclose(np.asarray(share).sum(-1), 2.448, rtol=1e-5)
    _, ref_weight = ref.route(cfg, rc.Matmul("float32"), tokens[None], p,
                              jnp.asarray(bias))
    np.testing.assert_allclose(np.sort(np.asarray(ref_weight[0]), -1),
                               np.sort(np.asarray(share), -1), rtol=1e-5)


def test_the_bias_is_a_constant_and_no_gradient_reaches_it():
    """The layer holds no leaf for b; a b of zeros fed in and no b at all
    compute alike, and the output has no gradient with respect to b."""
    cfg, p, z = sublayer_inputs()
    params, _ = layer(ALL).init(jax.random.key(0), (64,), jnp.float32)
    assert sorted(params) == ["Wg", "down", "gate", "shared_down",
                              "shared_gate", "shared_up", "up"]
    bare = {k: v for k, v in p.items() if k != "norm"}
    y0, _ = layer(ALL).apply(dict(bare, bias=jnp.zeros(8)), {}, z)
    y1, _ = layer(ALL).apply(bare, {}, z)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    bias = jnp.asarray(cfg["correction_bias"][0], jnp.float32)
    grad = jax.grad(lambda b: jnp.sum(
        layer(ALL).apply(dict(bare, bias=b), {}, z)[0]))(bias)
    np.testing.assert_array_equal(np.asarray(grad), np.zeros(8))


# -- the program's names and the trainer ---------------------------------------

def test_the_sub_scopes_hold_the_latent_and_the_shared_experts():
    """In the compiled step, as the readers walk it: every instruction of
    ``mla_latent`` is in ``attn`` and every one of ``moe_shared`` in
    ``mlp``, and there are some of each."""
    from deeplearning4j_tpu.observability.runtime import scopes_of_hlo

    assert {vocab.SCOPE_MLA_LATENT, vocab.SCOPE_MOE_SHARED} <= set(
        vocab.SUB_SCOPES)
    cfg = tiny_cfg()
    model = program(cfg)
    params = model.init(0)["params"]
    text = jax.jit(jax.grad(lambda p: model.loss_fn(p, {}, batch(cfg))[0])
                   ).lower(params).compile().as_text()
    _, scopes = scopes_of_hlo(text)
    _, subs = scopes_of_hlo(text, scope_of=vocab.subscope_of)
    for sub, component in ((vocab.SCOPE_MLA_LATENT, "attn"),
                           (vocab.SCOPE_MOE_SHARED, "mlp")):
        inside = [name for name, s in subs.items() if s == sub]
        assert inside, sub
        assert {scopes[name] for name in inside} == {component}, sub
    assert vocab.subscope_of(
        "jit(step)/transpose(jvp(mlp))/moe_shared/dot_general"
    ) == "moe_shared"


def test_fit_publishes_what_the_last_step_routed(monkeypatch):
    """Through ``Trainer.fit``, mixed precision: the loss falls, and the
    experts' counters of the last step are in the process table as the
    fit returns, one row an expert layer."""
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.observability import runtime
    from deeplearning4j_tpu.train.listeners import TrainingListener
    from deeplearning4j_tpu.train.trainer import Trainer
    from deeplearning4j_tpu.train.updaters import Adam

    monkeypatch.setattr(runtime, "_STEP_COUNTERS", {})
    cfg = tiny_cfg((0, 1, 2, 3))
    model = program(cfg, net=NeuralNetConfiguration(
        updater=Adam(lr=3e-3), mixed_precision=True))
    trainer = Trainer(model)
    ts = trainer.init_state()
    feed = [batch(cfg, 1) for _ in range(4)]

    class Keep(TrainingListener):
        seen = []

        def on_iteration(self, epoch, step, ts, metrics):
            self.seen.append(metrics)
            return False

    ts = trainer.fit(ts, feed, listeners=[Keep()])
    losses = [float(m["total_loss"]) for m in Keep.seen]
    assert losses[-1] < losses[0]
    counters = runtime.step_counters()
    assert set(counters) == set(vocab.MOE_COUNTERS)
    here = np.asarray(Keep.seen[-1][vocab.COUNTER_MOE_TOKENS_HERE])
    assert here.shape == (2, 4) and here.dtype == np.int32
    assert counters[vocab.COUNTER_MOE_TOKENS_HERE] == here.tolist()
