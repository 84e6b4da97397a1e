"""SmallThinker's decoder at a tiny size on the CPU, seeded random weights,
float32: the program (``models/smallthinker.py``,
``grouped_query_attention`` with and without positions and a window,
``RoutedExperts`` at top-3 of 8 ReLU-gated experts behind a linear router
that reads the layer's input) against its plain reference
(``benchmark/configs/smallthinker_reference.py``), and what the cut to a
chip's share of the experts must keep true."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.configs import reference_common as rc
from benchmark.configs import smallthinker_reference as ref
from deeplearning4j_tpu.models.smallthinker import (
    smallthinker_21b_a3b,
    smallthinker_tiny,
)
from deeplearning4j_tpu.nn.layers import attention as attn
from deeplearning4j_tpu.nn.layers.moe import RoutedExperts
from deeplearning4j_tpu.observability import vocab

ROWS, SEQ, WINDOW = 2, 32, 8
ALL = tuple(range(8))
SCALE = 0.5  # of the two projections that write into the residual stream
PERIOD = [0, 1, 1, 1]


def tiny_cfg(held=ALL, layers=4, window=WINDOW):
    return {
        "hidden_size": 64, "head_dim": 16, "num_attention_heads": 6,
        "num_key_value_heads": 2, "moe_ffn_hidden_size": 32,
        "moe_num_primary_experts_total": 8,
        "moe_num_primary_experts": len(held), "experts_held": list(held),
        "moe_num_active_primary_experts": 3, "num_hidden_layers": layers,
        "vocab_size": 96, "rms_norm_eps": 1e-6, "rope_theta": 1.5e6,
        "rope_layout": PERIOD * 13, "sliding_window_layout": PERIOD * 13,
        "sliding_window_size": window, "initializer_range": 0.2,
        "residual_init_scale": SCALE,
    }


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


@pytest.mark.parametrize("held", [ALL, (0, 1, 2, 3), (1, 6)])
def test_loss_and_every_gradient_match_the_reference(held):
    cfg = tiny_cfg(held)
    params = seeded(cfg)
    rows = batch(cfg)
    model = smallthinker_tiny(experts_held=held, residual_init_scale=SCALE)
    assert (jax.tree_util.tree_structure(model.init(0)["params"])
            == jax.tree_util.tree_structure(params))
    want, want_grads = jax.value_and_grad(
        lambda p: reference_loss(cfg, p, rows))(params)
    got, got_grads = jax.value_and_grad(
        lambda p: model.loss_fn(p, {}, rows)[0])(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    flat_want = jax.tree_util.tree_flatten_with_path(want_grads)[0]
    flat_got = jax.tree_util.tree_leaves(got_grads)
    scale = max(float(jnp.max(jnp.abs(g))) for _, g in flat_want)
    for (path, w), g in zip(flat_want, flat_got):
        assert g.shape == w.shape
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-4, atol=1e-5 * scale,
            err_msg=jax.tree_util.keystr(path))
        assert np.any(np.asarray(g)), jax.tree_util.keystr(path)


def test_init_has_the_references_leaves():
    cfg = tiny_cfg((0, 1, 2))
    model = smallthinker_tiny(experts_held=(0, 1, 2))
    made = model.init(0)["params"]
    sizes = rc.leaf_sizes(ref.param_shapes(cfg))
    got = {jax.tree_util.keystr(p): leaf.size for p, leaf in
           jax.tree_util.tree_flatten_with_path(made)[0]}
    assert got == sizes
    assert model.num_params({"params": made}) == sum(sizes.values())
    assert sorted(k for k in made if k.startswith("layer_")) == [
        f"layer_{i}" for i in range(4)]
    moe = made["layer_1"]["moe"]
    assert moe["gate"].shape == (3, 64, 32) and moe["Wg"].shape == (64, 8)
    assert sorted(made["layer_0"]["attn"]) == ["Wk", "Wo", "Wq", "Wv", "norm"]
    assert made["head"]["out"] is not made["embeddings"]["word"]  # untied


def test_the_published_sizes_and_the_layers_kinds():
    """The factory's defaults are the published config; the layers' kinds
    come from the two lists, and the lists' default is the published
    period."""
    c = smallthinker_21b_a3b().config
    assert (c.hidden, c.num_layers, c.num_heads, c.num_kv_heads, c.head_dim,
            c.experts_total, c.experts_per_token, c.expert_units,
            c.sliding_window, c.rope_theta, c.vocab_size) == (
        2560, 52, 28, 4, 128, 64, 6, 768, 4096, 1.5e6, 151936)
    assert c.rope_layout == c.sliding_window_layout == tuple(PERIOD * 13)
    cut = smallthinker_21b_a3b(num_layers=4, experts_held=ALL,
                               vocab_size=18992)
    shapes = jax.eval_shape(cut.init, 0)["params"]
    assert sum(leaf.size for leaf in
               jax.tree_util.tree_leaves(shapes)) == 370_547_200
    own = smallthinker_tiny(rope_layout=(1, 0, 0, 1),
                            sliding_window_layout=(1, 1, 0, 0)).config
    assert own.rope_layout == (1, 0, 0, 1)
    with pytest.raises(ValueError, match="rope_layout"):
        smallthinker_tiny(rope_layout=(0, 1))


# -- attention ----------------------------------------------------------------

def attention_inputs(seed=5):
    cfg = tiny_cfg()
    p = seeded(cfg, seed)["layer_1"]["attn"]
    h = jax.random.normal(jax.random.key(seed + 1), (ROWS, SEQ, 64))
    return cfg, p, h


@pytest.mark.parametrize("positions", [False, True])
@pytest.mark.parametrize("window", [None, 1, 5, WINDOW, SEQ, 3 * SEQ])
def test_each_kind_of_layer_is_the_references(positions, window):
    """Rotary or no positions at all, a window or none, seven... here three
    query heads to a key-value head."""
    cfg, p, h = attention_inputs()
    want = ref.attention(cfg, rc.Matmul("float32"), h, p,
                         positions=positions, window=window)
    got, counted = attn.grouped_query_attention(
        p, h, num_heads=6, num_kv_heads=2,
        rope_theta=cfg["rope_theta"] if positions else None, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)
    assert int(counted["pairs_required"]) == ROWS * ref.attended_pairs(
        SEQ, window)
    assert int(counted["pairs_touched"]) == ROWS * SEQ * SEQ  # XLA: all


def test_a_layer_without_positions_sees_none():
    """Reversing the order of the keys a query can see changes nothing in
    a layer without rotary positions, and changes a layer with them."""
    cfg, p, h = attention_inputs()
    # the last query sees every key: permute the others' rows
    perm = np.concatenate([np.arange(SEQ - 1)[::-1], [SEQ - 1]])

    def last(h, theta):
        y, _ = attn.grouped_query_attention(
            p, h, num_heads=6, num_kv_heads=2, rope_theta=theta, window=None)
        return np.asarray(y[:, -1])

    np.testing.assert_allclose(last(h, None), last(h[:, perm], None),
                               rtol=1e-4, atol=1e-6)
    assert not np.allclose(last(h, 1.5e6), last(h[:, perm], 1.5e6),
                           rtol=1e-2, atol=1e-4)


def test_the_window_counts_the_querys_own_position():
    """Keys at the window's length or further behind a query change
    nothing it gets; the key just inside does."""
    cfg, p, h = attention_inputs()

    def last(h):
        y, _ = attn.grouped_query_attention(
            p, h, num_heads=6, num_kv_heads=2, rope_theta=None, window=WINDOW)
        return np.asarray(y[:, -1])

    outside = h.at[:, :SEQ - WINDOW].set(0.0)
    np.testing.assert_allclose(last(h), last(outside), rtol=1e-5, atol=1e-7)
    inside = h.at[:, SEQ - WINDOW].set(0.0)
    assert not np.allclose(last(h), last(inside), rtol=1e-3, atol=1e-5)


def test_the_sub_scopes_tell_the_two_kinds_apart():
    assert {vocab.SCOPE_ATTN_WINDOW, vocab.SCOPE_ATTN_GLOBAL} <= set(
        vocab.SUB_SCOPES)
    model = smallthinker_tiny()
    params = model.init(0)["params"]
    rows = batch(tiny_cfg())
    text = jax.jit(jax.grad(lambda p: model.loss_fn(p, {}, rows)[0])).lower(
        params).as_text(debug_info=True)
    for scope, kernels in ((vocab.SCOPE_ATTN_GLOBAL, 1),
                           (vocab.SCOPE_ATTN_WINDOW, 3)):
        inside = [line for line in text.splitlines()
                  if f"/{scope}/" in line or f"({scope})" in line]
        assert inside, scope
        assert all("attn" in line for line in inside)
    assert vocab.subscope_of(
        "jit(step)/transpose(jvp(attn))/attn_window/dot_general"
    ) == "attn_window"
    assert vocab.scope_of(
        "jit(step)/transpose(jvp(attn))/attn_window/dot_general") == "attn"
    assert vocab.subscope_of("jit(step)/mlp/moe_route/sort") == "moe_route"


# -- the expert sub-layer: the shares add up ----------------------------------

def sublayer_inputs(seed=11):
    cfg = tiny_cfg()
    p = seeded(cfg, seed)["layer_2"]["moe"]
    kx, k1 = jax.random.split(jax.random.key(seed + 1))
    x = jax.random.normal(kx, (ROWS, SEQ, 64))       # the layer's input
    x1 = x + jax.random.normal(k1, (ROWS, SEQ, 64))  # after attention
    return cfg, p, x, x1


def share_of(p, held):
    held = np.asarray(held)
    return dict(p, gate=p["gate"][held], up=p["up"][held],
                down=p["down"][held])


def layer(held):
    return RoutedExperts(experts_total=8, experts_held=tuple(held), units=32,
                         top_k=3, router="linear", gate_activation="relu")


def program_sublayer(p, x, x1, held):
    g = ref.rms_norm(x1, p["norm"], 1e-6)
    p = {k: v for k, v in share_of(p, held).items() if k != "norm"}
    return layer(held).apply(p, {}, g, router_input=x)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One expert a chip, eight chips: the eight partial outputs sum to
    the uncut reference's; the router is computed alike in all and the
    weights are normalised over all three of a token's experts wherever
    they are held."""
    cfg, p, x, x1 = sublayer_inputs()
    mm = rc.Matmul("float32")
    whole = ref.expert_sublayer(cfg, mm, x, x1, p)
    parts, states = zip(*[program_sublayer(p, x, x1, (e,)) for e in ALL])
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=1e-4, atol=1e-6)
    landed = np.concatenate([np.asarray(s["tokens_here"]) for s in states])
    assert landed.sum() == 3 * ROWS * SEQ  # every pair landed on one chip
    assert np.count_nonzero(landed) >= 6, landed
    for held in ((0, 1, 2, 3), (4, 5, 6, 7), (1, 6)):
        cut = dict(cfg, experts_held=list(held))
        want = ref.expert_sublayer(cut, mm, x, x1, share_of(p, held))
        got, _ = program_sublayer(p, x, x1, held)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-6)
        assert np.any(np.asarray(got) != 0)


def test_the_router_reads_the_rows_it_is_given_and_the_gate_is_relu():
    cfg, p, x, x1 = sublayer_inputs()
    mm = rc.Matmul("float32")
    on_input, _ = program_sublayer(p, x, x1, ALL)
    on_own, _ = program_sublayer(p, x1, x1, ALL)
    assert not np.allclose(np.asarray(on_input), np.asarray(on_own),
                           rtol=1e-2, atol=1e-4)
    g = ref.rms_norm(x1, p["norm"], 1e-6)
    bare = {k: v for k, v in p.items() if k != "norm"}
    silu, _ = RoutedExperts(
        experts_total=8, experts_held=ALL, units=32, top_k=3,
        router="linear").apply(bare, {}, g, router_input=x)
    assert not np.allclose(np.asarray(on_input), np.asarray(silu),
                           rtol=1e-2, atol=1e-4)
    # the weights are the softmax over the chosen logits
    chosen, weight = ref.route(cfg, mm, x, p)
    np.testing.assert_allclose(np.asarray(weight.sum(-1)), 1.0, rtol=1e-6)
    z = np.asarray(mm("nte,ex->ntx", x, p["Wg"]))
    top = np.sort(z, axis=-1)[..., -3:][..., ::-1]
    np.testing.assert_allclose(
        np.asarray(weight), np.exp(top) / np.exp(top).sum(-1, keepdims=True),
        rtol=1e-5)
    with pytest.raises(ValueError, match="gate_activation"):
        RoutedExperts(gate_activation="gelu")


def test_without_router_input_the_layer_traces_what_it_traced():
    """``router_input=None`` and ``router_input`` equal to the input, with
    the default gate: one trace, operation for operation."""
    cfg, p, x, x1 = sublayer_inputs()
    bare = {k: v for k, v in p.items() if k != "norm"}
    experts = RoutedExperts(experts_total=8, experts_held=(0, 1, 2, 3),
                            units=32, top_k=3, router="linear")
    held = share_of(bare, (0, 1, 2, 3))
    before = jax.make_jaxpr(lambda p, g: experts.apply(p, {}, g))(held, x1)
    same = jax.make_jaxpr(
        lambda p, g: experts.apply(p, {}, g, router_input=g))(held, x1)
    assert str(before) == str(same)


# -- through the trainer ------------------------------------------------------

def test_fit_publishes_what_the_last_step_routed_and_attended(monkeypatch):
    """Through ``Trainer.fit``, mixed precision: the loss falls, and the
    counters of the last step are in the process table as the fit
    returns."""
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.observability import runtime
    from deeplearning4j_tpu.train.listeners import TrainingListener
    from deeplearning4j_tpu.train.trainer import Trainer
    from deeplearning4j_tpu.train.updaters import Adam

    monkeypatch.setattr(runtime, "_STEP_COUNTERS", {})
    model = smallthinker_tiny(experts_held=(0, 1, 2, 3),
                              net=NeuralNetConfiguration(
                                  updater=Adam(lr=3e-3),
                                  mixed_precision=True))
    trainer = Trainer(model)
    ts = trainer.init_state()
    feed = [batch(tiny_cfg((0, 1, 2, 3)), 1) for _ in range(4)]

    class Keep(TrainingListener):
        seen = []

        def on_iteration(self, epoch, step, ts, metrics):
            self.seen.append(metrics)
            return False

    ts = trainer.fit(ts, feed, listeners=[Keep()])
    losses = [float(m["total_loss"]) for m in Keep.seen]
    assert losses[-1] < losses[0]
    counters = runtime.step_counters()
    assert set(counters) == set(vocab.MOE_COUNTERS + vocab.SWA_COUNTERS)
    here = np.asarray(Keep.seen[-1][vocab.COUNTER_MOE_TOKENS_HERE])
    assert here.shape == (4, 4) and here.dtype == np.int32
    assert counters[vocab.COUNTER_MOE_TOKENS_HERE] == here.tolist()
    full, windowed = (ROWS * ref.attended_pairs(SEQ, w)
                      for w in (None, WINDOW))
    assert counters[vocab.COUNTER_SWA_PAIRS_REQUIRED] == [
        full, windowed, windowed, windowed]
    assert counters[vocab.COUNTER_SWA_PAIRS_TOUCHED] == [ROWS * SEQ * SEQ] * 4


def test_on_the_chips_path_the_counters_follow_the_tile_plans(monkeypatch):
    """With the flash kernels in the path (interpreted here) the pairs
    touched are the plans', a layer of each kind."""
    from deeplearning4j_tpu.kernels import flash_attention as fa

    monkeypatch.setenv("DL4J_TPU_FORCE_PALLAS", "1")
    cfg, p, h = attention_inputs()
    h = jnp.tile(h, (1, 4, 1))  # 128 positions
    for window in (None, 40):
        want = ref.attention(cfg, rc.Matmul("float32"), h, p,
                             positions=True, window=window)
        got, counted = attn.grouped_query_attention(
            p, h, num_heads=6, num_kv_heads=2, rope_theta=cfg["rope_theta"],
            window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)
        plan = fa.TilePlan(128, 128, 128, 128, True, window=window)
        assert int(counted["pairs_touched"]) == ROWS * plan.pairs_touched()
        assert int(counted["pairs_required"]) == ROWS * ref.attended_pairs(
            128, window)
