"""ZAYA1 at a tiny size on the CPU, seeded random weights, float32: the
program (``models/zaya.py``, ``cca_attention``, ``RoutedExperts``) against
its plain reference (``benchmark/configs/zaya1_reference.py``), and what
the cut to a chip's share of the experts must keep true."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.configs import reference_common as rc
from benchmark.configs import zaya1_reference as ref
from deeplearning4j_tpu.models.zaya import zaya_tiny
from deeplearning4j_tpu.nn.layers.moe import RoutedExperts

ROWS, SEQ = 2, 32


def tiny_cfg(held=(0, 1, 2, 3), layers=2):
    return {
        "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
        "num_key_value_heads": 2, "router_hidden_size": 16,
        "moe_intermediate_size": 64, "num_experts_total": 4,
        "experts_held": list(held), "cca_time0": 2, "cca_time1": 2,
        "num_hidden_layers": layers, "vocab_size": 96,
        "rms_norm_eps": 1e-5, "initializer_range": 0.2,
        "rope_parameters": {"hybrid": {"rope_theta": 5000000,
                                       "partial_rotary_factor": 0.5}},
    }


def seeded(cfg, seed=7):
    """Every leaf random, the ones and zeros too, so that no term of the
    model drops out of the comparison (gamma, tau, the residual scales)."""
    shapes = ref.param_shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[1], str))
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    out = []
    for key, (shape, kind) in zip(keys, leaves):
        noise = cfg["initializer_range"] * jax.random.normal(key, shape)
        out.append(noise + (1.0 if kind == "ones" else 0.0))
    params = jax.tree_util.tree_unflatten(treedef, out)
    for i in range(cfg["num_hidden_layers"]):
        # a bias that tilts the choice and leaves every expert its tokens
        params[f"layer_{i}"]["moe"]["bias"] *= 0.02
    return params


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


@pytest.mark.parametrize("held", [(0, 1, 2, 3), (0, 1), (1, 3)])
def test_loss_and_every_gradient_match_the_reference(held):
    cfg = tiny_cfg(held)
    params = seeded(cfg)
    rows = batch(cfg)
    model = zaya_tiny(experts_held=held)
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
            np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-6 * scale,
            err_msg=jax.tree_util.keystr(path))
    # the balancing bias moves the argmax and no gradient reaches it
    assert not np.any(np.asarray(got_grads["layer_1"]["moe"]["bias"]))


def test_init_has_the_references_leaves():
    cfg = tiny_cfg((0, 1))
    model = zaya_tiny(experts_held=(0, 1))
    made = model.init(0)["params"]
    sizes = rc.leaf_sizes(ref.param_shapes(cfg))
    got = {jax.tree_util.keystr(p): leaf.size for p, leaf in
           jax.tree_util.tree_flatten_with_path(made)[0]}
    assert got == sizes
    assert model.num_params({"params": made}) == sum(sizes.values())
    moe = made["layer_1"]["moe"]
    assert moe["gate"].shape == (2, 64, 64) and moe["Wc"].shape == (16, 4)
    assert "gamma" in moe and "gamma" not in made["layer_0"]["moe"]
    assert float(moe["gamma"]) == 0.0 and not np.any(np.asarray(moe["bias"]))


def sublayer_inputs(seed=11):
    cfg = tiny_cfg()
    p = seeded(cfg, seed)["layer_1"]["moe"]
    kh, kr = jax.random.split(jax.random.key(seed))
    h = jax.random.normal(kh, (ROWS, SEQ, 64))
    carried = jax.random.normal(kr, (ROWS, SEQ, 16))
    return cfg, p, h, carried


def share_of(p, held):
    return dict(p, **{k: p[k][jnp.asarray(held)]
                      for k in ("gate", "up", "down")})


def program_sublayer(p, h, carried, held):
    layer = RoutedExperts(experts_total=4, experts_held=tuple(held),
                          units=64, router_hidden=16)
    return layer.apply(share_of(p, held), {"router": carried.reshape(-1, 16)},
                       h)


def test_the_shares_add_up_to_the_uncut_layer():
    """Experts {0, 1} here and {2, 3} on the other chip: the two partial
    outputs sum to the uncut reference's; nothing is counted twice (there
    is no shared expert) and the router is computed alike in both."""
    cfg, p, h, carried = sublayer_inputs()
    mm = rc.Matmul("float32")
    whole, r_whole = ref.expert_sublayer(cfg, mm, h, p, carried)
    parts, states = zip(*[program_sublayer(p, h, carried, held)
                          for held in ((0, 1), (2, 3))])
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1]),
                               np.asarray(whole), rtol=1e-4, atol=1e-6)
    for state in states:
        np.testing.assert_allclose(
            np.asarray(state["router"]).reshape(r_whole.shape),
            np.asarray(r_whole), rtol=1e-5, atol=1e-6)
    landed = np.concatenate([np.asarray(s["tokens_here"]) for s in states])
    assert landed.sum() == ROWS * SEQ  # every token landed on one chip
    assert np.count_nonzero(landed) >= 3, landed  # and not all on one expert
    # and the reference cut the same way gives each share
    for held, part in zip(((0, 1), (2, 3)), parts):
        cut = dict(cfg, experts_held=list(held))
        want, _ = ref.expert_sublayer(cut, mm, h, share_of(p, held), carried)
        np.testing.assert_allclose(np.asarray(part), np.asarray(want),
                                   rtol=1e-4, atol=1e-6)


def test_no_token_is_dropped_when_all_choose_one_expert():
    cfg, p, h, carried = sublayer_inputs()
    p = dict(p, bias=jnp.asarray([0.0, 0.0, 50.0, 0.0]))
    y, state = program_sublayer(p, h, carried, (2, 3))
    assert np.asarray(state["tokens_here"]).tolist() == [ROWS * SEQ, 0]
    want, _ = ref.expert_sublayer(dict(cfg, experts_held=[2, 3]),
                                  rc.Matmul("float32"), h,
                                  share_of(p, (2, 3)), carried)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-6)
    assert np.all(np.any(np.asarray(y) != 0, axis=-1))  # every token served
    # the same tokens on the chip that does not hold expert 2: all zeros
    y, state = program_sublayer(p, h, carried, (0, 1))
    assert not np.any(np.asarray(y))
    assert np.asarray(state["tokens_here"]).tolist() == [0, 0]


@pytest.mark.parametrize("held,crowd,ran", [
    ((2,), 2, 2), ((3,), 2, 1), ((1,), None, None), ((0, 1), 1, 1)])
def test_one_expert_a_token_walked_in_pieces_gives_the_reference(held, crowd,
                                                                 ran):
    """A quarter of the experts held: the 64 sorted tokens are two pieces
    of 32. All tokens on the expert held (both pieces run), all on one held
    elsewhere, a router's draw, and half held (one piece, as before):
    output and every gradient are the float32 reference's."""
    from deeplearning4j_tpu.nn.layers import moe

    cfg, p, h, carried = sublayer_inputs()
    if crowd is not None:
        p = dict(p, bias=jnp.zeros((4,)).at[crowd].set(50.0))
    p = share_of(p, held)
    rows_a_piece = moe._piece_rows(ROWS * SEQ, len(held), 4)
    assert rows_a_piece == (32 if len(held) == 1 else ROWS * SEQ)
    cut, mm = dict(cfg, experts_held=list(held)), rc.Matmul("float32")
    weigh = jax.random.normal(jax.random.key(9), h.shape)
    layer = RoutedExperts(experts_total=4, experts_held=held, units=64,
                          router_hidden=16)

    def want(p, h):
        return jnp.sum(weigh * ref.expert_sublayer(cut, mm, h, p, carried)[0])

    def got(p, h):
        y, state = jax.checkpoint(layer.apply)(
            p, {"router": carried.reshape(-1, 16)}, h)
        return jnp.sum(weigh * y), state

    want_loss, want_grads = jax.value_and_grad(want, argnums=(0, 1))(p, h)
    (got_loss, state), got_grads = jax.value_and_grad(
        got, argnums=(0, 1), has_aux=True)(p, h)
    landed = int(np.asarray(state["tokens_here"]).sum())
    assert int(state["pieces_run"]) == max(1, -(-landed // rows_a_piece))
    if ran is not None:
        assert int(state["pieces_run"]) == ran
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5,
                                            abs=1e-6)
    for name in want_grads[0]:
        if name in ("bias", "norm"):  # no gradient reaches the bias, and
            continue  # the norm is the model's, outside the layer
        np.testing.assert_allclose(
            np.asarray(got_grads[0][name]), np.asarray(want_grads[0][name]),
            rtol=2e-4, atol=2e-6, err_msg=name)
    np.testing.assert_allclose(np.asarray(got_grads[1]),
                               np.asarray(want_grads[1]), rtol=2e-4,
                               atol=2e-6)


def test_the_router_keeps_its_width_when_half_the_experts_are_held():
    layer = RoutedExperts(experts_total=4, experts_held=(1, 3), units=64,
                          router_hidden=16)
    params, _ = layer.init(jax.random.key(0), (64,), jnp.float32)
    assert params["Wc"].shape == (16, 4) and params["bias"].shape == (4,)
    assert params["gate"].shape == (2, 64, 64)
    tokens = jax.random.normal(jax.random.key(1), (40, 64))
    _, chosen, share = layer.route(params, tokens, jnp.zeros((40, 16)))
    full = RoutedExperts(experts_total=4, experts_held=(0, 1, 2, 3),
                         units=64, router_hidden=16)
    _, chosen_full, share_full = full.route(params, tokens,
                                            jnp.zeros((40, 16)))
    assert np.array_equal(np.asarray(chosen), np.asarray(chosen_full))
    assert np.array_equal(np.asarray(share), np.asarray(share_full))
    assert share.dtype == jnp.float32


@pytest.mark.parametrize("rows", [ROWS, 3])
def test_the_chips_grouped_product_gives_the_same_sublayer(rows, monkeypatch):
    """The kernel the chip runs (megablox ``gmm``, here interpreted), with
    the rows of no group here last and, for 3 x 32 tokens, rows added to
    make whole tiles: the same output and gradients as XLA's product."""
    _, p, _, _ = sublayer_inputs()
    kh, kr = jax.random.split(jax.random.key(5))
    h = jax.random.normal(kh, (rows, SEQ, 64))
    carried = jax.random.normal(kr, (rows, SEQ, 16))

    def run(p, h):
        y, state = program_sublayer(p, h, carried, (1, 3))
        return jnp.sum(jnp.square(y)), state["tokens_here"]

    step = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)
    (want, landed), want_grads = step(p, h)
    monkeypatch.setenv("DL4J_TPU_FORCE_PALLAS", "1")
    (got, landed_kernel), got_grads = step(p, h)
    assert 0 < int(landed.sum()) < rows * SEQ
    assert np.array_equal(np.asarray(landed), np.asarray(landed_kernel))
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for w, g in zip(jax.tree_util.tree_leaves(want_grads),
                    jax.tree_util.tree_leaves(got_grads)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-5)


@pytest.mark.parametrize("product", ["jax.lax.ragged_dot", "megablox.gmm"])
def test_the_rows_of_no_group_come_back_as_zeros(product, monkeypatch):
    """What ``_grouped`` promises of either product: a group's rows times
    its own matrix, and zeros, not what was there and not NaN, for the
    rows whose expert is held elsewhere, in the result and in the rows'
    gradient."""
    from deeplearning4j_tpu.nn.layers import moe

    if product == "megablox.gmm":
        monkeypatch.setenv("DL4J_TPU_FORCE_PALLAS", "1")
    kr, kw = jax.random.split(jax.random.key(11))
    rows = jax.random.normal(kr, (24, 16)) + 3.0
    weights = jax.random.normal(kw, (2, 16, 8))
    sizes = jnp.array([7, 6, 11], jnp.int32)  # 11 rows of no group here
    out, pull = jax.vjp(lambda r: moe._grouped(r, weights, sizes), rows)
    want = np.concatenate([np.asarray(rows[:7] @ weights[0]),
                           np.asarray(rows[7:13] @ weights[1])])
    np.testing.assert_allclose(np.asarray(out[:13]), want, rtol=1e-5,
                               atol=1e-5)
    assert np.array_equal(np.asarray(out[13:]), np.zeros((11, 8)))
    back, = pull(jnp.ones_like(out))
    assert np.all(np.asarray(back[:13]) != 0)
    assert np.array_equal(np.asarray(back[13:]), np.zeros((11, 16)))


def test_fit_publishes_the_last_steps_expert_load(monkeypatch):
    """Through ``Trainer.fit``, mixed precision: the loss falls, and the
    counters of the last step are in the process table as the fit returns
    (one read, after the loop), with the product's flight event."""
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.observability import runtime, vocab
    from deeplearning4j_tpu.observability.flightrecorder import (
        FlightRecorder,
        get_flight_recorder,
        set_flight_recorder,
    )
    from deeplearning4j_tpu.train.listeners import TrainingListener
    from deeplearning4j_tpu.train.trainer import Trainer
    from deeplearning4j_tpu.train.updaters import Adam

    monkeypatch.setattr(runtime, "_STEP_COUNTERS", {})
    before = get_flight_recorder()
    flight = set_flight_recorder(FlightRecorder())
    try:
        model = zaya_tiny(experts_held=(0, 1), net=NeuralNetConfiguration(
            updater=Adam(lr=3e-3), mixed_precision=True))
        trainer = Trainer(model)
        ts = trainer.init_state()
        feed = [batch(tiny_cfg((0, 1)), seed) for seed in (1, 1, 1, 1)]

        class Keep(TrainingListener):
            seen = []

            def on_iteration(self, epoch, step, ts, metrics):
                self.seen.append(metrics)
                return False

        ts = trainer.fit(ts, feed, listeners=[Keep()])
    finally:
        set_flight_recorder(before)
    losses = [float(m["total_loss"]) for m in Keep.seen]
    assert losses[-1] < losses[0]
    counters = runtime.step_counters()
    assert set(counters) == set(vocab.MOE_COUNTERS)  # no indexer: no dsa.*
    last = Keep.seen[-1]
    here = np.asarray(last[vocab.COUNTER_MOE_TOKENS_HERE])
    assert here.shape == (2, 2) and here.dtype == np.int32
    assert counters[vocab.COUNTER_MOE_TOKENS_HERE] == here.tolist()
    want = np.mean(here.max(axis=1) / np.maximum(here.mean(axis=1), 1.0))
    assert counters[vocab.COUNTER_MOE_LOAD] == pytest.approx(want, rel=1e-6)
    events = flight.events(kinds=["kernel.grouped_product"])
    assert events and events[0]["data"]["product"] == "jax.lax.ragged_dot"
    assert events[0]["data"]["groups"] == 2
    # half of the experts held: the sorted tokens are one piece, which ran
    assert counters[vocab.COUNTER_MOE_PIECES_RUN] == [1, 1]  # a layer
    assert events[0]["data"]["rows_a_piece"] == ROWS * SEQ
    assert events[0]["data"]["pieces"] == 1
    assert events[0]["data"]["combine"] == "inverse_gather"
    assert "kernel.grouped_product" in vocab.known_event_kinds()


def test_a_fit_without_counters_publishes_none(monkeypatch):
    from deeplearning4j_tpu.models.gpt import gpt_tiny
    from deeplearning4j_tpu.observability import runtime
    from deeplearning4j_tpu.train.trainer import Trainer

    monkeypatch.setattr(runtime, "_STEP_COUNTERS", {})
    model = gpt_tiny()
    trainer = Trainer(model)
    ids = np.random.default_rng(0).integers(0, 128, (2, 16)).astype(np.int32)
    trainer.fit(trainer.init_state(), [{"features": {"token_ids": ids}}])
    assert runtime.step_counters() == {}
