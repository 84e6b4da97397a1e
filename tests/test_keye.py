"""Keye-VL-2.0's language model at a tiny size on the CPU, seeded random
weights, float32: the program (``models/keye.py``, ``indexed_attention``,
``RoutedExperts`` at top-2 behind a linear router) against its plain
reference (``benchmark/configs/keye_vl2_reference.py``), and what the cut
to a chip's share of the experts must keep true."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.configs import keye_vl2_reference as ref
from benchmark.configs import reference_common as rc
from deeplearning4j_tpu.kernels.flash_attention import reference_attention
from deeplearning4j_tpu.models.keye import keye_tiny
from deeplearning4j_tpu.nn.layers import attention as attn
from deeplearning4j_tpu.nn.layers import moe
from deeplearning4j_tpu.nn.layers.moe import RoutedExperts

ROWS, SEQ, TOP_K = 2, 32, 8
ALL = tuple(range(8))
SCALE = 0.5  # of the two projections that write into the residual stream


def tiny_cfg(held=ALL, layers=2, top_k=TOP_K):
    return {
        "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
        "num_key_value_heads": 2, "moe_intermediate_size": 32,
        "num_experts_total": 8, "num_experts": len(held),
        "experts_held": list(held), "num_experts_per_tok": 2,
        "num_hidden_layers": layers, "vocab_size": 96,
        "rms_norm_eps": 1e-6, "rope_theta": 1e7, "initializer_range": 0.2,
        "residual_init_scale": SCALE,
        "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                      "indexer_num_kv_heads": 1, "topk": top_k,
                      "q_chunk_size": 512, "kv_chunk_size": 512},
    }


def seeded(cfg, seed=7):
    """Every leaf random, the ones and zeros too, so that no term of the
    model drops out of the comparison (the gains, the indexer's bias)."""
    shapes = ref.param_shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[1], str))
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        cfg["initializer_range"] * jax.random.normal(key, shape)
        + (1.0 if kind == "ones" else 0.0)
        for key, (shape, kind) in zip(keys, leaves)])


def layer_of(params, i):
    return params[f"layer_{i}"]


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
    model = keye_tiny(experts_held=held, residual_init_scale=SCALE)
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
    # the indexer chooses and is not trained: nought in both
    for grads in (want_grads, got_grads):
        for i in range(2):
            for leaf in jax.tree_util.tree_leaves(
                    grads[f"layer_{i}"]["attn"]["index"]):
                assert not np.any(np.asarray(leaf))
    # and the rest of the attention sub-layer is, in every layer
    for i in range(2):
        assert np.any(np.asarray(got_grads[f"layer_{i}"]["attn"]["q_norm"]))


def test_init_has_the_references_leaves():
    cfg = tiny_cfg((0, 1, 2))
    model = keye_tiny(experts_held=(0, 1, 2))
    made = model.init(0)["params"]
    sizes = rc.leaf_sizes(ref.param_shapes(cfg))
    got = {jax.tree_util.keystr(p): leaf.size for p, leaf in
           jax.tree_util.tree_flatten_with_path(made)[0]}
    assert got == sizes
    assert model.num_params({"params": made}) == sum(sizes.values())
    assert sorted(k for k in made if k.startswith("layer_")) == [
        "layer_0", "layer_1"]  # a tree a layer, as Zaya's
    moe = made["layer_1"]["moe"]
    assert moe["gate"].shape == (3, 64, 32)
    assert moe["Wg"].shape == (64, 8)
    assert made["head"]["out"].shape == made["embeddings"]["word"].shape
    assert made["head"]["out"] is not made["embeddings"]["word"]  # untied
    assert not np.array_equal(np.asarray(made["head"]["out"]),
                              np.asarray(made["embeddings"]["word"]))


# -- the selection --------------------------------------------------------------

def index_inputs(seed, tied, seq=SEQ):
    """qI, kI, w as the indexer would hand them on; ``tied``: whole
    numbers, so that many pairs score alike (and many score nought)."""
    kq, kk, kw = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(kq, (ROWS, seq, 2, 8))
    k = jax.random.normal(kk, (ROWS, seq, 8))
    w = jax.random.normal(kw, (ROWS, seq, 2))
    if tied:
        q, k, w = jnp.round(q), jnp.round(k), jnp.round(2 * w)
    return q, k, w


@pytest.mark.parametrize("tied", [False, True])
@pytest.mark.parametrize("top_k", [1, 5, TOP_K, SEQ - 1])
def test_the_selected_set_is_the_references(top_k, tied):
    """Rows shorter than ``top_k`` keep their whole past, the others their
    ``top_k`` best and whatever ties the last of them, as the reference's
    sort says; scored in pieces of a few rows, as at the cell's size."""
    q, k, w = index_inputs(top_k + 10 * tied, tied)
    scores = ref.index_scores(rc.Matmul("float32"), q, k, w)
    want = np.asarray(ref.selected(scores, jnp.arange(SEQ), top_k))
    got = np.asarray(attn._selected_pairs(q, k, w, top_k))
    assert got.dtype == np.int8 and got.shape == (ROWS, SEQ, SEQ)
    assert np.array_equal(got != 0, want)
    counts = want.sum(axis=-1)
    short = np.arange(SEQ) + 1 <= top_k
    assert np.array_equal(counts[:, short],
                          np.broadcast_to(np.arange(SEQ) + 1, counts.shape)
                          [:, short])
    assert np.all(counts[:, ~short] >= top_k)
    if tied:
        assert np.any(counts[:, ~short] > top_k)  # ties were among the cases
    assert not np.any(np.triu(want, 1))  # nothing from the future


def test_the_pieces_of_the_score_change_no_pair(monkeypatch):
    q, k, w = index_inputs(5, False)
    whole = np.asarray(attn._selected_pairs(q, k, w, 4))
    monkeypatch.setattr(attn, "_INDEX_ROWS", 5)
    attn._selected_pairs.clear_cache()
    try:
        pieces = np.asarray(attn._selected_pairs(q, k, w, 4))
    finally:
        monkeypatch.undo()
        attn._selected_pairs.clear_cache()
    assert np.array_equal(whole, pieces)


def test_the_models_selected_set_is_the_references():
    """Through the layer's own indexer (projections, LayerNorm, rotary,
    the weights' scale) on the model's first sub-layer."""
    cfg = tiny_cfg()
    p = layer_of(seeded(cfg), 0)["attn"]
    h = jax.random.normal(jax.random.key(2), (ROWS, SEQ, 64))
    want = ref.selected_pairs(cfg, rc.Matmul("float32"), h, p)
    _, counted = attn.indexed_attention(
        p, h, num_heads=4, num_kv_heads=2, index_heads=2, top_k=TOP_K,
        rope_theta=1e7, eps=1e-6)
    assert int(counted["pairs_selected"]) == int(np.asarray(want).sum())
    assert float(counted["tiles_empty_share"]) == 0.0


def test_at_a_sequence_no_longer_than_top_k_the_layer_is_causal_gqa():
    cfg = tiny_cfg(top_k=SEQ)
    p = layer_of(seeded(cfg), 0)["attn"]
    h = jax.random.normal(jax.random.key(4), (ROWS, SEQ, 64))
    got, counted = attn.indexed_attention(
        p, h, num_heads=4, num_kv_heads=2, index_heads=2, top_k=SEQ,
        rope_theta=1e7, eps=1e-6)
    assert int(counted["pairs_selected"]) == ROWS * SEQ * (SEQ + 1) // 2

    def heads(x, n):
        return x.reshape(ROWS, SEQ, n, 16)

    q = ref.rotary(ref.rms_norm(heads(h @ p["Wq"], 4), p["q_norm"], 1e-6),
                   1e7)
    k = ref.rotary(ref.rms_norm(heads(h @ p["Wk"], 2), p["k_norm"], 1e-6),
                   1e7)
    v = heads(h @ p["Wv"], 2)
    q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    want = reference_attention(q, jnp.repeat(k, 2, axis=1),
                               jnp.repeat(v, 2, axis=1), causal=True)
    want = want.transpose(0, 2, 1, 3).reshape(ROWS, SEQ, 64) @ p["Wo"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)
    # and the reference's sub-layer says the same
    np.testing.assert_allclose(
        np.asarray(ref.attention(cfg, rc.Matmul("float32"), h, p)),
        np.asarray(want), rtol=1e-4, atol=1e-5)


# -- the experts ----------------------------------------------------------------

def sublayer_inputs(seed=11, total=8):
    cfg = dict(tiny_cfg(tuple(range(total))), num_experts_total=total)
    p = layer_of(seeded(cfg, seed), 1)["moe"]
    h = jax.random.normal(jax.random.key(seed), (ROWS, SEQ, 64))
    return cfg, p, h


def share_of(p, held):
    return dict(p, **{k: p[k][jnp.asarray(held)]
                      for k in ("gate", "up", "down")})


def layer(held, total=8):
    return RoutedExperts(experts_total=total, experts_held=tuple(held),
                         units=32, top_k=2, router="linear")


def program_sublayer(p, h, held):
    p = {k: v for k, v in share_of(p, held).items() if k != "norm"}
    return layer(held).apply(p, {}, h)


def test_the_shares_add_up_to_the_uncut_layer():
    """Experts {0-3} here and {4-7} on the other chip: the two partial
    outputs sum to the uncut reference's; the router is computed alike in
    both and the weights are normalised over both of a token's experts
    wherever they are held."""
    cfg, p, h = sublayer_inputs()
    mm = rc.Matmul("float32")
    whole = ref.expert_sublayer(cfg, mm, h, p)
    shares = ((0, 1, 2, 3), (4, 5, 6, 7))
    parts, states = zip(*[program_sublayer(p, h, held) for held in shares])
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1]),
                               np.asarray(whole), rtol=1e-4, atol=1e-6)
    landed = np.concatenate([np.asarray(s["tokens_here"]) for s in states])
    assert landed.sum() == 2 * ROWS * SEQ  # every pair landed on one chip
    assert np.count_nonzero(landed) >= 6, landed
    assert all("router" not in s for s in states)  # no state to carry
    for held, part in zip(shares, parts):
        # a token with one expert here and one there is not the uncut
        # layer's share renormalised: it is half of it
        cut = dict(cfg, experts_held=list(held))
        want = ref.expert_sublayer(cut, mm, h, share_of(p, held))
        np.testing.assert_allclose(np.asarray(part), np.asarray(want),
                                   rtol=1e-4, atol=1e-6)
        assert np.any(np.asarray(part) != 0)


def test_no_pair_is_dropped_when_all_tokens_choose_the_same_experts():
    cfg, p, h = sublayer_inputs()
    h = jnp.abs(h)  # so that a positive column of Wg is a large logit:
    # every token's two largest are experts 2 and 5
    p = crowded(p, 2, 5)
    y, state = program_sublayer(p, h, (2, 5))
    assert np.asarray(state["tokens_here"]).tolist() == [ROWS * SEQ] * 2
    assert int(state["pieces_run"]) == 2  # of 2: every piece is full
    want = ref.expert_sublayer(dict(cfg, experts_held=[2, 5]),
                               rc.Matmul("float32"), h, share_of(p, (2, 5)))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4,
                               atol=1e-6)
    assert np.all(np.any(np.asarray(y) != 0, axis=-1))  # every token served
    # half of the pairs on a chip that holds one of the two
    y, state = program_sublayer(p, h, (5, 7))
    assert np.asarray(state["tokens_here"]).tolist() == [ROWS * SEQ, 0]
    assert int(state["pieces_run"]) == 1  # half of the pairs: one piece
    # and none where neither is held: all zeros
    y, state = program_sublayer(p, h, (0, 1))
    assert not np.any(np.asarray(y))
    assert np.asarray(state["tokens_here"]).tolist() == [0, 0]
    assert int(state["pieces_run"]) == 1  # the first runs whatever lands


def crowded(p, first, second=None):
    """The router's matrix with every token's largest logit on expert
    ``first`` and, if given, its next on ``second``; the input is made
    positive, so that a positive column is a large logit."""
    wg = (0.01 * p["Wg"]).at[:, first].set(1.0)
    return dict(p, Wg=wg if second is None else wg.at[:, second].set(0.9))


# experts in all, held, the experts all tokens choose, rows of a piece,
# pieces, pieces run
_WALKS = {
    "fills_both_of_2": (8, (2, 5), (2, 5), 64, 2, 2),
    "fits_the_first_of_2": (8, (5, 7), (2, 5), 64, 2, 1),
    # every token's first choice and some second choices
    "spills_into_the_second_of_2": (8, (5, 7), (5,), 64, 2, 2),
    "fills_2_of_4": (8, (5,), (2, 5), 32, 4, 2),
    "fills_all_4": (16, (2, 5), (2, 5), 32, 4, 4),
    "a_draw_fits_the_first_of_4": (8, (3,), None, 32, 4, 1),
    "the_last_piece_is_padded": (8, (0, 2, 5), (2, 5), 96, 2, 2),
    "nothing_lands": (8, (0, 1), (2, 5), 64, 2, 1),
}


@pytest.mark.parametrize("walk", sorted(_WALKS))
def test_the_walk_in_pieces_gives_the_references_sublayer(walk):
    """The sorted pairs walked in 2 and in 4 pieces, at loads that fit the
    first piece, spill into the next and fill all of them, and where the
    pairs are no whole number of pieces: output, ``tokens_here`` and every
    gradient are the float32 reference's, and ``pieces_run`` counts the
    pieces a landed pair lies in (the first runs whatever lands)."""
    total, held, chosen, rows_a_piece, pieces, ran = _WALKS[walk]
    cfg, p, h = sublayer_inputs(total=total)
    if chosen is not None:
        h, p = jnp.abs(h), crowded(p, *chosen)
    p = {k: v for k, v in share_of(p, held).items() if k != "norm"}
    pairs = 2 * ROWS * SEQ
    assert moe._piece_rows(pairs, len(held), total) == rows_a_piece
    assert -(-pairs // rows_a_piece) == pieces
    cut = dict(cfg, experts_held=list(held))
    mm = rc.Matmul("float32")
    weigh = jax.random.normal(jax.random.key(9), h.shape)

    def want(p, h):
        return jnp.sum(weigh * ref.expert_sublayer(cut, mm, h, p))

    def got(p, h):
        y, state = jax.checkpoint(layer(held, total).apply)(p, {}, h)
        return jnp.sum(weigh * y), state

    want_loss, want_grads = jax.value_and_grad(want, argnums=(0, 1))(p, h)
    (got_loss, state), got_grads = jax.value_and_grad(
        got, argnums=(0, 1), has_aux=True)(p, h)
    landed = int(np.asarray(state["tokens_here"]).sum())
    assert int(state["pieces_run"]) == ran
    assert ran == max(1, -(-landed // rows_a_piece))
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5,
                                            abs=1e-6)
    flat_want = jax.tree_util.tree_flatten_with_path(want_grads)[0]
    scale = max(float(jnp.max(jnp.abs(g))) for _, g in flat_want)
    for (path, w), g in zip(flat_want, jax.tree_util.tree_leaves(got_grads)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-6 * scale,
            err_msg=jax.tree_util.keystr(path))


def test_the_router_keeps_its_width_when_a_part_is_held():
    part = layer((1, 3))
    params, _ = part.init(jax.random.key(0), (64,), jnp.float32)
    assert params["Wg"].shape == (64, 8) and "bias" not in params
    assert params["gate"].shape == (2, 64, 32)
    assert set(params) == {"Wg", "gate", "up", "down"}
    tokens = jax.random.normal(jax.random.key(1), (40, 64))
    state, chosen, share = part.route(params, tokens, None)
    _, chosen_all, share_all = layer(ALL).route(params, tokens, None)
    assert state is None and chosen.shape == share.shape == (40, 2)
    assert np.array_equal(np.asarray(chosen), np.asarray(chosen_all))
    assert np.array_equal(np.asarray(share), np.asarray(share_all))
    assert share.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(share.sum(-1)), 1.0, rtol=1e-6)
    assert np.all(np.asarray(chosen[:, 0] != chosen[:, 1]))


@pytest.mark.parametrize("rows,pieces_run", [(ROWS, 1), (3, 1), (ROWS, 2)])
def test_the_chips_grouped_product_gives_the_same_sublayer(rows, pieces_run,
                                                           monkeypatch):
    """The kernel the chip runs (megablox ``gmm``, here interpreted) over
    the sorted pairs, those of no group here last, in two pieces of which
    a router's draw fills the first and a crowd on two of the experts
    held both: the same output and gradients as XLA's product."""
    _, p, _ = sublayer_inputs()
    h = jax.random.normal(jax.random.key(5), (rows, SEQ, 64))
    if pieces_run == 2:
        h, p = jnp.abs(h), crowded(p, 3, 6)

    def run(p, h):
        y, state = program_sublayer(p, h, (1, 3, 6))
        return jnp.sum(jnp.square(y)), (state["tokens_here"],
                                        state["pieces_run"])

    step = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)
    (want, (landed, ran)), want_grads = step(p, h)
    monkeypatch.setenv("DL4J_TPU_FORCE_PALLAS", "1")
    (got, (landed_kernel, ran_kernel)), got_grads = step(p, h)
    assert int(ran) == int(ran_kernel) == pieces_run
    assert 0 < int(landed.sum()) <= 2 * rows * SEQ
    assert np.array_equal(np.asarray(landed), np.asarray(landed_kernel))
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for w, g in zip(jax.tree_util.tree_leaves(want_grads),
                    jax.tree_util.tree_leaves(got_grads)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=5e-4,
                                   atol=5e-5)


# -- through the trainer --------------------------------------------------------

def test_fit_publishes_what_the_last_step_selected_and_routed(monkeypatch):
    """Through ``Trainer.fit``, mixed precision: the loss falls, and the
    counters of the last step are in the process table as the fit returns,
    with the flight events of the selection, the pair-masked attention and
    the grouped product."""
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
        model = keye_tiny(experts_held=(0, 1, 2, 3),
                          net=NeuralNetConfiguration(
                              updater=Adam(lr=3e-3), mixed_precision=True))
        trainer = Trainer(model)
        ts = trainer.init_state()
        index_before = jax.tree_util.tree_map(
            np.asarray, ts.params["layer_1"]["attn"]["index"])
        feed = [batch(tiny_cfg((0, 1, 2, 3)), 1) for _ in range(4)]

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
    assert set(counters) == set(vocab.MOE_COUNTERS + vocab.DSA_COUNTERS)
    last = Keep.seen[-1]
    here = np.asarray(last[vocab.COUNTER_MOE_TOKENS_HERE])
    assert here.shape == (2, 4) and here.dtype == np.int32
    assert counters[vocab.COUNTER_MOE_TOKENS_HERE] == here.tolist()
    assert counters[vocab.COUNTER_MOE_PIECES_RUN] == [1, 1]  # a layer
    pairs = np.asarray(last[vocab.COUNTER_DSA_PAIRS])
    assert pairs.shape == (2,) and counters[vocab.COUNTER_DSA_PAIRS] == \
        pairs.tolist()
    least = ROWS * (TOP_K * (TOP_K + 1) // 2 + (SEQ - TOP_K) * TOP_K)
    assert np.all(pairs >= least) and np.all(pairs < ROWS * SEQ * SEQ // 2)
    assert counters[vocab.COUNTER_DSA_KEYS_MEAN] == pytest.approx(
        pairs.mean() / (ROWS * SEQ), rel=1e-6)
    assert counters[vocab.COUNTER_DSA_TILES_EMPTY] == 0.0
    # the indexer is in the tree and Adam leaves it where it was
    for name, leaf in ts.params["layer_1"]["attn"]["index"].items():
        assert np.array_equal(np.asarray(leaf), index_before[name]), name
    selection = flight.events(kinds=["attention.dsa_select"])
    assert selection and selection[0]["data"]["top_k"] == TOP_K
    assert selection[0]["data"]["method"] == "threshold_by_bit_search_xla"
    products = flight.events(kinds=["kernel.grouped_product"])
    assert products and products[0]["data"]["rows"] == 2 * ROWS * SEQ
    # half of the experts held: the sorted pairs are one piece
    assert products[0]["data"]["rows_a_piece"] == 2 * ROWS * SEQ
    assert products[0]["data"]["pieces"] == 1
    assert products[0]["data"]["combine"] == "inverse_gather"
    assert "attention.dsa_select" in vocab.known_event_kinds()
