"""``RoutedExperts``' sum of a piece's rows into their tokens
(``nn/layers/moe.py::_sum_by_token`` where pieces are walked: the rows put
in token order and summed by sorted segments) against a float32
``jax.ops.segment_sum``: the XLA form that runs off the TPU and the Pallas
kernel the chip runs (``kernels/segment_rows.py``), here interpreted."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.layers import moe

@pytest.fixture(params=["xla", "kernel"])
def form(request, monkeypatch):
    if request.param == "kernel":
        monkeypatch.setenv("DL4J_TPU_FORCE_PALLAS", "1")
    return request.param


def wanted(rows, source, count):
    """The float32 sum of each token's rows, rounded once; a row whose
    source is ``count`` is of no token."""
    return jax.ops.segment_sum(
        rows.astype(jnp.float32), source,
        num_segments=count + 1)[:count].astype(rows.dtype)


def last_places(got, want):
    """The largest gap in units of the wanted value's last bfloat16
    place."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    place = np.maximum(np.abs(want), 2.0 ** -120) * 2.0 ** -7
    return float(np.max(np.abs(got - want) / place))


def a_piece(seed, rows, count, most, landed, width, dtype):
    """``landed`` rows of real tokens, none of more than ``most`` rows, in
    no order, then rows of no group that hold anything but zeros."""
    rng = np.random.default_rng(seed)
    source = np.repeat(np.arange(count), most)
    rng.shuffle(source)
    source = np.concatenate([source[:landed],
                             np.full(rows - landed, count)]).astype(np.int32)
    values = rng.normal(size=(rows, width)).astype(np.float32)
    return jnp.asarray(values, dtype), jnp.asarray(source)


# rows, tokens, the most rows of one token, rows that landed, width
PIECES = {
    "top_k_1": (64, 40, 1, 30, 32),
    "top_k_6": (96, 40, 6, 70, 40),
    "top_k_8": (128, 24, 8, 128, 48),
    "nothing_of_no_group": (48, 16, 3, 48, 32),
    "one_row_landed": (32, 300, 4, 1, 24),
    "more_tokens_than_a_tile": (72, 600, 2, 60, 128),
}


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("name", sorted(PIECES))
def test_a_pieces_rows_sum_to_their_tokens(name, dtype, form):
    rows_n, count, most, landed, width = PIECES[name]
    rows, source = a_piece(3, rows_n, count, most, landed, width, dtype)
    got = moe._sum_by_token(rows, source, None, count, most)
    want = wanted(rows, source, count)
    assert got.shape == (count, width) and got.dtype == rows.dtype
    if dtype == "bfloat16":  # at most the order of the float32 additions
        assert last_places(got, want) <= 1.0
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)


def test_every_row_of_one_token(form):
    rows = jnp.asarray(np.random.default_rng(0).normal(size=(8, 40)),
                       jnp.bfloat16)
    source = jnp.full((8,), 3, jnp.int32)
    got = moe._sum_by_token(rows, source, None, 5, 8)
    want = wanted(rows, source, 5)
    assert last_places(got, want) <= 1.0
    assert np.all(np.asarray(got[jnp.array([0, 1, 2, 4])]) == 0)


def test_a_token_whose_rows_lie_at_the_pieces_two_ends(form):
    rng = np.random.default_rng(1)
    rows = jnp.asarray(rng.integers(-8, 8, size=(40, 32)), jnp.bfloat16)
    source = np.asarray(rng.permutation(40) % 20, np.int32)
    source[0] = source[-1] = 7
    source[np.flatnonzero(source == 7)[1:-1]] = 20  # its others: no group
    got = moe._sum_by_token(rows, jnp.asarray(source), None, 20, 3)
    want = wanted(rows, jnp.asarray(source), 20)
    assert np.array_equal(np.asarray(got), np.asarray(want))  # whole numbers
    assert np.array_equal(np.asarray(got[7]), np.asarray(
        (rows[0].astype(jnp.float32) + rows[-1].astype(jnp.float32)).astype(
            jnp.bfloat16)))


def test_a_piece_in_which_nothing_landed_adds_nothing(form):
    rows = jnp.ones((24, 32), jnp.bfloat16)
    got = moe._sum_by_token(rows, jnp.full((24,), 10, jnp.int32), None, 10, 2)
    assert got.shape == (10, 32) and not np.any(np.asarray(got))


def test_the_sum_is_float32_and_rounded_once(form):
    """256 + 1 + 1 is 258 in float32 and stays 256 where each addition is
    rounded to bfloat16; 1 + 1/256 + 1/256 rounds once, up, to 1 + 1/128."""
    rows = jnp.asarray([[256.0, 1.0], [1.0, 2.0 ** -8], [1.0, 2.0 ** -8],
                        [5.0, 5.0]], jnp.bfloat16)
    rows = jnp.tile(rows, (1, 16))
    source = jnp.asarray([2, 2, 2, 3], jnp.int32)  # row 3: of no group
    got = np.asarray(moe._sum_by_token(rows, source, None, 3, 3),
                     np.float32)
    assert np.all(got[2, 0::2] == 258.0)
    assert np.all(got[2, 1::2] == 1.0 + 2.0 ** -7)
    assert not np.any(got[:2])


def layer(held, top_k, total=16):
    return moe.RoutedExperts(experts_total=total, experts_held=tuple(held),
                             units=32, top_k=top_k, router="linear")


def test_a_last_piece_padded_past_the_last_pair_keeps_token_0s_row(form):
    """5 tokens at top-3 of 12 experts, 3 held and every token on those
    three: 15 sorted pairs in pieces of 8, all landed, so the second piece
    holds token 0's pair on the third expert beside one row of padding,
    which names pair 0 and so token 0. Every token comes out as the sum of
    its three experts, in the output and in the gradients."""
    part = layer((0, 1, 2), 3, total=12)
    params, _ = part.init(jax.random.key(0), (32,), jnp.float32)
    params["Wg"] = jnp.zeros((32, 12)).at[:, :3].set(1.0)
    x = jnp.abs(jax.random.normal(jax.random.key(1), (1, 5, 32))) + 0.1
    assert moe._piece_rows(15, 3, 12) == 8
    mix = jax.random.normal(jax.random.key(2), x.shape)

    def got(params, x):
        y, routed = part.apply(params, {}, x)
        return jnp.sum(mix * y), routed

    def want(params, x):
        tokens = x.reshape(-1, 32)
        _, chosen, share = part.route(params, tokens, None)
        y = jnp.zeros_like(tokens)
        for k in range(3):
            for e in range(3):
                out = (jax.nn.silu(tokens @ params["gate"][e])
                       * (tokens @ params["up"][e])) @ params["down"][e]
                y = y + jnp.where((chosen[:, k] == e)[:, None],
                                  share[:, k, None] * out, 0.0)
        return jnp.sum(mix * y.reshape(x.shape))

    (value, routed), grads = jax.value_and_grad(
        got, argnums=(0, 1), has_aux=True)(params, x)
    value_w, grads_w = jax.value_and_grad(want, argnums=(0, 1))(params, x)
    assert int(routed["pieces_run"]) == 2
    assert np.asarray(routed["tokens_here"]).tolist() == [5, 5, 5]
    assert float(value) == pytest.approx(float(value_w), rel=1e-5)
    for (path, w), g in zip(
            jax.tree_util.tree_flatten_with_path(grads_w)[0],
            jax.tree_util.tree_leaves(grads)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-5,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("most", [1, 2, 6, 8])
def test_the_gradient_through_dispatch_and_combine_is_the_references(
        most, form):
    """``_dispatch``, a weight a row and ``_combine`` over a piece against
    the same written with a gather and ``segment_sum`` in float32: the
    value, and the gradients of the tokens and of the rows' weights."""
    count, width, rows_n, landed = 48, 40, 32, 27
    rng = np.random.default_rng(most)
    _, source = a_piece(most, rows_n, count, most, landed, width, "float32")
    tokens = jnp.asarray(rng.normal(size=(count, width)), jnp.float32)
    weight = jnp.asarray(rng.normal(size=(rows_n,)), jnp.float32)
    mix = jnp.asarray(rng.normal(size=(count, width)), jnp.float32)
    live = (source < count)[:, None]

    def got(tokens, weight):
        rows = moe._dispatch(tokens, source, None, count, most)
        out = jnp.where(live, rows * weight[:, None], 0.0)
        return jnp.sum(mix * moe._combine(out, source, None, count, most))

    def want(tokens, weight):
        rows = tokens[jnp.minimum(source, count - 1)]
        out = jnp.where(live, rows * weight[:, None], 0.0)
        return jnp.sum(mix * jax.ops.segment_sum(
            out, source, num_segments=count + 1)[:count])

    value, grads = jax.value_and_grad(got, argnums=(0, 1))(tokens, weight)
    value_w, grads_w = jax.value_and_grad(want, argnums=(0, 1))(tokens,
                                                                weight)
    assert float(value) == pytest.approx(float(value_w), rel=1e-5)
    for g, w in zip(grads, grads_w):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                                   atol=1e-5)


def test_a_layer_that_walks_pieces_names_the_form_and_one_that_does_not():
    from deeplearning4j_tpu.observability.flightrecorder import (
        FlightRecorder,
        get_flight_recorder,
        set_flight_recorder,
    )

    before = get_flight_recorder()
    flight = set_flight_recorder(FlightRecorder())
    try:
        x = jnp.ones((4, 16, 32))
        for held in ((0, 1), tuple(range(8))):
            part = layer(held, 3)
            params, _ = part.init(jax.random.key(0), (32,), jnp.float32)
            part.apply(params, {}, x)
    finally:
        set_flight_recorder(before)
    walked, whole = [e["data"] for e in
                     flight.events(kinds=["kernel.grouped_product"])]
    assert walked["pieces"] == 4 and walked["rows_a_piece"] == 48
    assert walked["combine"] == moe.COMBINE_A_PIECE == "sorted_segments"
    assert whole["pieces"] == 1 and whole["combine"] == "inverse_gather"


def test_the_kernels_tiles_cover_a_piece_at_the_cells_shapes(monkeypatch):
    """The kernel's geometry at the two shapes that walk pieces, lowered
    for the TPU from here: one call named ``segment_rows_sum`` over the
    piece's rows, a grid of token tiles by the chunks a tile's span can
    touch."""
    import re

    monkeypatch.setattr(moe, "use_pallas", lambda: True)
    monkeypatch.setattr(moe, "interpret", lambda: False)
    for rows_n, count, width, most in ((24576, 16384, 2560, 6),
                                       (32768, 16384, 2048, 8)):
        rows = jax.ShapeDtypeStruct((rows_n, width), jnp.bfloat16)
        source = jax.ShapeDtypeStruct((rows_n,), jnp.int32)
        text = jax.jit(
            lambda r, s: moe._sum_by_token(r, s, None, count, most)).trace(
            rows, source).lower(lowering_platforms=("tpu",)).as_text()
        assert re.findall(r'kernel_name = "(\w+)"', text) == [
            "segment_rows_sum"]
        assert "stablehlo.scatter" not in text
        assert f"tensor<{rows_n}x{width}xbf16>" in text
