"""``RoutedExperts`` looks nothing up by index over the token-expert pairs
(``nn/layers/moe.py::_of_chosen``: a compare against the experts' numbers
and a select, summed; ``_place_of``: a compare and a select an expert held;
``_sorted_by_place``: the weights carried through the sort of the pairs). Held, to the last bit in float32, against the same
layer with the lookups written here the way they stood: ``take_along_axis``
of the probabilities, ``place[chosen]``, and the weights gathered into the
sorted order; and the compiled form held to holding no such gather."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.layers import moe

# experts, top_k, held, the router
ROUTERS = {
    "top_1_of_16_bias_8_held": (16, 1, tuple(range(8)), "mlp"),
    "top_8_of_128_16_held": (128, 8, tuple(range(16)), "linear"),
    "top_6_of_64_8_held": (64, 6, tuple(range(8)), "linear"),
}
HIDDEN = 32


def by_index(prob, chosen):
    """``_of_chosen`` as it stood: a gather."""
    return jnp.take_along_axis(
        prob, chosen.reshape(chosen.shape[0], -1), axis=-1).reshape(
            chosen.shape)


TABLE = 128  # the most experts of ``ROUTERS``


def place_by_index(chosen, experts_held):
    """``_place_of`` as it stood: a table of the experts, indexed."""
    held = len(experts_held)
    place = np.full((TABLE,), held, np.int32)
    place[list(experts_held)] = np.arange(held)
    return jnp.asarray(place)[chosen]


def sorted_by_index(local, weight):
    """``_sorted_by_place`` as it stood: an argsort and a gather."""
    order = jnp.argsort(local)
    return order, weight[order]


@pytest.fixture
def standing(monkeypatch):
    """Switches the layer to the lookups by index and back."""
    def switch(on):
        monkeypatch.setattr(moe, "_of_chosen",
                            by_index if on else _OF_CHOSEN)
        monkeypatch.setattr(moe, "_place_of",
                            place_by_index if on else _PLACE_OF)
        monkeypatch.setattr(moe, "_sorted_by_place",
                            sorted_by_index if on else _SORTED_BY_PLACE)
    return switch


_OF_CHOSEN, _PLACE_OF, _SORTED_BY_PLACE = (
    moe._of_chosen, moe._place_of, moe._sorted_by_place)


def a_layer(name, key=0, held=None):
    total, top_k, all_held, router = ROUTERS[name]
    layer = moe.RoutedExperts(
        experts_total=total, experts_held=all_held if held is None else held,
        units=24, router_hidden=16, top_k=top_k, router=router)
    params, _ = layer.init(jax.random.key(key), (HIDDEN,), jnp.float32)
    if "bias" in params:  # a bias that tilts the choice away from the top
        params["bias"] = 0.05 * jax.random.normal(
            jax.random.key(key + 1), params["bias"].shape)
        params["gamma"] = jnp.float32(0.5)
    return layer, params


def everything(layer, params, x, carried, jit):
    """``route``'s and ``apply``'s results and every gradient, as numpy."""
    state = {} if carried is None else {"router": carried}
    mix = jax.random.normal(jax.random.key(7), x.shape, jnp.float32)

    def loss(params, x):
        y, routed = layer.apply(params, state, x)
        return jnp.sum(mix * y.astype(jnp.float32)), (y, routed)

    def run(params, x):
        (value, (y, routed)), grads = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(params, x)
        _, chosen, share = layer.route(
            params, x.reshape(-1, x.shape[-1]), carried)
        return dict(value=value, y=y, tokens_here=routed["tokens_here"],
                    pieces_run=routed["pieces_run"], chosen=chosen,
                    share=share, grads=grads)

    out = (jax.jit(run) if jit else run)(params, x)
    return jax.tree_util.tree_map(np.asarray, out)


def assert_same_to_the_bit(got, want, but_close=False):
    got, _ = jax.tree_util.tree_flatten_with_path(got)
    want = jax.tree_util.tree_leaves(want)
    assert len(got) == len(want)
    for (path, g), w in zip(got, want):
        name = jax.tree_util.keystr(path)
        assert g.dtype == w.dtype and g.shape == w.shape, name
        if but_close and g.dtype.kind not in "iub":
            # a last place of the leaf's type, and sums that cancel
            wide = w.astype(np.float32)
            np.testing.assert_allclose(
                g.astype(np.float32), wide, err_msg=name,
                rtol=2 * float(jnp.finfo(w.dtype).eps),
                atol=1e-6 * np.abs(wide).max())
        else:
            assert np.array_equal(g, w), name


def both(layer, params, x, standing, jit=False, carried=None):
    """``everything`` of the layer as it is and with the lookups by index;
    an MLP router's carried state is drawn unless given."""
    if carried is None and layer.router == "mlp":
        carried = jax.random.normal(
            jax.random.key(3), (x.size // x.shape[-1], 16), jnp.float32)
    got = everything(layer, params, x, carried, jit)
    standing(True)
    want = everything(layer, params, x, carried, jit)
    standing(False)
    return got, want


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", sorted(ROUTERS))
def test_the_layer_is_the_layer_of_lookups_by_index(name, dtype, jit,
                                                    standing):
    """Tokens in float32 and bfloat16 (the router and every lookup are
    float32 either way): the shares, the experts, the output, the loads
    and the gradients of every leaf and of the tokens. Operation by
    operation all of it is equal to the bit. Compiled as one program the
    whole numbers are, and the others are held to a last place and a
    millionth of their leaf: the compiler fuses the passes around a lookup
    (the softmax before it, the normalisation after it) with whatever
    feeds them, a select here and a gather there, and forms their
    quotients and multiply-adds as it sees fit (the lookup alone,
    compiled, is the next test's)."""
    x = jax.random.normal(jax.random.key(1), (2, 40, HIDDEN), dtype)
    got, want = both(*a_layer(name), x, standing, jit)
    assert got["share"].dtype == np.float32
    assert got["tokens_here"].sum() > 0
    assert_same_to_the_bit(got, want, but_close=jit)


@pytest.mark.parametrize("name", sorted(ROUTERS))
def test_the_lookups_alone_compiled_are_the_gathers_to_the_bit(name):
    """``_of_chosen`` of the probabilities with its gradient, the shares
    normalised after it, and ``_place_of``, each jitted on its own, against
    ``take_along_axis`` (whose gradient is a scatter-add) and a table of
    places indexed. The experts held are no run of numbers here."""
    total, fan, all_held, _ = ROUTERS[name]
    held = tuple(range(1, 2 * len(all_held), 2))
    prob = jax.nn.softmax(
        jax.random.normal(jax.random.key(8), (40, total)), axis=-1)
    _, chosen = jax.lax.top_k(prob, fan)
    if fan == 1:
        chosen = chosen[:, 0]
    mix = jax.random.normal(jax.random.key(9), chosen.shape)

    def reading(lookup, place_of):
        def loss(prob):
            share = lookup(prob, chosen)
            if fan > 1:
                share = share / jnp.sum(share, axis=-1, keepdims=True)
            return jnp.sum(mix * share), share

        (_, share), grad = jax.jit(
            jax.value_and_grad(loss, has_aux=True))(prob)
        local = jax.jit(lambda chosen: place_of(chosen, held))(chosen)
        return np.asarray(share), np.asarray(grad), np.asarray(local)

    got = reading(moe._of_chosen, moe._place_of)
    places = set(got[2].reshape(-1).tolist())  # some here, some elsewhere
    assert {len(held)} < places <= set(range(len(held) + 1))
    for g, w in zip(got, reading(by_index, place_by_index)):
        assert g.dtype == w.dtype and np.array_equal(g, w)


@pytest.mark.parametrize("name", sorted(ROUTERS))
def test_two_experts_of_equal_probability(name, standing):
    """Every token's logits are equal over all the experts (a router of
    zeros): the choice falls on the first ``top_k`` in both forms, and the
    shares are equal parts."""
    layer, params = a_layer(name)
    for leaf in ("Wg", "Wc"):
        if leaf in params:
            params[leaf] = jnp.zeros_like(params[leaf])
    if "bias" in params:
        params["bias"] = jnp.zeros_like(params["bias"])
    x = jax.random.normal(jax.random.key(2), (1, 24, HIDDEN), jnp.float32)
    got, want = both(layer, params, x, standing)
    assert_same_to_the_bit(got, want)
    fan = layer.top_k
    assert np.array_equal(got["chosen"].reshape(24, fan),
                          np.tile(np.arange(fan), (24, 1)))
    assert np.all(got["share"] == np.float32(
        1 / layer.experts_total if fan == 1 else 1 / fan))


@pytest.mark.parametrize("name", sorted(ROUTERS))
def test_every_chosen_expert_is_held_elsewhere(name, standing):
    """The layer holds the last experts and the router's last matrix sends
    every token to the first: nothing lands, the output and the experts'
    gradients are zeros, and the forms agree on that to the bit."""
    total, fan, all_held, _ = ROUTERS[name]
    held = tuple(range(total - len(all_held), total))
    layer, params = a_layer(name, held=held)
    last = "Wg" if "Wg" in params else "Wc"
    params[last] = jnp.zeros_like(params[last]).at[:, :fan].set(1.0)
    if "bias" in params:
        params["bias"] = jnp.zeros_like(params["bias"])
    x = jnp.abs(jax.random.normal(
        jax.random.key(4), (1, 24, HIDDEN), jnp.float32)) + 0.1
    carried = None
    if layer.router == "mlp":  # gelu of a positive state stays positive
        carried = jnp.ones((24, 16), jnp.float32)
        for leaf in ("Wr", "Wa", "Wb"):
            params[leaf] = jnp.abs(params[leaf])
    got, want = both(layer, params, x, standing, carried=carried)
    assert_same_to_the_bit(got, want)
    assert set(got["chosen"].reshape(-1).tolist()) <= set(range(fan))
    assert not got["tokens_here"].any() and not got["y"].any()
    assert not got["grads"][0]["down"].any()


@pytest.mark.parametrize("name", sorted(ROUTERS))
def test_one_token(name, standing):
    x = jax.random.normal(jax.random.key(5), (1, 1, HIDDEN), jnp.float32)
    got, want = both(*a_layer(name), x, standing)
    assert got["share"].shape == ((1,) if ROUTERS[name][1] == 1
                                  else (1, ROUTERS[name][1]))
    assert_same_to_the_bit(got, want)


@pytest.mark.parametrize("name", sorted(ROUTERS))
def test_a_layer_that_walks_pieces(name, standing):
    """Two of the experts held (one where a token has one expert, so that
    pieces are walked there too): the later pieces run behind the scan,
    recomputed, and every gradient is still the bit the gather gave."""
    total, fan, _, _ = ROUTERS[name]
    held = (3,) if fan == 1 else (1, 5)
    x = jax.random.normal(jax.random.key(6), (2, 64, HIDDEN), jnp.float32)
    got, want = both(*a_layer(name, held=held), x, standing)
    pairs = 128 * fan
    assert moe._piece_rows(pairs, len(held), total) < pairs
    assert_same_to_the_bit(got, want)


# ---- the compiled form -------------------------------------------------------


def lookups_in(jaxpr, sizes):
    """Every ``gather`` and ``scatter`` (``scatter-add`` too) of a jaxpr and
    of the jaxprs inside it whose operand's shape is one of ``sizes``."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "gather" or name.startswith("scatter"):
                if tuple(eqn.invars[0].aval.shape) in sizes:
                    found.append((name, tuple(eqn.invars[0].aval.shape)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr)
    return found


def value_and_grad_jaxpr(name, held, tokens):
    layer, params = a_layer(name, held=held)
    state = ({"router": jnp.zeros((tokens, 16), jnp.float32)}
             if layer.router == "mlp" else {})

    def loss(params, x):
        return jnp.sum(layer.apply(params, state, x)[0])

    return jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1)))(
        params, jnp.ones((1, tokens, HIDDEN), jnp.float32)).jaxpr


@pytest.mark.parametrize("walks", [False, True],
                         ids=["one_piece", "pieces_walked"])
@pytest.mark.parametrize("name", sorted(ROUTERS))
def test_no_gather_or_scatter_reads_the_probabilities_or_the_places(
        name, walks, standing):
    """The jaxpr of value and gradients: no ``gather`` and no ``scatter``
    whose operand is ``[tokens, experts_total]`` (the probabilities, or
    their gradient), a table of the experts (there is none) or ``[pairs]``
    (the weights into sorted order, and their gradient back); the form by
    index, through the same reader, shows all three."""
    total, fan, all_held, _ = ROUTERS[name]
    tokens = 48  # no other array of the layer is 48 * top_k long
    held = ((3,) if fan == 1 else (1, 5)) if walks else all_held
    sizes = {(tokens, total), (total,), (TABLE,), (tokens * fan,)}
    assert not lookups_in(value_and_grad_jaxpr(name, held, tokens), sizes)
    standing(True)
    seen = {shape for _, shape in
            lookups_in(value_and_grad_jaxpr(name, held, tokens), sizes)}
    assert seen == {(tokens, total), (TABLE,), (tokens * fan,)}


def test_the_flight_event_names_the_lookup():
    from deeplearning4j_tpu.observability.flightrecorder import (
        FlightRecorder,
        get_flight_recorder,
        set_flight_recorder,
    )

    before = get_flight_recorder()
    flight = set_flight_recorder(FlightRecorder())
    try:
        for name in sorted(ROUTERS):
            layer, params = a_layer(name)
            state = ({"router": jnp.zeros((8, 16), jnp.float32)}
                     if layer.router == "mlp" else {})
            layer.apply(params, state, jnp.ones((1, 8, HIDDEN)))
    finally:
        set_flight_recorder(before)
    events = [e["data"] for e in
              flight.events(kinds=["kernel.grouped_product"])]
    assert len(events) == 3
    assert all(e["lookup"] == moe.LOOKUP_A_PAIR == "compare_select"
               for e in events)
    assert all("combine" in e for e in events)
