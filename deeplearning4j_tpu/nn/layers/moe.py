"""Mixture-of-experts FFN block with top-k routing (SURVEY §2.6 P10
"expert parallelism"; capability superset — the reference has no MoE layer,
its P10 row maps to this block sharded over an ``expert`` mesh axis).

TPU-first formulation (GShard/Switch style): routing is DENSE tensor
algebra — a [tokens, experts, capacity] one-hot dispatch tensor built from
top-k gates and a per-expert running position (cumsum), everything static
shape so XLA can lay it out — and the experts are one STACKED weight tensor
``[E, H, I]`` applied with a single einsum. Under a mesh, sharding that
leading E dim over the 'expert' (or 'model') axis makes GSPMD insert the
all-to-all dispatch/combine collectives the reference would have needed a
parameter server for; see parallel/specs.expert_parallel_plan.

Tokens routed beyond an expert's capacity are dropped (standard MoE
semantics — the residual path carries them); ``load_balance_loss`` exposes
the GShard auxiliary loss for callers that want to regularize routing.

``RoutedExperts``, beside it on the same ``[E, H, I]`` stacks, is what a
language model of today runs: drop-free top-k routing with no capacity
and no dispatch tensor (the token-expert pairs are sorted by expert and
the experts' products run grouped over the sorted rows, a piece at a time
and only the pieces in which a pair landed), told which of the experts it
holds, so that it is one chip's share of an expert-parallel layer.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.kernels._dispatch import interpret, use_pallas
from deeplearning4j_tpu.nn.activations import get_activation
from deeplearning4j_tpu.nn.config import LayerConfig, register_config
from deeplearning4j_tpu.nn.initializers import get_initializer
from deeplearning4j_tpu.observability.vocab import (
    SCOPE_MOE_EXPERTS,
    SCOPE_MOE_ROUTE,
)


@register_config
@dataclass
class MoEBlock(LayerConfig):
    """Top-k routed expert FFN: y = x + combine(experts(dispatch(x))).

    Input [..., H] (leading dims are flattened into a token axis). The
    residual add keeps capacity-dropped tokens on the identity path.
    """

    num_experts: int = 8
    units: int = 0                # expert FFN hidden width (I)
    top_k: int = 2
    capacity_factor: float = 1.25
    activation: str = "gelu"
    weight_init: Optional[str] = None
    residual: bool = True
    # GShard-style fixed-size routing groups: capacity is computed per
    # group of this many tokens, keeping the dispatch tensor O(tokens)
    # instead of O(tokens^2). None = one global group (small inputs).
    group_size: Optional[int] = None

    def output_shape(self, input_shape):
        return tuple(input_shape)

    def init(self, rng, input_shape, dtype):
        h = input_shape[-1]
        i = self.units or 4 * h
        w_init = get_initializer(self.weight_init or "xavier")
        k1, k2, k3 = jax.random.split(rng, 3)
        params = {
            "Wg": w_init(k1, (h, self.num_experts), dtype),
            "W1": w_init(k2, (self.num_experts, h, i), dtype),
            "b1": jnp.zeros((self.num_experts, i), dtype),
            "W2": w_init(k3, (self.num_experts, i, h), dtype),
            "b2": jnp.zeros((self.num_experts, h), dtype),
        }
        # state structure must be stable across init/apply (sharding trees
        # are built from the init-time template)
        state = {"router_probs_mean": jnp.zeros((self.num_experts,), dtype),
                 "expert_fraction": jnp.zeros((self.num_experts,), dtype)}
        return params, state

    # -- routing -----------------------------------------------------------

    def _route(self, probs):
        """probs [B, E] → (dispatch [B, E, C] {0,1}, combine [B, E, C]).

        Slot bookkeeping (one-hots, cumsum positions, fill counters) runs
        in int32 regardless of probs.dtype: a bf16 cumsum loses integer
        exactness past 256 tokens and would silently collide tokens into
        the same capacity slot."""
        b, e = probs.shape
        c = max(1, int(self.capacity_factor * self.top_k * b / e))
        dispatch = jnp.zeros((b, e, c), probs.dtype)
        combine = jnp.zeros((b, e, c), probs.dtype)
        remaining = probs
        fill = jnp.zeros((e,), jnp.int32)  # tokens already in each expert
        for _ in range(self.top_k):
            choice = jnp.argmax(remaining, axis=-1)            # [B]
            gate = jnp.take_along_axis(remaining, choice[:, None], 1)[:, 0]
            onehot_i = jax.nn.one_hot(choice, e, dtype=jnp.int32)  # [B, E]
            # position of each token within its chosen expert, in token
            # order (exclusive cumsum), offset by previous rounds' fill
            pos = jnp.cumsum(onehot_i, axis=0) - onehot_i + fill[None, :]
            pos_tok = jnp.sum(pos * onehot_i, axis=-1)         # [B] int32
            keep = pos_tok < c
            slot = jax.nn.one_hot(jnp.where(keep, pos_tok, c), c,
                                  dtype=probs.dtype)           # [B, C]
            d = (onehot_i.astype(probs.dtype)[:, :, None]
                 * slot[:, None, :]
                 * keep[:, None, None].astype(probs.dtype))
            dispatch = dispatch + d
            combine = combine + d * gate[:, None, None]
            fill = fill + jnp.sum(onehot_i * keep[:, None].astype(jnp.int32),
                                  axis=0)
            remaining = remaining * (1.0 - onehot_i.astype(probs.dtype))
        return dispatch, combine

    def _ffn_one_group(self, params, tokens):
        """Route + dispatch + experts + combine for one token group."""
        probs = jax.nn.softmax(tokens @ params["Wg"], axis=-1)  # [B, E]
        dispatch, combine = self._route(probs)

        expert_in = jnp.einsum("bec,bh->ech", dispatch, tokens)
        act = get_activation(self.activation)
        hmid = act(jnp.einsum("ech,ehi->eci", expert_in, params["W1"])
                   + params["b1"][:, None, :])
        expert_out = (jnp.einsum("eci,eih->ech", hmid, params["W2"])
                      + params["b2"][:, None, :])
        y = jnp.einsum("bec,ech->bh", combine, expert_out)
        # routing stats: mean router prob + fraction routed, per expert —
        # exactly what load_balance_loss needs (see load_balance_loss_from_state)
        stats = (jnp.mean(probs, axis=0),
                 jnp.mean(jnp.sum(dispatch, axis=-1), axis=0))
        return y, stats

    def apply(self, params, state, x, *, train=False, rng=None):
        shape = x.shape
        h = shape[-1]
        tokens = x.reshape(-1, h)                               # [B, H]
        b = tokens.shape[0]
        g = self.group_size
        if g is not None and b > g and b % g == 0:
            groups = tokens.reshape(b // g, g, h)
            y, stats = jax.vmap(self._ffn_one_group, in_axes=(None, 0))(
                params, groups)
            y = y.reshape(b, h)
            stats = tuple(jnp.mean(s, axis=0) for s in stats)
        else:
            y, stats = self._ffn_one_group(params, tokens)
        if self.residual:
            y = y + tokens
        new_state = dict(state)
        new_state["router_probs_mean"] = stats[0]
        new_state["expert_fraction"] = stats[1]
        return y.reshape(shape), new_state


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _dispatch(tokens, source, inverse, count, most):
    """The rows of the sorted pairs, or of a piece of them: ``tokens[source]``
    (``count`` tokens, of which each has ``most`` rows at most), with the
    gradient ``_combine`` of the rows' gradients, its transpose, and not a
    scatter in ``tokens``' own rounding."""
    return tokens[source]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _combine(rows, source, inverse, count, most):
    """For each of ``count`` tokens the sum of its pairs' ``rows``; its
    gradient is ``_dispatch`` of the tokens' gradients."""
    return _sum_by_token(rows, source, inverse, count, most)


def _sum_by_token(rows, source, inverse, count, most):
    """Each token's rows summed in float32 and rounded once. Where the
    rows are all the sorted pairs, ``inverse`` [pairs] is each pair's row,
    a token's pairs side by side: a gather back and the sum. Where they
    are a piece of them it is None, a token's other pairs may lie in
    another piece, and ``source`` names each row's token, ``count`` for a
    row of no group, which adds nothing: the rows are put in token order
    and summed by sorted segments (``_sum_runs``)."""
    if inverse is None:
        return _sum_runs(rows, source, count, most)
    back = rows[inverse]
    if back.shape[0] > count:
        back = jnp.sum(back.reshape(count, -1, rows.shape[-1]).astype(
            jnp.float32), axis=1).astype(rows.dtype)
    return back


# How a piece's rows are summed into their tokens, as the flight event
# ``kernel.grouped_product`` names it (``combine``).
COMBINE_A_PIECE = "sorted_segments"


def _sum_runs(rows, source, count, most):
    """``rows`` [n, H] summed by ``source`` [n] into [``count``, H], where
    no token below ``count`` has more than ``most`` rows and a row whose
    source is ``count`` is dropped. No index is written through, so none
    can repeat: the rows are sorted by token (one sort of ``n`` keys, one
    row gather) and, on the chip, summed by the kernel
    ``kernels/segment_rows.py``. Off it each row takes in its next
    ``most - 1`` neighbours of the same token (in float32, rounded once),
    and each token reads the first row of its run, found by counting the
    keys below it; a token of no row reads a row of zeros gathered past
    the last."""
    n = rows.shape[0]
    keys, at = jax.lax.sort(
        (source.astype(jnp.int32), jnp.arange(n, dtype=jnp.int32)),
        num_keys=1)
    if use_pallas():
        from deeplearning4j_tpu.kernels.segment_rows import sum_sorted_rows

        return sum_sorted_rows(rows[at], keys, count, most,
                               interpret=interpret())
    # past the last row ``most`` more, each under a key of its own that no
    # token has; the first of them is the row of zeros
    keys = jnp.concatenate([keys, -1 - jnp.arange(most, dtype=jnp.int32)])
    ordered = rows[jnp.pad(at, (0, most))]
    real = (jnp.arange(n + 1) < n)[:, None]
    total = jnp.where(real, ordered[:n + 1].astype(jnp.float32), 0.0)
    for ahead in range(1, most):
        same = keys[ahead:ahead + n + 1] == keys[:n + 1]
        total = total + jnp.where(
            same[:, None], ordered[ahead:ahead + n + 1].astype(jnp.float32),
            0.0)
    total = total.astype(rows.dtype)
    first = jnp.searchsorted(keys[:n], jnp.arange(count + 1, dtype=jnp.int32),
                             method="sort")
    return total[jnp.where(first[1:] > first[:-1], first[:-1], n)]


_dispatch.defvjp(
    lambda tokens, source, inverse, count, most: (tokens[source],
                                                  (source, inverse)),
    lambda count, most, kept, g: (_sum_by_token(g, *kept, count, most),
                                  None, None))
_combine.defvjp(
    lambda rows, source, inverse, count, most: (
        _sum_by_token(rows, source, inverse, count, most), source),
    lambda count, most, source, g: (g[source], None, None))

# The grouped product on the chip: megablox ``gmm`` (Pallas; JAX ships it),
# chosen over ``jax.lax.ragged_dot`` by traces on a v5e (PERF.md section 6,
# PR 28): faster at every load read, zeros for the rows of no group (XLA's
# ragged-dot kernels leave those rows as they find them), and its calls
# keep the scope they are traced in. Tiles are rows x inner x columns: the
# fastest read where the groups' edges fall inside tiles, as a router's do.
GROUPED_TILES = (256, 2048, 512)


def _grouped(rows, weights, sizes):
    """``rows`` [M,K], sorted by group, times each group's own matrix of
    ``weights`` [G,K,N]. ``sizes`` [G+1] counts the rows of each group and
    last the rows of no group here, which give zeros. Off the TPU the
    product is XLA's own ``ragged_dot`` (``kernels/_dispatch.py``), which
    is only valid where it gives those rows zeros, as the CPU's does
    (``tests/test_zaya.py`` holds it to that); the TPU's lowering leaves
    them as it found them (PERF.md section 6, PR 28), which is one reason
    the chip runs ``gmm``."""
    if not use_pallas():
        return jax.lax.ragged_dot(rows, weights, sizes[:-1])
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m = rows.shape[0]
    tiles = _tiles(m, rows.shape[1], weights.shape[2])
    spare = -m % tiles[0]  # gmm wants whole tiles of rows: more of no group
    if spare:
        rows = jnp.pad(rows, ((0, spare), (0, 0)))
        sizes = sizes.at[-1].add(spare)
    return gmm(rows, weights, sizes, preferred_element_type=rows.dtype,
               tiling=tiles, interpret=interpret())[:m]


def _tiles(m, k, n):
    return tuple(min(tile, size) for tile, size in zip(
        GROUPED_TILES, (-(-m // 8) * 8, k, n)))


# The sorted pair rows are walked a piece at a time, and a piece in which no
# pair landed is not run. A piece holds this many times the pairs that a
# balanced router lands on the experts held: every load a balanced cell has
# shown fits the first (PERF.md section 5, 2b, has the chip's sweep).
PIECE_OVER_BALANCED = 2


def _piece_rows(pairs, held, total):
    """Rows of a piece of ``pairs`` sorted rows where ``held`` of ``total``
    experts are held: whole row tiles, and all the rows where half of the
    experts or more are held."""
    rows = -(-PIECE_OVER_BALANCED * pairs * held // total)
    tile = _tiles(rows, 0, 0)[0]  # of rows: 8 at least, as ``_grouped`` pads
    return min(pairs, -(-rows // tile) * tile)


def _slice_of(ordered, first, rows):
    """``ordered[first:first + rows]`` of a number a sorted pair, with zeros
    past the last pair."""
    if rows == ordered.shape[0]:
        return ordered
    return jax.lax.dynamic_slice_in_dim(
        jnp.pad(ordered, (0, -ordered.shape[0] % rows)), first, rows)


def _piece_of(order, sizes, first, rows):
    """Of the sorted rows ``[first, first + rows)``: the pair in each
    (``order`` there; past the last pair a piece is padded with rows of no
    group) and the rows of each group, last those of no group."""
    if rows == order.shape[0]:
        return order, sizes
    span = _slice_of(order, first, rows)
    ends = jnp.cumsum(sizes[:-1])
    here = (jnp.clip(ends, first, first + rows)
            - jnp.clip(ends - sizes[:-1], first, first + rows))
    return span, jnp.append(here, rows - jnp.sum(here))


# How a token-expert pair finds what belongs to its expert, as the flight
# event ``kernel.grouped_product`` names it (``lookup``).
LOOKUP_A_PAIR = "compare_select"


def _of_chosen(prob, chosen):
    """Each token's row of ``prob`` [M, experts] at its chosen experts ([M],
    or [M, top_k]), by no index: the experts' numbers are compared with the
    chosen one and the row's entries selected and summed over the experts.
    One term of each sum is not zero, so it is the entry to the bit, and
    its transpose gives each (token, expert) one term at most, a token's
    experts being distinct. XLA fuses it as an elementwise pass with a
    reduction where an index is a gather of scalars (and its gradient a
    scatter of them), which the chip runs one number at a time. The
    barrier keeps that pass out of its consumer's fusion: fused with the
    sum over a token's shares, the two reductions become one over all the
    [top_k, experts] terms, which adds the shares in another order and
    runs five times slower (PERF.md section 5, 2b)."""
    picked = chosen[..., None] == jnp.arange(prob.shape[-1])
    rows = jnp.expand_dims(prob, tuple(range(1, chosen.ndim)))
    return jax.lax.optimization_barrier(
        jnp.sum(jnp.where(picked, rows, 0.0), axis=-1))


def _place_of(chosen, experts_held):
    """Each chosen expert's place among ``experts_held``, and their count
    for one held elsewhere: a compare and a select an expert held, so an
    elementwise pass over the pairs with no table to index and nothing to
    reduce."""
    local = jnp.full(chosen.shape, len(experts_held), jnp.int32)
    for at, expert in enumerate(experts_held):
        local = jnp.where(chosen == expert, at, local)
    return local


@jax.custom_vjp
def _sorted_by_place(local, weight):
    """The pairs in the order of ``local`` (stable: ``jnp.argsort``'s) and
    their ``weight``s in that order, carried through the one sort; the
    weights' gradient is sorted back by the same order, so that no pair's
    number is gathered or scattered by index."""
    _, order, carried = jax.lax.sort(
        (local, jnp.arange(local.shape[0], dtype=jnp.int32), weight),
        num_keys=1)
    return order, carried


def _sorted_by_place_fwd(local, weight):
    order, carried = _sorted_by_place(local, weight)
    return (order, carried), order


_sorted_by_place.defvjp(
    _sorted_by_place_fwd,
    lambda order, g: (None, jax.lax.sort((order, g[1]), num_keys=1)[1]))


def _record_grouped_product(pairs, m, k, n, groups):
    """One ``kernel.grouped_product`` flight event per layer, at trace
    time: which product the experts run through, its tiles, the pieces of
    ``m`` rows that the ``pairs`` sorted rows are walked in, under
    ``combine`` how a piece's rows are summed into their tokens
    (``"inverse_gather"`` where one piece holds all the pairs), and under
    ``lookup`` how a pair finds its expert's probability and place."""
    from deeplearning4j_tpu.observability.flightrecorder import record_event

    on_chip = use_pallas()
    record_event(
        "kernel.grouped_product",
        product="megablox.gmm" if on_chip else "jax.lax.ragged_dot",
        tiles=list(_tiles(m, k, n)) if on_chip else None,
        rows=pairs, rows_a_piece=m, pieces=-(-pairs // m),
        combine="inverse_gather" if m == pairs else COMBINE_A_PIECE,
        lookup=LOOKUP_A_PAIR, inner=k, columns=n, groups=groups)


_GATES = {"silu": jax.nn.silu, "relu": jax.nn.relu}


@register_config
@dataclass
class RoutedExperts(LayerConfig):
    """Drop-free top-``top_k`` routing over ``experts_total`` gated
    experts (``down(act(gate x) * (up x))``, ``act`` by
    ``gate_activation``: SiLU or ReLU), of which this layer holds
    ``experts_held`` (expert parallelism: the other chips hold the rest).

    The router is, by ``router``, one of two forms, both over ALL the
    experts whatever is held and both in float32 from their first matrix
    on. ``"mlp"``: a small MLP over a ``router_hidden``-wide state that
    crosses layers (ZAYA1, arXiv:2511.17127): r = x Wr + gamma r_before,
    z = Wc gelu(Wb gelu(Wa r)), p = softmax(z), the experts chosen by
    p + bias; the bias balances load and no gradient reaches it.
    ``"linear"``: z = x Wg, p = softmax(z), no bias and no state. A token's
    ``top_k`` largest are its experts, each weighted by its p over the sum
    of the chosen p's, wherever those experts are held (one expert a token
    keeps its own p). The router reads the rows the experts read unless
    ``apply`` is given others (``router_input``: a layer whose router
    stands before its attention reads the layer's input).

    Nothing is looked up by index over the token-expert pairs: a chosen
    expert's probability and its place among the experts held come from
    compares and selects (``_of_chosen``, ``_place_of``), and a pair's
    weight reaches its sorted row through the sort itself
    (``_sorted_by_place``), where gathers of scalars, which the chip runs
    one number at a time, stood. The pairs are sorted by the expert's place
    among those held (a pair whose expert is held elsewhere sorts last and
    adds nothing here), and the sorted rows are walked in pieces of
    ``_piece_rows``: twice what a balanced router lands here, so one
    piece of all the pairs where half of the experts or more are held.
    A piece gathers its tokens' rows, runs the experts' three products
    grouped over them (``[E, H, I]`` stacks, as ``MoEBlock``'s) and adds
    each token's weighted results to the output; a piece in which no pair
    landed is not run. No pair is dropped for want of room, because there
    is no capacity: a load that fills every piece runs every piece.

    ``apply`` takes the MLP router's state of the layer before under
    ``state["router"]`` (absent in the first layer, which has no
    ``gamma``) and returns its own there, beside ``tokens_here``: how many
    pairs landed on each expert held, and ``pieces_run``: how many pieces
    of the sorted rows ran.
    """

    experts_total: int = 16
    experts_held: Tuple[int, ...] = tuple(range(16))
    units: int = 0            # the experts' inner width; 0 -> the input's
    router_hidden: int = 256
    carries_router: bool = True   # False in the first layer: no gamma
    top_k: int = 1
    router: str = "mlp"           # or "linear": one matrix, no state
    gate_activation: str = "silu"  # or "relu"

    def __post_init__(self):
        if self.gate_activation not in _GATES:
            raise ValueError(f"gate_activation {self.gate_activation!r} not "
                             f"in {sorted(_GATES)}")

    def output_shape(self, input_shape):
        return tuple(input_shape)

    def init(self, rng, input_shape, dtype):
        h = input_shape[-1]
        i, r, held = self.units or h, self.router_hidden, len(self.experts_held)
        w_init = get_initializer("xavier")
        ks = jax.random.split(rng, 7)
        if self.router == "linear":
            params = {"Wg": w_init(ks[0], (h, self.experts_total), dtype)}
        else:
            params = {
                "Wr": w_init(ks[0], (h, r), dtype),
                "Wa": w_init(ks[1], (r, r), dtype),
                "Wb": w_init(ks[2], (r, r), dtype),
                "Wc": w_init(ks[3], (r, self.experts_total), dtype),
                "bias": jnp.zeros((self.experts_total,), dtype),
            }
            if self.carries_router:
                params["gamma"] = jnp.zeros((), dtype)
        params.update(
            gate=w_init(ks[4], (held, h, i), dtype),
            up=w_init(ks[5], (held, h, i), dtype),
            down=w_init(ks[6], (held, i, h), dtype))
        return params, {}

    def route(self, params, tokens, carried):
        """The MLP router's state [M,R] (None for the linear router), each
        token's experts and their shares of its output, in float32: [M]
        where ``top_k`` is 1, else [M, top_k]."""
        f32, exact = jnp.float32, jax.lax.Precision.HIGHEST

        def product(x, name):
            return jnp.matmul(x, params[name].astype(f32), precision=exact)

        if self.router == "linear":
            r, z = None, product(tokens.astype(f32), "Wg")
        else:
            r = product(tokens.astype(f32), "Wr")
            if "gamma" in params:
                r = r + params["gamma"].astype(f32) * carried
            z = product(
                jax.nn.gelu(product(jax.nn.gelu(product(r, "Wa")), "Wb")),
                "Wc")
        prob = tilted = jax.nn.softmax(z, axis=-1)
        if "bias" in params:
            tilted = prob + jax.lax.stop_gradient(params["bias"].astype(f32))
        if self.top_k == 1:
            chosen = jnp.argmax(tilted, axis=-1)
        else:
            _, chosen = jax.lax.top_k(tilted, self.top_k)
        # ``prob`` at ``chosen``, whether or not a bias tilted the choice
        share = _of_chosen(prob, chosen)
        if self.top_k > 1:
            share = share / jnp.sum(share, axis=-1, keepdims=True)
        return r, chosen, share

    def apply(self, params, state, x, *, train=False, rng=None,
              router_input=None):
        shape = x.shape
        tokens = x.reshape(-1, shape[-1])
        routed_on = (tokens if router_input is None or router_input is x
                     else router_input.reshape(-1, router_input.shape[-1]))
        gate = _GATES[self.gate_activation]
        held, fan = len(self.experts_held), self.top_k
        pairs = tokens.shape[0] * fan
        rows_a_piece = _piece_rows(pairs, held, self.experts_total)
        # the tokens, and the most rows that one of them has in a piece
        sums = (tokens.shape[0], min(fan, held))
        with jax.named_scope(SCOPE_MOE_ROUTE):
            r, chosen, share = self.route(
                params, routed_on, state.get("router"))
            # a token's pairs lie side by side: pair p is of token p // fan
            local = _place_of(chosen, self.experts_held).reshape(-1)
            # what a pair's row weighs: nothing where its expert is not here
            weight = jnp.where(local < held, share.reshape(-1), 0.0)
            order, weight = _sorted_by_place(local, weight)
            # each pair's row, where one piece holds them all
            inverse = (jnp.argsort(order) if rows_a_piece == pairs
                       else None)
            sizes = jnp.sum(local[:, None] == jnp.arange(held + 1)[None, :],
                            axis=0, dtype=jnp.int32)
        _record_grouped_product(pairs, rows_a_piece, shape[-1],
                                params["gate"].shape[-1], held)

        def piece(first):
            """What the sorted rows from ``first`` on, a piece of them,
            add to every token's output."""
            with jax.named_scope(SCOPE_MOE_ROUTE):
                span, sizes_here = _piece_of(order, sizes, first,
                                             rows_a_piece)
                source = span if fan == 1 else span // fan
                if inverse is None:  # a row of no group: of no token
                    source = jnp.where(
                        jnp.arange(rows_a_piece) < rows_a_piece
                        - sizes_here[-1], source, tokens.shape[0])
                rows = _dispatch(tokens, source, inverse, *sums)
            with jax.named_scope(SCOPE_MOE_EXPERTS):
                inner = (
                    gate(_grouped(rows, params["gate"], sizes_here))
                    * _grouped(rows, params["up"], sizes_here))
                out = _grouped(inner, params["down"], sizes_here)
            with jax.named_scope(SCOPE_MOE_ROUTE):
                here = _slice_of(weight, first, rows_a_piece)
                out = (out * here[:, None]).astype(x.dtype)
                # the weighted results of one token add up
                return _combine(out, source, inverse, *sums)

        y, pieces_run = piece(0), jnp.int32(1)
        if rows_a_piece < pairs:
            landed = pairs - sizes[-1]
            # recomputed in the backward pass, so that the scan keeps no
            # [rows of a piece, .] array for a piece that may not run
            later = jax.checkpoint(piece)

            def walk(y, first):
                return jax.lax.cond(first < landed, lambda: y + later(first),
                                    lambda: y), None

            def spilled(y):
                return jax.lax.scan(walk, y, jnp.arange(
                    rows_a_piece, pairs, rows_a_piece, dtype=jnp.int32))[0]

            # one branch not taken, where all that landed fits the first
            y = jax.lax.cond(rows_a_piece < landed, spilled, lambda y: y, y)
            pieces_run = jnp.maximum(1, -(-landed // rows_a_piece))
        y = y.reshape(shape)
        routed = {"tokens_here": sizes[:held], "pieces_run": pieces_run}
        if r is not None:
            routed["router"] = r
        return y, routed


def load_max_over_mean(tokens_here) -> jnp.ndarray:
    """``RoutedExperts``' ``tokens_here`` of a model's layers [layers, held]
    as one number of the step: the largest load over the mean load of the
    experts held, averaged over the layers."""
    load = tokens_here.astype(jnp.float32)
    return jnp.mean(jnp.max(load, axis=1)
                    / jnp.maximum(jnp.mean(load, axis=1), 1.0))


def load_balance_loss(probs, dispatch) -> jnp.ndarray:
    """GShard auxiliary loss: E * Σ_e fraction_routed_e · mean_prob_e.

    probs [B, E] softmax router outputs; dispatch [B, E, C] the one-hot
    dispatch tensor. Minimized (→ top_k) by uniform routing."""
    e = probs.shape[-1]
    frac = jnp.mean(jnp.sum(dispatch, axis=-1), axis=0)   # [E] routed frac
    mean_prob = jnp.mean(probs, axis=0)                   # [E]
    return e * jnp.sum(frac * mean_prob)


def load_balance_loss_from_state(layer_state) -> jnp.ndarray:
    """Aux loss from the stats MoEBlock.apply stores in its state — the
    wiring point for training: pass this (per MoE layer, via the model's
    new_state) into Trainer(extra_metrics=...) or add it to a custom loss.
    """
    mean_prob = layer_state["router_probs_mean"]
    frac = layer_state["expert_fraction"]
    return mean_prob.shape[-1] * jnp.sum(frac * mean_prob)
