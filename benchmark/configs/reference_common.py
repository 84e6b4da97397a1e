"""Plain arithmetic shared by the configurations' references.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision: no kernels, no cache, no batching tricks. Nothing
here imports the program under test. ``Matmul`` is the one place where a
*control* lowers the precision: the same reference, computed as a later PR
might be tempted to compute the model.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("float32", "bfloat16", "fp8")

# (exponent bits, mantissa bits, largest finite value) of what a precision
# holds values in and what it holds gradients in. fp8 is the pair fp8
# training uses, e4m3 and e5m2, here with IEEE's reserved top exponent.
FORMATS = {
    "bfloat16": {"value": (8, 7, None), "gradient": (8, 7, None)},
    "fp8": {"value": (4, 3, 240.0), "gradient": (5, 2, 57344.0)},
}


def _round(x, precision: str, kind: str):
    """``x`` as the stated precision holds it. ``reduce_precision`` is an
    operation the compiler keeps (a cast down and up again it may drop,
    and the TPU's does); an 8-bit format is scaled per tensor so that its
    largest value is the format's."""
    exponent, mantissa, largest = FORMATS[precision][kind]
    if largest is None:
        return jax.lax.reduce_precision(x, exponent, mantissa)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / largest
    return jax.lax.reduce_precision(x / scale, exponent, mantissa) * scale


class Matmul:
    """``einsum`` at a stated operand precision, accumulated in float32.
    Below float32 the operands are rounded on the way in (the gradient
    passes straight through the rounding, so the backward products see the
    rounded operands too), and the gradient that comes back to the
    product is rounded before the backward products use it."""

    def __init__(self, precision: str = "float32"):
        if precision not in PRECISIONS:
            raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
        self.precision = precision

        @jax.custom_vjp
        def held_gradient(y):
            return y

        held_gradient.defvjp(
            lambda y: (y, None),
            lambda _, g: (_round(g, precision, "gradient"),))
        self._held_gradient = held_gradient

    def _operand(self, x):
        if self.precision == "float32":
            return x
        rounded = _round(x, self.precision, "value")
        return x + jax.lax.stop_gradient(rounded - x)

    def __call__(self, spec: str, a, b):
        y = jnp.einsum(spec, self._operand(a), self._operand(b),
                       precision=HIGHEST,
                       preferred_element_type=jnp.float32)
        return y if self.precision == "float32" else self._held_gradient(y)


def layer_norm(x, gamma, beta, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gamma + beta


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def cross_entropy(logits, labels):
    """Per-position negative log-likelihood of ``labels`` under ``logits``."""
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                keepdims=True)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def attention(mm: Matmul, x, p, num_heads: int, *, causal: bool, key_mask):
    """Multi-head self-attention over ``x`` [N,T,E] with the block's
    projection leaves ``p`` (Wq, bq, Wk, bk, Wv, bv, Wo, bo)."""
    n, t, e = x.shape
    d = e // num_heads

    def heads(z):
        return z.reshape(n, t, num_heads, d).transpose(0, 2, 1, 3)

    q = heads(mm("nte,ef->ntf", x, p["Wq"]) + p["bq"])
    k = heads(mm("nte,ef->ntf", x, p["Wk"]) + p["bk"])
    v = heads(mm("nte,ef->ntf", x, p["Wv"]) + p["bv"])
    scores = mm("nhqd,nhkd->nhqk", q, k) / math.sqrt(d)
    allowed = jnp.ones((1, 1, t, t), bool)
    if causal:
        allowed = allowed & jnp.tril(jnp.ones((t, t), bool))[None, None]
    if key_mask is not None:
        allowed = allowed & (key_mask[:, None, None, :] > 0)
    scores = jnp.where(allowed, scores, -1e30)
    weights = jax.nn.softmax(scores, axis=-1)
    y = mm("nhqk,nhkd->nhqd", weights, v)
    y = y.transpose(0, 2, 1, 3).reshape(n, t, e)
    return mm("nte,ef->ntf", y, p["Wo"]) + p["bo"]


def transformer_block(mm: Matmul, x, p, *, num_heads: int, eps: float,
                      causal: bool, post_ln: bool, key_mask):
    """One encoder block: attention and a GELU feed-forward, each with a
    residual; LayerNorm after the sum (``post_ln``, BERT) or before the
    sublayer (pre-LN, GPT-2)."""

    def ln(h, which):
        return layer_norm(h, p[f"{which}_gamma"], p[f"{which}_beta"], eps)

    def ffn(h):
        f = gelu_tanh(mm("nte,ef->ntf", h, p["W1"]) + p["b1"])
        return mm("ntf,fe->nte", f, p["W2"]) + p["b2"]

    att = p["attention"]
    if post_ln:
        x = ln(x + attention(mm, x, att, num_heads, causal=causal,
                             key_mask=key_mask), "ln1")
        return ln(x + ffn(x), "ln2")
    x = x + attention(mm, ln(x, "ln1"), att, num_heads, causal=causal,
                      key_mask=key_mask)
    return x + ffn(ln(x, "ln2"))


def block_shapes(hidden: int, intermediate: int) -> Dict[str, Any]:
    """Leaves of one transformer block: shape and kind of initial value."""
    e, f = hidden, intermediate
    return {
        "attention": {
            "Wq": ((e, e), "normal"), "Wk": ((e, e), "normal"),
            "Wv": ((e, e), "normal"), "Wo": ((e, e), "normal"),
            "bq": ((e,), "zeros"), "bk": ((e,), "zeros"),
            "bv": ((e,), "zeros"), "bo": ((e,), "zeros"),
        },
        "W1": ((e, f), "normal"), "b1": ((f,), "zeros"),
        "W2": ((f, e), "normal"), "b2": ((e,), "zeros"),
        "ln1_gamma": ((e,), "ones"), "ln1_beta": ((e,), "zeros"),
        "ln2_gamma": ((e,), "ones"), "ln2_beta": ((e,), "zeros"),
    }


def _is_leaf_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def make_params(shapes, seed: int, std: float, dtype=jnp.float32):
    """The weights, from the seed, in one jitted call: ``normal`` leaves
    are N(0, std), the others the constant their kind names. The key is
    the compiled program's argument, so one program serves every seed."""
    return params_from_key(shapes, seed_key(seed), std, dtype)


def params_from_key(shapes, key, std: float, dtype=jnp.float32):
    return _make_params(key, shapes=_hashable(shapes), std=std,
                        dtype=jnp.dtype(dtype))


def seed_key(seed: int):
    return jax.random.key(seed_to_int31(seed))


def _hashable(shapes):
    """The tree of shapes as nested tuples, for a static argument."""
    if _is_leaf_spec(shapes):
        return shapes
    return tuple((k, _hashable(v)) for k, v in sorted(shapes.items()))


def _unhashable(shapes):
    if _is_leaf_spec(shapes):
        return shapes
    return {k: _unhashable(v) for k, v in shapes}


@functools.partial(jax.jit, static_argnames=("shapes", "std", "dtype"))
def _make_params(key, *, shapes, std, dtype):
    leaves, treedef = jax.tree_util.tree_flatten(
        _unhashable(shapes), is_leaf=_is_leaf_spec)
    out = []
    for i, (shape, kind) in enumerate(leaves):
        if kind == "normal":
            out.append(std * jax.random.normal(
                jax.random.fold_in(key, i), shape, dtype))
        elif kind == "ones":
            out.append(jnp.ones(shape, dtype))
        elif kind == "zeros":
            out.append(jnp.zeros(shape, dtype))
        else:
            raise ValueError(f"unknown initial value {kind!r}")
    return jax.tree_util.tree_unflatten(treedef, out)


def leaf_sizes(shapes) -> Dict[str, int]:
    """Every leaf's count of elements, keyed as ``leaf_norms`` keys them."""
    flat = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=_is_leaf_spec)[0]
    return {jax.tree_util.keystr(path): math.prod(shape)
            for path, (shape, _) in flat}


def seed_to_int31(seed: int) -> int:
    """Any whole number (the driver's pass 2**31) to a key seed jax takes."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0] >> 1)


def leaf_norms(tree) -> Dict[str, Any]:
    """L2 norm of every leaf, keyed by its path."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(path): jnp.sqrt(jnp.sum(jnp.square(
        leaf.astype(jnp.float32)))) for path, leaf in flat}


def adam_step(params, grads, m, v, t: int, *, lr: float, beta1: float,
              beta2: float, eps: float):
    """Adam with bias-corrected moments; ``t`` counts from 1."""
    tm = jax.tree_util.tree_map
    m = tm(lambda mm_, g: beta1 * mm_ + (1 - beta1) * g, m, grads)
    v = tm(lambda vv, g: beta2 * vv + (1 - beta2) * jnp.square(g), v, grads)
    bc1, bc2 = 1.0 - beta1 ** t, 1.0 - beta2 ** t
    params = tm(lambda p, mm_, vv: p - lr * (mm_ / bc1) / (
        jnp.sqrt(vv / bc2) + eps), params, m, v)
    return params, m, v


def split_rows(batch, rows: int) -> List[Any]:
    """The batch cut into blocks of at most ``rows`` rows."""
    n = jax.tree_util.tree_leaves(batch)[0].shape[0]
    return [jax.tree_util.tree_map(lambda a: a[i:i + rows], batch)
            for i in range(0, n, rows)]


def follow_training(
    loss_parts: Callable, part_weights: Callable, params, batches: Sequence,
    *, adam: Dict[str, float], row_block: int,
) -> Dict[str, Any]:
    """Follow the first ``len(batches)`` training steps from ``params``.

    ``loss_parts(params, rows) -> {part: sum}`` gives each loss part's sum
    over a block of rows; ``part_weights(batch) -> {part: weight}`` gives
    the whole batch's normaliser of each part, so that gradients of blocks
    add up to the gradient of the batch's loss. Returns each step's loss,
    every leaf's norm of the first gradient, and every leaf's norm of the
    parameters' change over the steps.
    """

    def block_loss(p, rows, weights):
        parts = loss_parts(p, rows)
        return sum(parts[k] / weights[k] for k in sorted(parts))

    grad_fn = jax.jit(jax.value_and_grad(block_loss))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
    step = jax.jit(lambda p, g, m, v, t: adam_step(p, g, m, v, t, **adam),
                   static_argnums=4, donate_argnums=(0, 2, 3))
    norms = jax.jit(leaf_norms)
    diff_norms = jax.jit(lambda a, b: leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b)))

    start = jax.tree_util.tree_map(jnp.copy, params)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for t, batch in enumerate(batches, start=1):
        weights = {k: jnp.float32(w) for k, w in part_weights(batch).items()}
        loss, grads = None, None
        for rows in split_rows(batch, row_block):
            l, g = grad_fn(params, rows, weights)
            loss = l if loss is None else loss + l
            grads = g if grads is None else add(grads, g)
        losses.append(float(loss))
        if t == 1:
            grad_norms = {k: float(x) for k, x in norms(grads).items()}
        params, m, v = step(params, grads, m, v, t)
    change = {k: float(x) for k, x in diff_norms(params, start).items()}
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}
