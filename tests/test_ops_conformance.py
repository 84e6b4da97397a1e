"""Op-catalog conformance matrix.

ref strategy: nd4j OpValidationSuite — every op in the public catalog gets a
golden test against an fp64 numpy oracle, swept across dtypes. The catalog
under test is ops/math.py (↔ NDMath), including every bare ``jnp`` alias:
an alias block is only an implemented op catalog if each alias is pinned to
reference semantics by a test. A coverage gate at the bottom enforces that
the matrix stays complete as ops are added.
"""

import math as pymath

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import math as M

# ---------------------------------------------------------------------------
# Input generators (deterministic per case; fp64 ground truth)
# ---------------------------------------------------------------------------

SHAPE = (4, 6)


def _gen(kind, seed):
    r = np.random.default_rng(seed)
    if kind == "any":
        return (r.uniform(-3, 3, SHAPE),)
    if kind == "offint":
        # values >= 0.1 away from every integer: ceil/floor/round stay
        # stable under bf16 input rounding (rel err ~0.4% << 0.1)
        return (r.integers(-3, 3, SHAPE) + r.uniform(0.1, 0.9, SHAPE),)
    if kind == "pos":
        return (r.uniform(0.1, 3, SHAPE),)
    if kind == "unit":
        return (r.uniform(-0.9, 0.9, SHAPE),)
    if kind == "ge1":
        return (r.uniform(1.1, 3, SHAPE),)
    if kind == "distinct":
        x = np.arange(SHAPE[0] * SHAPE[1], dtype=np.float64)
        return (r.permutation(x).reshape(SHAPE) - x.size / 2,)
    if kind == "prob":
        x = r.uniform(0.05, 1.0, SHAPE)
        return (x / x.sum(axis=-1, keepdims=True),)
    if kind == "binary_any":
        return r.uniform(-3, 3, SHAPE), r.uniform(-3, 3, SHAPE)
    if kind == "binary_pos":
        return r.uniform(0.1, 3, SHAPE), r.uniform(0.1, 3, SHAPE)
    if kind == "bool2":
        return (r.integers(0, 2, SHAPE).astype(bool),
                r.integers(0, 2, SHAPE).astype(bool))
    if kind == "int2":
        return (r.integers(0, 5, SHAPE).astype(np.int32),
                r.integers(1, 5, SHAPE).astype(np.int32))
    raise ValueError(kind)


class C:
    """One conformance case: catalog fn vs fp64 numpy oracle."""

    def __init__(self, fn, oracle, kind="any", dtypes=("float32", "bfloat16"),
                 tol=None, exact=False, postprocess=None):
        self.fn = fn
        self.oracle = oracle
        self.kind = kind
        self.dtypes = dtypes
        self.tol = tol or {}
        self.exact = exact
        self.postprocess = postprocess  # applied to BOTH results


_TOL = {"float32": dict(rtol=2e-5, atol=1e-5), "bfloat16": dict(rtol=6e-2, atol=6e-2)}

_erf = np.vectorize(pymath.erf)
_erfc = np.vectorize(pymath.erfc)


def _np_clip_by_norm(x, max_norm):
    n = np.sqrt(np.square(x).sum())
    return x * min(1.0, max_norm / max(n, 1e-12))


def _np_segment(op, data, ids, num):
    out = np.zeros((num,) + data.shape[1:])
    if op in ("max", "min"):
        out[:] = -np.inf if op == "max" else np.inf
    for i, s in enumerate(ids):
        if op == "sum":
            out[s] += data[i]
        elif op == "max":
            out[s] = np.maximum(out[s], data[i])
        elif op == "min":
            out[s] = np.minimum(out[s], data[i])
    return out


F32 = ("float32",)

CASES = {
    # --- transforms -------------------------------------------------------
    "abs": C(M.abs, np.abs),
    "ceil": C(M.ceil, np.ceil, "offint"),
    "floor": C(M.floor, np.floor, "offint"),
    "round": C(M.round, np.round, "offint"),
    "rint": C(M.rint, np.rint, "offint"),
    "exp": C(M.exp, np.exp),
    "expm1": C(M.expm1, np.expm1),
    "log": C(M.log, np.log, "pos"),
    "log1p": C(M.log1p, np.log1p, "pos"),
    "log2": C(M.log2, np.log2, "pos"),
    "log10": C(M.log10, np.log10, "pos"),
    "sqrt": C(M.sqrt, np.sqrt, "pos"),
    "cbrt": C(M.cbrt, np.cbrt, "pos"),
    "square": C(M.square, np.square),
    "reciprocal": C(M.reciprocal, lambda x: 1.0 / x, "pos"),
    "neg": C(M.neg, np.negative),
    "sign": C(M.sign, np.sign),
    "sin": C(M.sin, np.sin),
    "cos": C(M.cos, np.cos),
    "tan": C(M.tan, np.tan, "unit"),
    "asin": C(M.asin, np.arcsin, "unit"),
    "acos": C(M.acos, np.arccos, "unit"),
    "atan": C(M.atan, np.arctan),
    "atan2": C(M.atan2, np.arctan2, "binary_any"),
    "sinh": C(M.sinh, np.sinh),
    "cosh": C(M.cosh, np.cosh),
    "tanh": C(M.tanh, np.tanh),
    "asinh": C(M.asinh, np.arcsinh),
    "acosh": C(M.acosh, np.arccosh, "ge1"),
    "atanh": C(M.atanh, np.arctanh, "unit"),
    "erf": C(M.erf, _erf),
    "erfc": C(M.erfc, _erfc),
    "pow": C(M.pow, np.power, "binary_pos"),
    "cube": C(M.cube, lambda x: x ** 3),
    "rsqrt": C(M.rsqrt, lambda x: 1.0 / np.sqrt(x), "pos"),
    "clip_by_value": C(lambda x: M.clip_by_value(x, -1.0, 1.0),
                       lambda x: np.clip(x, -1.0, 1.0)),
    "clip_by_norm": C(lambda x: M.clip_by_norm(x, 2.0),
                      lambda x: _np_clip_by_norm(x, 2.0)),
    "clip_by_global_norm": C(
        lambda x: M.clip_by_global_norm({"a": x, "b": 2 * x}, 1.5)[0]["a"],
        lambda x: _np_clip_by_norm_global(x), dtypes=F32),
    # --- pairwise / comparison -------------------------------------------
    "add": C(M.add, np.add, "binary_any"),
    "sub": C(M.sub, np.subtract, "binary_any"),
    "mul": C(M.mul, np.multiply, "binary_any"),
    "div": C(M.div, np.divide, "binary_pos"),
    "floordiv": C(M.floordiv, np.floor_divide, "int2", dtypes=F32, exact=True),
    "mod": C(M.mod, np.mod, "int2", dtypes=F32, exact=True),
    "maximum": C(M.maximum, np.maximum, "binary_any"),
    "minimum": C(M.minimum, np.minimum, "binary_any"),
    "eq": C(M.eq, np.equal, "int2", dtypes=F32, exact=True),
    "neq": C(M.neq, np.not_equal, "int2", dtypes=F32, exact=True),
    "gt": C(M.gt, np.greater, "binary_any", dtypes=F32, exact=True),
    "gte": C(M.gte, np.greater_equal, "binary_any", dtypes=F32, exact=True),
    "lt": C(M.lt, np.less, "binary_any", dtypes=F32, exact=True),
    "lte": C(M.lte, np.less_equal, "binary_any", dtypes=F32, exact=True),
    "logical_and": C(M.logical_and, np.logical_and, "bool2", dtypes=F32, exact=True),
    "logical_or": C(M.logical_or, np.logical_or, "bool2", dtypes=F32, exact=True),
    "logical_not": C(lambda a, b: M.logical_not(a), lambda a, b: np.logical_not(a),
                     "bool2", dtypes=F32, exact=True),
    "logical_xor": C(M.logical_xor, np.logical_xor, "bool2", dtypes=F32, exact=True),
    "where": C(lambda x, y: M.where(x > 0, x, y),
               lambda x, y: np.where(x > 0, x, y), "binary_any"),
    # --- reductions -------------------------------------------------------
    "sum": C(lambda x: M.sum(x, axis=-1), lambda x: np.sum(x, axis=-1)),
    "prod": C(lambda x: M.prod(x, axis=-1), lambda x: np.prod(x, axis=-1), "unit"),
    "mean": C(lambda x: M.mean(x, axis=-1), lambda x: np.mean(x, axis=-1)),
    "var": C(lambda x: M.var(x, axis=-1), lambda x: np.var(x, axis=-1)),
    "std": C(lambda x: M.std(x, axis=-1), lambda x: np.std(x, axis=-1)),
    "max": C(lambda x: M.max(x, axis=-1), lambda x: np.max(x, axis=-1)),
    "min": C(lambda x: M.min(x, axis=-1), lambda x: np.min(x, axis=-1)),
    "argmax": C(lambda x: M.argmax(x, axis=-1), lambda x: np.argmax(x, axis=-1),
                "distinct", dtypes=F32, exact=True),
    "argmin": C(lambda x: M.argmin(x, axis=-1), lambda x: np.argmin(x, axis=-1),
                "distinct", dtypes=F32, exact=True),
    "any": C(lambda a, b: M.any(a, axis=-1), lambda a, b: np.any(a, axis=-1),
             "bool2", dtypes=F32, exact=True),
    "all": C(lambda a, b: M.all(a, axis=-1), lambda a, b: np.all(a, axis=-1),
             "bool2", dtypes=F32, exact=True),
    "cumsum": C(lambda x: M.cumsum(x, axis=-1), lambda x: np.cumsum(x, axis=-1)),
    "cumprod": C(lambda x: M.cumprod(x, axis=-1), lambda x: np.cumprod(x, axis=-1),
                 "unit"),
    "norm1": C(lambda x: M.norm1(x, axis=-1),
               lambda x: np.abs(x).sum(axis=-1)),
    "norm2": C(lambda x: M.norm2(x, axis=-1),
               lambda x: np.sqrt(np.square(x).sum(axis=-1))),
    "norm_max": C(lambda x: M.norm_max(x, axis=-1),
                  lambda x: np.abs(x).max(axis=-1)),
    "count_nonzero": C(lambda a, b: M.count_nonzero(a),
                       lambda a, b: np.count_nonzero(a), "int2", dtypes=F32,
                       exact=True),
    "count_zero": C(lambda a, b: M.count_zero(a),
                    lambda a, b: a.size - np.count_nonzero(a), "int2",
                    dtypes=F32, exact=True),
    "entropy": C(lambda x: M.entropy(x, axis=-1),
                 lambda x: -(x * np.log(x)).sum(axis=-1), "prob"),
    "log_entropy": C(lambda x: M.log_entropy(x, axis=-1),
                     lambda x: np.log(-(x * np.log(x)).sum(axis=-1)), "prob"),
    "shannon_entropy": C(lambda x: M.shannon_entropy(x, axis=-1),
                         lambda x: -(x * np.log2(x)).sum(axis=-1), "prob"),
    "amean": C(lambda x: M.amean(x, axis=-1), lambda x: np.abs(x).mean(axis=-1)),
    "amax": C(lambda x: M.amax(x, axis=-1), lambda x: np.abs(x).max(axis=-1)),
    "amin": C(lambda x: M.amin(x, axis=-1), lambda x: np.abs(x).min(axis=-1)),
    "asum": C(lambda x: M.asum(x, axis=-1), lambda x: np.abs(x).sum(axis=-1)),
    # --- reduce3 ----------------------------------------------------------
    "cosine_similarity": C(
        M.cosine_similarity,
        lambda x, y: (x * y).sum(-1) / (np.linalg.norm(x, axis=-1)
                                        * np.linalg.norm(y, axis=-1)),
        "binary_any"),
    "cosine_distance": C(
        M.cosine_distance,
        lambda x, y: 1 - (x * y).sum(-1) / (np.linalg.norm(x, axis=-1)
                                            * np.linalg.norm(y, axis=-1)),
        "binary_any"),
    "euclidean_distance": C(M.euclidean_distance,
                            lambda x, y: np.linalg.norm(x - y, axis=-1),
                            "binary_any"),
    "manhattan_distance": C(M.manhattan_distance,
                            lambda x, y: np.abs(x - y).sum(-1), "binary_any"),
    "hamming_distance": C(M.hamming_distance,
                          lambda x, y: (x != y).sum(-1).astype(float),
                          "int2", dtypes=F32),
    "jaccard_distance": C(
        M.jaccard_distance,
        lambda x, y: 1 - np.minimum(x, y).sum(-1) / np.maximum(x, y).sum(-1),
        "binary_pos"),
    "dot": C(M.dot, lambda x, y: (x * y).sum(-1), "binary_any"),
    # --- index reductions -------------------------------------------------
    "iamax": C(lambda x: M.iamax(x, axis=-1),
               lambda x: np.argmax(np.abs(x), axis=-1), "distinct",
               dtypes=F32, exact=True),
    "iamin": C(lambda x: M.iamin(x, axis=-1),
               lambda x: np.argmin(np.abs(x), axis=-1), "distinct",
               dtypes=F32, exact=True),
    "first_index": C(lambda x: M.first_index(x, x[1, 2]),
                     lambda x: np.argmax(x == x[1, 2], axis=-1), "distinct",
                     dtypes=F32, exact=True),
    # --- matrix -----------------------------------------------------------
    "matmul": C(lambda x, y: M.matmul(x, y.T),
                lambda x, y: x @ y.T, "binary_any",
                tol={"float32": dict(rtol=1e-4, atol=1e-4)}),
    "mmul": C(lambda x, y: M.mmul(x, y, transpose_a=True),
              lambda x, y: x.T @ y, "binary_any",
              tol={"float32": dict(rtol=1e-4, atol=1e-4)}),
    "tensordot": C(lambda x, y: M.tensordot(x, y.T, axes=1),
                   lambda x, y: np.tensordot(x, y.T, axes=1), "binary_any",
                   tol={"float32": dict(rtol=1e-4, atol=1e-4)}),
    "einsum": C(lambda x, y: M.einsum("ij,kj->ik", x, y),
                lambda x, y: np.einsum("ij,kj->ik", x, y), "binary_any",
                tol={"float32": dict(rtol=1e-4, atol=1e-4)}),
    "trace": C(M.trace, np.trace),
    "diag": C(lambda x: M.diag(x[0]), lambda x: np.diag(x[0])),
    "outer": C(lambda x, y: M.outer(x[0], y[0]),
               lambda x, y: np.outer(x[0], y[0]), "binary_any"),
    "kron": C(lambda x, y: M.kron(x[:2, :2], y[:2, :2]),
              lambda x, y: np.kron(x[:2, :2], y[:2, :2]), "binary_any"),
    # --- shape ops --------------------------------------------------------
    "reshape": C(lambda x: M.reshape(x, (3, 8)), lambda x: x.reshape(3, 8),
                 exact=True, dtypes=F32),
    "transpose": C(M.transpose, np.transpose, exact=True, dtypes=F32),
    "permute": C(M.permute, np.transpose, exact=True, dtypes=F32),
    "concat": C(lambda x, y: M.concat([x, y], axis=0),
                lambda x, y: np.concatenate([x, y], axis=0), "binary_any",
                exact=True, dtypes=F32),
    "stack": C(lambda x, y: M.stack([x, y], axis=1),
               lambda x, y: np.stack([x, y], axis=1), "binary_any",
               exact=True, dtypes=F32),
    "unstack": C(lambda x: M.unstack(x, axis=0)[2], lambda x: x[2],
                 exact=True, dtypes=F32),
    "split": C(lambda x: M.split(x, 2, axis=1)[1],
               lambda x: np.split(x, 2, axis=1)[1], exact=True, dtypes=F32),
    "tile": C(lambda x: M.tile(x, (2, 1)), lambda x: np.tile(x, (2, 1)),
              exact=True, dtypes=F32),
    "repeat": C(lambda x: M.repeat(x, 2, axis=1),
                lambda x: np.repeat(x, 2, axis=1), exact=True, dtypes=F32),
    "squeeze": C(lambda x: M.squeeze(x[None]), lambda x: x, exact=True,
                 dtypes=F32),
    "expand_dims": C(lambda x: M.expand_dims(x, 1),
                     lambda x: np.expand_dims(x, 1), exact=True, dtypes=F32),
    "flip": C(lambda x: M.flip(x, axis=1), lambda x: np.flip(x, axis=1),
              exact=True, dtypes=F32),
    "roll": C(lambda x: M.roll(x, 2, axis=1), lambda x: np.roll(x, 2, axis=1),
              exact=True, dtypes=F32),
    "pad": C(lambda x: M.pad(x, ((1, 1), (0, 2))),
             lambda x: np.pad(x, ((1, 1), (0, 2))), exact=True, dtypes=F32),
    "gather": C(lambda x: M.gather(x, np.array([2, 0, 1]), axis=0),
                lambda x: np.take(x, [2, 0, 1], axis=0), exact=True,
                dtypes=F32),
    "take_along_axis": C(
        lambda x: M.take_along_axis(x, np.argsort(np.asarray(x), axis=1), axis=1),
        lambda x: np.take_along_axis(x, np.argsort(x, axis=1), axis=1),
        "distinct", exact=True, dtypes=F32),
    "gather_nd": C(
        lambda x: M.gather_nd(x, np.array([[0, 1], [3, 5], [2, 2]])),
        lambda x: x[[0, 3, 2], [1, 5, 2]], exact=True, dtypes=F32),
    "scatter_update": C(
        lambda x: M.scatter_update(x, np.array([1, 3]), jnp.zeros((2, SHAPE[1]), x.dtype)),
        lambda x: _np_scatter(x, "set"), exact=True, dtypes=F32),
    "scatter_add": C(
        lambda x: M.scatter_add(x, np.array([1, 1]), jnp.ones((2, SHAPE[1]), x.dtype)),
        lambda x: _np_scatter(x, "add"), dtypes=F32),
    "one_hot": C(lambda a, b: M.one_hot(a[0] % 5, 5, on_value=0.9, off_value=0.1),
                 lambda a, b: np.eye(5)[a[0] % 5] * 0.8 + 0.1, "int2",
                 dtypes=F32),
    # --- segment ops ------------------------------------------------------
    "segment_sum": C(
        lambda x: M.segment_sum(x, np.array([0, 0, 1, 3]), 4),
        lambda x: _np_segment("sum", x, [0, 0, 1, 3], 4), dtypes=F32),
    "segment_max": C(
        lambda x: M.segment_max(x, np.array([0, 0, 1, 3]), 4),
        lambda x: _np_segment("max", x, [0, 0, 1, 3], 4), dtypes=F32,
        postprocess=lambda a: np.where(np.isfinite(a), a, 0.0)),
    "segment_min": C(
        lambda x: M.segment_min(x, np.array([0, 0, 1, 3]), 4),
        lambda x: _np_segment("min", x, [0, 0, 1, 3], 4), dtypes=F32,
        postprocess=lambda a: np.where(np.isfinite(a), a, 0.0)),
    "segment_mean": C(
        lambda x: M.segment_mean(x, np.array([0, 0, 1, 1]), 2),
        lambda x: np.stack([x[:2].mean(0), x[2:4].mean(0)]), dtypes=F32),
    "unsorted_segment_sum": C(
        lambda x: M.unsorted_segment_sum(x, np.array([2, 0, 2, 1]), 3),
        lambda x: _np_segment("sum", x, [2, 0, 2, 1], 3), dtypes=F32),
    # --- top-k / sort -----------------------------------------------------
    "top_k": C(lambda x: M.top_k(x, 3)[0],
               lambda x: -np.sort(-x, axis=-1)[:, :3], "distinct",
               exact=True, dtypes=F32),
    "sort": C(lambda x: M.sort(x, axis=-1), lambda x: np.sort(x, axis=-1),
              "distinct", exact=True, dtypes=F32),
    "argsort": C(lambda x: M.argsort(x, axis=-1),
                 lambda x: np.argsort(x, axis=-1), "distinct", exact=True,
                 dtypes=F32),
    "in_top_k": C(
        lambda x: M.in_top_k(x, np.argmax(np.asarray(x), axis=-1), 2),
        lambda x: np.ones(x.shape[0], bool), "distinct", exact=True,
        dtypes=F32),
    # --- misc -------------------------------------------------------------
    "is_nan": C(lambda x: M.is_nan(_specials(x)),
                lambda x: np.isnan(_specials(x)), exact=True, dtypes=F32),
    "is_inf": C(lambda x: M.is_inf(_specials(x)),
                lambda x: np.isinf(_specials(x)), exact=True, dtypes=F32),
    "is_finite": C(lambda x: M.is_finite(_specials(x)),
                   lambda x: np.isfinite(_specials(x)), exact=True, dtypes=F32),
    "nan_to_num": C(lambda x: M.nan_to_num(_specials(x)),
                    lambda x: np.nan_to_num(_specials(x)), dtypes=F32),
    "unique": C(lambda a, b: M.unique(a), lambda a, b: np.unique(a), "int2",
                exact=True, dtypes=F32),
    "searchsorted": C(lambda x: M.searchsorted(np.sort(np.asarray(x[0])), x[1]),
                      lambda x: np.searchsorted(np.sort(x[0]), x[1]),
                      exact=True, dtypes=F32),
    "linspace": C(lambda x: M.linspace(0.0, 5.0, 7),
                  lambda x: np.linspace(0.0, 5.0, 7), dtypes=F32),
    "arange": C(lambda x: M.arange(1, 17, 3), lambda x: np.arange(1, 17, 3),
                exact=True, dtypes=F32),
    "eye": C(lambda x: M.eye(5), lambda x: np.eye(5), exact=True, dtypes=F32),
    "meshgrid": C(lambda x: M.meshgrid(x[0], x[1])[0],
                  lambda x: np.meshgrid(x[0], x[1])[0], exact=True, dtypes=F32),
    "zeros_like": C(M.zeros_like, np.zeros_like, exact=True, dtypes=F32),
    "ones_like": C(M.ones_like, np.ones_like, exact=True, dtypes=F32),
    "full_like": C(lambda x: M.full_like(x, 3.5),
                   lambda x: np.full_like(x, 3.5), exact=True, dtypes=F32),
    "moments": C(lambda x: M.moments(x, axes=-1)[1],
                 lambda x: np.var(x, axis=-1)),
    "standardize": C(
        M.standardize,
        lambda x: (x - x.mean(-1, keepdims=True)) / x.std(-1, keepdims=True),
        tol={"bfloat16": dict(rtol=1e-1, atol=1e-1)}),
    "zero_fraction": C(lambda a, b: M.zero_fraction(a),
                       lambda a, b: (a == 0).mean(), "int2", dtypes=F32),
    "confusion_matrix": C(
        lambda a, b: M.confusion_matrix(a[0] % 4, b[0] % 4, 4),
        lambda a, b: _np_confusion(a[0] % 4, b[0] % 4, 4), "int2", dtypes=F32),
}


def _np_clip_by_norm_global(x):
    tree = [x, 2 * x]
    g = np.sqrt(sum(np.square(t).sum() for t in tree))
    return x * min(1.0, 1.5 / max(g, 1e-12))


def _np_scatter(x, mode):
    c = np.asarray(x).copy()
    if mode == "set":
        c[[1, 3]] = 0.0
    else:
        c[1] = c[1] + 2.0  # two updates accumulate at the same index
    return c


def _specials(x):
    x = np.asarray(x, np.float32).copy()
    x[0, 0] = np.nan
    x[1, 1] = np.inf
    x[2, 2] = -np.inf
    return x


def _np_confusion(labels, preds, n):
    out = np.zeros((n, n))
    for l, p in zip(labels.ravel(), preds.ravel()):
        out[l, p] += 1
    return out


# ---------------------------------------------------------------------------
# The matrix
# ---------------------------------------------------------------------------

_PARAMS = [(name, dt) for name, case in sorted(CASES.items())
           for dt in case.dtypes]


@pytest.mark.parametrize("name,dtype", _PARAMS, ids=[f"{n}-{d}" for n, d in _PARAMS])
def test_op_conformance(name, dtype):
    import zlib

    case = CASES[name]
    raw = _gen(case.kind, seed=zlib.crc32(name.encode()) % 2 ** 31)

    def cast(a):
        if a.dtype.kind in "fc":
            return jnp.asarray(a, dtype=jnp.dtype(dtype))
        return jnp.asarray(a)

    got = case.fn(*[cast(a) for a in raw])
    if case.exact:
        # structural ops: the oracle sees the SAME cast inputs (bit-identity)
        oracle = np.asarray(case.oracle(*[np.asarray(cast(a)) for a in raw]))
        np.testing.assert_array_equal(np.asarray(got, oracle.dtype), oracle,
                                      err_msg=name)
    else:
        # numeric ops: fp64 ground truth, dtype-scaled tolerance
        oracle = np.asarray(case.oracle(*raw), np.float64)
        got = np.asarray(got, np.float64)
        if case.postprocess is not None:
            got = case.postprocess(got)
            oracle = case.postprocess(oracle)
        tol = dict(_TOL[dtype])
        tol.update(case.tol.get(dtype, {}))
        np.testing.assert_allclose(got, oracle, err_msg=name, **tol)


def test_catalog_coverage():
    """Every public callable/alias in ops/math.py must be in the matrix."""
    public = set()
    for n, v in vars(M).items():
        if n.startswith("_") or n in ("annotations", "jax", "jnp", "lax"):
            continue
        if callable(v):
            public.add(n)
    covered = set(CASES)
    missing = sorted(public - covered)
    frac = len(public & covered) / max(len(public), 1)
    assert frac >= 0.95, f"op catalog coverage {frac:.0%}; missing: {missing}"
