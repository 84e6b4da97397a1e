"""SameDiff-analogue graph program layer — define-by-run graph, compiled whole.

ref: org.nd4j.autodiff.samediff.SameDiff (the ~12k-LoC graph builder god
class), SDVariable, the SD op namespaces (SDMath/SDNN/SDCNN/SDRNN/SDLoss),
AbstractSession/InferenceSession/TrainingSession (topological per-op
interpreters), SameDiff.createGradFunction, SameDiff.save/load (FlatBuffers
.fb), TrainingConfig (SURVEY §2.3, §3.2).

TPU-first inversion: the reference *interprets* the graph op-by-op, paying
JNI dispatch + dependency tracking + refcounting per op per batch. Here the
recorded graph is *replayed once inside jax tracing* and compiled by XLA to
a single TPU program; the per-op interpreter disappears (one dispatch per
step, fusion across the whole graph). An interpreted eager mode is kept for
debugging/listeners (``sd.output(..., interpreted=True)``) — the moral
equivalent of InferenceSession, useful for per-op inspection, never for the
hot path.

Variable kinds match the reference: VARIABLE (trainable, persisted),
CONSTANT (persisted, not trained), PLACEHOLDER (fed per call), ARRAY
(activations — here just recorded graph nodes, never materialized except
under the interpreter).

Serialization: the reference stores graph+weights+updater state in one
FlatBuffers file; here ``save()`` writes a zip of ``graph.json`` (ops,
variables, attrs) + ``arrays.npz`` (VARIABLE/CONSTANT values) + optional
updater state, and ``export_stablehlo()`` additionally serializes the
compiled program itself (jax.export) — the analogue of shipping the
FlatBuffers graph to the native graph executor.
"""

from __future__ import annotations

import dataclasses
import enum
import io
import json
import math
import zipfile
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.ops import cnn as ops_cnn
from deeplearning4j_tpu.ops import loss as ops_loss
from deeplearning4j_tpu.ops import math as ops_math
from deeplearning4j_tpu.ops import nn as ops_nn
from deeplearning4j_tpu.ops import rnn as ops_rnn

# ---------------------------------------------------------------------------
# Op registry: op-name -> pure callable. Ops must be registered by name so
# graphs are serializable (↔ libnd4j OpRegistrator / DifferentialFunction
# opName()). kwargs recorded in the graph must be JSON-able.
# ---------------------------------------------------------------------------

OP_REGISTRY: Dict[str, Callable] = {}


def register_op(name: str, fn: Callable) -> None:
    OP_REGISTRY[name] = fn


def _register_module(prefix: str, module, names: Optional[Sequence[str]] = None):
    for attr in names if names is not None else dir(module):
        if attr.startswith("_"):
            continue
        fn = getattr(module, attr, None)
        if callable(fn):
            register_op(f"{prefix}.{attr}", fn)


_register_module("math", ops_math)
_register_module("nn", ops_nn)
_register_module("cnn", ops_cnn)
_register_module("rnn", ops_rnn)
_register_module("loss", ops_loss)

# Core structural ops (↔ the reference's SDBaseOps on SameDiff itself).
_CORE_OPS = {
    "add": jnp.add,
    "sub": jnp.subtract,
    "mul": jnp.multiply,
    "div": jnp.divide,
    "pow": jnp.power,
    "mod": jnp.mod,
    "neg": jnp.negative,
    "matmul": lambda a, b: jnp.matmul(a, b),
    "reshape": lambda x, shape: jnp.reshape(x, shape),
    "transpose": lambda x, axes=None: jnp.transpose(x, axes),
    "permute": lambda x, axes: jnp.transpose(x, axes),
    "expand_dims": lambda x, axis: jnp.expand_dims(x, axis),
    "squeeze": lambda x, axis=None: jnp.squeeze(x, axis),
    "concat": lambda *xs, axis=0: jnp.concatenate(xs, axis=axis),
    "stack": lambda *xs, axis=0: jnp.stack(xs, axis=axis),
    "unstack": lambda x, axis=0: tuple(jnp.moveaxis(x, axis, 0)),
    "slice": lambda x, begin, size: jax.lax.dynamic_slice(x, begin, size),
    "strided_slice": lambda x, begin, end, strides: x[
        tuple(slice(b, e, s) for b, e, s in zip(begin, end, strides))
    ],
    "gather": lambda x, indices, axis=0: jnp.take(x, jnp.asarray(indices), axis=axis),
    "tile": lambda x, reps: jnp.tile(x, reps),
    "cast": lambda x, dtype: x.astype(jnp.dtype(dtype)),
    "sum": lambda x, axis=None, keepdims=False: jnp.sum(x, axis=_ax(axis), keepdims=keepdims),
    "mean": lambda x, axis=None, keepdims=False: jnp.mean(x, axis=_ax(axis), keepdims=keepdims),
    "max": lambda x, axis=None, keepdims=False: jnp.max(x, axis=_ax(axis), keepdims=keepdims),
    "min": lambda x, axis=None, keepdims=False: jnp.min(x, axis=_ax(axis), keepdims=keepdims),
    "prod": lambda x, axis=None, keepdims=False: jnp.prod(x, axis=_ax(axis), keepdims=keepdims),
    "std": lambda x, axis=None, keepdims=False, bias_corrected=True: jnp.std(
        x, axis=_ax(axis), keepdims=keepdims, ddof=1 if bias_corrected else 0
    ),
    "var": lambda x, axis=None, keepdims=False, bias_corrected=True: jnp.var(
        x, axis=_ax(axis), keepdims=keepdims, ddof=1 if bias_corrected else 0
    ),
    "argmax": lambda x, axis=None: jnp.argmax(x, axis=axis),
    "argmin": lambda x, axis=None: jnp.argmin(x, axis=axis),
    "softmax": lambda x, axis=-1: jax.nn.softmax(x, axis=axis),
    "log_softmax": lambda x, axis=-1: jax.nn.log_softmax(x, axis=axis),
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "relu": jax.nn.relu,
    "relu6": jax.nn.relu6,
    "leaky_relu": lambda x, alpha=0.01: jax.nn.leaky_relu(x, alpha),
    "elu": jax.nn.elu,
    "gelu": jax.nn.gelu,
    "selu": jax.nn.selu,
    "softplus": jax.nn.softplus,
    "softsign": jax.nn.soft_sign,
    "swish": jax.nn.swish,
    "exp": jnp.exp,
    "log": jnp.log,
    "sqrt": jnp.sqrt,
    "square": jnp.square,
    "abs": jnp.abs,
    "eq": lambda a, b: jnp.equal(a, b),
    "neq": lambda a, b: jnp.not_equal(a, b),
    "gt": lambda a, b: jnp.greater(a, b),
    "gte": lambda a, b: jnp.greater_equal(a, b),
    "lt": lambda a, b: jnp.less(a, b),
    "lte": lambda a, b: jnp.less_equal(a, b),
    "where": lambda c, a, b: jnp.where(c, a, b),
    "maximum": jnp.maximum,
    "minimum": jnp.minimum,
    "cumsum": lambda x, axis=0: jnp.cumsum(x, axis=axis),
    "cumprod": lambda x, axis=0: jnp.cumprod(x, axis=axis),
    "zeros_like": jnp.zeros_like,
    "ones_like": jnp.ones_like,
    "identity": lambda x: x,
    "shape_of": lambda x: jnp.asarray(x.shape, jnp.int32),
    "size": lambda x: jnp.asarray(x.size, jnp.int32),
    "rank": lambda x: jnp.asarray(x.ndim, jnp.int32),
}


def _ax(axis):
    return tuple(axis) if isinstance(axis, list) else axis


for _n, _f in _CORE_OPS.items():
    register_op(_n, _f)


class VariableType(str, enum.Enum):
    """ref: org.nd4j.autodiff.samediff.VariableType."""

    VARIABLE = "VARIABLE"
    CONSTANT = "CONSTANT"
    PLACEHOLDER = "PLACEHOLDER"
    ARRAY = "ARRAY"


@dataclasses.dataclass
class OpNode:
    """One recorded graph op (↔ SameDiffOp: op + input/output var names)."""

    op: str
    inputs: List[str]
    outputs: List[str]
    attrs: Dict[str, Any]
    subgraphs: Optional[Dict[str, "SameDiff"]] = None  # control flow branches


class SDVariable:
    """Symbolic handle into a SameDiff graph (↔ org.nd4j.autodiff.samediff.SDVariable)."""

    def __init__(self, sd: "SameDiff", name: str, var_type: VariableType,
                 shape=None, dtype=None):
        self.sd = sd
        self.name = name
        self.var_type = var_type
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = str(np.dtype(dtype)) if dtype is not None else None

    # -- arithmetic sugar (↔ SDVariable.add/sub/mul/... and rsub/rdiv) -----
    def _bin(self, op, other, reverse=False):
        other = self.sd._lift(other)
        a, b = (other, self) if reverse else (self, other)
        return self.sd._record(op, [a, b], {})

    def __add__(self, o):
        return self._bin("add", o)

    __radd__ = __add__

    def __sub__(self, o):
        return self._bin("sub", o)

    def __rsub__(self, o):
        return self._bin("sub", o, reverse=True)

    def __mul__(self, o):
        return self._bin("mul", o)

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._bin("div", o)

    def __rtruediv__(self, o):
        return self._bin("div", o, reverse=True)

    def __pow__(self, o):
        return self._bin("pow", o)

    def __matmul__(self, o):
        return self._bin("matmul", o)

    def __neg__(self):
        return self.sd._record("neg", [self], {})

    # DL4J method names
    def add(self, o):
        return self + o

    def sub(self, o):
        return self - o

    def mul(self, o):
        return self * o

    def div(self, o):
        return self / o

    def rsub(self, o):
        return self._bin("sub", o, reverse=True)

    def rdiv(self, o):
        return self._bin("div", o, reverse=True)

    def mmul(self, o):
        return self @ o

    def dot(self, o):
        return self @ o

    # comparisons
    def eq(self, o):
        return self._bin("eq", o)

    def neq(self, o):
        return self._bin("neq", o)

    def gt(self, o):
        return self._bin("gt", o)

    def gte(self, o):
        return self._bin("gte", o)

    def lt(self, o):
        return self._bin("lt", o)

    def lte(self, o):
        return self._bin("lte", o)

    # shape ops
    def reshape(self, *shape):
        shape = shape[0] if len(shape) == 1 and isinstance(shape[0], (tuple, list)) else shape
        return self.sd._record("reshape", [self], {"shape": list(shape)})

    def transpose(self, axes=None):
        return self.sd._record("transpose", [self], {"axes": list(axes) if axes else None})

    def permute(self, *axes):
        axes = axes[0] if len(axes) == 1 and isinstance(axes[0], (tuple, list)) else axes
        return self.sd._record("permute", [self], {"axes": list(axes)})

    def cast(self, dtype):
        return self.sd._record("cast", [self], {"dtype": str(np.dtype(dtype))})

    # reductions
    def sum(self, axis=None, keepdims=False):
        return self.sd._record("sum", [self], {"axis": axis, "keepdims": keepdims})

    def mean(self, axis=None, keepdims=False):
        return self.sd._record("mean", [self], {"axis": axis, "keepdims": keepdims})

    def max(self, axis=None, keepdims=False):
        return self.sd._record("max", [self], {"axis": axis, "keepdims": keepdims})

    def min(self, axis=None, keepdims=False):
        return self.sd._record("min", [self], {"axis": axis, "keepdims": keepdims})

    def std(self, bias_corrected=True, axis=None, keepdims=False):
        return self.sd._record(
            "std", [self],
            {"axis": axis, "keepdims": keepdims, "bias_corrected": bias_corrected})

    def norm2(self, axis=None):
        return self.sd._record("math.norm2", [self], {"axis": axis})

    def argmax(self, axis=None):
        return self.sd._record("argmax", [self], {"axis": axis})

    def argmin(self, axis=None):
        return self.sd._record("argmin", [self], {"axis": axis})

    # evaluation
    def eval(self, feeds: Optional[Dict[str, Any]] = None):
        """Evaluate this variable (↔ SDVariable.eval())."""
        return self.sd.output(feeds or {}, [self.name])[self.name]

    def __repr__(self):
        return (f"SDVariable(name={self.name!r}, type={self.var_type.value}, "
                f"shape={self.shape}, dtype={self.dtype})")


class _Namespace:
    """Recording wrapper over one ops module (↔ SDMath/SDNN/SDCNN/SDRNN/SDLoss)."""

    def __init__(self, sd: "SameDiff", prefix: str):
        self._sd = sd
        self._prefix = prefix

    def __getattr__(self, opname: str):
        full = f"{self._prefix}.{opname}"
        if full not in OP_REGISTRY:
            raise AttributeError(f"no op {full!r} in registry")
        sd = self._sd

        def record(*args, **kwargs):
            var_args = [sd._lift(a) if _is_arrayish(a) or isinstance(a, SDVariable) else a
                        for a in args]
            inputs = [a for a in var_args if isinstance(a, SDVariable)]
            # Non-variable positional args (ints, tuples...) become attrs by
            # position; the replay reconstructs the original arg order.
            arg_kinds = ["var" if isinstance(a, SDVariable) else "attr" for a in var_args]
            attr_pos = [a for a in var_args if not isinstance(a, SDVariable)]
            attrs = dict(kwargs)
            attrs["__argspec__"] = arg_kinds
            attrs["__posattrs__"] = attr_pos
            return sd._record(full, inputs, attrs)

        return record


def _is_arrayish(a) -> bool:
    # Python scalars stay attrs (serializable); arrays become constants.
    return isinstance(a, (np.ndarray, jax.Array))


def _replay_call(fn, node: OpNode, input_vals: List[Any]):
    attrs = dict(node.attrs)
    argspec = attrs.pop("__argspec__", None)
    posattrs = list(attrs.pop("__posattrs__", []))
    if argspec is None:
        return fn(*input_vals, **_dejson(attrs))
    args = []
    vi = iter(input_vals)
    ai = iter(posattrs)
    for kind in argspec:
        args.append(next(vi) if kind == "var" else _dejson_val(next(ai)))
    return fn(*args, **_dejson(attrs))


def _dejson(attrs: Dict[str, Any]) -> Dict[str, Any]:
    return {k: _dejson_val(v) for k, v in attrs.items()}


def _dejson_val(v):
    if isinstance(v, list):
        return tuple(_dejson_val(x) for x in v)
    return v


class SameDiff:
    """The graph builder + executor (↔ org.nd4j.autodiff.samediff.SameDiff).

    Usage mirrors the reference::

        sd = SameDiff.create()
        x = sd.placeholder("x", (None, 784), "float32")
        w = sd.var("w", np.zeros((784, 10), np.float32))
        b = sd.var("b", np.zeros((10,), np.float32))
        logits = x.mmul(w) + b
        probs = sd.nn.softmax(logits)  # recorded op
        out = probs.eval({"x": batch})
    """

    def __init__(self):
        self._vars: Dict[str, SDVariable] = {}
        self._values: Dict[str, np.ndarray] = {}  # VARIABLE + CONSTANT data
        self._nodes: List[OpNode] = []
        self._producer: Dict[str, int] = {}  # var name -> node index
        self._counter = 0
        self._fn_cache: Dict[Tuple, Callable] = {}
        self.math = _Namespace(self, "math")
        self.nn = _Namespace(self, "nn")
        self.cnn = _Namespace(self, "cnn")
        self.rnn = _Namespace(self, "rnn")
        self.loss = _Namespace(self, "loss")
        self.training_config: Optional[TrainingConfig] = None
        self._updater_state = None
        self._updater_leaves = None  # loaded-from-checkpoint leaves, pending restore
        self._iteration = 0
        self.listeners: List[Any] = []
        # When this graph is a control-flow branch (cond/while subgraph),
        # an explicit ordered output list. None = the terminal-vars
        # heuristic in _as_branch_fn. The TF importer sets this: a
        # FunctionDef's rets are named and ordered, and loop-carry order
        # must match lax.while_loop's carry exactly.
        self.branch_outputs: Optional[List[str]] = None

    # -- construction ------------------------------------------------------

    @staticmethod
    def create() -> "SameDiff":
        return SameDiff()

    def _fresh_name(self, base: str) -> str:
        self._counter += 1
        name = f"{base}_{self._counter}"
        while name in self._vars:
            self._counter += 1
            name = f"{base}_{self._counter}"
        return name

    def _add_var(self, name, var_type, shape=None, dtype=None) -> SDVariable:
        if name in self._vars:
            raise ValueError(f"variable {name!r} already exists")
        v = SDVariable(self, name, var_type, shape, dtype)
        self._vars[name] = v
        return v

    def var(self, name: str, value=None, shape=None, dtype="float32",
            initializer=None, seed: int = 0) -> SDVariable:
        """Trainable VARIABLE (↔ sd.var). Give ``value`` or ``shape``+init."""
        if value is None:
            if shape is None:
                raise ValueError("var needs value or shape")
            if initializer is None:
                value = np.zeros(shape, dtype)
            else:
                from deeplearning4j_tpu.nn.initializers import get_initializer
                init = get_initializer(initializer)
                value = np.asarray(
                    init(jax.random.key(seed), tuple(shape), jnp.dtype(dtype)))
        value = np.asarray(value)
        v = self._add_var(name, VariableType.VARIABLE, value.shape, value.dtype)
        self._values[name] = value
        return v

    def constant(self, name: str, value) -> SDVariable:
        value = np.asarray(value)
        v = self._add_var(name, VariableType.CONSTANT, value.shape, value.dtype)
        self._values[name] = value
        return v

    def placeholder(self, name: str, shape=None, dtype="float32") -> SDVariable:
        return self._add_var(name, VariableType.PLACEHOLDER, shape, dtype)

    def convert_to_variable(self, name: str) -> SDVariable:
        """CONSTANT → trainable VARIABLE in place (↔ sd.convertToVariable).

        The model-import path creates weights as constants; fine-tuning an
        imported graph promotes them so gradients/updaters apply.
        """
        v = self._vars[name]
        if v.var_type == VariableType.VARIABLE:
            return v
        if v.var_type != VariableType.CONSTANT:
            raise ValueError(f"{name!r} is {v.var_type.value}, not constant")
        v.var_type = VariableType.VARIABLE
        self._fn_cache.clear()
        # Updater state is keyed to the trainable set; a stale pytree would
        # mismatch on the next fit().
        self._updater_state = None
        self._updater_leaves = None
        return v

    def convert_to_constant(self, name: str) -> SDVariable:
        """VARIABLE → CONSTANT in place (↔ sd.convertToConstant) — e.g.
        freezing layers before fine-tuning."""
        v = self._vars[name]
        if v.var_type == VariableType.CONSTANT:
            return v
        if v.var_type != VariableType.VARIABLE:
            raise ValueError(f"{name!r} is {v.var_type.value}, not variable")
        v.var_type = VariableType.CONSTANT
        self._fn_cache.clear()
        self._updater_state = None
        self._updater_leaves = None
        return v

    def _lift(self, value) -> SDVariable:
        """Wrap a literal array/scalar as an (anonymous) constant variable."""
        if isinstance(value, SDVariable):
            return value
        arr = np.asarray(value)
        name = self._fresh_name("const")
        v = self._add_var(name, VariableType.CONSTANT, arr.shape, arr.dtype)
        self._values[name] = arr
        return v

    # -- recording ---------------------------------------------------------

    def _record(self, op: str, inputs: List[SDVariable], attrs: Dict[str, Any],
                subgraphs: Optional[Dict[str, "SameDiff"]] = None):
        if op not in OP_REGISTRY:
            raise KeyError(f"op {op!r} not registered")
        for v in inputs:
            if v.sd is not self:
                raise ValueError(f"variable {v.name} belongs to another graph")
        out_structs = self._infer(op, inputs, attrs, subgraphs)
        base = op.split(".")[-1]
        outs: List[SDVariable] = []
        for s in out_structs:
            name = self._fresh_name(base)
            shape = getattr(s, "shape", None)
            dtype = getattr(s, "dtype", None)
            outs.append(self._add_var(name, VariableType.ARRAY, shape, dtype))
        node = OpNode(op, [v.name for v in inputs], [v.name for v in outs],
                      _jsonable_attrs(attrs), subgraphs)
        idx = len(self._nodes)
        self._nodes.append(node)
        for v in outs:
            self._producer[v.name] = idx
        self._fn_cache.clear()
        return outs[0] if len(outs) == 1 else tuple(outs)

    def _infer(self, op, inputs, attrs, subgraphs):
        """Shape/dtype inference via abstract eval (↔ libnd4j shape functions)."""
        fn = OP_REGISTRY[op]
        structs = []
        for v in inputs:
            shape = tuple(2 if (d is None or d == -1) else d for d in (v.shape or ()))
            dtype = v.dtype or "float32"
            structs.append(jax.ShapeDtypeStruct(shape, jnp.dtype(dtype)))
        node = OpNode(op, [v.name for v in inputs], [], dict(attrs), subgraphs)
        try:
            out = jax.eval_shape(
                lambda *vals: _replay_call_node(self, node, fn, list(vals)), *structs)
        except Exception as e:
            if op in ("__while__", "__cond__"):
                # Control flow MUST infer: its output arity equals the
                # carry/branch arity, and a silent single-unknown fallback
                # would mis-wire every downstream consumer (carry dtype
                # mismatches surface here, e.g. lax.while_loop rejecting
                # an inconsistent body).
                raise ValueError(
                    f"control-flow op {op} failed shape inference: "
                    f"{e}") from e
            return [_UnknownStruct()]
        leaves = out if isinstance(out, (tuple, list)) else [out]
        sym = any(v.shape is not None and any(d in (None, -1) for d in v.shape)
                  for v in inputs)
        if sym:
            # dims were substituted; keep rank/dtype, drop dim values we faked
            return [_UnknownStruct(getattr(s, "dtype", None)) for s in leaves]
        return list(leaves)

    # -- execution ---------------------------------------------------------

    def _ancestors(self, names: Sequence[str]) -> List[int]:
        """Node indices needed to compute `names`, in topological order."""
        needed: set = set()
        stack = [n for n in names if n in self._producer]
        while stack:
            vn = stack.pop()
            idx = self._producer.get(vn)
            if idx is None or idx in needed:
                continue
            needed.add(idx)
            stack.extend(self._nodes[idx].inputs)
        return sorted(needed)

    def _build_fn(self, output_names: Tuple[str, ...], placeholder_names: Tuple[str, ...]):
        """Pure fn(variables, constants, placeholders) -> outputs: replays the
        recorded graph inside jax tracing — compiled ONCE by XLA."""
        order = self._ancestors(output_names)
        nodes = [self._nodes[i] for i in order]

        def fn(variables, constants, placeholders):
            env: Dict[str, Any] = {}
            env.update(constants)
            env.update(variables)
            env.update(placeholders)
            for node in nodes:
                f = OP_REGISTRY[node.op]
                vals = [env[n] for n in node.inputs]
                out = _replay_call_node(self, node, f, vals)
                if isinstance(out, (tuple, list)):
                    for n, o in zip(node.outputs, out):
                        env[n] = o
                else:
                    env[node.outputs[0]] = out
            missing = [n for n in output_names if n not in env]
            if missing:
                raise KeyError(f"outputs not computable: {missing}")
            return {n: env[n] for n in output_names}

        return fn

    def _split_feeds(self, feeds: Dict[str, Any]):
        placeholders = {}
        for k, v in feeds.items():
            if k not in self._vars:
                raise KeyError(f"unknown placeholder {k!r}")
            vt = self._vars[k].var_type
            if vt != VariableType.PLACEHOLDER:
                # Feeding a VARIABLE/CONSTANT would silently shadow its
                # stored value (r1 advisor); state changes go through
                # set_value / convert_to_variable instead.
                raise ValueError(
                    f"cannot feed {vt.name} {k!r}: only placeholders accept "
                    "feeds (use set_value to change stored values)")
            placeholders[k] = jnp.asarray(v)
        variables = {n: self._values[n] for n, v in self._vars.items()
                     if v.var_type == VariableType.VARIABLE}
        constants = {n: self._values[n] for n, v in self._vars.items()
                     if v.var_type == VariableType.CONSTANT}
        return variables, constants, placeholders

    def output(self, feeds: Dict[str, Any], outputs: Sequence[str],
               interpreted: bool = False) -> Dict[str, Any]:
        """Run the graph (↔ SameDiff.output / InferenceSession).

        Compiled by default (whole-graph XLA). ``interpreted=True`` replays
        op-by-op eagerly — the InferenceSession analogue for debugging; op
        listeners (``listeners`` with ``on_op(node, outputs)``) fire only in
        this mode, since compiled execution has no per-op host boundary.
        """
        outputs = tuple(outputs)
        variables, constants, placeholders = self._split_feeds(feeds)
        if interpreted:
            return self._interpret(variables, constants, placeholders, outputs)
        key = (outputs, tuple(sorted(placeholders)))
        if key not in self._fn_cache:
            fn = self._build_fn(outputs, tuple(sorted(placeholders)))
            self._fn_cache[key] = jax.jit(fn)
        res = self._fn_cache[key](variables, constants, placeholders)
        return {k: np.asarray(v) for k, v in res.items()}

    def _interpret(self, variables, constants, placeholders, outputs):
        env = {**constants, **variables, **placeholders}
        for idx in self._ancestors(outputs):
            node = self._nodes[idx]
            f = OP_REGISTRY[node.op]
            out = _replay_call_node(self, node, f, [env[n] for n in node.inputs])
            outs = out if isinstance(out, (tuple, list)) else [out]
            for n, o in zip(node.outputs, outs):
                env[n] = o
            for lst in self.listeners:
                if hasattr(lst, "on_op"):
                    lst.on_op(node, {n: env[n] for n in node.outputs})
        return {n: np.asarray(env[n]) for n in outputs}

    def batch_output(self, feeds, outputs):
        return self.output(feeds, outputs)

    # -- gradients (↔ SameDiff.createGradFunction / calculateGradients) ----

    def calculate_gradients(self, feeds: Dict[str, Any], loss: str,
                            wrt: Optional[Sequence[str]] = None) -> Dict[str, np.ndarray]:
        """Gradients of scalar `loss` w.r.t. VARIABLEs (default: all).

        The reference builds a reverse-mode grad *sub-graph* lazily via
        per-op doDiff; here jax.grad derives it from the same replayed
        trace and XLA compiles forward+backward as one program.
        """
        variables, constants, placeholders = self._split_feeds(feeds)
        wrt = tuple(wrt) if wrt is not None else tuple(sorted(variables))
        fn = self._build_fn((loss,), tuple(sorted(placeholders)))

        def loss_of(wrt_vals):
            merged = dict(variables)
            merged.update(wrt_vals)
            out = fn(merged, constants, placeholders)[loss]
            if out.ndim != 0:
                raise ValueError(f"loss {loss!r} is not scalar: shape {out.shape}")
            return out

        grads = jax.jit(jax.grad(loss_of))({n: variables[n] for n in wrt})
        return {k: np.asarray(v) for k, v in grads.items()}

    def grad(self, feeds, loss, var_name):
        return self.calculate_gradients(feeds, loss, [var_name])[var_name]

    # -- control flow (↔ sd.ifCond / sd.whileLoop; lax.cond / while_loop) --

    def cond(self, pred: SDVariable, true_graph: "SameDiff", false_graph: "SameDiff",
             inputs: Sequence[SDVariable]):
        """Record an If: branch subgraphs map their placeholders (declared
        order) to `inputs`. ↔ sd.ifCond; compiles to lax.cond (both branches
        traced, one executed — XLA control flow, no host round-trip)."""
        return self._record("__cond__", [pred, *inputs], {},
                            {"true": true_graph, "false": false_graph})

    def while_loop(self, cond_graph: "SameDiff", body_graph: "SameDiff",
                   inits: Sequence[SDVariable]):
        """Record a While: ↔ sd.whileLoop; compiles to lax.while_loop.

        Reverse-mode differentiation through a while_loop is undefined
        (XLA semantics: dynamic trip count, nothing to checkpoint
        against); calculate_gradients over a graph containing one raises.
        Express differentiable loops as scan-style programs (fixed trip
        count) instead."""
        return self._record("__while__", list(inits), {},
                            {"cond": cond_graph, "body": body_graph})

    def _as_branch_fn(self):
        """This graph as fn(*placeholder_values) -> outputs tuple.
        Outputs are ``branch_outputs`` when set (explicit, ordered — may
        include placeholders for pass-through loop vars), else all
        terminal ARRAY vars (no consumer)."""
        ph = [n for n, v in self._vars.items() if v.var_type == VariableType.PLACEHOLDER]
        if self.branch_outputs is not None:
            outs = list(self.branch_outputs)
        else:
            consumed = {n for node in self._nodes for n in node.inputs}
            outs = [n for n, v in self._vars.items()
                    if v.var_type == VariableType.ARRAY and n not in consumed]
        fn = self._build_fn(tuple(outs), tuple(ph))
        variables = {n: self._values[n] for n, v in self._vars.items()
                     if v.var_type == VariableType.VARIABLE}
        constants = {n: self._values[n] for n, v in self._vars.items()
                     if v.var_type == VariableType.CONSTANT}

        def branch(*vals):
            res = fn(variables, constants, dict(zip(ph, vals)))
            out_vals = tuple(res[n] for n in outs)
            return out_vals[0] if len(out_vals) == 1 else out_vals

        return branch

    # -- training (↔ TrainingSession + SameDiff.fit) -----------------------

    def fit(self, data, config: Optional["TrainingConfig"] = None, *,
            epochs: int = 1, listeners: Optional[List] = None):
        """Train the graph's VARIABLEs. `data` yields dict batches mapping
        placeholder names -> arrays."""
        from deeplearning4j_tpu.train.updaters import apply_updates, resolve_updater

        config = config or self.training_config
        if config is None:
            raise ValueError("no TrainingConfig set")
        self.training_config = config
        listeners = listeners or []

        upd_init, upd_update = resolve_updater(config.updater, **config.updater_args).make()
        variables, constants, _ = self._split_feeds({})
        trainable = {n: jnp.asarray(v) for n, v in variables.items()}
        if self._updater_state is not None:
            opt_state = self._updater_state
        else:
            opt_state = upd_init(trainable)
            if self._updater_leaves is not None:
                # restore a loaded checkpoint's optimizer state into the
                # freshly-built state's tree structure
                treedef = jax.tree_util.tree_structure(opt_state)
                opt_state = jax.tree_util.tree_unflatten(treedef, self._updater_leaves)
                self._updater_leaves = None
        ph_names = tuple(sorted(config.placeholders(self)))
        fn = self._build_fn((config.loss_variable,), ph_names)

        def step(params, opt_state, step_i, batch):
            def loss_of(p):
                loss = fn(p, constants, batch)[config.loss_variable]
                if config.l2 > 0:
                    loss = loss + config.l2 * sum(
                        jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(p))
                if config.l1 > 0:
                    loss = loss + config.l1 * sum(
                        jnp.sum(jnp.abs(x)) for x in jax.tree_util.tree_leaves(p))
                return loss

            loss, grads = jax.value_and_grad(loss_of)(params)
            updates, new_opt = upd_update(grads, opt_state, params, step_i)
            return apply_updates(params, updates), new_opt, loss

        jit_step = jax.jit(step, donate_argnums=(0, 1))
        it_count = self._iteration
        history = []
        for epoch in range(epochs):
            epoch_losses = []
            for batch in data:
                batch = {k: jnp.asarray(v) for k, v in batch.items() if k in ph_names}
                trainable, opt_state, loss = jit_step(
                    trainable, opt_state, jnp.asarray(it_count), batch)
                it_count += 1
                epoch_losses.append(loss)
                for lst in listeners:
                    if hasattr(lst, "on_iteration"):
                        lst.on_iteration(epoch, it_count, None,
                                         {"total_loss": loss})
            if not epoch_losses:
                if epoch == 0:
                    raise ValueError("fit(): data iterable yielded no batches")
                break  # one-shot generator exhausted; don't record stale epochs
            if hasattr(data, "reset"):
                data.reset()
            history.append(float(np.mean(jax.device_get(epoch_losses))))
        for n, v in trainable.items():
            self._values[n] = np.asarray(jax.device_get(v))
        self._updater_state = jax.device_get(opt_state)
        self._iteration = it_count
        return history

    # -- introspection -----------------------------------------------------

    def variables(self) -> List[SDVariable]:
        return list(self._vars.values())

    def get_variable(self, name: str) -> SDVariable:
        return self._vars[name]

    def get_value(self, name: str) -> np.ndarray:
        return self._values[name]

    def set_value(self, name: str, value) -> None:
        if self._vars[name].var_type not in (VariableType.VARIABLE, VariableType.CONSTANT):
            raise ValueError(f"{name} holds no persistent value")
        self._values[name] = np.asarray(value)

    def ops(self) -> List[OpNode]:
        return list(self._nodes)

    def summary(self) -> str:
        lines = [f"SameDiff: {len(self._vars)} vars, {len(self._nodes)} ops"]
        for n, v in self._vars.items():
            if v.var_type != VariableType.ARRAY:
                lines.append(f"  {v.var_type.value:<12} {n:<24} {v.shape} {v.dtype}")
        for node in self._nodes:
            lines.append(f"  op {node.op:<20} {node.inputs} -> {node.outputs}")
        return "\n".join(lines)

    # -- serialization (↔ SameDiff.save/load FlatBuffers .fb) --------------

    def to_dict(self) -> Dict[str, Any]:
        d = {
            "format": "deeplearning4j_tpu.samediff.v1",
            "variables": [
                {"name": n, "type": v.var_type.value, "shape": list(v.shape) if v.shape else None,
                 "dtype": v.dtype}
                for n, v in self._vars.items()
            ],
            "ops": [
                {
                    "op": node.op, "inputs": node.inputs, "outputs": node.outputs,
                    "attrs": node.attrs,
                    "subgraphs": {k: g.to_dict() for k, g in node.subgraphs.items()}
                    if node.subgraphs else None,
                }
                for node in self._nodes
            ],
            "training_config": dataclasses.asdict(self.training_config)
            if self.training_config else None,
            "iteration": self._iteration,
        }
        if self.branch_outputs is not None:
            d["branch_outputs"] = list(self.branch_outputs)
        return d

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "SameDiff":
        sd = SameDiff()
        for v in d["variables"]:
            sd._vars[v["name"]] = SDVariable(
                sd, v["name"], VariableType(v["type"]), v["shape"], v["dtype"])
        for i, o in enumerate(d["ops"]):
            subgraphs = {k: SameDiff.from_dict(g) for k, g in o["subgraphs"].items()} \
                if o.get("subgraphs") else None
            node = OpNode(o["op"], list(o["inputs"]), list(o["outputs"]),
                          dict(o["attrs"]), subgraphs)
            sd._nodes.append(node)
            for out in node.outputs:
                sd._producer[out] = i
        if d.get("training_config"):
            sd.training_config = TrainingConfig(**d["training_config"])
        sd._iteration = int(d.get("iteration", 0))
        sd._counter = len(sd._vars)
        if d.get("branch_outputs") is not None:
            sd.branch_outputs = list(d["branch_outputs"])
        return sd

    def _collect_subgraph_values(self, prefix: str, out: Dict[str, Any]) -> None:
        """Flatten control-flow subgraph constants into npz-able keys:
        ``__sub__|<node_idx>|<subgraph_key>|...|<var_name>``. Subgraphs
        hold their own _values (loop bounds — or captured weights, for
        functional TF imports), which the top-level npz otherwise never
        sees; npz keeps weight-scale constants binary instead of blowing
        up graph.json as JSON text."""
        for i, node in enumerate(self._nodes):
            if not node.subgraphs:
                continue
            for k, g in node.subgraphs.items():
                p = f"{prefix}{i}|{k}|"
                for n, v in g._values.items():
                    if "|" in n:
                        raise ValueError(
                            f"subgraph variable name {n!r} contains '|'")
                    out[f"__sub__|{p}{n}"] = np.asarray(v)
                g._collect_subgraph_values(p, out)

    def _inject_subgraph_value(self, key: str, value) -> None:
        tokens = key.split("|")
        g = self
        while len(tokens) > 1:
            g = g._nodes[int(tokens[0])].subgraphs[tokens[1]]
            tokens = tokens[2:]
        g._values[tokens[0]] = value

    def save(self, path, save_updater_state: bool = True) -> None:
        """One-file zip: graph.json + arrays.npz (+ updater npz)."""
        sub_vals: Dict[str, Any] = {}
        self._collect_subgraph_values("", sub_vals)
        with zipfile.ZipFile(path, "w", zipfile.ZIP_DEFLATED) as zf:
            zf.writestr("graph.json", json.dumps(self.to_dict(), indent=1))
            buf = io.BytesIO()
            np.savez(buf, **self._values, **sub_vals)
            zf.writestr("arrays.npz", buf.getvalue())
            if save_updater_state and self._updater_state is not None:
                leaves, treedef = jax.tree_util.tree_flatten(self._updater_state)
                ubuf = io.BytesIO()
                np.savez(ubuf, **{f"leaf_{i}": np.asarray(x) for i, x in enumerate(leaves)})
                zf.writestr("updater.npz", ubuf.getvalue())

    @staticmethod
    def load(path) -> "SameDiff":
        with zipfile.ZipFile(path, "r") as zf:
            sd = SameDiff.from_dict(json.loads(zf.read("graph.json")))
            with np.load(io.BytesIO(zf.read("arrays.npz"))) as npz:
                sd._values = {k: npz[k] for k in npz.files
                              if not k.startswith("__sub__|")}
                for k in npz.files:
                    if k.startswith("__sub__|"):
                        sd._inject_subgraph_value(
                            k[len("__sub__|"):], npz[k])
            if "updater.npz" in zf.namelist():
                with np.load(io.BytesIO(zf.read("updater.npz"))) as unpz:
                    sd._updater_leaves = [
                        unpz[f"leaf_{i}"] for i in range(len(unpz.files))]
        return sd

    # -- StableHLO export (↔ shipping the .fb graph to the native executor) -

    def export_stablehlo(self, outputs: Sequence[str],
                         feed_specs: Dict[str, Tuple[Tuple[int, ...], str]]) -> bytes:
        """Serialize the compiled program (jax.export). feed_specs maps
        placeholder name -> (shape, dtype). The result runs anywhere PJRT
        does — the role libnd4j's FlatBuffers GraphExecutioner played."""
        from jax import export as jexport

        outputs = tuple(outputs)
        self._require_placeholders(feed_specs)
        ph_names = tuple(sorted(feed_specs))
        fn = self._build_fn(outputs, ph_names)
        variables, constants, _ = self._split_feeds({})

        def program(placeholders):
            return fn(variables, constants, placeholders)

        specs = {n: jax.ShapeDtypeStruct(tuple(s), jnp.dtype(d))
                 for n, (s, d) in feed_specs.items()}
        return bytes(jexport.export(jax.jit(program))(specs).serialize())

    def _require_placeholders(self, names) -> None:
        """Exported-program inputs must be PLACEHOLDERs: a VARIABLE or
        CONSTANT name here would silently become a runtime input shadowing
        its stored value (same hazard _split_feeds rejects for feeds)."""
        for n in names:
            if n not in self._vars:
                raise KeyError(f"unknown placeholder {n!r}")
            vt = self._vars[n].var_type
            if vt != VariableType.PLACEHOLDER:
                raise ValueError(
                    f"export input {n!r} is {vt.name}, not a placeholder")

    @staticmethod
    def run_stablehlo(blob: bytes, feeds: Dict[str, Any]) -> Dict[str, np.ndarray]:
        from jax import export as jexport

        fn = jexport.deserialize(blob)
        out = fn.call({k: jnp.asarray(v) for k, v in feeds.items()})
        return {k: np.asarray(v) for k, v in out.items()}

    def export_stablehlo_text(self, outputs: Sequence[str],
                              feed_specs: Dict[str, Tuple[Tuple[int, ...], str]]
                              ) -> Tuple[str, List[str]]:
        """Raw StableHLO MLIR of the compiled program + the positional arg
        order (sorted placeholder names). This is the form
        runtime/native.NativeRuntime.compile consumes directly — the
        north-star #4 seam: import → train → export → PJRT execute
        without jax in the serving process."""
        outputs = tuple(outputs)
        self._require_placeholders(feed_specs)
        ph_names = tuple(sorted(feed_specs))
        fn = self._build_fn(outputs, ph_names)
        variables, constants, _ = self._split_feeds({})

        def program(*placeholder_vals):
            feeds = dict(zip(ph_names, placeholder_vals))
            out = fn(variables, constants, feeds)
            return tuple(out[o] for o in outputs)

        specs = [jax.ShapeDtypeStruct(tuple(s), jnp.dtype(d))
                 for _, (s, d) in sorted(feed_specs.items())]
        # keep_unused: the MLIR signature must carry EVERY declared
        # placeholder, or arg_order would misalign with main()'s params
        # when an output doesn't consume some feed.
        mlir = jax.jit(program, keep_unused=True).lower(*specs).compiler_ir(
            "stablehlo")
        return str(mlir), list(ph_names)


def _while_static_trip(sd: SameDiff, node: OpNode) -> Optional[int]:
    """Static trip count of a counter-bounded while, or None.

    Recognizes conds that are (conjunctions of) ``lt(counter, bound)``
    where each counter carry slot is updated by ``add(counter, step)``
    with a positive body-constant step, the bound is a cond-graph
    constant or a pass-through carry slot, and every needed init is a
    CONSTANT of the outer graph. This is exactly the loop shape TF/keras
    RNN imports produce (loop_counter < max_iterations AND time < T),
    and it lowers to ``lax.scan`` — reverse-differentiable (imported
    RNNs train) where lax.while_loop is not, and scan is the TPU-native
    loop form.
    """
    cond_sd = (node.subgraphs or {}).get("cond")
    body_sd = (node.subgraphs or {}).get("body")
    if cond_sd is None or body_sd is None:
        return None
    if cond_sd.branch_outputs is None or body_sd.branch_outputs is None:
        return None
    phc = [n for n, v in cond_sd._vars.items()
           if v.var_type == VariableType.PLACEHOLDER]
    phb = [n for n, v in body_sd._vars.items()
           if v.var_type == VariableType.PLACEHOLDER]
    if len(phc) != len(node.inputs) or len(phb) != len(node.inputs):
        return None
    slot_c = {n: i for i, n in enumerate(phc)}
    b_outs = body_sd.branch_outputs
    if len(b_outs) != len(node.inputs):
        return None

    def static_outer(j):
        name = node.inputs[j]
        v = sd._vars.get(name)
        if v is None or v.var_type != VariableType.CONSTANT:
            return None
        arr = np.asarray(sd._values[name])
        return arr.reshape(()).item() if arr.size == 1 else None

    def static_cond_const(name):
        v = cond_sd._vars.get(name)
        if v is None or v.var_type != VariableType.CONSTANT:
            return None
        arr = np.asarray(cond_sd._values[name])
        return arr.reshape(()).item() if arr.size == 1 else None

    def body_step(j):
        idx = body_sd._producer.get(b_outs[j])
        if idx is None:
            return None
        nd = body_sd._nodes[idx]
        if nd.op != "add" or len(nd.inputs) != 2:
            return None
        a, b = nd.inputs
        other = b if a == phb[j] else (a if b == phb[j] else None)
        if other is None:
            return None
        v = body_sd._vars.get(other)
        if v is None or v.var_type != VariableType.CONSTANT:
            return None
        arr = np.asarray(body_sd._values[other])
        step = arr.reshape(()).item() if arr.size == 1 else None
        return step if step is not None and step > 0 else None

    def analyze(name):
        idx = cond_sd._producer.get(name)
        if idx is None:
            return None
        nd = cond_sd._nodes[idx]
        if nd.op == "math.logical_and" and len(nd.inputs) == 2:
            left = analyze(nd.inputs[0])
            right = analyze(nd.inputs[1])
            return None if left is None or right is None else left + right
        if nd.op == "lt" and len(nd.inputs) == 2:
            j = slot_c.get(nd.inputs[0])
            if j is None:
                return None
            bound = static_cond_const(nd.inputs[1])
            if bound is None:
                m = slot_c.get(nd.inputs[1])
                if m is None or b_outs[m] != phb[m]:
                    return None  # bound must be invariant
                bound = static_outer(m)
            i0 = static_outer(j)
            step = body_step(j)
            if bound is None or i0 is None or step is None:
                return None
            # INTEGER counters only: a float counter's accumulated value
            # can disagree with ceil((bound-i0)/step) (0.1-steps hit
            # 10.000000000000002), and a silently-wrong trip count is
            # worse than staying on lax.while_loop
            if not (float(step).is_integer() and float(bound).is_integer()
                    and float(i0).is_integer()):
                return None
            return [max(0, -(-(int(bound) - int(i0)) // int(step)))]
        return None

    trips = analyze(cond_sd.branch_outputs[0])
    return None if trips is None else int(min(trips))


def _replay_call_node(sd: SameDiff, node: OpNode, fn, vals: List[Any]):
    if node.op == "__cond__":
        pred, *operands = vals
        tb = node.subgraphs["true"]._as_branch_fn()
        fb = node.subgraphs["false"]._as_branch_fn()
        return jax.lax.cond(pred, tb, fb, *operands)
    if node.op == "__while__":
        bg = node.subgraphs["body"]._as_branch_fn()
        trip = _while_static_trip(sd, node)
        if trip is not None:
            # counter-bounded loop -> lax.scan: differentiable, and the
            # TPU-native loop form (unrolled trip metadata for XLA)
            def step(carry, _):
                out = bg(*carry)
                return (out if isinstance(out, tuple) else (out,)), None

            final, _ = jax.lax.scan(step, tuple(vals), None, length=trip)
            return final
        cg = node.subgraphs["cond"]._as_branch_fn()
        carry = tuple(vals)

        def c(state):
            return cg(*state)

        def b(state):
            out = bg(*state)
            return out if isinstance(out, tuple) else (out,)

        return jax.lax.while_loop(c, b, carry)
    return _replay_call(fn, node, vals)


def _cond_impl(*a, **k):  # placeholder: handled in _replay_call_node
    raise RuntimeError("__cond__ replayed specially")


def _while_impl(*a, **k):
    raise RuntimeError("__while__ replayed specially")


# Registered at import time so graphs containing control flow execute after
# load() in a fresh process (not only in the process that recorded them).
register_op("__cond__", _cond_impl)
register_op("__while__", _while_impl)


class _UnknownStruct:
    """Shape-inference fallback: dtype may be known, shape is not."""

    def __init__(self, dtype=None):
        self.shape = None
        self.dtype = dtype


def _jsonable_attrs(attrs: Dict[str, Any]) -> Dict[str, Any]:
    def conv(v):
        if isinstance(v, (np.integer,)):
            return int(v)
        if isinstance(v, (np.floating,)):
            return float(v)
        if isinstance(v, (tuple, list)):
            return [conv(x) for x in v]
        if v is None or isinstance(v, (bool, int, float, str, dict)):
            return v
        raise TypeError(
            f"op attr {v!r} ({type(v).__name__}) is not serializable; "
            "pass arrays as SDVariables/constants")

    return {k: conv(v) for k, v in attrs.items()}


@dataclasses.dataclass
class TrainingConfig:
    """↔ org.nd4j.autodiff.samediff.TrainingConfig: updater, regularization,
    and the feature/label placeholder mapping."""

    loss_variable: str
    feature_placeholders: List[str] = dataclasses.field(default_factory=list)
    label_placeholders: List[str] = dataclasses.field(default_factory=list)
    updater: str = "adam"
    updater_args: Dict[str, Any] = dataclasses.field(default_factory=dict)
    l1: float = 0.0
    l2: float = 0.0

    def placeholders(self, sd: SameDiff) -> List[str]:
        names = list(self.feature_placeholders) + list(self.label_placeholders)
        if not names:
            names = [n for n, v in sd._vars.items()
                     if v.var_type == VariableType.PLACEHOLDER]
        return names
