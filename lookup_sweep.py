"""On the chip: what ``RoutedExperts`` looks up per token-expert pair, by
index as it stood and by compare and select as ``nn/layers/moe.py`` has it
(``_of_chosen``, ``_sorted_by_place``), each form alone at the three
routers' shapes, forward and with its gradient.

    python lookup_sweep.py          # a TPU; writes chiprun_out/lookup_sweep.json
    python lookup_sweep.py --cpu    # tiny shapes here: a rehearsal, no times kept

- ``share_*``: a token's chosen experts' probabilities out of ``prob``
  [tokens, experts]: ``take_along_axis`` (its gradient a scatter-add into
  ``[tokens, experts]``) against ``_of_chosen``.
- ``place_*``: a chosen expert's place among those held: ``place[chosen]``
  out of a table of ``experts`` entries against ``_place_of``, a chain of
  one compare and select an expert held with nothing to reduce
  (``compare``); tried beside it: the compare against all the experts'
  numbers, selected from the table and summed, in int32
  (``compare_reduced``) and in float32 (``compare_reduced_float``). No
  gradient.
- ``tail_*``: the shares as ``route`` hands them on, the lookup and the
  normalisation over a token's experts: with ``_of_chosen``, whose barrier
  keeps the two apart (``compare``), and without it (``compare_fused``: the
  compiler makes one pass with two reductions of it, which need not add a
  token's shares in the order they were added).
- ``weights_*``: the pairs sorted by place and the first piece's weights in
  that order: an argsort and a gather of the piece's numbers (its gradient
  a scatter into ``[pairs]``) against ``_sorted_by_place``, which carries
  the weights through the sort and sorts their gradient back.

Every pair of forms is held equal to the bit. A form runs in tens of
microseconds, which on the host's clock is the call's dispatch (PERF.md
section 5, 2c), so beside ``ms`` (the host's clock, as ``combine_sweep.py``
reads it) each form has ``device_ms``: the summed device time of its
program's operations in one profiler trace of all the forms.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

SHAPES = {
    "keye": dict(tokens=16384, total=128, top_k=8, held=16),
    "smallthinker": dict(tokens=16384, total=64, top_k=6, held=8),
    "zaya": dict(tokens=8192, total=16, top_k=1, held=8),
}
TINY = {
    "keye": dict(tokens=64, total=16, top_k=4, held=2),
    "smallthinker": dict(tokens=96, total=16, top_k=3, held=2),
    "zaya": dict(tokens=48, total=16, top_k=1, held=8),
}


def forms(seed, tokens, total, top_k, held):
    """label -> (function, arguments), and the pairs of labels whose
    results must be equal to the bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.nn.layers import moe

    k_prob, k_mix = jax.random.split(jax.random.key(seed))
    prob = jax.nn.softmax(jax.random.normal(k_prob, (tokens, total)), -1)
    if top_k == 1:
        chosen = jnp.argmax(prob, axis=-1)
    else:
        _, chosen = jax.lax.top_k(prob, top_k)
    mix = jax.random.normal(k_mix, chosen.shape)
    place = np.full((total,), held, np.int32)
    place[:held] = np.arange(held)
    pairs = tokens * top_k
    rows = moe._piece_rows(pairs, held, total)

    def gathered(prob, chosen):
        return jnp.take_along_axis(
            prob, chosen.reshape(tokens, -1), axis=-1).reshape(chosen.shape)

    def with_gradient(share):
        return jax.value_and_grad(
            lambda prob, chosen, mix: jnp.sum(mix * share(prob, chosen)))

    def weights_gather(local, weight):
        order = jnp.argsort(local)
        return order, weight[moe._slice_of(order, 0, rows)]

    def weights_sorted(local, weight):
        order, carried = moe._sorted_by_place(local, weight)
        return order, moe._slice_of(carried, 0, rows)

    def weights_gradient(form):
        def loss(weight, local, mix):
            return jnp.sum(mix * form(local, weight)[1])
        return jax.value_and_grad(loss)

    def reduced(table, chosen):
        """The compare and select summed over the experts with nothing
        between it and its consumer: ``moe._of_chosen`` without its
        barrier, and the same over a table of ``experts`` entries."""
        picked = chosen[..., None] == jnp.arange(table.shape[-1])
        if table.ndim == 2:
            table = jnp.expand_dims(table, tuple(range(1, chosen.ndim)))
        return jnp.sum(jnp.where(picked, table, 0), axis=-1,
                       dtype=table.dtype)

    def tail(lookup):
        """The shares as ``route`` hands them on: looked up, normalised
        where a token has several."""
        def shares(prob, chosen):
            share = lookup(prob, chosen)
            if top_k > 1:
                share = share / jnp.sum(share, axis=-1, keepdims=True)
            return share
        return shares

    local = jnp.asarray(place)[chosen.reshape(-1)]
    weight = jnp.where(local < held, gathered(prob, chosen).reshape(-1), 0.0)
    mix_piece = jax.random.normal(k_mix, (rows,))
    table = {
        "share_gather": (gathered, (prob, chosen)),
        # (a lambda: ``sweep`` names each form's function after its label)
        "share_compare": (lambda prob, chosen: moe._of_chosen(prob, chosen),
                          (prob, chosen)),
        "share_gather_grad": (with_gradient(gathered), (prob, chosen, mix)),
        "share_compare_grad": (with_gradient(moe._of_chosen),
                               (prob, chosen, mix)),
        "place_gather": (lambda chosen: jnp.asarray(place)[chosen],
                         (chosen,)),
        "place_compare": (
            lambda chosen: moe._place_of(chosen, tuple(range(held))),
            (chosen,)),
        "place_compare_reduced": (lambda chosen: reduced(place, chosen),
                                  (chosen,)),
        "place_compare_reduced_float": (
            lambda chosen: reduced(place.astype(np.float32),
                                   chosen).astype(jnp.int32), (chosen,)),
        "tail_gather": (tail(gathered), (prob, chosen)),
        "tail_compare": (tail(moe._of_chosen), (prob, chosen)),
        "tail_compare_fused": (tail(reduced), (prob, chosen)),
        "tail_gather_grad": (with_gradient(tail(gathered)),
                             (prob, chosen, mix)),
        "tail_compare_grad": (with_gradient(tail(moe._of_chosen)),
                              (prob, chosen, mix)),
        "tail_compare_fused_grad": (with_gradient(tail(reduced)),
                                    (prob, chosen, mix)),
        "weights_gather": (weights_gather, (local, weight)),
        "weights_sorted": (weights_sorted, (local, weight)),
        "weights_gather_grad": (weights_gradient(weights_gather),
                                (weight, local, mix_piece)),
        "weights_sorted_grad": (weights_gradient(weights_sorted),
                                (weight, local, mix_piece)),
    }
    if top_k == 1:  # nothing to normalise: the tail is the lookup
        table = {label: form for label, form in table.items()
                 if not label.startswith("tail_")}
    same = [(a, a.replace("gather", b)) for a in table if "gather" in a
            for b in ("compare", "compare_reduced", "compare_reduced_float",
                      "compare_fused", "sorted")
            if a.replace("gather", b) in table]
    return table, same, dict(pairs=pairs, rows_a_piece=rows)


def device_times(jitted, iters):
    """label -> (ms a call of its program's operations on the device, its
    three longest operations), from one trace of ``iters`` calls of each
    ``jitted[label] = (program's name, function, arguments)``; {} where
    the trace has no device plane (the CPU)."""
    import jax

    from benchmark.harness import trace_reduce

    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            for _, fn, args in jitted.values():
                for _ in range(iters):
                    out = fn(*args)
                jax.block_until_ready(out)
        data = trace_reduce.load(trace_reduce.find_trace(tmp))
    out = {}
    for plane in data.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        runs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                for e in lines.get(trace_reduce.MODULES_LINE, [])]
        for label, (name, _, _) in jitted.items():
            mine = [(s, e) for s, e, module in runs
                    if module.split("(")[0] == name]
            ops = {}
            for e in lines.get(trace_reduce.OPS_LINE, []):
                if any(s <= e.start_ns < end for s, end in mine):
                    ops[e.name] = ops.get(e.name, 0.0) + e.duration_ns
            if mine:
                longest = sorted(ops.items(), key=lambda kv: -kv[1])[:3]
                out[label] = (
                    sum(ops.values()) / len(mine) * 1e-6,
                    [[n.split(" = ")[0], round(ns / len(mine) * 1e-6, 4)]
                     for n, ns in longest])
    return out


def sweep(name, shape, seed, iters):
    import jax
    import numpy as np

    from combine_sweep import timed

    table, same, sizes = forms(seed, **shape)
    out = {"shape": dict(shape, **sizes), "forms": {}}
    jitted, results = {}, {}
    for label, (fn, args) in table.items():
        fn.__name__ = fn.__qualname__ = program = f"{name}_{label}"
        ms, call = timed(fn, args, iters)
        jitted[label] = (f"jit_{program}", call, args)
        results[label] = jax.tree_util.tree_map(np.asarray, call(*args))
        out["forms"][label] = {"ms": round(ms, 4)}
    for label, (ms, longest) in device_times(jitted, iters).items():
        out["forms"][label].update(device_ms=round(ms, 4), longest=longest)
    for a, b in same:
        # every array to the bit; a loss (a sum of all the pairs' numbers
        # in an order the compiler picks for each program) to a millionth
        pairs_of = list(zip(jax.tree_util.tree_leaves(results[a]),
                            jax.tree_util.tree_leaves(results[b])))
        out["forms"][b]["equal_to_the_bit"] = all(
            x.dtype == y.dtype and np.array_equal(x, y)
            for x, y in pairs_of if x.ndim)
        gaps = [abs(float(x) - float(y)) / max(abs(float(x)), 1e-30)
                for x, y in pairs_of if not x.ndim]
        if gaps:
            out["forms"][b]["loss_gap"] = max(gaps)
            out["forms"][b]["equal_to_the_bit"] &= max(gaps) < 1e-6
    for label, entry in out["forms"].items():
        print(f"{name:13s} {label:22s} {entry['ms']:9.4f} ms"
              f"   device {entry.get('device_ms', float('nan')):9.4f} ms"
              f"   {entry.get('equal_to_the_bit', '')}"
              f"   {entry.get('longest', '')}", flush=True)
    return out


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--cpu", action="store_true")
    parser.add_argument("--seed", type=int, default=3800100019)
    parser.add_argument("--iters", type=int, default=10)
    args = parser.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    device = jax.devices()[0]
    if not args.cpu and device.platform != "tpu":
        sys.exit(f"lookup_sweep: no TPU here ({device.platform}); "
                 "--cpu rehearses")
    result = {"device": {"platform": device.platform,
                         "device_kind": device.device_kind},
              "seed": args.seed, "iters": args.iters, "shapes": {}}
    for name, shape in (TINY if args.cpu else SHAPES).items():
        result["shapes"][name] = sweep(name, shape, args.seed,
                                       2 if args.cpu else args.iters)
    unequal = [f"{name}.{label}" for name, swept in result["shapes"].items()
               for label, entry in swept["forms"].items()
               if entry.get("equal_to_the_bit") is False
               and "fused" not in label]  # a form tried: its order is its own
    if not args.cpu:
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/lookup_sweep.json", "w") as f:
            json.dump(result, f, indent=1)
    print(json.dumps(result["device"]))
    if unequal:
        sys.exit(f"lookup_sweep: not equal to the bit: {unequal}")


if __name__ == "__main__":
    main()
