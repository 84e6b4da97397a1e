"""On the chip: the forms of ``RoutedExperts``' sum of a piece's rows into
their tokens (``nn/layers/moe.py::_sum_by_token`` where pieces are walked),
timed alone at the two shapes that walk pieces.

    python combine_sweep.py            # a TPU; writes chiprun_out/combine_sweep.json
    python combine_sweep.py --short    # the kept form, its stages and the sorts only
    python combine_sweep.py --cpu      # tiny shapes here: a rehearsal, no times kept

For each shape a router's draw makes a piece as the layer makes it (top-k
of the experts, the pairs sorted by the expert's place, the first piece of
them), and each form sums the piece's rows into ``[tokens, width]``:

- ``scatter``: the form that stood until PR 36, a scatter-add into float32
  zeros (the rows of no group add zeros to a real token); ``scatter_drop``
  with those rows sent past the last token; ``scatter_slabs_<w>`` with the
  operand in column slabs of ``w``; and the same scatter at other widths
  with the rows held, which is how the width's cost is read.
- ``segments_<starts>_<place>``: the rows put in token order by one sort
  and one row gather, each run summed over shifted views, a run's start
  found by ``starts`` (``sort``, ``compare_all``, ``scan``: the methods of
  ``jnp.searchsorted``; ``bincount``: a scalar scatter-add and a cumsum)
  and each token's row placed by ``place`` (``gather``: each token reads
  its run's first row; ``unique``: the runs' first rows are scattered
  under ``unique_indices``).
- ``kernel_<tokens>_<rows>``: the same sort and row gather, then the
  Pallas kernel ``kernels/segment_rows.py`` at that tile of tokens and
  chunk of rows; ``kernel_alone_*`` is the kernel without them.
- ``kept``: ``moe._sum_by_token`` as the tree has it, forward, and as
  ``_dispatch``'s gradient; the XLA form's stages one after another
  (``segments_upto_*``); and the sort of the piece's keys beside the rows'
  numbers as two operands, not stable, and packed into one (``sort_*``:
  the packed sort alone is twice as fast and bought the whole form
  nothing, PERF.md section 5 (2c), so the tree sorts two operands).

Every form's result is held against a float32 ``segment_sum`` rounded once.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

SHAPES = {
    "smallthinker": dict(tokens=16384, width=2560, top_k=6, total=64, held=8),
    "keye": dict(tokens=16384, width=2048, top_k=8, total=128, held=16),
}
KERNEL_TILES = ((128, 128), (128, 256), (256, 256), (256, 512), (512, 256),
                (512, 512))
TINY = {
    "smallthinker": dict(tokens=96, width=40, top_k=3, total=16, held=2),
    "keye": dict(tokens=64, width=32, top_k=4, total=16, held=2),
}


def make_piece(seed, tokens, width, top_k, total, held):
    """A router's draw, and the first piece of its sorted pairs as
    ``RoutedExperts.apply`` makes it: the rows (zeros where the row is of
    no group, as the grouped product leaves them), each row's token as it
    stood (a real token, or 0 for padding) and as it stands (``tokens``
    for a row of no group)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers import moe

    k_route, k_rows = jax.random.split(jax.random.key(seed))
    _, chosen = jax.lax.top_k(jax.random.uniform(k_route, (tokens, total)),
                              top_k)
    local = jnp.minimum(chosen.reshape(-1), held).astype(jnp.int32)
    order = jnp.argsort(local)
    sizes = jnp.sum(local[:, None] == jnp.arange(held + 1)[None, :], axis=0,
                    dtype=jnp.int32)
    n = moe._piece_rows(tokens * top_k, held, total)
    span, sizes_here = moe._piece_of(order, sizes, 0, n)
    landed = n - sizes_here[-1]
    stood = (span // top_k).astype(jnp.int32)
    stands = jnp.where(jnp.arange(n) < landed, stood, tokens)
    rows = jax.random.normal(k_rows, (n, width), jnp.float32)
    rows = jnp.where((jnp.arange(n) < landed)[:, None], rows, 0.0)
    return rows.astype(jnp.bfloat16), stood, stands, int(landed)


def scatter(rows, source, count, slab=None):
    import jax.numpy as jnp

    def one(part):
        total = jnp.zeros((count, part.shape[-1]), jnp.float32)
        return total.at[source].add(
            part.astype(jnp.float32), mode="drop").astype(rows.dtype)

    if slab is None:
        return one(rows)
    return jnp.concatenate([one(rows[:, at:at + slab])
                            for at in range(0, rows.shape[-1], slab)], axis=1)


def run_starts(keys, count, starts):
    """For each token and one past the last, the keys below it."""
    import jax.numpy as jnp

    if starts == "bincount":
        hits = jnp.zeros((count + 1,), jnp.int32).at[keys].add(1)
        return jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                jnp.cumsum(hits[:count])])
    return jnp.searchsorted(keys, jnp.arange(count + 1, dtype=jnp.int32),
                            method=starts)


def segments(rows, source, count, most, starts="sort", place="gather",
             upto=None):
    """``moe._sum_runs`` with its choices open, and cut short after the
    stage ``upto`` for the stages' times."""
    import jax
    import jax.numpy as jnp

    n = rows.shape[0]
    keys, at = jax.lax.sort(
        (source.astype(jnp.int32), jnp.arange(n, dtype=jnp.int32)),
        num_keys=1)
    if upto == "sort":
        return keys, at
    keys = jnp.concatenate([keys, -1 - jnp.arange(most, dtype=jnp.int32)])
    ordered = rows[jnp.pad(at, (0, most))]
    if upto == "rows":
        return ordered
    real = (jnp.arange(n + 1) < n)[:, None]
    total = jnp.where(real, ordered[:n + 1].astype(jnp.float32), 0.0)
    for ahead in range(1, most):
        same = keys[ahead:ahead + n + 1] == keys[:n + 1]
        total = total + jnp.where(
            same[:, None], ordered[ahead:ahead + n + 1].astype(jnp.float32),
            0.0)
    total = total.astype(rows.dtype)
    if upto == "runs":
        return total
    if place == "unique":
        first = keys[:n] != jnp.pad(keys[:n - 1], (1, 0), constant_values=-1)
        to = jnp.where(first, keys[:n], count + jnp.arange(n))
        return jnp.zeros((count, rows.shape[-1]), rows.dtype).at[to].set(
            total[:n], mode="drop", unique_indices=True)
    first = run_starts(keys[:n], count, starts)
    if upto == "starts":
        return total, first
    return total[jnp.where(first[1:] > first[:-1], first[:-1], n)]


def reference(rows, source, count):
    import jax
    import jax.numpy as jnp

    return jax.ops.segment_sum(rows.astype(jnp.float32), source,
                               num_segments=count + 1)[:count].astype(
                                   rows.dtype)


def timed(fn, args, iters, windows=3):
    """ms a call: the least of ``windows`` windows of ``iters`` calls, each
    closed by ``block_until_ready`` on the last result."""
    import jax

    jitted = jax.jit(fn)
    jax.block_until_ready(jitted(*args))
    jax.block_until_ready(jitted(*args))
    best = None
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = jitted(*args)
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) / iters * 1e3
        best = ms if best is None else min(best, ms)
    return best, jitted


def worst_gap(got, want):
    """The largest gap, in units of the wanted value's last bf16 place."""
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    place = np.maximum(np.abs(want), 2.0 ** -126) * 2.0 ** -7
    return float(np.max(np.abs(got - want) / place))


def sweep(name, shape, seed, iters, full):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers import moe

    count, width = shape["tokens"], shape["width"]
    most = min(shape["top_k"], shape["held"])
    rows, stood, stands, landed = make_piece(seed, **shape)
    n = rows.shape[0]
    want = reference(rows, stands, count)
    out = {"shape": dict(shape, rows=n, landed=landed, most=most),
           "forms": {}}

    def read(label, fn, args, check=True):
        ms, jitted = timed(fn, args, iters)
        entry = {"ms": round(ms, 4)}
        if check:
            entry["gap_in_last_places"] = round(
                worst_gap(jitted(*args), want), 3)
        out["forms"][label] = entry
        print(f"{name:13s} {label:34s} {ms:9.4f} ms"
              + (f"   gap {entry['gap_in_last_places']}" if check else ""),
              flush=True)

    read("scatter", lambda r, s: scatter(r, s, count), (rows, stood))
    read("scatter_drop", lambda r, s: scatter(r, s, count), (rows, stands))
    read("kept", lambda r, s: moe._sum_by_token(r, s, None, count, most),
         (rows, stands))
    tokens = jnp.zeros((count, width), rows.dtype)
    read("kept_as_dispatch_gradient",
         lambda t, s, g: jax.vjp(
             lambda t: moe._dispatch(t, s, None, count, most), t)[1](g)[0],
         (tokens, stands, rows))
    for upto in ("sort", "rows", "runs", "starts"):
        read(f"segments_upto_{upto}",
             lambda r, s, upto=upto: segments(r, s, count, most, upto=upto),
             (rows, stands), check=False)
    rank = jnp.arange(n, dtype=jnp.int32)
    bits = (n - 1).bit_length()
    read("sort_keys_and_rows", lambda s: jax.lax.sort((s, rank), num_keys=1),
         (stands,), check=False)
    read("sort_not_stable",
         lambda s: jax.lax.sort((s, rank), num_keys=1, is_stable=False),
         (stands,), check=False)
    read("sort_packed",  # one operand: the key above the row's own bits
         lambda s: jax.lax.sort((s << bits) | rank), (stands,), check=False)
    if not full:
        return out
    for starts in ("sort", "compare_all", "scan", "bincount"):
        read(f"segments_{starts}_gather",
             lambda r, s, starts=starts: segments(r, s, count, most, starts),
             (rows, stands))
    if moe.use_pallas():
        from deeplearning4j_tpu.kernels.segment_rows import sum_sorted_rows

        keys, at = segments(rows, stands, count, most, upto="sort")
        ordered = rows[at]
        for tiles in KERNEL_TILES:
            label = "_".join(map(str, tiles))

            def through_kernel(r, s, tiles=tiles):
                keys, at = segments(r, s, count, most, upto="sort")
                return sum_sorted_rows(r[at], keys, count, most, tiles=tiles,
                                       interpret=moe.interpret())

            read(f"kernel_{label}", through_kernel, (rows, stands))
            read(f"kernel_alone_{label}",
                 lambda r, k, tiles=tiles: sum_sorted_rows(
                     r, k, count, most, tiles=tiles,
                     interpret=moe.interpret()), (ordered, keys))
    read("segments_unique",
         lambda r, s: segments(r, s, count, most, place="unique"),
         (rows, stands))
    for slab in (512, 1024, 1280):
        if width % slab == 0 and slab < width:
            read(f"scatter_slabs_{slab}",
                 lambda r, s, slab=slab: scatter(r, s, count, slab),
                 (rows, stood))
    for other in (1024, 1280, 2048, 2304, 2560, 3072, 4096):
        wide = jnp.tile(rows, (1, -(-other // width)))[:, :other]
        read(f"scatter_rows_{n}_width_{other}",
             lambda r, s: scatter(r, s, count), (wide, stood), check=False)
    return out


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--cpu", action="store_true")
    parser.add_argument("--seed", type=int, default=3600100019)
    parser.add_argument("--iters", type=int, default=10)
    parser.add_argument("--short", action="store_true",
                        help="the kept form, its stages and the sorts only")
    args = parser.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["DL4J_TPU_FORCE_PALLAS"] = "1"  # the kernel, interpreted
    import jax

    device = jax.devices()[0]
    if not args.cpu and device.platform != "tpu":
        sys.exit(f"combine_sweep: no TPU here ({device.platform}); "
                 "--cpu rehearses")
    shapes = TINY if args.cpu else SHAPES
    result = {"device": {"platform": device.platform,
                         "device_kind": device.device_kind},
              "seed": args.seed, "iters": args.iters, "shapes": {}}
    for name, shape in shapes.items():
        result["shapes"][name] = sweep(name, shape, args.seed,
                                       2 if args.cpu else args.iters,
                                       full=not args.short)
    if not args.cpu:
        os.makedirs("chiprun_out", exist_ok=True)
        name = "combine_sweep_short" if args.short else "combine_sweep"
        with open(f"chiprun_out/{name}.json", "w") as f:
            json.dump(result, f, indent=1)
    print(json.dumps(result["device"]))


if __name__ == "__main__":
    main()
