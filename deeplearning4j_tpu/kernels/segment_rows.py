"""Sum rows that lie sorted by segment into one row a segment (Pallas/TPU).

``RoutedExperts`` walks its sorted token-expert pairs a piece at a time
and adds each piece's weighted rows to the tokens they belong to. Once the
piece's rows lie in token order, the rows of a tile of tokens are one
contiguous span, and the sum needs no index at all: the kernel walks the
token tiles, reads the chunks of rows a tile's span touches (their first
and their number come by scalar prefetch, so a chunk that two steps share
is read once), adds them through a one-hot ``[tile tokens, chunk rows]``
product on the matrix unit, accumulated in float32, and writes each
token's row once. XLA's scatter-add, which this replaces, adds rows that
may share a token one after another (PERF.md section 5, 2c).

The one-hot product is exact for rows in bfloat16 (a product with 0 or 1,
float32 sums); float32 rows take the matrix unit's full precision. A row
that is not finite reaches, as NaN, every token of the tiles whose chunks
hold it, where a scatter would spoil its own token alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# tokens a tile x rows a chunk: the fastest of six read on a v5e at the two
# shapes that walk pieces, 0.43-0.44 ms on the host's clock against
# 0.46-0.53, and 0.35 in the step's trace (PERF.md section 5, 2c)
TILES = (128, 256)


def _kernel(first_ref, chunks_ref, keys_ref, rows_ref, out_ref, acc_ref, *,
            token_tile, steps):
    tile, step = pl.program_id(0), pl.program_id(1)

    @pl.when(step == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(step < chunks_ref[tile])
    def _():
        rows = rows_ref[...]
        token = tile * token_tile + jax.lax.broadcasted_iota(
            jnp.int32, (token_tile, rows.shape[0]), 0)
        hit = (keys_ref[...] == token).astype(rows.dtype)
        acc_ref[...] += jnp.dot(
            hit, rows, preferred_element_type=jnp.float32,
            precision=(jax.lax.Precision.HIGHEST
                       if rows.dtype == jnp.float32 else None))

    @pl.when(step == steps - 1)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def sum_sorted_rows(rows, keys, count, most, *, tiles=None, interpret=False):
    """``rows`` [n, H] under sorted ``keys`` [n] summed into [``count``,
    H]: row ``t`` is the float32 sum, rounded once, of the rows whose key
    is ``t``, of which there are ``most`` at most; a key outside
    ``[0, count)`` is of no token. ``interpret`` as
    ``kernels/_dispatch.py::interpret`` gives it."""
    token_tile, chunk = tiles or TILES
    n, width = rows.shape
    token_tile = min(token_tile, -(-count // 8) * 8)
    tiles_n = -(-count // token_tile)
    chunks_n = -(-n // chunk)
    spare = chunks_n * chunk - n
    if spare:  # whole chunks: rows of no token, and finite
        rows = jnp.pad(rows, ((0, spare), (0, 0)))
        keys = jnp.pad(keys, (0, spare), constant_values=-1)
    # the keys below each tile's first token: where its span starts
    bounds = jnp.minimum(
        jnp.arange(tiles_n + 1, dtype=jnp.int32) * token_tile, count)
    edges = jnp.sum(keys[None, :n] < bounds[:, None], axis=1,
                    dtype=jnp.int32)
    first = jnp.minimum(edges[:-1] // chunk, chunks_n - 1)
    chunks = jnp.where(edges[1:] > edges[:-1],
                       (edges[1:] - 1) // chunk - first + 1, 0)
    # a span of most x token_tile rows touches this many chunks at most
    steps = min(chunks_n, (most * token_tile - 1) // chunk + 2)

    def chunk_of(tile, step, first_ref, chunks_ref):
        return first_ref[tile] + jnp.minimum(
            step, jnp.maximum(chunks_ref[tile] - 1, 0))

    out = pl.pallas_call(
        functools.partial(_kernel, token_tile=token_tile, steps=steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles_n, steps),
            in_specs=[
                pl.BlockSpec((None, 1, chunk),
                             lambda t, s, f, c: (chunk_of(t, s, f, c), 0, 0)),
                pl.BlockSpec((chunk, width),
                             lambda t, s, f, c: (chunk_of(t, s, f, c), 0)),
            ],
            out_specs=pl.BlockSpec((token_tile, width),
                                   lambda t, s, f, c: (t, 0)),
            scratch_shapes=[pltpu.VMEM((token_tile, width), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((tiles_n * token_tile, width),
                                       rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="segment_rows_sum",
    )(first, chunks, keys.reshape(chunks_n, 1, chunk), rows)
    return out[:count]
