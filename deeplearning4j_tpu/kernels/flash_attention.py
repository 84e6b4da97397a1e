"""Pallas TPU blockwise (flash) attention kernel.

ref: the reference's only attention is the O(T²)-memory libnd4j
``multi_head_dot_product_attention`` op behind SameDiff attention layers
(SURVEY §5.7) — it materializes the [T,S] score matrix in HBM. This kernel
is the TPU-native replacement: online-softmax tiling keeps only
[block_q, block_k] score tiles in VMEM, so memory is O(T·D) and the two
matmuls per tile run back-to-back on the MXU.

Grid: (batch*heads, q_blocks, kv_blocks), kv innermost so the running
max/denominator/accumulator for one q block live in VMEM scratch across the
kv sweep. Which grid steps have a ``[block_q, block_k]`` score tile to
work on is decided by one function of the shapes, :class:`TilePlan`: a tile
above the causal diagonal is dead (skipped, and its index map points at a
block already fetched, so no copy is issued for it); every other tile is
live and builds the mask the shapes call for (the causal triangle, padded
keys or queries, a key mask), each term only where the shapes can make it
false. A live tile that the diagonal crosses corner to corner is not
computed whole: inside its one grid step it is swept in static row
sub-blocks of ``_SUB_BLOCK`` rows, each against only the key prefix of the
tile that its rows can see (slices known at trace time: straight-line code
to Mosaic, no new grid step, copy or scratch). The pairs skipped are those
the causal mask sets to -1e30, whose p and ds are exactly 0, so only the
order of the additions changes. A per-example key padding mask ([B,S] 1/0
— the BERT attention-mask case) runs inside the kernel, and so does a
per-pair mask ([B,T,S] int8, one for all heads of a sequence: the keys a
learned indexer selected for each query), fetched tile by tile beside k and
v; only arbitrary additive ``bias`` falls back to the XLA reference. A
``window`` (the last so many keys of a query's past, itself counted) is in
the plan too: the tiles wholly behind it are dead, the sweep over a query
block's keys (a key block's queries) starts at its first live block and
is only as long as the widest run of live ones, and the tile the window's
far edge crosses corner to corner is swept in sub-blocks as the diagonal's
mirror image. The head dimension is never padded (a block as wide
as the array is legal at any width; 8 to 256 were compiled for the v5e),
and the softmax scale goes on the ``[block_q, D]`` operand, not on the
scores. The value, and with it the output, its gradient and dv, may be
narrower than q and k (latent attention's 128 under 192): each operand,
accumulator and gradient takes its own width, so the products that read
v (PV, dP, dV) run at v's and no operand is padded to the other's.

Backward: blockwise Pallas kernels (FlashAttention-2 style). The forward
saves the per-row logsumexp (lane-broadcast [BH,T,128] layout, the Mosaic
tiling-friendly shape jax's own TPU flash kernel uses); backward runs two
kernels — dk/dv with a q-block sweep per kv block, dq with a kv-block
sweep per q block — plus one XLA pass for delta = rowsum(dO*O). Scores are
recomputed on-chip, so backward memory stays O(T·D) like forward. Each of
the three kernels has a geometry of its own (``_dispatch.flash_block_sizes``).

What the chip showed (TPU v5e, [16, 12, 1024, 64] bf16 causal, one layer's
three calls, device time from a trace; PERF.md sections 5 and 6): 1.87 /
1.65 / 1.50 ms before this layout, 0.70 / 1.10 / 0.88 ms with it. Nearly
all of that is the geometry: at 256 x 512 the padding and the scale
together gave 2%, the clamped index maps 8%. The kernels are bound by the
matrix unit at half rate (a 64-wide head fills half of it in every product)
and by per-tile work that does not shrink with the tile, so the largest
tile wins. The dead half of that tile then went through the split above
(PR 32; the same calls, whole / in 2 / 4 / 8 sub-blocks): ``flash_bwd_dq``
0.874 / 0.678 / 0.580 / 0.574 ms, ``flash_bwd_dkv`` at one 1024 tile 1.223
/ 0.961 / 0.821 / 0.890 (its 512 x 512 tiles of before: 1.051), so four
sub-blocks of 256 rows; ``flash_fwd`` 0.698 / 0.863 / 0.838 / 0.694, a
*loss*, until its running maximum and sum were read as the lane-broadcast
rows they are stored as (read by their first column they cost a lane
broadcast a use: 3,700 of the kernel's 4,900 instruction bundles held a
cross-lane operation): 0.679 / 0.521 / 0.515 / 0.591. At ``[2, 8, 4096,
128]`` (4 diagonal tiles of 10 live) the three read 0.739 / 1.024 / 0.877
whole and 0.615 / 0.888 / 0.783 in four. The body that computes a tile
whole, which a grid of several key tiles still needs under the diagonal,
builds no causal term there (tried alone before: 0-2%). Tried and dropped,
each measured: ``flash_bwd_dkv`` split by key columns against the query
suffix (0.885 against 0.821 ms); sweeping a grid step's keys tile by tile
inside the kernel (``fori_loop``, bounds from the plan: 6% off
``flash_bwd_dq``, the other two slower); and building ``flash_bwd_dkv``'s
score tile key-major so that its two transposed products become plain ones
(1.096 against 1.106 ms: nothing). With a window of 4,096 keys at ``[1,
28, 16384, 128]`` (PR 35; 70 live tiles of 256, 16 diagonal and 12 on the
edge) the three read 8.10 / 12.55 / 9.64 ms with every key block a grid
step and 6.83 / 10.85 / 7.88 with the sweeps cut to the window's 5 blocks
(the 176 dead steps a head cost 0.3 us each); with the edge tile whole
and not in sub-blocks 8.66 / 11.59 / 8.41; the same call without a window
14.32 / 22.81 / 17.69.

The same kernels run everywhere: compiled on TPU, interpret-mode in CPU
tests (via DL4J_TPU_FORCE_PALLAS=1; plain CPU callers never reach them
because flash_attention auto-dispatches to reference_attention off-TPU, and
an explicit ``backend="pallas"`` there raises).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import operator
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.kernels._dispatch import (
    FlashBlocks,
    active_kernel_mesh as _active_kernel_mesh,
    flash_block_sizes as _flash_block_sizes,
    flash_min_seq as _flash_min_seq,
    force_pallas as _force_pallas,
    interpret as _interpret,
    on_tpu as _on_tpu,
    use_pallas as _use_pallas,
)

_NEG_INF = -1e30


def _matmul_dtype(dtype):
    """MXU input dtype for score/value matmuls.

    fp32 operands are cast to bf16 (fp32 accumulation via
    ``preferred_element_type`` is kept): a true-fp32 MXU matmul costs ~6
    passes, while XLA's einsum at its DEFAULT precision runs ONE bf16 pass —
    that asymmetry was most of the r3 kernels_ab 8x forward loss at T=512
    (the XLA reference was single-pass bf16, the kernel six-pass fp32).
    Matching XLA's default keeps the A/B apples-to-apples and the parity
    bound unchanged (both sides now carry bf16 matmul error).
    DL4J_TPU_FLASH_FP32=1 restores true-fp32 matmuls.

    Off-TPU (interpret-mode unit tests) the input dtype is kept: those
    tests pin kernel LOGIC against the fp32 XLA oracle at tight tolerance,
    and numpy emulation has no MXU whose precision policy needs matching.
    DL4J_TPU_FLASH_BF16=1 opts interpret mode into the cast path so the
    policy itself is testable on CPU.
    """
    import os

    if os.environ.get("DL4J_TPU_FLASH_FP32", "") == "1":
        return jnp.float32
    if not _on_tpu() and os.environ.get("DL4J_TPU_FLASH_BF16", "") != "1":
        return dtype
    return jnp.bfloat16 if dtype == jnp.float32 else dtype


# A call with a pair mask holds two [block_q, block_k] int8 tiles more (one in
# flight), which at 1024 x 1024 and head width 128 puts ``flash_bwd_dkv`` 20 KB
# over the compiler's default 16 MiB of scoped VMEM; the v5e has 128 MiB.
_PAIR_MASK_VMEM_BYTES = 32 * 1024 * 1024


def _compiler_params(*semantics, pair_mask=False):
    """Mosaic grid-dimension semantics (parallel dims enable multi-core
    partitioning on megacore chips and better pipelining); only meaningful
    when compiled for TPU — interpret mode ignores them."""
    if not _on_tpu():
        return None
    if pair_mask:
        return pltpu.CompilerParams(dimension_semantics=tuple(semantics),
                                    vmem_limit_bytes=_PAIR_MASK_VMEM_BYTES)
    return pltpu.CompilerParams(dimension_semantics=tuple(semantics))


def reference_attention(q, k, v, *, causal=False, bias=None, key_mask=None,
                        pair_mask=None, scale=None, window=None):
    """XLA O(T²) attention; q [B,H,T,D], k/v [B,H,S,D]. fp32 softmax.

    ``key_mask`` [B,S] 1/0 is folded into an additive bias, ``pair_mask``
    [B,T,S] (nonzero: attend) into the scores. ``window`` (with ``causal``)
    keeps of each query's past its own position and the ``window - 1``
    before it. Fully-masked rows produce uniform attention (softmax of
    constant) — callers never read those outputs.
    """
    d = q.shape[-1]
    scale = (d ** -0.5) if scale is None else scale
    s = jnp.einsum("bhtd,bhsd->bhts", q, k).astype(jnp.float32) * scale
    if bias is not None:
        s = s + bias
    if key_mask is not None:
        s = s + jnp.where(key_mask[:, None, None, :] > 0, 0.0, _NEG_INF)
    if pair_mask is not None:
        s = jnp.where(pair_mask[:, None] != 0, s, _NEG_INF)
    if causal:
        t_len, s_len = s.shape[-2], s.shape[-1]
        idx_t = jnp.arange(t_len)[:, None]
        idx_s = jnp.arange(s_len)[None, :]
        seen = idx_t + (s_len - t_len) >= idx_s
        if window is not None:
            seen = seen & (idx_t + (s_len - t_len) - idx_s < window)
        s = jnp.where(seen, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhts,bhsd->bhtd", p, v)


# Rows of a diagonal tile's sub-block (TilePlan.sub_blocks): of 512, 256 and
# 128, the fastest in all three kernels at head widths 64 and 128 (PERF.md
# section 5); every sub-block is a copy of the body in the kernel's code.
_SUB_BLOCK = 256


class Part(NamedTuple):
    """One rectangle of a live tile that a kernel computes: rows ``row`` to
    ``row + rows`` of the tile against its keys ``key`` to ``key + keys``,
    and which of the two position terms can be false in it."""

    row: int
    rows: int
    key: int
    keys: int
    causal: bool
    window: bool


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """Which score tiles of a ``[seq_q, seq_k]`` attention a kernel with
    ``[block_q, block_k]`` tiles has to touch.

    The one place that knows it: the kernels ask it per grid step (``qi``,
    ``ki`` are then traced ``program_id``s), the index maps ask it where a
    dead step should point, the flight event and the tests ask it with
    numbers. Every method takes ints, numpy arrays or traced values.

    A pair's distance is how far the key lies behind the query, ``i +
    offset - j``: the causal mask keeps distances from 0 up, a ``window``
    those under it (the query's own position counts, so ``window`` keys at
    most). A tile is dead when every pair of it lies above the causal
    diagonal or at the window's length or further behind: it is skipped
    and nothing is fetched for it. Every other tile is live. A live tile
    is diagonal when the causal diagonal runs from its first corner to its
    last (square blocks, and its first query row's last visible key is its
    first key): nearly half of its pairs are dead, so it is computed in
    ``sub_blocks`` row sub-blocks, each against only the keys of the tile
    its rows can see (:meth:`parts`). A live tile is an edge tile when the
    window's far edge runs through it corner to corner (a window of whole
    blocks): the mirror image, its rows see the keys *after* their own
    place in the tile, and it is swept the same way. Where tiles are split
    so, every other live tile lies whole between the two edges and has
    neither term to build.
    """

    seq_q: int
    seq_k: int
    block_q: int
    block_k: int
    causal: bool
    pair_mask: bool = False  # the call carries a [B, T, S] mask of pairs
    window: Optional[int] = None  # keys a query reaches back over, itself
                                  # counted; only with ``causal``

    def __post_init__(self):
        if self.window is not None and not (self.causal and self.window > 0):
            raise ValueError("a window is a positive count of keys under a "
                             f"causal mask, got window={self.window!r} "
                             f"causal={self.causal!r}")

    @property
    def n_q(self) -> int:
        return -(-self.seq_q // self.block_q)

    @property
    def n_k(self) -> int:
        return -(-self.seq_k // self.block_k)

    @property
    def offset(self) -> int:
        """Query row i sees keys j <= i + offset (the causal offset of
        cross-shaped calls)."""
        return self.seq_k - self.seq_q

    @property
    def pads_q(self) -> bool:
        return self.seq_q % self.block_q != 0

    @property
    def pads_k(self) -> bool:
        return self.seq_k % self.block_k != 0

    @property
    def rows_can_be_empty(self) -> bool:
        """Whether a real query row can have no live key at all: under
        causal masking alone only when there are fewer keys than queries
        (otherwise every row's first live block holds key 0). The running
        state of a row that a key mask, a pair mask or a window has masked
        whole so far needs the same care, tile by tile
        (``_flash_kernel``)."""
        return self.causal and self.seq_k < self.seq_q

    @property
    def sub_blocks(self) -> int:
        """Row sub-blocks of a diagonal tile; 1 where no tile is diagonal
        (not causal, unequal blocks, an offset that no tile edge meets) or
        the block holds no two sub-blocks of ``_SUB_BLOCK`` rows."""
        if not (self.causal and self.block_q == self.block_k
                and self.offset % self.block_q == 0
                and self.block_q % _SUB_BLOCK == 0):
            return 1
        return self.block_q // _SUB_BLOCK

    @property
    def splits_edge(self) -> bool:
        """Whether the window's far edge crosses tiles corner to corner and
        those tiles are swept in sub-blocks (10.8% faster than whole under
        the mask at the cell's shape: PERF.md section 5)."""
        return (self.window is not None and self.sub_blocks > 1
                and self.window % self.block_q == 0)

    def _nearest(self, qi, ki):
        """The least distance of a pair of the (qi, ki) tile."""
        return qi * self.block_q + self.offset - (ki + 1) * self.block_k + 1

    def _corner(self, qi, ki):
        """The distance of the (qi, ki) tile's first pair."""
        return qi * self.block_q + self.offset - ki * self.block_k

    def _behind(self, qi, ki, distance):
        """Whether ``distance`` is inside the window and the (qi, ki) tile
        inside the arrays (a windowed sweep's last steps can lie past
        them)."""
        return (distance < self.window) & (qi < self.n_q) & (ki < self.n_k)

    def live(self, qi, ki):
        if not self.causal:
            return True
        live = (qi + 1) * self.block_q - 1 + self.offset >= ki * self.block_k
        if self.window is not None:
            live = live & self._behind(qi, ki, self._nearest(qi, ki))
        return live

    def diagonal(self, qi, ki):
        """Whether the (qi, ki) tile is run by the split body."""
        if self.sub_blocks == 1:
            return False
        diagonal = qi * self.block_q + self.offset == ki * self.block_k
        if self.window is not None:
            diagonal = diagonal & self._behind(qi, ki, 0)
        return diagonal

    def edge(self, qi, ki):
        """Whether the (qi, ki) tile is run by the split body's mirror."""
        if not self.splits_edge:
            return False
        return ((self._corner(qi, ki) == self.window)
                & self._behind(qi, ki, 0))

    def whole(self, qi, ki):
        """Whether the (qi, ki) tile is live and computed whole."""
        if self.sub_blocks == 1:
            return self.live(qi, ki)
        under = qi * self.block_q + self.offset > ki * self.block_k
        if self.window is None:
            return under
        return under & self._behind(qi, ki, (
            self._corner if self.splits_edge else self._nearest)(qi, ki))

    def parts(self, kind: str = "whole") -> list:
        """The rectangles (:class:`Part`) of a live tile of ``kind``
        (``"whole"``, ``"diagonal"`` or ``"edge"``) that a kernel
        computes. A split tile's corner distance is known while tracing
        (0 on the diagonal, the window on its edge), and so are the keys
        of the tile that each row sub-block can see: whole sub-block
        columns from the first to the last of them."""
        if kind == "whole":
            return [Part(0, self.block_q, 0, self.block_k,
                         self.causal and self.sub_blocks == 1,
                         self.window is not None and not self.splits_edge)]
        corner = 0 if kind == "diagonal" else self.window
        size = self.block_q // self.sub_blocks
        reach = math.inf if self.window is None else self.window
        parts = []
        for row in range(0, self.block_q, size):
            first = max(0, corner + row - reach + 1)
            last = min(self.block_k - 1, corner + row + size - 1)
            if first > last:
                continue
            key = first // size * size
            end = -(-(last + 1) // size) * size
            parts.append(Part(row, size, key, end - key,
                              causal=corner + row - (end - 1) < 0,
                              window=corner + row + size - 1 - key >= reach))
        return parts

    def first_live_k(self, qi):
        """The first live key block of query block ``qi``, inside the
        array."""
        if self.window is None:
            return 0
        return _xp(qi).clip((qi * self.block_q + self.offset + 1
                             - self.window) // self.block_k, 0, self.n_k - 1)

    def last_live_k(self, qi):
        """The last live key block of query block ``qi``, inside the array."""
        if not self.causal:
            return self.n_k - 1
        return _xp(qi).clip(((qi + 1) * self.block_q - 1 + self.offset)
                            // self.block_k, 0, self.n_k - 1)

    def first_live_q(self, ki):
        """The first live query block of key block ``ki``, inside the array."""
        if not self.causal:
            return 0
        return _xp(ki).clip((ki * self.block_k - self.offset) // self.block_q,
                            0, self.n_q - 1)

    def last_live_q(self, ki):
        """The last live query block of key block ``ki``, inside the
        array."""
        if self.window is None:
            return self.n_q - 1
        return _xp(ki).clip(((ki + 1) * self.block_k - 2 + self.window
                             - self.offset) // self.block_q, 0, self.n_q - 1)

    @property
    def steps_k(self) -> int:
        """Grid steps of a query block's sweep over the keys (``flash_fwd``,
        ``flash_bwd_dq``): every key block, or under a window only as many
        as the widest run of live blocks of any query block, from its
        first live one (:meth:`key_of_step`)."""
        if self.window is None:
            return self.n_k
        qi = np.arange(self.n_q)
        return int((self.last_live_k(qi) - self.first_live_k(qi)).max()) + 1

    @property
    def steps_q(self) -> int:
        """The same for a key block's sweep over the queries
        (``flash_bwd_dkv``)."""
        if self.window is None:
            return self.n_q
        ki = np.arange(self.n_k)
        return int((self.last_live_q(ki) - self.first_live_q(ki)).max()) + 1

    def key_of_step(self, qi, step):
        """The key block of step ``step`` of query block ``qi``'s sweep;
        past the last key block where the sweep has run out of them."""
        return step if self.window is None else self.first_live_k(qi) + step

    def query_of_step(self, ki, step):
        """The query block of step ``step`` of key block ``ki``'s sweep."""
        return step if self.window is None else self.first_live_q(ki) + step

    def fetch_k(self, qi, ki):
        """The key block a (qi, ki) grid step fetches: its own while live,
        the row's first live one before that and its last live one after,
        so a dead step copies nothing."""
        if not self.causal:
            return ki
        fetched = _xp(qi, ki).minimum(ki, self.last_live_k(qi))
        if self.window is not None:
            fetched = _xp(qi, ki).maximum(fetched, self.first_live_k(qi))
        return fetched

    def fetch_q(self, qi, ki):
        """The same for the key-major sweep of ``flash_bwd_dkv``: dead steps
        before a column's live ones point at its first live block, those
        after them at its last."""
        if not self.causal:
            return qi
        fetched = _xp(qi, ki).maximum(qi, self.first_live_q(ki))
        if self.window is not None:
            fetched = _xp(qi, ki).minimum(fetched, self.last_live_q(ki))
        return fetched

    def _of_every_tile(self, answer) -> np.ndarray:
        qi = np.arange(self.n_q)[:, None]
        ki = np.arange(self.n_k)[None, :]
        return np.broadcast_to(answer(qi, ki), (self.n_q, self.n_k))

    def live_tiles(self) -> np.ndarray:
        """``[n_q, n_k]`` of bool."""
        return self._of_every_tile(self.live)

    def diagonal_tiles(self) -> np.ndarray:
        """``[n_q, n_k]`` of bool."""
        return self._of_every_tile(self.diagonal)

    def edge_tiles(self) -> np.ndarray:
        """``[n_q, n_k]`` of bool."""
        return self._of_every_tile(self.edge)

    def pairs_required(self) -> int:
        """The pairs the mask of the shapes leaves."""
        if not self.causal:
            return self.seq_q * self.seq_k
        last = np.arange(self.seq_q) + self.offset  # each row's last key
        first = (np.zeros_like(last) if self.window is None
                 else last - self.window + 1)
        return int(np.clip(np.minimum(last, self.seq_k - 1)
                           - np.maximum(first, 0) + 1, 0, None).sum())

    def pairs_touched(self) -> int:
        """The pairs a kernel computes, a split tile's sub-blocks counted
        as they run (padding counts as computed)."""
        split = {"diagonal": int(self.diagonal_tiles().sum()),
                 "edge": int(self.edge_tiles().sum())}
        tiles = dict(split, whole=int(self.live_tiles().sum())
                     - sum(split.values()))
        return sum(n * p.rows * p.keys for kind, n in tiles.items() if n
                   for p in self.parts(kind))

    def counts(self) -> dict:
        """What the flight event says of a kernel: its tiles by kind, and
        the pairs it computes over the pairs the mask of the shapes leaves
        (padding counts as computed, not as required)."""
        live = int(self.live_tiles().sum())
        return {"dead": self.n_q * self.n_k - live,
                # of the dead tiles, those a query-major sweep still steps
                # over (under a window it starts at its first live block)
                "dead_steps": self.n_q * self.steps_k - live, "live": live,
                "diagonal": int(self.diagonal_tiles().sum()),
                "edge": int(self.edge_tiles().sum()),
                "sub_blocks": self.sub_blocks,
                "pairs_touched_over_required": round(
                    self.pairs_touched() / self.pairs_required(), 4)}


def _xp(*xs):
    """jnp where any of ``xs`` is traced (a grid index), else numpy."""
    return jnp if any(isinstance(x, jax.Array) for x in xs) else np


def _when(condition):
    """``pl.when``, decided while tracing where the condition is a number
    (a grid of one tile asks the plan with numbers)."""
    if isinstance(condition, jax.Array):
        return pl.when(condition)
    return (lambda body: body()) if condition else (lambda body: None)


def _grid_ids(plan, q_axis, k_axis, key_major=False):
    """(qi, ki, the step of the inner sweep) of a grid step; a number and
    not traced along an axis of one step. The inner sweep is over the keys
    of a query block, or (``key_major``) over the queries of a key block,
    and under a window starts at the block's first live one."""
    if key_major:
        ki = pl.program_id(k_axis) if plan.n_k > 1 else 0
        step = pl.program_id(q_axis) if plan.steps_q > 1 else 0
        return plan.query_of_step(ki, step), ki, step
    qi = pl.program_id(q_axis) if plan.n_q > 1 else 0
    step = pl.program_id(k_axis) if plan.steps_k > 1 else 0
    return qi, plan.key_of_step(qi, step), step


def _on_live_tile(plan, qi, ki, body):
    """Run ``body(part)`` on each rectangle (:class:`Part`) that the
    (qi, ki) step has to compute: a diagonal or an edge tile's sub-blocks,
    any other live tile whole, a dead tile not at all."""
    for kind, here in (("diagonal", plan.diagonal(qi, ki)),
                       ("whole", plan.whole(qi, ki)),
                       ("edge", plan.edge(qi, ki))):
        @_when(here)
        def _parts():
            for part in plan.parts(kind):
                body(part)


def _tile_mask(plan, qi, ki, km_ref, pm_ref, part):
    """The mask of a rectangle of a live tile: key padding, the per-example
    key mask, the per-pair mask, query padding (the backward's padded rows
    carry no residuals), the causal triangle and the window's far edge;
    each term only where the shapes can make it false, and None where none
    can."""
    shape = (part.rows, part.keys)
    rows = slice(part.row, part.row + part.rows)
    keys = slice(part.key, part.key + part.keys)
    first_key = ki * plan.block_k
    if part.key:
        first_key = first_key + part.key
    key_idx = first_key + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    terms = []
    if plan.pads_k:
        terms.append(key_idx < plan.seq_k)
    if km_ref is not None:  # [1, keys] broadcasts over rows
        terms.append(km_ref[0, :, keys] > 0)
    if pm_ref is not None:  # [block_q, block_k] of int8, the tile's own
        terms.append(pm_ref[0, rows, keys].astype(jnp.int32) != 0)
    if part.causal or part.window or plan.pads_q:
        query_idx = (qi * plan.block_q + part.row
                     + jax.lax.broadcasted_iota(jnp.int32, shape, 0))
        if plan.pads_q:
            terms.append(query_idx < plan.seq_q)
        if part.causal:
            terms.append(query_idx + plan.offset >= key_idx)
        if part.window:
            terms.append(query_idx + plan.offset - key_idx < plan.window)
    if not terms:
        return None
    return jnp.broadcast_to(functools.reduce(operator.and_, terms), shape)


def _scaled(q, scale, mm):
    """q times the softmax scale, on the ``[rows, D]`` operand and not on
    the ``[rows, keys]`` scores: in float32 before the cast, so it is
    exact where the scale is a power of two (64 ** -0.5 is)."""
    return (q.astype(jnp.float32) * scale).astype(mm)


def _lanes(x, width):
    """``[rows, 128]`` whose lanes are equal, as ``[rows, width]``."""
    if width <= x.shape[1]:
        return x[:, :width]
    return jnp.tile(x, (1, -(-width // x.shape[1])))[:, :width]


def _flash_kernel(q_ref, k_ref, v_ref, km_ref, pm_ref, o_ref, lse_ref, m_scr,
                  l_scr, acc_scr, *, scale, plan):
    qi, ki, step = _grid_ids(plan, 1, 2)

    @_when(step == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute(part):
        rows = slice(part.row, part.row + part.rows)
        keys = slice(part.key, part.key + part.keys)
        mm = _matmul_dtype(q_ref.dtype)
        s = jax.lax.dot_general(
            _scaled(q_ref[0, rows, :], scale, mm),
            k_ref[0, keys, :].astype(mm),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [rows, keys]
        mask = _tile_mask(plan, qi, ki, km_ref, pm_ref, part)
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)
        # The running maximum and sum stay the lane-broadcast [rows, 128]
        # they are stored as: read by their first column they cost a lane
        # broadcast each, which a diagonal tile's sub-blocks do not repay.
        m_prev = m_scr[rows, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, part.keys))
        if mask is not None and (km_ref is not None or pm_ref is not None
                                 or plan.rows_can_be_empty or part.window):
            # A row masked whole so far keeps m_new at _NEG_INF, and
            # exp(s - m_new) would be 1 there, not 0. (Under a window a
            # row's first live tile is the edge's, of which its last rows
            # see nothing.)
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[rows, :] = (l_scr[rows, :] * alpha
                          + jnp.sum(p, axis=1, keepdims=True))
        acc_scr[rows, :] = (
            acc_scr[rows, :] * _lanes(alpha, acc_scr.shape[1])
            + jax.lax.dot_general(
                p.astype(mm), v_ref[0, keys, :].astype(mm),
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32))
        m_scr[rows, :] = m_new

    _on_live_tile(plan, qi, ki, _compute)

    @_when(step == plan.steps_k - 1)
    def _finish():
        # Fully-masked rows: l == 0 → output 0 (callers never read them).
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / _lanes(l, acc_scr.shape[1])
                    ).astype(o_ref.dtype)
        if lse_ref is not None:
            # Row logsumexp, lane-broadcast — the backward residual. Fully
            # masked / padded rows get ~-1e30; backward clamps before exp.
            lse_ref[0] = m_scr[:] + jnp.log(l)


def _round_up(x, m):
    return -(-x // m) * m


def _pad_to(x, axis, multiple):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _clamp_blocks(block_q, block_k, t, s_len):
    """Blocks stay (8,128)-tile-aligned even for short sequences."""
    return min(block_q, _round_up(t, 8)), min(block_k, _round_up(s_len, 128))


def _rows(x, block):
    """``[B, H, L, D]`` as the kernels take it: ``[B*H, L', D]`` with the
    sequence padded to whole blocks (no copy where it already is) and the
    head dimension as it came: a block whose last dimension is the array's
    is legal in Mosaic at any width."""
    b, h, length, d = x.shape
    return _pad_to(x.reshape(b * h, length, d), 1, block)


def _key_mask_rows(key_mask, heads, block_k):
    """``[B, S]`` 1/0 as ``[B*H, 1, S']``: tiny; the unit middle dim keeps
    the Mosaic block shape (1, 1, block_k) legal."""
    km = _pad_to(key_mask.astype(jnp.float32), 1, block_k)
    return jnp.repeat(km, heads, axis=0)[:, None, :]


def _pair_mask_tiles(pair_mask, block_q, block_k):
    """``[B, T, S]`` of int8 padded to whole tiles (the padding selects
    nothing); the kernels' index maps drop the head: ``bh // heads``."""
    return _pad_to(_pad_to(pair_mask.astype(jnp.int8), 1, block_q), 2,
                   block_k)


def _flash_fwd(q, k, v, key_mask, pair_mask, *, causal, scale, block_q,
               block_k, window=None, save_lse=False):
    b, h, t, d = q.shape
    s_len, dv = k.shape[2], v.shape[-1]
    plan = TilePlan(t, s_len, block_q, block_k, causal, pair_mask is not None,
                    window)
    qp, kp, vp = _rows(q, block_q), _rows(k, block_k), _rows(v, block_k)
    tq = qp.shape[1]

    def q_index(bh, qi, step):
        return (bh, qi, 0)

    def fetched_k(qi, step):
        return plan.fetch_k(qi, plan.key_of_step(qi, step))

    def kv_index(bh, qi, step):
        return (bh, fetched_k(qi, step), 0)

    operands = [qp, kp, vp]
    in_specs = [pl.BlockSpec((1, block_q, d), q_index),
                pl.BlockSpec((1, block_k, d), kv_index),
                pl.BlockSpec((1, block_k, dv), kv_index)]
    if key_mask is not None:
        operands.append(_key_mask_rows(key_mask, h, block_k))
        in_specs.append(pl.BlockSpec(
            (1, 1, block_k), lambda bh, qi, step: (bh, 0, fetched_k(qi, step))))
    if pair_mask is not None:
        operands.append(_pair_mask_tiles(pair_mask, block_q, block_k))
        in_specs.append(pl.BlockSpec(
            (1, block_q, block_k),
            lambda bh, qi, step: (bh // h, qi, fetched_k(qi, step))))
    out_specs = [pl.BlockSpec((1, block_q, dv), q_index)]
    out_shape = [jax.ShapeDtypeStruct((b * h, tq, dv), q.dtype)]
    if save_lse:
        out_specs.append(pl.BlockSpec((1, block_q, 128), q_index))
        out_shape.append(jax.ShapeDtypeStruct((b * h, tq, 128), jnp.float32))

    def kernel(*refs):
        q_ref, k_ref, v_ref, *rest = refs
        km_ref = rest.pop(0) if key_mask is not None else None
        pm_ref = rest.pop(0) if pair_mask is not None else None
        o_ref = rest.pop(0)
        lse_ref = rest.pop(0) if save_lse else None
        _flash_kernel(q_ref, k_ref, v_ref, km_ref, pm_ref, o_ref, lse_ref,
                      *rest, scale=scale, plan=plan)

    res = pl.pallas_call(
        kernel,
        grid=(b * h, plan.n_q, plan.steps_k),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary",
                                         pair_mask=plan.pair_mask),
        interpret=_interpret(),
        name="flash_fwd",
    )(*operands)
    out = res[0][:, :t].reshape(b, h, t, dv)
    return out, (res[1] if save_lse else None)


def _bwd_recompute(q_ref, k_ref, v_ref, km_ref, pm_ref, g_ref, lse_ref,
                   delta_ref, qi, ki, part, *, scale, plan):
    """Recompute p and ds for one rectangle of a (q-block, kv-block) pair —
    the math both backward kernels share. Returns (q, k, g, p, ds) in the
    MXU compute dtype (see _matmul_dtype); ds lacks the softmax scale, which
    each kernel puts on its ``[*, D]`` accumulator at the end."""
    rows = slice(part.row, part.row + part.rows)
    keys = slice(part.key, part.key + part.keys)
    mm = _matmul_dtype(q_ref.dtype)
    q = q_ref[0, rows, :]
    k = k_ref[0, keys, :].astype(mm)
    g = g_ref[0, rows, :].astype(mm)
    # Clamp: fully-masked rows carry lse ≈ -1e30; their scores are -1e30
    # too, so the clamped difference underflows exp to exactly 0 (no
    # inf·0 NaNs).
    lse = jnp.maximum(lse_ref[0, rows, :1], -1e20)
    delta = delta_ref[0, rows, :1]

    s = jax.lax.dot_general(
        _scaled(q, scale, mm), k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    mask = _tile_mask(plan, qi, ki, km_ref, pm_ref, part)
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    p = jnp.exp(s - lse)  # [rows, keys]; exactly 0 where masked
    dp = jax.lax.dot_general(
        g, v_ref[0, keys, :].astype(mm), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    # p/ds feed straight into MXU matmuls at the call sites — hand them
    # over in the compute dtype (fp32 accumulation happens there).
    return q.astype(mm), k, g, p.astype(mm), ds.astype(mm)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, km_ref, pm_ref, g_ref, lse_ref,
                          delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                          scale, plan):
    qi, ki, step = _grid_ids(plan, 2, 1, key_major=True)

    @_when(step == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute(part):
        q, _, g, p, ds = _bwd_recompute(
            q_ref, k_ref, v_ref, km_ref, pm_ref, g_ref, lse_ref, delta_ref,
            qi, ki, part, scale=scale, plan=plan)
        keys = slice(part.key, part.key + part.keys)
        dv_scr[keys, :] += jax.lax.dot_general(
            p, g, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dk_scr[keys, :] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        )

    _on_live_tile(plan, qi, ki, _compute)

    @_when(step == plan.steps_q - 1)
    def _finish():
        dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, km_ref, pm_ref, g_ref, lse_ref,
                         delta_ref, dq_ref, dq_scr, *, scale, plan):
    qi, ki, step = _grid_ids(plan, 1, 2)

    @_when(step == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute(part):
        _, k, _, _, ds = _bwd_recompute(
            q_ref, k_ref, v_ref, km_ref, pm_ref, g_ref, lse_ref, delta_ref,
            qi, ki, part, scale=scale, plan=plan)
        rows = slice(part.row, part.row + part.rows)
        dq_scr[rows, :] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    _on_live_tile(plan, qi, ki, _compute)

    @_when(step == plan.steps_k - 1)
    def _finish():
        dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _flash_bwd_call(kernel_fn, q, k, v, key_mask, pair_mask, g, lse, delta, *,
                    causal, scale, window, block_q, block_k, key_major):
    """One backward kernel at its own geometry, as (kernel, the keyword
    arguments of its ``pallas_call``, operands). ``key_major``: the grid is
    (bh, ki, qi), the query sweep innermost (``flash_bwd_dkv``); else
    (bh, qi, ki) (``flash_bwd_dq``). The outputs come back padded. q, k
    and dq, dk are ``d`` wide, v, the output's gradient ``g`` and dv
    ``dv`` wide."""
    b, h, t, d = q.shape
    s_len, dv = k.shape[2], v.shape[-1]
    plan = TilePlan(t, s_len, block_q, block_k, causal, pair_mask is not None,
                    window)

    def ids(bh, outer, step):  # -> (bh, qi, ki)
        if key_major:
            return bh, plan.query_of_step(outer, step), outer
        return bh, outer, plan.key_of_step(outer, step)

    def q_index(*grid):
        bh, qi, ki = ids(*grid)
        return (bh, plan.fetch_q(qi, ki) if key_major else qi, 0)

    def kv_index(*grid):
        bh, qi, ki = ids(*grid)
        return (bh, ki if key_major else plan.fetch_k(qi, ki), 0)

    def km_index(*grid):
        bh, block, _ = kv_index(*grid)
        return (bh, 0, block)

    def pm_index(*grid):
        return (grid[0] // h, q_index(*grid)[1], kv_index(*grid)[1])

    def rows_q(x):  # [BH, T(+), 128] residuals at this kernel's padding
        return _pad_to(x[:, :t], 1, block_q)

    q_spec = pl.BlockSpec((1, block_q, d), q_index)
    kv_spec = pl.BlockSpec((1, block_k, d), kv_index)
    v_spec = pl.BlockSpec((1, block_k, dv), kv_index)
    g_spec = pl.BlockSpec((1, block_q, dv), q_index)
    row_spec = pl.BlockSpec((1, block_q, 128), q_index)
    operands = [_rows(q, block_q), _rows(k, block_k), _rows(v, block_k)]
    in_specs = [q_spec, kv_spec, v_spec]
    if key_mask is not None:
        operands.append(_key_mask_rows(key_mask, h, block_k))
        in_specs.append(pl.BlockSpec((1, 1, block_k), km_index))
    if pair_mask is not None:
        operands.append(_pair_mask_tiles(pair_mask, block_q, block_k))
        in_specs.append(pl.BlockSpec((1, block_q, block_k), pm_index))
    operands += [_rows(g, block_q), rows_q(lse), rows_q(delta)]
    in_specs += [g_spec, row_spec, row_spec]
    tq, tk = operands[0].shape[1], operands[1].shape[1]

    if key_major:
        grid = (b * h, plan.n_k, plan.steps_q)
        out_specs = [kv_spec, v_spec]
        out_shape = [jax.ShapeDtypeStruct((b * h, tk, d), k.dtype),
                     jax.ShapeDtypeStruct((b * h, tk, dv), v.dtype)]
        scratch = [pltpu.VMEM((block_k, d), jnp.float32),
                   pltpu.VMEM((block_k, dv), jnp.float32)]
    else:
        grid = (b * h, plan.n_q, plan.steps_k)
        out_specs = [q_spec]
        out_shape = [jax.ShapeDtypeStruct((b * h, tq, d), q.dtype)]
        scratch = [pltpu.VMEM((block_q, d), jnp.float32)]

    def kernel(*refs):
        q_ref, k_ref, v_ref, *rest = refs
        km_ref = rest.pop(0) if key_mask is not None else None
        pm_ref = rest.pop(0) if pair_mask is not None else None
        kernel_fn(q_ref, k_ref, v_ref, km_ref, pm_ref, *rest, scale=scale,
                  plan=plan)

    return kernel, dict(
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary",
                                         pair_mask=plan.pair_mask),
        interpret=_interpret(),
    ), operands


def _flash_bwd_impl(q, k, v, key_mask, pair_mask, out, lse, g, *, causal,
                    scale, blocks, window):
    """Blockwise backward: two kernels, each at its own geometry; delta is
    taken at the output's width, v's."""
    b, h, t, d = q.shape
    s_len, width_v = k.shape[2], v.shape[-1]
    delta = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta.reshape(b * h, t)[:, :, None],
                             (b * h, t, 128))
    args = (q, k, v, key_mask, pair_mask, g, lse, delta)
    kernel, call, operands = _flash_bwd_call(
        _flash_bwd_dkv_kernel, *args, causal=causal, scale=scale,
        window=window, block_q=blocks.dkv[0], block_k=blocks.dkv[1], key_major=True)
    dk, dv = pl.pallas_call(
        kernel,
        name="flash_bwd_dkv",
        **call,
    )(*operands)
    kernel, call, operands = _flash_bwd_call(
        _flash_bwd_dq_kernel, *args, causal=causal, scale=scale,
        window=window, block_q=blocks.dq[0], block_k=blocks.dq[1], key_major=False)
    dq, = pl.pallas_call(
        kernel,
        name="flash_bwd_dq",
        **call,
    )(*operands)
    return (dq[:, :t].reshape(b, h, t, d),
            dk[:, :s_len].reshape(b, h, s_len, d),
            dv[:, :s_len].reshape(b, h, s_len, width_v))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, key_mask, causal, scale, blocks, window, pair_mask=None):
    """``blocks``: a ``FlashBlocks`` already clamped to the shapes."""
    out, _ = _flash_fwd(q, k, v, key_mask, pair_mask, causal=causal,
                        scale=scale, window=window, block_q=blocks.fwd[0],
                        block_k=blocks.fwd[1])
    return out


def _flash_vjp_fwd(q, k, v, key_mask, causal, scale, blocks, window,
                   pair_mask):
    out, lse = _flash_fwd(q, k, v, key_mask, pair_mask, causal=causal,
                          scale=scale, window=window, block_q=blocks.fwd[0],
                          block_k=blocks.fwd[1], save_lse=True)
    return out, (q, k, v, key_mask, pair_mask, out, lse)


def _flash_vjp_bwd(causal, scale, blocks, window, res, g):
    q, k, v, key_mask, pair_mask, out, lse = res
    dq, dk, dv = _flash_bwd_impl(
        q, k, v, key_mask, pair_mask, out, lse, g,
        causal=causal, scale=scale, blocks=blocks, window=window,
    )
    dkm = jnp.zeros_like(key_mask) if key_mask is not None else None
    dpm = (np.zeros(pair_mask.shape, jax.dtypes.float0)  # integers: no
           if pair_mask is not None else None)           # cotangent
    return dq, dk, dv, dkm, dpm


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _how_traced():
    """What the kernels' text depends on besides a call's arguments, read
    while tracing: compiled or interpreted, and the products' dtype."""
    return (_on_tpu(), _interpret(), _matmul_dtype(jnp.float32),
            _matmul_dtype(jnp.bfloat16))


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _flash_traced_once(q, k, v, key_mask, causal, scale, blocks, how, window,
                       pair_mask=None):
    """``_flash`` behind a ``jax.jit`` of its own: the layers of a model
    trace and lower each kernel once, not once a layer. A diagonal tile's
    sub-blocks are copies of the body in the kernel's code, and traced a
    layer at a time they cost ``gpt2_small``'s twelve layers 5 s of set-up
    (PERF.md section 6, PR 32). ``how`` (:func:`_how_traced`) only keys the
    cache of traces."""
    del how
    return _flash(q, k, v, key_mask, causal, scale, blocks, window, pair_mask)


def _flash_on_mesh(mesh, q, k, v, key_mask, pair_mask, causal, scale, blocks,
                   window):
    """The kernel under a multi-device mesh: inside ``shard_map``, batch
    split over the data-like axes and heads over the model axis (attention
    is independent across both, so no collective is needed); a dimension
    the axis size does not divide stays whole and is computed redundantly.
    """
    from deeplearning4j_tpu.runtime.device import MODEL_AXIS, data_like_axes

    b, h = q.shape[:2]
    batch_axes = data_like_axes(mesh)
    if b % math.prod(mesh.shape[a] for a in batch_axes):
        batch_axes = ()
    head_axis = (MODEL_AXIS if h % mesh.shape.get(MODEL_AXIS, h + 1) == 0
                 else None)
    qkv_spec = P(batch_axes or None, head_axis, None, None)
    args, specs = [q, k, v], [qkv_spec] * 3
    if key_mask is not None:
        args.append(key_mask)
        specs.append(P(batch_axes or None, None))
    if pair_mask is not None:
        args.append(pair_mask)
        specs.append(P(batch_axes or None, None, None))

    how = _how_traced()

    def local(q, k, v, *masks):
        masks = list(masks)
        km = masks.pop(0) if key_mask is not None else None
        pm = masks.pop(0) if pair_mask is not None else None
        return _flash_traced_once(q, k, v, km, causal, scale, blocks, how,
                                  window, pm)

    return jax.shard_map(local, mesh=mesh, in_specs=tuple(specs),
                         out_specs=qkv_spec, check_vma=False)(*args)


def flash_attention(q, k, v, *, causal: bool = False, scale=None, bias=None,
                    key_mask=None, pair_mask=None, window: int = None,
                    block_q: int = None, block_k: int = None,
                    backend: str = None):
    """Blockwise attention; q [B,H,T,D], k [B,H,S,D], v [B,H,S,Dv] →
    [B,H,T,Dv]. The value may be narrower than the query and key (latent
    attention's 192-wide query-key heads over 128-wide values): the
    kernels then hold v, the output, its gradient and dv at Dv and q, k,
    dq, dk at D, and the softmax scale stays D ** -0.5.

    ``key_mask`` [B,S] 1/0 (padding mask) runs inside the kernel — the
    BERT path keeps the flash fast path. ``pair_mask`` [B,T,S] of int8
    (nonzero: the query attends to the key; one mask for all heads of a
    sequence) runs inside it too; no gradient reaches it. ``window``
    (with ``causal``): a query attends to its own position and the
    ``window - 1`` before it and nothing older; the kernels skip the tiles
    that lie wholly behind it as they skip those above the diagonal
    (:class:`TilePlan`), and a window no shorter than the keys is no
    window. Arbitrary additive ``bias`` forces the XLA fallback.

    ``backend``: None (auto), 'pallas', or 'xla'. Auto dispatch picks XLA's
    fused attention below ``_dispatch.flash_min_seq()`` keys, off-TPU, for
    biased attention and for fewer than 8 queries, and the Pallas kernel
    at long sequences where the O(T^2) score materialization pressures
    HBM. DL4J_TPU_FORCE_PALLAS=1 (kernel unit tests) forces the kernel
    path. An explicit 'pallas' that cannot be honoured raises.
    """
    d = q.shape[-1]
    scale = (d ** -0.5) if scale is None else scale
    window = _window_of_call(window, causal, k.shape[2])
    backend = _backend_of(q.shape[2], k.shape[2], bias is not None, backend)
    if backend == "xla":
        return reference_attention(q, k, v, causal=causal, bias=bias,
                                   key_mask=key_mask, pair_mask=pair_mask,
                                   scale=scale, window=window)
    t, s_len = q.shape[2], k.shape[2]
    dv = v.shape[-1]
    # the widest operand sets the tile: q and k, or v where it is wider
    blocks = _call_blocks(t, s_len, max(d, dv), causal, block_q, block_k)
    _record_plan(t, s_len, d, dv, causal, key_mask is not None,
                 pair_mask is not None, window, blocks)
    mesh = _active_kernel_mesh()
    if mesh is not None:
        return _flash_on_mesh(mesh, q, k, v, key_mask, pair_mask, causal,
                              scale, blocks, window)
    return _flash_traced_once(q, k, v, key_mask, causal, scale, blocks,
                              _how_traced(), window, pair_mask)


def _window_of_call(window, causal, seq_k):
    """A call's window as the plans take it: None where it is no shorter
    than the keys (every key of a query's past is then inside it)."""
    if window is None:
        return None
    if not causal or window < 1:
        raise ValueError("window counts the keys a query reaches back over "
                         "under a causal mask, itself included; got "
                         f"window={window!r} with causal={causal!r}")
    return None if window >= seq_k else window


def _backend_of(seq_q, seq_k, biased, backend=None):
    """``"pallas"`` or ``"xla"`` for a call of these shapes: the caller's
    choice where it made one and it can be honoured, else the dispatch
    rule of :func:`flash_attention`."""
    if backend not in (None, "pallas", "xla"):
        raise ValueError(f"backend must be None|'pallas'|'xla', got {backend!r}")
    can_pallas = not biased and seq_q >= 8 and _use_pallas()
    if backend == "pallas" and not can_pallas:
        # an explicit request is a contract: a caller that asked for the
        # kernel must never be handed XLA without a word
        raise ValueError(
            "backend='pallas' cannot be honoured: "
            + ("additive bias is not supported by the kernel"
               if biased else
               f"query length {seq_q} < 8" if seq_q < 8 else
               "not on TPU and DL4J_TPU_FORCE_PALLAS is not set"))
    if backend is None:
        backend = "pallas" if can_pallas and (
            _force_pallas() or seq_k >= _flash_min_seq()) else "xla"
    return backend


def _call_blocks(seq_q, seq_k, head_dim, causal, block_q=None, block_k=None):
    """The geometry of a call's three kernels, clamped to its shapes; an
    explicit ``block_q`` / ``block_k`` gives all three that geometry."""
    return FlashBlocks(*[
        _clamp_blocks(block_q or bq, block_k or bk, seq_q, seq_k)
        for bq, bk in _flash_block_sizes(seq_q, seq_k, head_dim, causal)])


def forward_plan(seq_q, seq_k, head_dim, *, causal, pair_mask=False):
    """The :class:`TilePlan` of ``flash_fwd`` for a call of these shapes
    at the default geometry: what a caller that counts tiles asks."""
    blocks = _call_blocks(seq_q, seq_k, head_dim, causal)
    return TilePlan(seq_q, seq_k, *blocks.fwd, causal, pair_mask)


def pairs_of_call(seq_q, seq_k, head_dim, *, causal, window=None):
    """Of one head of a call of these shapes left to the dispatch rule and
    the default geometry: the query-key pairs its mask and window require,
    and the pairs that what runs computes: a flash kernel's tile plan with
    a split tile's sub-blocks counted as they run (the three kernels'
    mean), every pair where XLA's attention runs."""
    window = _window_of_call(window, causal, seq_k)
    plans = [TilePlan(seq_q, seq_k, bq, bk, causal, window=window)
             for bq, bk in _call_blocks(seq_q, seq_k, head_dim, causal)]
    required = plans[0].pairs_required()
    if _backend_of(seq_q, seq_k, False) == "xla":
        return required, seq_q * seq_k
    return required, sum(p.pairs_touched() for p in plans) // len(plans)


def _record_plan(seq_q, seq_k, head_dim, head_dim_v, causal, has_mask,
                 has_pairs, window, blocks):
    """One ``kernel.flash_plan`` flight event per call, at trace time (a
    jitted step traces its calls once, so this costs a run nothing): the
    geometry of each kernel, how many of its tiles are dead (skipped,
    nothing fetched), live and, of the live, diagonal or on the window's
    edge (computed in ``sub_blocks`` row sub-blocks), and the pairs it
    computes over the pairs the call's mask and window leave
    (``TilePlan.counts``)."""
    from deeplearning4j_tpu.observability.flightrecorder import record_event

    record_event(
        "kernel.flash_plan", seq_q=seq_q, seq_k=seq_k, head_dim=head_dim,
        head_dim_v=head_dim_v, causal=causal, key_mask=has_mask,
        pair_mask=has_pairs, window=window,
        **{name: {"block_q": bq, "block_k": bk,
                  **TilePlan(seq_q, seq_k, bq, bk, causal,
                             window=window).counts()}
           for name, (bq, bk) in blocks._asdict().items()})
