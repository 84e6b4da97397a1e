"""Pallas kernel dispatch policy.

The compiled Pallas path is used only on real TPU devices. Off-TPU (CPU
CI, the driver's virtual-device dry-run) the kernels' callers take the XLA
reference implementations instead: interpret-mode Pallas is an emulator
meant for unit-testing kernel logic, and is far too slow to sit inside a
jitted train step (a cold BERT step exceeds several minutes).

Kernel unit tests opt back in by setting ``DL4J_TPU_FORCE_PALLAS=1``, which
routes through the kernel in interpret mode so the kernel body itself is
exercised against the XLA oracle on CPU. That flag is the ONLY way into
interpret mode: a ``pallas_call`` reached off-TPU without it raises
(:func:`interpret`) instead of quietly emulating.
"""

from __future__ import annotations

import contextlib
import os
import threading
from typing import NamedTuple

import jax

# The mesh the enclosing program is being partitioned over, published at
# TRACE time by whoever owns that mesh (Trainer does, around its loss).
# GSPMD cannot partition a Mosaic kernel ("wrap the call in a shard_map"),
# so a kernel traced under a multi-device mesh places itself inside
# shard_map over this mesh. Thread-local: replicas trace concurrently.
_trace_ctx = threading.local()


@contextlib.contextmanager
def kernel_mesh(mesh):
    """Publish ``mesh`` (or None, to shadow an outer one — code already
    inside its own shard_map) to the kernels traced within."""
    prev = getattr(_trace_ctx, "mesh", None)
    _trace_ctx.mesh = mesh
    try:
        yield mesh
    finally:
        _trace_ctx.mesh = prev


def active_kernel_mesh():
    """The published mesh when it spans more than one device, else None."""
    mesh = getattr(_trace_ctx, "mesh", None)
    return mesh if mesh is not None and mesh.devices.size > 1 else None


def on_tpu() -> bool:
    """True when the default jax backend is a real TPU. A backend that
    fails to initialize raises — it must not read as "no TPU" and route
    every kernel to XLA."""
    return jax.devices()[0].platform == "tpu"


def force_pallas() -> bool:
    """True when tests force the (interpret-mode) Pallas path off-TPU."""
    return os.environ.get("DL4J_TPU_FORCE_PALLAS", "") == "1"


def use_pallas() -> bool:
    """Should callers dispatch to the Pallas kernel at all?"""
    return on_tpu() or force_pallas()


def interpret() -> bool:
    """The ``interpret=`` argument of every ``pallas_call``: False on TPU
    (Mosaic compiles the kernel), True off-TPU under the tests' explicit
    ``DL4J_TPU_FORCE_PALLAS=1``, and an error otherwise."""
    if on_tpu():
        return False
    if force_pallas():
        return True
    raise RuntimeError(
        "Pallas kernel reached on platform "
        f"{jax.devices()[0].platform!r} without DL4J_TPU_FORCE_PALLAS=1: "
        "the compiled kernel exists only on TPU, and interpret mode is "
        "for kernel unit tests")


class FlashBlocks(NamedTuple):
    """(block_q, block_k) of each flash kernel; they need not share one."""

    fwd: tuple[int, int]
    dkv: tuple[int, int]
    dq: tuple[int, int]


def flash_block_sizes(seq_q: int, seq_k: int, head_dim: int,
                      causal: bool) -> FlashBlocks:
    """Default geometry of the three flash kernels for a call's shapes.

    One rule: the largest tile, 1024 x 1024, which ``flash_attention`` cuts
    to the sequence where that is shorter. Swept on the chip (TPU v5e,
    ``[16, 12, 1024, 64]`` and ``[4, 12, 4096, 64]`` bf16 causal, blocks of
    128 to 1024 each way, each kernel's device time read from a trace;
    table in PERF.md section 5), every kernel is fastest there: their
    per-tile work that does not grow with the tile's width (the row maxima
    and sums, the rescale of the accumulators, the step's copies) outweighs
    the dead pairs a large tile drags in, and a tile that the causal
    diagonal crosses corner to corner no longer computes its dead half
    (``flash_attention.TilePlan``: four row sub-blocks of 256 at this
    size). That ended the one exception, ``flash_bwd_dkv`` at 512 x 512 for
    1024 causal queries and keys, which skipped the dead quarter through
    the grid: 0.821 ms a layer at one tile in four sub-blocks against 1.042
    at 512 x 512 tiles in two each and 1.051 whole (PR 32, the same trace).

    Not measured, so the rule is a guess there: any ``head_dim`` but 64 and
    128, float32 operands, calls that are not causal, carry a ``key_mask``
    or have ``seq_q != seq_k``, and sequences that are no multiple of the
    tile (padded up to whole tiles). Heads wider than 128 get 512 x 512
    because the chip's compiler refuses 1024 x 1024 there (``flash_bwd_dkv``
    at 256 wide in float32 does not fit its share of VMEM); what the
    defaults compile for is pinned in
    ``tests/test_flash_compiles_for_v5e.py``.

    DL4J_TPU_FLASH_BLOCK_Q/K, where set, give all three kernels that one
    geometry; the split follows from it.
    """
    largest = 1024 if head_dim <= 128 else 512
    blocks = FlashBlocks(*[(largest, largest)] * 3)
    env_q = os.environ.get("DL4J_TPU_FLASH_BLOCK_Q")
    env_k = os.environ.get("DL4J_TPU_FLASH_BLOCK_K")
    if env_q or env_k:
        blocks = FlashBlocks(*[(int(env_q or bq), int(env_k or bk))
                               for bq, bk in blocks])
    return blocks


def flash_min_seq() -> int:
    """Sequence length at/above which attention auto-dispatch prefers the
    Pallas flash kernel over XLA's fused attention: the flash kernel's
    O(T) memory advantage only pays once the T^2 score materialization
    pressures HBM. The crossover has not been measured on this round's
    chip. Override with DL4J_TPU_FLASH_MIN_SEQ.
    """
    return int(os.environ.get("DL4J_TPU_FLASH_MIN_SEQ", "1024"))
