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

    One rule: the largest tile whose operands fit, which ``flash_attention``
    cuts to the sequence where that is shorter. The widest operand sets it:
    ``head_dim`` is the widest of q and k's width and v's (the output and
    the gradients are as wide as their operands), and ``flash_attention``
    passes that. 1024 x 1024 where it is no wider than 192, 512 x 512
    where it is. A kernel's blocks of q, k, v, the output's gradient and
    its float32 accumulators all grow with the tile times their width, so
    the widest decides what fits.

    Swept on the chip at width 64 (TPU v5e, ``[16, 12, 1024, 64]`` and
    ``[4, 12, 4096, 64]`` bf16 causal, blocks of 128 to 1024 each way, each
    kernel's device time read from a trace; table in PERF.md section 5):
    every kernel is fastest at 1024 x 1024. Their per-tile work that does
    not grow with the tile's width (the row maxima and sums, the rescale
    of the accumulators, the step's copies) outweighs the dead pairs a
    large tile drags in, and a tile that the causal diagonal crosses
    corner to corner no longer computes its dead half
    (``flash_attention.TilePlan``: four row sub-blocks of 256 at this
    size). That ended the one exception, ``flash_bwd_dkv`` at 512 x 512
    for 1024 causal queries and keys (0.821 ms a layer at one tile in four
    sub-blocks against 1.042 at 512 x 512 tiles in two each; PR 32). At
    width 128 the same tile was timed in the cells (``[2, 8, 4096, 128]``,
    ``[2, 32, 8192, 128]`` under a pair mask, ``[1, 28, 16384, 128]`` under
    a window) but not swept against smaller ones.

    Swept on the chip at latent attention's 192-wide q and k over 128-wide
    v (``kanana2_30b_a3b``'s ``[2, 32, 8192]``, bf16 causal, blocks of 512
    to 2048 each way wherever a kernel compiles, device time from a trace;
    PERF.md section 5): every kernel is fastest at 1024 x 1024 here too,
    ``flash_fwd`` / ``flash_bwd_dkv`` / ``flash_bwd_dq`` 12.08 / 19.40 /
    17.44 ms a layer against 13.96 / 22.28 / 20.22 at 512 x 512, 12 to 14%
    less each; 512 x 1024 and 1024 x 512 take at most 1 ms a kernel off
    512 x 512, and every tile 2048 long that compiles is slower than 512 x
    512 (a tile whose sides differ has no diagonal split). Over 192 the
    rule is 512 x 512 because the chip's compiler refuses 1024 x 1024 at
    256 wide in float32 (``flash_bwd_dkv`` does not fit its share of
    VMEM). A small float32 call at 192/128 under a window and both masks
    (``[2, 4, 3000]``) reads 0.86 ms at 1024 x 1024 against 0.83 at 512 x
    512. Not measured: calls that are not causal or have ``seq_q !=
    seq_k``. What the defaults compile for is pinned in
    ``tests/test_flash_compiles_for_v5e.py``.

    DL4J_TPU_FLASH_BLOCK_Q/K, where set, give all three kernels that one
    geometry; the split follows from it.
    """
    largest = 1024 if head_dim <= 192 else 512
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
