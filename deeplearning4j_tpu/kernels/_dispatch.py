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


def flash_block_sizes() -> tuple[int, int]:
    """Default (block_q, block_k) for the flash kernel.

    Tunable via DL4J_TPU_FLASH_BLOCK_Q/K so the on-chip kernels_ab sweep
    can promote a winning geometry without a code change. 256x512 default:
    larger kv blocks amortize the per-grid-step overhead along the
    innermost (sequential) dimension while [block_q, block_k] score tiles
    stay comfortably inside VMEM.
    """
    return (int(os.environ.get("DL4J_TPU_FLASH_BLOCK_Q", "256")),
            int(os.environ.get("DL4J_TPU_FLASH_BLOCK_K", "512")))


def flash_min_seq() -> int:
    """Sequence length at/above which attention auto-dispatch prefers the
    Pallas flash kernel over XLA's fused attention: the flash kernel's
    O(T) memory advantage only pays once the T^2 score materialization
    pressures HBM. The crossover has not been measured on this round's
    chip. Override with DL4J_TPU_FLASH_MIN_SEQ.
    """
    return int(os.environ.get("DL4J_TPU_FLASH_MIN_SEQ", "1024"))
