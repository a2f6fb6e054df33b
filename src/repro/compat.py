"""Collective shims for the XLA:CPU SPMD partitioner.

``shard_map`` and ``lax.axis_size`` are used straight from the installed
JAX (the 0.9 line); only the CPU partitioner gaps below need code here.
"""
from __future__ import annotations

import jax


# ---------------------------------------------------------------------------
# Partial-manual collective shims (XLA:CPU SPMD partitioner gaps)
# ---------------------------------------------------------------------------
# Inside a PARTIALLY-manual shard_map (some mesh axes stay automatic /
# GSPMD — the dp_compress training step keeps the model axis auto), the
# XLA:CPU partitioner supports psum and psum_scatter but
#   * aborts on all_gather ("Check failed: target.IsManualSubgroup() ==
#     sharding().IsManualSubgroup()", spmd_partitioner.cc), and
#   * rejects lax.axis_index ("PartitionId instruction is not supported
#     for SPMD partitioning").
# (psum_scatter additionally crashes when its operand is a body-created
# constant such as an iota — the partitioner constant-folds it into a
# manual-subgroup mismatch — so shard indices must arrive as SHARDED
# INPUTS, e.g. an arange(D) with in_spec P(dp_axes): each shard reads its
# own id. See train/step.py.)
# The gather helper below is expressed in terms of the collectives that DO
# lower everywhere, so the distributed-refresh path runs identically on
# the CPU CI mesh and on real hardware. TPU/GPU backends take the native
# op (the emulated gather costs ~2x the ring all-gather bytes, which only
# matters for large payloads — here they are low-rank grads and INT4 Ps).


def _emulate_collectives() -> bool:
    return jax.default_backend() == "cpu"


def all_gather_tiled(x, axes, *, axis: int, world: int, index):
    """``lax.all_gather(..., tiled=True)`` that also lowers on XLA:CPU
    partial-manual regions: each shard writes its block at its offset in a
    zeros global-size buffer and the psum concatenates (exactly one shard
    contributes per position, so integer payloads can't overflow)."""
    if not _emulate_collectives():
        return jax.lax.all_gather(x, axes, axis=axis, tiled=True)
    jnp = jax.numpy
    shape = list(x.shape)
    shape[axis] *= world
    start = [0] * x.ndim
    start[axis] = index * x.shape[axis]
    buf = jax.lax.dynamic_update_slice(jnp.zeros(shape, x.dtype), x,
                                       tuple(start))
    return jax.lax.psum(buf, axes)
