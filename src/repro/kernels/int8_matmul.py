"""Pallas TPU kernels: BF16/F32 activation × INT8 weight matmuls with the
dequant scale FUSED into the matmul — no dequantized weight tile in VMEM.

TPU adaptation of the paper's INT8 GEMM (bitsandbytes on CUDA): v5e has no
INT8 training GEMM, so the win is HBM traffic — weights stream at 1 byte
instead of 2 and are widened in VMEM, tile by tile. Block layout matches
the training representation: scales per (row, 256-col group), so the
kernel consumes optimizer output with zero relayout.

MXU operands are f32 for bf16 and f32 activations alike: the activation
tile and the INT8 codes (exact in f32) widen in VMEM, and the f32
accumulator lives across the contraction loop. On a v5e at the training
step's shapes, bf16 operands ran within 1% of these and gave the same
numbers (PERF.md §6): the tiles, not the operand format, set the speed.

Two orientations over the SAME stored blocks, with the scale applied on
opposite sides of the dot (the scale axis is the weight's ROW axis K times
the column group, so where it can fuse depends on which axis contracts):

* :func:`int8_matmul` — ``x (M, K) @ deq(W (K, N))`` (forward / serving).
  The contraction runs over K, where the scale VARIES, so a pure
  accumulator epilogue is impossible; instead the per-group scale column
  ``s[:, g]`` (a K-vector) folds into the activation operand:
  ``out[:, g·B:(g+1)·B] += (x * s[:, g]) @ q[:, g·B:(g+1)·B]``.
  One (BM, BK) scaled-activation operand per group replaces the old
  (BK, BN) f32 dequantized weight tile.
* :func:`int8_matmul_t` — ``g (M, N) @ deq(W (K, N))^T`` (backward dL/dx
  and the tied-embedding head). The contraction runs over N — the quant
  axis — so the scale is CONSTANT per (output column k, group g) and a
  true accumulator epilogue applies: the raw-code partial dot
  ``g[:, gg] @ q[:, gg]^T`` lands on the (BM, BK) accumulator scaled once
  by ``s[:, gg]``.

Both associations change only the order of f32 multiplies (exact for the
scale-by-code product; the x·s fold rounds once before the MXU instead of
once after the dequant multiply), so they stay within the existing
parity tolerances of the ref oracles — see kernels/ref.py, which mirrors
the same association order.

``int8_matmul`` grid: (M/BM, N/BN, K/BK), K innermost; f32 accumulator
lives in a VMEM scratch across the K loop. BN is a multiple of the quant
block (256) so each weight tile owns whole scale groups. ``int8_matmul_t``
walks (M/BM, K/BK, N/BN) with N innermost. Tiles come from
``dispatch.tuned_blocks``: the training step's row and contraction
buckets (M 8192) have measured entries in ``autotune_table.json``; a tile
set whose VMEM footprint passes the default scoped limit raises the limit
for its call (:func:`_compiler_params`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Mosaic's default scoped VMEM on v5e; tile sets above it raise the limit.
_DEFAULT_VMEM = 16 * 2**20
_MAX_VMEM = 100 * 2**20


def _compiler_params(bm: int, bn: int, bk: int, a_cols: int, out_cols: int,
                     act_bytes: int):
    """``CompilerParams`` raising the scoped-VMEM limit when the tiles'
    footprint estimate passes the default, else None (default limit).

    Counted: double-buffered activation, code, scale and output blocks;
    the f32 accumulator; the f32 activation tile, one f32 operand per
    group beside it, and the codes widened to f32."""
    est = (2 * (bm * a_cols * act_bytes + bk * bn + bk * (bn // 128) * 4
                + bm * out_cols * act_bytes)
           + bm * out_cols * 4 + bk * bn * 4 + bm * a_cols * 12)
    if est <= _DEFAULT_VMEM * 3 // 4:
        return None
    return pltpu.CompilerParams(
        vmem_limit_bytes=min(_MAX_VMEM, max(2 * est, _DEFAULT_VMEM)))


def _scale_tiles(scale, bn: int, block: int):
    """(K, N // block) scales → (N // bn, bn // block, K): one lane-dense
    (groups, K) slab per column tile. Mosaic requires a block's last two
    dims to be (8, 128)-aligned or whole; a (bk, bn // block) window of
    the stored layout is neither, while the slab's last two dims are
    whole-or-``bk`` and each group's scale row broadcasts over the tile's
    rows with no relayout."""
    K, nb = scale.shape
    return scale.T.reshape(nb // (bn // block), bn // block, K)


def _kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, block: int, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)            # (BM, BK)
    q = q_ref[...]                                # (BK, BN) int8
    s = s_ref[...]                                # (BN // block, BK)
    BK, BN = q.shape
    # Scale varies along the contraction axis K → fold it into the
    # activation per quant group instead of materializing deq(W) in VMEM.
    for g in range(BN // block):
        xs = x * s[g:g + 1, :]                    # (BM, BK)
        acc_ref[:, g * block:(g + 1) * block] += jax.lax.dot_general(
            xs, q[:, g * block:(g + 1) * block].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block", "bm", "bn", "bk", "interpret"))
def int8_matmul(x, q, scale, *, block: int = 256, bm: int = 128,
                bn: int = 256, bk: int = 512, interpret: bool = True):
    """x (M,K) bf16/f32 @ dequant(q (K,N) int8, scale (K, N/block)) → (M,N).

    Shapes must tile evenly (the ops.py wrapper pads); BN % block == 0.
    """
    M, K = x.shape
    Kq, N = q.shape
    assert K == Kq and N % block == 0 and bn % block == 0
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    grid = (M // bm, N // bn, K // bk)
    params = _compiler_params(bm, bn, bk, bk, bn, x.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_kernel, block=block, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((None, bn // block, bk), lambda i, j, k: (j, 0, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
    )(x, q, _scale_tiles(scale, bn, block))


def _kernel_t(g_ref, q_ref, s_ref, o_ref, acc_ref, *, block: int, n_n: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = g_ref[...].astype(jnp.float32)            # (BM, BN)
    q = q_ref[...]                                # (BK, BN) int8
    s = s_ref[...]                                # (BN // block, BK)
    BK, BN = q.shape
    # Contraction runs along N — the quant axis — so the scale applies
    # ONCE per group on the (BM, BK) accumulator: a true epilogue on the
    # raw-code partial dots.
    for gg in range(BN // block):
        sl = slice(gg * block, (gg + 1) * block)
        pdot = jax.lax.dot_general(
            g[:, sl], q[:, sl].astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)   # (BM, BK)
        acc_ref[...] += pdot * s[gg:gg + 1, :]

    @pl.when(pl.program_id(2) == n_n - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block", "bm", "bn", "bk", "interpret"))
def int8_matmul_t(g, q, scale, *, block: int = 256, bm: int = 128,
                  bn: int = 256, bk: int = 512, interpret: bool = True):
    """g (M,N) bf16/f32 @ dequant(q (K,N) int8, scale (K, N/block))^T → (M,K).

    Streams the SAME int8 blocks as :func:`int8_matmul` (no transposed
    weight copy); the contraction runs over N, the quant-block axis, so
    the scale multiply is a true accumulator epilogue. Shapes must tile
    evenly (the ops.py wrapper pads); BN % block == 0.
    """
    M, N = g.shape
    K, Nq = q.shape
    assert N == Nq and N % block == 0 and bn % block == 0
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    grid = (M // bm, K // bk, N // bn)
    params = _compiler_params(bm, bn, bk, bn, bk, g.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_kernel_t, block=block, n_n=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, k, n: (i, n)),
            pl.BlockSpec((bk, bn), lambda i, k, n: (k, n)),
            pl.BlockSpec((None, bn // block, bk), lambda i, k, n: (n, 0, k)),
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda i, k, n: (i, k)),
        out_shape=jax.ShapeDtypeStruct((M, K), g.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
    )(g, q, _scale_tiles(scale, bn, block))
