"""Pallas TPU kernel: the fused Q-GaLore per-step weight update.

One kernel replaces the three-op hot path (INT4 dequant-project →
low-rank Adam → SR requant). Per weight tile it:

1. updates the low-rank Adam moments ``m, v`` from the low-rank gradient
   and forms the bias-corrected direction (paper's 8-bit Adam math, moments
   handled in f32 here — the wrapper (de)quantizes 8-bit moment state),
2. dequantizes the INT4 projection ``P`` in VMEM (nibble unpack on the
   VPU, asymmetric per-block scale/zero — P never exists in HBM above
   4 bits + scales),
3. back-projects the direction to full rank on the MXU,
4. dequantizes the INT8 weight tile, applies ``w - lr * upd`` (plus
   optional weight decay), recomputes per-block absmax scales, and
   stochastically rounds back to INT8.

The full-rank f32 update/weight transients live only in VMEM — they never
round-trip HBM, which is the bulk of the speedup: the op is memory-bound
and the unfused path streams the (m, n) f32 intermediate to HBM twice.
The uniform SR randoms remain a full-rank input stream (generated with
jax.random outside, as in sr_requant.py, for interpret-mode parity; on
real TPU pltpu.prng_random_bits seeded per program would generate them
in-kernel and remove that stream too).

Orientation (GaLore side convention):

* ``side="right"`` (m ≥ n): W (M, N), low-rank L/moments (M, r),
  P (N, r). Grid tiles rows: each program owns a (BM, N) weight stripe,
  its (BM, r) moment rows, and the whole packed P.
* ``side="left"`` (m < n): W (M, N), low-rank L/moments (r, N),
  P (M, r). Grid tiles columns, then rows: each program owns a (BM, BN)
  weight tile and recomputes the Adam step of its (r, BN) moment
  columns, which every row tile of that column writes identically.

The right side owns every moment element in exactly one program; the
left side repeats r·BN Adam elements per row tile (cheap next to the
tile's BM·BN back-projection) so that no row tile waits on another.

``lr`` and ``count`` (the 1-based step, for bias correction) are traced
scalars; ``lr`` enters as a (1, 1) array and ``count`` as the (1, 2) pair
of bias corrections.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.quant import unpack_int4


def _adam(g, m, v, bc_ref, *, beta1, beta2, eps):
    """f32 Adam moment update + bias-corrected direction. ``bc_ref``
    holds ``(1 - beta1**c, 1 - beta2**c)``, formed outside the kernel:
    Mosaic cannot lower a scalar ``powf``."""
    m_new = beta1 * m + (1.0 - beta1) * g
    v_new = beta2 * v + (1.0 - beta2) * (g * g)
    m_hat = m_new / bc_ref[0, 0]
    v_hat = v_new / bc_ref[0, 1]
    return m_new, v_new, m_hat / (jnp.sqrt(v_hat) + eps)


def _split_nibbles(packed):
    """(d, r/2) interleaved nibbles → (r/2, d) uint8 holding row ``j`` of
    the INT4 P^T in its low nibble and row ``j + r/2`` in its high one.

    Runs in XLA before the kernel, on 4-bit data (``d*r/2`` bytes). The
    kernel then unpacks with elementwise ops and one sublane concat:
    Mosaic unrolls the stored lane interleave row by row, which takes
    minutes to compile at d=2048."""
    u = unpack_int4(packed).T                      # (r, d) nibble codes
    rh = u.shape[0] // 2
    return (u[:rh] | (u[rh:] << 4)).astype(jnp.uint8)


def _dequant_pt(packed_t, s, z, pblock):
    """(r/2, d) split nibbles + (r//pblock, d) scale/zero → P^T (r, d)
    f32. The nibbles widen through int32: Mosaic has no uint8 → f32
    cast."""
    p = packed_t.astype(jnp.int32)
    u = jnp.concatenate([p & 0xF, (p >> 4) & 0xF], axis=0)
    u = u.astype(jnp.float32) - 8.0                # qmin = -8
    groups = [(u[g * pblock:(g + 1) * pblock] - z[g:g + 1]) * s[g:g + 1]
              for g in range(u.shape[0] // pblock)]
    return groups[0] if len(groups) == 1 else jnp.concatenate(groups, 0)


def _sr_requant(w, u, wblock):
    """w (R, C) f32 → (int8 codes (R, C), scales (R, C//wblock))."""
    R, C = w.shape
    nb = C // wblock
    wb = w.reshape(R, nb, wblock)
    absmax = jnp.max(jnp.abs(wb), axis=-1)
    scale = jnp.maximum(absmax / 127.0, 1e-12)
    codes = jnp.floor(wb / scale[..., None] + u.reshape(R, nb, wblock))
    return (jnp.clip(codes, -128, 127).reshape(R, C).astype(jnp.int8),
            scale)


def _deq_w(q, s, wblock):
    R, C = q.shape
    return (q.astype(jnp.float32).reshape(R, C // wblock, wblock)
            * s[..., None]).reshape(R, C)


def _kernel_right(g_ref, m_ref, v_ref, p_ref, ps_ref, pz_ref, q_ref, ws_ref,
                  u_ref, bc_ref, lr_ref, qo_ref, so_ref, mo_ref, vo_ref, *,
                  pblock: int, wblock: int, beta1: float, beta2: float,
                  eps: float, gscale: float, wd: float):
    lr = lr_ref[0, 0]
    m_new, v_new, dirn = _adam(
        g_ref[...].astype(jnp.float32), m_ref[...], v_ref[...], bc_ref,
        beta1=beta1, beta2=beta2, eps=eps)
    mo_ref[...] = m_new
    vo_ref[...] = v_new

    Pt = _dequant_pt(p_ref[...], ps_ref[...], pz_ref[...], pblock)  # (r, N)
    upd = gscale * jax.lax.dot_general(
        dirn, Pt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                       # (BM, N)

    w = _deq_w(q_ref[...], ws_ref[...], wblock)
    if wd:
        upd = upd + wd * w
    codes, scale = _sr_requant(w - lr * upd, u_ref[...], wblock)
    qo_ref[...] = codes
    so_ref[...] = scale


def _kernel_left(g_ref, m_ref, v_ref, p_ref, ps_ref, pz_ref, q_ref, ws_ref,
                 u_ref, bc_ref, lr_ref, qo_ref, so_ref, mo_ref, vo_ref, *,
                 pblock: int, wblock: int, beta1: float, beta2: float,
                 eps: float, gscale: float, wd: float):
    lr = lr_ref[0, 0]
    m_new, v_new, dirn = _adam(
        g_ref[...].astype(jnp.float32), m_ref[...], v_ref[...], bc_ref,
        beta1=beta1, beta2=beta2, eps=eps)
    mo_ref[...] = m_new
    vo_ref[...] = v_new

    Pt = _dequant_pt(p_ref[...], ps_ref[...], pz_ref[...], pblock)  # (r, BM)
    upd = gscale * jax.lax.dot_general(
        Pt, dirn, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                       # (BM, BN)

    w = _deq_w(q_ref[...], ws_ref[...], wblock)
    if wd:
        upd = upd + wd * w
    codes, scale = _sr_requant(w - lr * upd, u_ref[...], wblock)
    qo_ref[...] = codes
    so_ref[...] = scale


@functools.partial(
    jax.jit,
    static_argnames=("side", "pblock", "wblock", "beta1", "beta2", "eps",
                     "gscale", "wd", "bm", "bn", "interpret"))
def fused_qgalore_update(g, m, v, p_packed, p_scale, p_zero, q, wscale, u01,
                         count, lr, *, side: str, pblock: int, wblock: int,
                         beta1: float = 0.9, beta2: float = 0.999,
                         eps: float = 1e-8, gscale: float = 0.25,
                         wd: float = 0.0, bm: int = 256, bn: int = 512,
                         interpret: bool = True):
    """Fused low-rank-Adam + back-projection + SR weight update.

    All arrays pre-padded to tile boundaries by the caller
    (:func:`repro.kernels.ops.fused_qgalore_update` does this):

    side="right": g/m/v (M, r); P (N, r//2) packed + (N, r//pblock)
    scale/zero; q (M, N) int8 + wscale (M, N//wblock); u01 (M, N);
    M % bm == 0.
    side="left":  g/m/v (r, N); P (M, r//2) packed; q (M, N);
    N % bn == 0, bn % wblock == 0 and M % bm == 0.

    Returns ``(q', wscale', m', v')``.
    """
    M, N = q.shape
    c = jnp.asarray(count, jnp.float32)
    bc = jnp.stack([1.0 - beta1 ** c, 1.0 - beta2 ** c]).reshape(1, 2)
    lr2 = jnp.asarray(lr, jnp.float32).reshape(1, 1)
    kw = dict(pblock=pblock, wblock=wblock, beta1=beta1, beta2=beta2,
              eps=eps, gscale=gscale, wd=wd)
    r = g.shape[1] if side == "right" else g.shape[0]
    rh, rp = r // 2, r // pblock
    nb = N // wblock

    if side == "right":
        assert M % bm == 0, (M, bm)
        grid = (M // bm,)
        row = lambda i: (i, 0)
        fixed = lambda i: (0, 0)
        in_specs = [
            pl.BlockSpec((bm, r), row),          # g
            pl.BlockSpec((bm, r), row),          # m
            pl.BlockSpec((bm, r), row),          # v
            pl.BlockSpec((rh, N), fixed),        # split-nibble P^T
            pl.BlockSpec((rp, N), fixed),        # P scale^T
            pl.BlockSpec((rp, N), fixed),        # P zero^T
            pl.BlockSpec((bm, N), row),          # q
            pl.BlockSpec((bm, nb), row),         # wscale
            pl.BlockSpec((bm, N), row),          # u01
            pl.BlockSpec((1, 2), fixed),         # bias corrections
            pl.BlockSpec((1, 1), fixed),         # lr
        ]
        out_specs = [
            pl.BlockSpec((bm, N), row),
            pl.BlockSpec((bm, nb), row),
            pl.BlockSpec((bm, r), row),
            pl.BlockSpec((bm, r), row),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((M, N), jnp.int8),
            jax.ShapeDtypeStruct((M, nb), jnp.float32),
            jax.ShapeDtypeStruct((M, r), jnp.float32),
            jax.ShapeDtypeStruct((M, r), jnp.float32),
        ]
        kernel = functools.partial(_kernel_right, **kw)
    else:
        assert N % bn == 0 and bn % wblock == 0 and M % bm == 0, (
            M, N, bm, bn, wblock)
        # (bm, bn) weight tiles, M innermost: each program back-projects
        # its own P rows, so the f32 transients stay (bm, bn) at any M.
        # The (r, bn) moment block is revisited across M; every visit
        # writes the same values.
        gpt = bn // wblock
        grid = (N // bn, M // bm)
        col = lambda j, i: (0, j)
        pcol = lambda j, i: (0, i)
        tile = lambda j, i: (i, j)
        slab = lambda j, i: (j, i, 0)
        fixed = lambda j, i: (0, 0)
        in_specs = [
            pl.BlockSpec((r, bn), col),          # g
            pl.BlockSpec((r, bn), col),          # m
            pl.BlockSpec((r, bn), col),          # v
            pl.BlockSpec((rh, bm), pcol),        # split-nibble P^T
            pl.BlockSpec((rp, bm), pcol),        # P scale^T
            pl.BlockSpec((rp, bm), pcol),        # P zero^T
            pl.BlockSpec((bm, bn), tile),        # q
            pl.BlockSpec((None, bm, gpt), slab),  # wscale slab
            pl.BlockSpec((bm, bn), tile),        # u01
            pl.BlockSpec((1, 2), fixed),         # bias corrections
            pl.BlockSpec((1, 1), fixed),         # lr
        ]
        out_specs = [
            pl.BlockSpec((bm, bn), tile),
            pl.BlockSpec((None, bm, gpt), slab),
            pl.BlockSpec((r, bn), col),
            pl.BlockSpec((r, bn), col),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((M, N), jnp.int8),
            jax.ShapeDtypeStruct((N // bn, M, gpt), jnp.float32),
            jax.ShapeDtypeStruct((r, N), jnp.float32),
            jax.ShapeDtypeStruct((r, N), jnp.float32),
        ]
        # (M, N // wblock) → (N // bn, M, gpt): Mosaic needs a block's
        # last two dims (8, 128)-aligned or whole, and a (bm, gpt) window
        # of the stored layout is neither.
        wscale = wscale.reshape(M, N // bn, gpt).transpose(1, 0, 2)
        kernel = functools.partial(_kernel_left, **kw)

    qn, sn, mn, vn = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(g, m, v, _split_nibbles(p_packed), p_scale.T, p_zero.T, q, wscale,
      u01, bc, lr2)
    if side == "left":
        sn = sn.transpose(1, 0, 2).reshape(M, nb)
    return qn, sn, mn, vn
