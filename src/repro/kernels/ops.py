"""Public, jit-friendly wrappers around the Pallas kernels.

This module is the only sanctioned entry point into :mod:`repro.kernels`:
it handles padding to tile boundaries, QTensor (un)packing, RNG plumbing,
and — via :mod:`repro.kernels.dispatch` — backend selection per op
(``pallas-tpu`` / ``pallas-interpret`` / pure-XLA ``ref``) from platform
detection instead of a hard-coded ``interpret=True`` default. Every
wrapper accepts

* ``backend=`` — an explicit backend name (wins over everything), and
* ``interpret=`` — legacy switch kept for tests: ``True`` forces
  ``pallas-interpret``, ``False`` forces ``pallas-tpu``, ``None`` defers
  to :func:`repro.kernels.dispatch.default_backend`.

Memory model (paper Table 2 — where each byte lives)
----------------------------------------------------
For a GaLore-eligible weight ``W (m, n)`` with rank ``r`` and
quantization block ``B`` (default 256):

==========================  =========================================
state                       storage
==========================  =========================================
weight ``W``                INT8 codes ``m*n`` bytes + f32 scales
                            ``4*m*n/B`` bytes (symmetric, per-block)
projection ``P (d, r)``     INT4 nibbles ``d*r/2`` bytes + f32
                            scale & zero ``2*4*d*r/B`` bytes
                            (``d = min(m, n)``, asymmetric)
Adam ``m``/``v``            INT8 codes over the LOW-RANK shape
                            (``max(m,n)*r`` each) + f32 scales;
                            ``v`` is stored in the sqrt domain
gradient (steady state)     low-rank bf16/f32, ``max(m,n)*r``
f32 update ``(m, n)``       **VMEM only** on the fused path — never
                            materialized in HBM
SR randoms ``(m, n)``       f32, generated by XLA outside the kernel
                            and streamed in once (kept as an input for
                            interpret-mode parity; in-kernel TPU PRNG
                            would remove this stream too)
==========================  =========================================

The fused-update kernel (:func:`fused_qgalore_update`) is what enforces
the update row: the three-op path streams the full-rank f32 update to HBM
between the back-projection and the SR requant (and the dequantized
weight besides); the fused kernel keeps both in VMEM for the lifetime of
one weight tile, leaving the uniform randoms as the only remaining
full-rank input stream.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant
from repro.core.quant import QTensor, QVirtual
from repro.kernels import dispatch, profile, ref
from repro.kernels.blockwise_quant import blockwise_quant as _bq
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.fused_update import fused_qgalore_update as _fused
from repro.kernels.int4_matmul import int4_matmul as _i4mm
from repro.kernels.int8_matmul import int8_matmul as _i8mm
from repro.kernels.int8_matmul import int8_matmul_t as _i8mmt
from repro.kernels.sr_requant import sr_requant as _srq

_FUSED_STATIC = ("side", "pblock", "wblock", "beta1", "beta2", "eps",
                 "gscale", "wd")

# jitted ref oracles (the fast pure-XLA backend off-TPU)
_ref_i8 = jax.jit(ref.int8_matmul_ref, static_argnames=("block",))
_ref_i8t = jax.jit(ref.int8_matmul_t_ref, static_argnames=("block",))
_ref_i4 = jax.jit(ref.int4_matmul_ref, static_argnames=("block",))
_ref_srq = jax.jit(ref.sr_requant_ref, static_argnames=("block",))
_ref_bq = jax.jit(ref.blockwise_quant_ref, static_argnames=("block",))
_ref_flash = jax.jit(ref.flash_attention_ref, static_argnames=("causal",))
_ref_fused = jax.jit(ref.fused_qgalore_update_ref,
                     static_argnames=_FUSED_STATIC)

# Tile-size kwarg names: meaningful to the Pallas kernels, dropped for the
# ref oracles (whose math has no tiling) so every backend shares one
# calling convention.
_BLOCK_KWARGS = ("bm", "bn", "bk", "br", "bc", "bq", "bkv")


def _drop_blocks(fn):
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        for k in _BLOCK_KWARGS:
            kwargs.pop(k, None)
        return fn(*args, **kwargs)
    return wrapped


# -- registry ----------------------------------------------------------------
for _op, _pfn, _rfn in (
        ("int8_matmul", _i8mm, _ref_i8),
        ("int8_matmul_t", _i8mmt, _ref_i8t),
        ("int4_matmul", _i4mm, _ref_i4),
        ("sr_requant", _srq, _ref_srq),
        ("blockwise_quant", _bq, _ref_bq),
        ("flash_attention", _flash, _ref_flash),
        ("fused_qgalore_update", _fused, _ref_fused),
):
    dispatch.register(_op, "pallas-tpu")(
        functools.partial(_pfn, interpret=False))
    dispatch.register(_op, "pallas-interpret")(
        functools.partial(_pfn, interpret=True))
    dispatch.register(_op, "ref")(_drop_blocks(_rfn))


def _pick(op: str, backend: Optional[str], interpret: Optional[bool]):
    """(backend_name, fn) honoring the legacy ``interpret`` switch."""
    if backend is None and interpret is not None:
        backend = "pallas-interpret" if interpret else "pallas-tpu"
    return dispatch.resolve(op, backend)


def _pad_to(x, axis: int, mult: int, value=0.0):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value), size


def _i8_bytes(M: int, K: int, N: int, block: int, x_itemsize: int) -> int:
    """HBM bytes one INT8 matmul call moves (x + codes + scales + out)."""
    return (M * K * x_itemsize + K * N                 # activations + codes
            + K * (N // block) * 4 + M * N * 4)        # scales + f32 out


def _i8_meta(name: str, shape, tiles: str) -> dict:
    """Profile meta of one INT8 matmul call: the problem, the backend and,
    for a Pallas kernel, where its tiles came from
    (``dispatch.tuned_lookup``)."""
    meta = {"shape": list(shape), "backend": name}
    if name != "ref":
        meta["tiles"] = tiles
    return meta


def int8_matmul(x, qt: QTensor, *, backend=None, interpret=None):
    """``x (..., K) @ dequant(qt (K, N))`` — QTensor must be symmetric INT8.

    Serving-path matmul: the weight stays INT8 in HBM (1 byte/elem + f32
    block scales); the dequant scale fuses into the matmul in VMEM.
    """
    assert qt.bits == 8 and qt.zero is None
    name, fn = _pick("int8_matmul", backend, interpret)
    lead = x.shape[:-1]
    K = x.shape[-1]
    xf = x.reshape(-1, K)
    N = qt.q.shape[-1]
    blocks, tiles = dispatch.tuned_lookup("int8_matmul", (xf.shape[0], K),
                                          str(x.dtype), backend=name)
    profile.record("int8_matmul",
                   nbytes=_i8_bytes(xf.shape[0], K, N, qt.block,
                                    x.dtype.itemsize),
                   flops=2 * xf.shape[0] * K * N,
                   meta=_i8_meta(name, (xf.shape[0], K, N), tiles))
    # pick the row tile from the TRUE M, then pad M up to it (a 1-row
    # decode matmul pads to one 8-row tile, not a 128-row boundary)
    blocks["bm"] = dispatch.pick_tile(xf.shape[0], blocks["bm"])
    xf, M = _pad_to(xf, 0, blocks["bm"])
    blocks["bn"] = dispatch.fit_block(N, blocks["bn"], qt.block)
    blocks["bk"] = dispatch.fit_block(K, blocks["bk"], 128)
    out = fn(xf, qt.q, qt.scale, block=qt.block, **blocks)
    return out[:M, : qt.orig_last].reshape(*lead, qt.orig_last)


# ---------------------------------------------------------------------------
# quantized_dense: the model-facing INT8 matmul with a custom VJP
# ---------------------------------------------------------------------------
#
# Forward:  out = x @ deq(W)        — int8_matmul streams INT8 blocks.
# Backward: dL/dx = g @ deq(W)^T    — int8_matmul_t streams the SAME blocks
#                                     (no transposed weight copy);
#           dL/dW = x^T @ g         — never touches W at all, emitted into
#                                     the QVirtual shadow cotangent (f32,
#                                     cropped to the virtual shape).
# The INT8 codes/scales get zero (float0) cotangents: the weight is data,
# not a differentiable primal — its virtual gradient lives on the shadow.
# Both custom-vjp cores work in the PADDED N space; leading-dim reshapes,
# row padding, and the orig_last crop happen outside (plain differentiable
# jnp ops), so one core serves every model call site.

def _deq_cropped(q, scale, block: int, orig: int):
    """Inline dequant cropped to the virtual column count (ref backend:
    skip the padded-N FLOPs entirely; XLA fuses this into the dot)."""
    K, Np = q.shape
    w = (q.astype(jnp.float32).reshape(K, Np // block, block)
         * scale[..., None]).reshape(K, Np)
    return w[:, :orig] if orig != Np else w


def _i8_call(name: str, x2, q, scale, block: int, orig: int):
    """x2 (M, K) @ deq(q (K, Np))[:, :orig] → (M, orig)."""
    shape = (x2.shape[0], x2.shape[1], q.shape[1])
    blocks, tiles = dispatch.tuned_lookup("int8_matmul", x2.shape,
                                          str(x2.dtype), backend=name)
    profile.record("quantized_dense/fwd",
                   nbytes=_i8_bytes(*shape, block, x2.dtype.itemsize),
                   flops=2 * shape[0] * shape[1] * shape[2],
                   meta=_i8_meta(name, shape, tiles))
    if name == "ref":
        return x2.astype(jnp.float32) @ _deq_cropped(q, scale, block, orig)
    fn = dispatch.resolve("int8_matmul", name)[1]
    # row tile from the TRUE M, then pad M up to the tile (tail-block fix)
    blocks["bm"] = dispatch.pick_tile(x2.shape[0], blocks["bm"])
    x2, M = _pad_to(x2, 0, blocks["bm"])
    blocks["bn"] = dispatch.fit_block(q.shape[1], blocks["bn"], block)
    blocks["bk"] = dispatch.fit_block(x2.shape[1], blocks["bk"], 128)
    return fn(x2, q, scale, block=block, **blocks)[:M, :orig]


def _i8t_call(name: str, g2, q, scale, block: int):
    """g2 (M, orig) @ deq(q (K, Np))[:, :orig]^T → (M, K)."""
    shape = (g2.shape[0], q.shape[0], q.shape[1])
    blocks, tiles = dispatch.tuned_lookup("int8_matmul_t", shape[:2],
                                          str(g2.dtype), backend=name)
    profile.record("quantized_dense/dx",
                   nbytes=_i8_bytes(*shape, block, g2.dtype.itemsize),
                   flops=2 * shape[0] * shape[1] * shape[2],
                   meta=_i8_meta(name, shape, tiles))
    if name == "ref":
        w = _deq_cropped(q, scale, block, g2.shape[-1])
        return g2.astype(jnp.float32) @ w.T
    fn = dispatch.resolve("int8_matmul_t", name)[1]
    pad = q.shape[1] - g2.shape[-1]
    if pad:  # padded weight columns dequantize to zero — zero-pad g to match
        g2 = jnp.pad(g2, ((0, 0), (0, pad)))
    blocks["bm"] = dispatch.pick_tile(g2.shape[0], blocks["bm"])
    g2, M = _pad_to(g2, 0, blocks["bm"])
    blocks["bn"] = dispatch.fit_block(q.shape[1], blocks["bn"], block)
    blocks["bk"] = dispatch.fit_block(q.shape[0], blocks["bk"], 128)
    return fn(g2, q, scale, block=block, **blocks)[:M]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _qdense_core(name: str, block: int, orig: int, x2, shadow, q, scale):
    return _i8_call(name, x2, q, scale, block, orig)


def _qdense_core_fwd(name, block, orig, x2, shadow, q, scale):
    return _i8_call(name, x2, q, scale, block, orig), (x2, shadow, q, scale)


def _qdense_core_bwd(name, block, orig, res, g):
    x2, shadow, q, scale = res
    dx = _i8t_call(name, g, q, scale, block).astype(x2.dtype)
    with profile.scope("repro.wgrad"):
        dw = jnp.einsum("mk,mn->kn", x2.astype(jnp.float32),
                        g.astype(jnp.float32)).astype(shadow.dtype)
    return dx, dw, quant._zero_cotangent(q), quant._zero_cotangent(scale)


_qdense_core.defvjp(_qdense_core_fwd, _qdense_core_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _qdense_t_core(name: str, block: int, orig: int, x2, shadow, q, scale):
    return _i8t_call(name, x2, q, scale, block)


def _qdense_t_core_fwd(name, block, orig, x2, shadow, q, scale):
    return _i8t_call(name, x2, q, scale, block), (x2, shadow, q, scale)


def _qdense_t_core_bwd(name, block, orig, res, g):
    x2, shadow, q, scale = res
    dx = _i8_call(name, g, q, scale, block, orig).astype(x2.dtype)
    with profile.scope("repro.wgrad"):
        dw = jnp.einsum("mv,md->vd", g.astype(jnp.float32),
                        x2.astype(jnp.float32)).astype(shadow.dtype)
    return dx, dw, quant._zero_cotangent(q), quant._zero_cotangent(scale)


_qdense_t_core.defvjp(_qdense_t_core_fwd, _qdense_t_core_bwd)


# -- no-shadow cores: the plain-QTensor (serving / frozen-weight) fast path --
#
# A plain QTensor weight has no gradient consumer — there is no QVirtual
# shadow leaf for dL/dW to land on — yet the shadow-carrying cores above
# still thread a FULL-SIZE zeros operand through every call and save it as
# a residual. That zeros stream is a real (~2%) overhead on the serving
# graphs that dominate prefill/decode. These cores drop the shadow and the
# dL/dW einsum; the forward math is the byte-identical _i8_call/_i8t_call,
# and dL/dx still streams the stored INT8 blocks through the transposed
# kernel, so x-side autodiff (e.g. activations feeding a trainable head)
# keeps working.

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _qdense_core_ns(name: str, block: int, orig: int, x2, q, scale):
    return _i8_call(name, x2, q, scale, block, orig)


def _qdense_core_ns_fwd(name, block, orig, x2, q, scale):
    return _i8_call(name, x2, q, scale, block, orig), (x2, q, scale)


def _qdense_core_ns_bwd(name, block, orig, res, g):
    x2, q, scale = res
    dx = _i8t_call(name, g, q, scale, block).astype(x2.dtype)
    return dx, quant._zero_cotangent(q), quant._zero_cotangent(scale)


_qdense_core_ns.defvjp(_qdense_core_ns_fwd, _qdense_core_ns_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _qdense_t_core_ns(name: str, block: int, orig: int, x2, q, scale):
    return _i8t_call(name, x2, q, scale, block)


def _qdense_t_core_ns_fwd(name, block, orig, x2, q, scale):
    return _i8t_call(name, x2, q, scale, block), (x2, q, scale)


def _qdense_t_core_ns_bwd(name, block, orig, res, g):
    x2, q, scale = res
    dx = _i8_call(name, g, q, scale, block, orig).astype(x2.dtype)
    return dx, quant._zero_cotangent(q), quant._zero_cotangent(scale)


_qdense_t_core_ns.defvjp(_qdense_t_core_ns_fwd, _qdense_t_core_ns_bwd)


def quantized_dense(x, w, *, dtype=jnp.bfloat16, backend=None,
                    interpret=None):
    """``x (..., K) @ deq(W (K, N))`` with W consumed in INT8 end-to-end.

    ``w`` is a symmetric INT8 QTensor (serving: no weight gradient) or a
    QVirtual (training: ``dL/dW`` flows to the shadow). Handles M/K not
    multiples of the tile (tile clamping + row padding) and N not a
    multiple of the quant block (the QTensor's own column padding).
    """
    qt = w.qt if isinstance(w, QVirtual) else w
    assert qt.bits == 8 and qt.zero is None, "need symmetric INT8 weight"
    assert qt.ndim == 2, qt.shape
    name, _ = _pick("quantized_dense", backend, interpret)
    lead, K = x.shape[:-1], x.shape[-1]
    if isinstance(w, QVirtual):
        out = _qdense_core(name, qt.block, qt.orig_last, x.reshape(-1, K),
                           w.shadow, qt.q, qt.scale)
    else:
        assert isinstance(w, QTensor), type(w)
        out = _qdense_core_ns(name, qt.block, qt.orig_last,
                              x.reshape(-1, K), qt.q, qt.scale)
    return out.reshape(*lead, qt.orig_last).astype(dtype)


def quantized_dense_t(x, w, *, dtype=jnp.bfloat16, backend=None,
                      interpret=None):
    """``x (..., D) @ deq(W (V, D))^T`` — the tied-embedding head matmul,
    streaming the same INT8 blocks (forward uses the transposed kernel,
    its backward dL/dx uses the plain one)."""
    qt = w.qt if isinstance(w, QVirtual) else w
    assert qt.bits == 8 and qt.zero is None, "need symmetric INT8 weight"
    assert qt.ndim == 2, qt.shape
    assert x.shape[-1] == qt.orig_last, (x.shape, qt.shape)
    name, _ = _pick("quantized_dense", backend, interpret)
    lead, D = x.shape[:-1], x.shape[-1]
    if isinstance(w, QVirtual):
        out = _qdense_t_core(name, qt.block, qt.orig_last,
                             x.reshape(-1, D), w.shadow, qt.q, qt.scale)
    else:
        assert isinstance(w, QTensor), type(w)
        out = _qdense_t_core_ns(name, qt.block, qt.orig_last,
                                x.reshape(-1, D), qt.q, qt.scale)
    return out.reshape(*lead, qt.q.shape[0]).astype(dtype)


def quantized_dense_batched(x, w, *, dtype=jnp.bfloat16, backend=None,
                            interpret=None):
    """Paired-leading-axis matmul ``x (E, ..., K) @ deq(W (E, K, N))`` →
    ``(E, ..., N)`` (MoE expert stacks). vmaps the 2-D op over the expert
    axis — experts stay batched into one XLA/Pallas program (no serialized
    scan), the per-expert custom VJP applies, and the batched shadow
    cotangent reassembles ``dL/dW (E, K, N)`` automatically."""
    qt = w.qt if isinstance(w, QVirtual) else w
    assert qt.bits == 8 and qt.zero is None, "need symmetric INT8 weight"
    assert qt.ndim == 3, qt.shape

    if isinstance(w, QVirtual):
        def one(xe, qe, se, she):
            we = QVirtual(QTensor(qe, se, None, qt.bits, qt.block,
                                  qt.orig_last, qt.dtype), she)
            return quantized_dense(xe, we, dtype=dtype, backend=backend,
                                   interpret=interpret)

        return jax.vmap(one)(x, qt.q, qt.scale, w.shadow)

    def one(xe, qe, se):
        we = QTensor(qe, se, None, qt.bits, qt.block, qt.orig_last,
                     qt.dtype)
        return quantized_dense(xe, we, dtype=dtype, backend=backend,
                               interpret=interpret)

    return jax.vmap(one)(x, qt.q, qt.scale)


for _b in dispatch.KNOWN_BACKENDS:
    dispatch.register("quantized_dense", _b)(
        functools.partial(quantized_dense, backend=_b))


def int4_project(g, qt: QTensor, *, backend=None, interpret=None):
    """GaLore projection ``g (..., K) @ dequant_int4(qt (K, R))``.

    The INT4 projection matrix is unpacked from nibbles in VMEM — P costs
    ``K*R/2`` bytes of HBM plus scales, never more (paper §3.3).
    """
    assert qt.bits == 4 and qt.zero is not None
    name, fn = _pick("int4_matmul", backend, interpret)
    lead = g.shape[:-1]
    K = g.shape[-1]
    gf = g.reshape(-1, K)
    blocks = dispatch.tuned_blocks("int4_matmul", (gf.shape[0], K),
                                   str(g.dtype), backend=name)
    profile.record("int4_project",
                   nbytes=(gf.shape[0] * K * g.dtype.itemsize
                           + qt.q.size + qt.scale.size * 8
                           + gf.shape[0] * qt.orig_last * 4),
                   flops=2 * gf.shape[0] * K * qt.orig_last,
                   meta={"shape": [gf.shape[0], K, qt.orig_last],
                         "backend": name})
    blocks["bm"] = dispatch.pick_tile(gf.shape[0], blocks["bm"])
    gf, M = _pad_to(gf, 0, blocks["bm"])
    blocks["bk"] = dispatch.fit_block(K, blocks["bk"])
    out = fn(gf, qt.q, qt.scale, qt.zero, block=qt.block, **blocks)
    return out[:M, : qt.orig_last].reshape(*lead, qt.orig_last)


def sr_requant_update(qt: QTensor, update, key, *, backend=None,
                      interpret=None):
    """Unfused SR weight update ``W' = SR_quant(deq(W) + update)``.

    Kept as the standalone building block (and fused-kernel baseline); the
    training hot path uses :func:`fused_qgalore_update` instead.
    """
    assert qt.bits == 8 and qt.zero is None
    name, fn = _pick("sr_requant", backend, interpret)
    R = int(np.prod(qt.q.shape[:-1])) if qt.q.ndim > 1 else 1
    q2 = qt.q.reshape(R, qt.q.shape[-1])
    s2 = qt.scale.reshape(R, qt.scale.shape[-1])
    upd = update.reshape(R, -1)
    pad = q2.shape[-1] - upd.shape[-1]
    if pad:
        upd = jnp.pad(upd, ((0, 0), (0, pad)))
    with profile.scope("repro.sr_randoms"):
        u01 = jax.random.uniform(key, q2.shape, jnp.float32)
    blocks = dispatch.tuned_blocks("sr_requant", q2.shape, backend=name)
    blocks["br"] = dispatch.fit_block(q2.shape[0], blocks["br"])
    blocks["bc"] = dispatch.fit_block(q2.shape[1], blocks["bc"], qt.block)
    q_new, s_new = fn(q2, s2, upd, u01, block=qt.block, **blocks)
    return QTensor(q_new.reshape(qt.q.shape), s_new.reshape(qt.scale.shape),
                   None, qt.bits, qt.block, qt.orig_last, qt.dtype)


def quantize_int8(x, *, block: int = 256, backend=None,
                  interpret=None) -> QTensor:
    """Symmetric block-wise INT8 quantization of a 2-D tensor."""
    name, fn = _pick("blockwise_quant", backend, interpret)
    orig_last = x.shape[-1]
    x2 = x.reshape(-1, orig_last)
    x2, R = _pad_to(x2, 0, 1)
    x2, _ = _pad_to(x2, 1, block)
    blocks = dispatch.tuned_blocks("blockwise_quant", x2.shape,
                                   backend=name)
    blocks["br"] = dispatch.fit_block(x2.shape[0], blocks["br"])
    blocks["bc"] = dispatch.fit_block(x2.shape[1], blocks["bc"], block)
    q, s = fn(x2, block=block, **blocks)
    q = q.reshape(*x.shape[:-1], x2.shape[-1])
    s = s.reshape(*x.shape[:-1], x2.shape[-1] // block)
    return QTensor(q, s, None, 8, block, orig_last, str(x.dtype))


def flash_attention(q, k, v, *, causal: bool = True, backend=None,
                    interpret=None):
    """Causal flash attention (B,S,H,d); GQA folded by the caller."""
    name, fn = _pick("flash_attention", backend, interpret)
    blocks = dispatch.tuned_blocks("flash_attention",
                                   (q.shape[1], k.shape[1]), backend=name)
    blocks["bq"] = dispatch.fit_block(q.shape[1], blocks["bq"])
    blocks["bkv"] = dispatch.fit_block(k.shape[1], blocks["bkv"])
    return fn(q, k, v, causal=causal, **blocks)


# ---------------------------------------------------------------------------
# Fused Q-GaLore update
# ---------------------------------------------------------------------------

def fused_qgalore_update(
    param: QTensor,
    low_g: jax.Array,
    m32: jax.Array,
    v32: jax.Array,
    proj: QTensor,
    count,
    lr,
    key,
    *,
    side: str,
    gscale: float,
    beta1: float = 0.9,
    beta2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    backend: Optional[str] = None,
    interpret: Optional[bool] = None,
) -> Tuple[QTensor, jax.Array, jax.Array]:
    """One fused Q-GaLore step for a single 2-D weight.

    Fuses low-rank Adam → INT4 back-projection → SR INT8 requant; the
    full-rank f32 update and dequantized weight exist only inside the
    kernel (VMEM), never in HBM — the uniform SR randoms are the one
    remaining full-rank input stream (generated outside for
    interpret-mode parity). Given the same ``key`` it draws the same SR
    randoms as the
    unfused composition ``adam8bit.update`` →
    ``projector.project_back`` → ``quant.requantize_sr`` and matches it
    to within one INT8 quantum (matmul reassociation can flip a value
    sitting exactly on a floor boundary).

    Args:
      param: symmetric INT8 QTensor, virtual shape ``(m, n)``.
      low_g: low-rank gradient, ``(m, r)`` for ``side="right"`` /
        ``(r, n)`` for ``side="left"``.
      m32, v32: f32 Adam moments, same shape as ``low_g`` (callers with
        8-bit moment state dequantize before / requantize after — the
        moments are low-rank, so this traffic is ``r / min(m, n)`` of the
        weight traffic).
      proj: INT4 QTensor projection ``(d, r)``, ``d = min(m, n)``.
      count: 1-based step (f32/int scalar) for bias correction.
      key: PRNG key for the stochastic rounding.
      gscale: GaLore alpha (applied to the back-projected update).

    Returns:
      ``(new_param QTensor, m', v')`` with moments still f32.
    """
    assert param.bits == 8 and param.zero is None, "need symmetric INT8 W"
    assert proj.bits == 4 and proj.zero is not None, "need INT4 projection"
    name, fn = _pick("fused_qgalore_update", backend, interpret)

    q2, ws = param.q, param.scale                 # (m, n_pad), (m, nb)
    mrows, n_pad = q2.shape
    wb = param.block
    pb = proj.block
    r_pad = proj.q.shape[-1] * 2                  # padded rank (nibbles)
    r = low_g.shape[-1] if side == "right" else low_g.shape[-2]

    # pad the rank axis of the low-rank triple up to the packed rank
    ax = -1 if side == "right" else -2
    low, _ = _pad_to(low_g.astype(jnp.float32), ax, r_pad)
    m32p, _ = _pad_to(m32, ax, r_pad)
    v32p, _ = _pad_to(v32, ax, r_pad)

    # SR randoms over the REAL (row, padded-col) region — matches the
    # draw quant.requantize_sr makes, so fused == unfused bit-for-bit.
    with profile.scope("repro.sr_randoms"):
        u01 = jax.random.uniform(key, q2.shape, jnp.float32)

    blocks = dispatch.tuned_blocks("fused_qgalore_update",
                                   (mrows, n_pad), backend=name)
    profile.record("fused_qgalore_update",
                   # one read of (low-rank triple, P, W codes+scales, SR
                   # randoms) + one write of (W', scales', m', v')
                   nbytes=(3 * low.size * 4 + proj.q.size
                           + 2 * proj.scale.size * 4
                           + 2 * (q2.size + ws.size * 4)
                           + u01.size * 4 + 2 * low.size * 4),
                   flops=4 * mrows * n_pad * r_pad,
                   meta={"shape": [mrows, n_pad], "rank": r_pad,
                         "backend": name})
    static = dict(side=side, pblock=pb, wblock=wb, beta1=beta1,
                  beta2=beta2, eps=eps, gscale=gscale, wd=weight_decay)

    if side == "right":
        # P rows span n (orig) — pad to the weight's padded n with zero
        # scales so the padded update columns are exactly zero.
        pq, _ = _pad_to(proj.q, 0, n_pad)
        pq = pq[:n_pad]
        ps, _ = _pad_to(proj.scale, 0, n_pad)
        ps = ps[:n_pad]
        pz, _ = _pad_to(proj.zero, 0, n_pad)
        pz = pz[:n_pad]
        # tile rows: pad to the row-block (a no-op cost for ref, which
        # drops the tile kwargs and just crops)
        bm = min(blocks.get("bm", 256), mrows)
        lowp, _ = _pad_to(low, 0, bm)
        mp, _ = _pad_to(m32p, 0, bm)
        vp, _ = _pad_to(v32p, 0, bm)
        q2p, _ = _pad_to(q2, 0, bm)
        wsp, _ = _pad_to(ws, 0, bm)
        u01p, _ = _pad_to(u01, 0, bm, value=0.5)
        qn, sn, mn, vn = fn(lowp, mp, vp, pq, ps, pz, q2p, wsp, u01p,
                            count, lr, bm=bm, **static)
        qn, sn = qn[:mrows], sn[:mrows]
        mn, vn = mn[:mrows], vn[:mrows]
    else:
        # left: P rows span m == weight rows; tile columns.
        lowc, _ = _pad_to(low, 1, n_pad)
        lowc = lowc[:, :n_pad]
        mc, _ = _pad_to(m32p, 1, n_pad)
        mc = mc[:, :n_pad]
        vc, _ = _pad_to(v32p, 1, n_pad)
        vc = vc[:, :n_pad]
        bn = max(wb, (min(blocks.get("bn", 512), n_pad) // wb) * wb)
        lowp, _ = _pad_to(lowc, 1, bn)
        mp, _ = _pad_to(mc, 1, bn)
        vp, _ = _pad_to(vc, 1, bn)
        q2p, _ = _pad_to(q2, 1, bn)
        wsp, _ = _pad_to(ws, 1, bn // wb)
        u01p, _ = _pad_to(u01, 1, bn, value=0.5)
        # bm is the lane dim of the (rh, bm) P^T / scale^T / zero^T blocks
        bm = dispatch.fit_block(mrows, blocks.get("bm", 256), 128)
        qn, sn, mn, vn = fn(lowp, mp, vp, proj.q, proj.scale, proj.zero,
                            q2p, wsp, u01p, count, lr, bm=bm, bn=bn,
                            **static)
        qn, sn = qn[:, :n_pad], sn[:, : n_pad // wb]
        mn, vn = mn[:, :n_pad], vn[:, :n_pad]

    # crop moments back to the true low-rank shape
    if side == "right":
        mn, vn = mn[:, :r], vn[:, :r]
        mn = mn[: low_g.shape[0]]
        vn = vn[: low_g.shape[0]]
    else:
        mn, vn = mn[:r], vn[:r]
        mn = mn[:, : low_g.shape[1]]
        vn = vn[:, : low_g.shape[1]]

    new_param = QTensor(qn, sn, None, param.bits, wb, param.orig_last,
                        param.dtype)
    return new_param, mn, vn
