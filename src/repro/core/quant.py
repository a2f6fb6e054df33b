"""Block-wise uniform quantization substrate (paper §3.1, §3.4).

Implements the paper's quantizer::

    W_q = clamp(round(W / s) + z, -2^{n-1}, 2^{n-1} - 1)

with per-block scale ``s`` and zero-point ``z`` computed over blocks of 256
elements along the last axis (lane dimension — this vectorizes on the TPU VPU
and lets Pallas kernels broadcast scales from SMEM).

``QTensor`` is a registered pytree so quantized weights flow through jit /
pjit / grad transparently. INT4 values are nibble-packed two-per-uint8.

Stochastic rounding (paper §3.4)::

    SR(x) = floor(x) + Bernoulli(x - floor(x))

is implemented as ``floor(x + u)``, ``u ~ U[0,1)`` which is the same
distribution and fuses into a single VPU pass.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import profile

DEFAULT_BLOCK = 256
_EPS = 1e-12


def _qrange(bits: int) -> Tuple[int, int]:
    return -(2 ** (bits - 1)), 2 ** (bits - 1) - 1


def auto_block(last_dim: int, block: int = DEFAULT_BLOCK) -> int:
    """Largest sensible block ≤ last_dim (avoids 2× padding waste when
    quantizing tensors whose last dim is smaller than the block, e.g. the
    rank-128 low-rank Adam moments)."""
    if last_dim >= block:
        return block
    b = 2
    while b * 2 <= last_dim:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# QTensor pytree
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclass
class QTensor:
    """A block-wise quantized tensor.

    ``q``      int8 codes (bits==8) or uint8 nibble-packed codes (bits==4),
               shape (..., padded_last) or (..., padded_last // 2) if packed.
    ``scale``  float32 per-block scales, shape (..., padded_last // block).
    ``zero``   float32 per-block zero points (None when symmetric).
    """
    q: jax.Array
    scale: jax.Array
    zero: Optional[jax.Array]
    bits: int
    block: int
    orig_last: int          # unpadded size of the last axis
    dtype: str              # dequantization dtype, e.g. "bfloat16"

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        return (self.q, self.scale, self.zero), (
            self.bits, self.block, self.orig_last, self.dtype)

    @classmethod
    def tree_unflatten(cls, aux, children):
        q, scale, zero = children
        bits, block, orig_last, dtype = aux
        return cls(q, scale, zero, bits, block, orig_last, dtype)

    # -- convenience --------------------------------------------------------
    @property
    def shape(self) -> Tuple[int, ...]:
        lead = self.q.shape[:-1]
        return tuple(lead) + (self.orig_last,)

    @property
    def ndim(self) -> int:
        return self.q.ndim

    @property
    def symmetric(self) -> bool:
        return self.zero is None

    def dequantize(self, dtype=None) -> jax.Array:
        return dequantize(self, dtype)

    def nbytes(self) -> int:
        n = int(np.prod(self.q.shape)) * self.q.dtype.itemsize
        n += int(np.prod(self.scale.shape)) * self.scale.dtype.itemsize
        if self.zero is not None:
            n += int(np.prod(self.zero.shape)) * self.zero.dtype.itemsize
        return n


def is_qtensor(x) -> bool:
    return isinstance(x, QTensor)


# ---------------------------------------------------------------------------
# QVirtual: the training-path view of a quantized weight
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclass
class QVirtual:
    """A quantized weight paired with a gradient slot for its virtual value.

    The INT8 representation stays the compute format — ``repro.kernels.ops.
    quantized_dense`` streams ``qt``'s blocks directly — while ``shadow``
    (a zeros array of the virtual, dequantized shape) is the float primal
    that ``jax.vjp`` differentiates. The custom VJPs route ``dL/dW`` into
    the shadow's cotangent, so gradients keep the repo-wide "one virtual
    full-rank leaf per QTensor" contract without the forward ever
    materializing ``W`` (the shadow itself is never read and is dead-code
    eliminated by XLA).
    """
    qt: QTensor
    shadow: jax.Array

    def tree_flatten(self):
        return (self.qt, self.shadow), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def shape(self):
        return self.qt.shape

    @property
    def ndim(self):
        return self.qt.ndim


def is_qvirtual(x) -> bool:
    return isinstance(x, QVirtual)


def virtualize(qt: QTensor) -> QVirtual:
    """Pair a QTensor with a zeros gradient slot of its virtual shape."""
    return QVirtual(qt, jnp.zeros(qt.shape, jnp.dtype(qt.dtype)))


def tree_virtualize(tree):
    """QTensor leaves → QVirtual (the differentiable training view)."""
    return jax.tree_util.tree_map(
        lambda l: virtualize(l) if is_qtensor(l) else l,
        tree, is_leaf=is_qtensor)


def tree_devirtualize_grads(tree):
    """Collapse QVirtual-structured cotangents to the shadow (= dL/dW)
    leaf, restoring the plain "one array per QTensor" gradient tree. Also
    drops the float0 cotangents of the integer code arrays, which must not
    escape scan bodies."""
    return jax.tree_util.tree_map(
        lambda l: l.shadow if is_qvirtual(l) else l,
        tree, is_leaf=is_qvirtual)


def _zero_cotangent(x: jax.Array):
    if jnp.issubdtype(x.dtype, jnp.floating):
        return jnp.zeros_like(x)
    return np.zeros(x.shape, jax.dtypes.float0)


def zero_qtensor_cotangent(qt: QTensor):
    """Cotangent for a QTensor primal: float0 for codes, zeros for scales."""
    return QTensor(_zero_cotangent(qt.q), _zero_cotangent(qt.scale),
                   None if qt.zero is None else _zero_cotangent(qt.zero),
                   qt.bits, qt.block, qt.orig_last, qt.dtype)


@jax.custom_vjp
def virtual_dequantize(shadow: jax.Array, qt: QTensor) -> jax.Array:
    """``dequantize(qt)`` whose gradient flows to ``shadow``.

    Fallback for QVirtual consumers that genuinely need the materialized
    weight (embedding gathers, MLA's absorbed decode matmul, expert
    oracles); matmuls should use ``ops.quantized_dense`` instead, which
    never materializes.
    """
    return dequantize(qt, shadow.dtype)


def _vdeq_fwd(shadow, qt):
    return virtual_dequantize(shadow, qt), (shadow, qt)


def _vdeq_bwd(res, g):
    shadow, qt = res
    return g.astype(shadow.dtype), zero_qtensor_cotangent(qt)


virtual_dequantize.defvjp(_vdeq_fwd, _vdeq_bwd)


def gather_rows(qt: QTensor, idx: jax.Array) -> QTensor:
    """Row-gather of a 2-D QTensor (e.g. embedding rows for a token batch)
    without dequantizing the full table: codes and scales are gathered,
    the result dequantizes to ``(*idx.shape, orig_last)``."""
    assert qt.ndim == 2, qt.shape
    return QTensor(jnp.take(qt.q, idx, axis=0),
                   jnp.take(qt.scale, idx, axis=0),
                   None if qt.zero is None else jnp.take(qt.zero, idx,
                                                         axis=0),
                   qt.bits, qt.block, qt.orig_last, qt.dtype)


# ---------------------------------------------------------------------------
# Packing helpers (INT4)
# ---------------------------------------------------------------------------

def pack_int4(u: jax.Array) -> jax.Array:
    """Pack unsigned nibbles (values 0..15, uint8) pairs into uint8.

    Last axis must be even; out last axis is halved.
    """
    lo = u[..., 0::2]
    hi = u[..., 1::2]
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_int4(p: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_int4` — interleaves nibbles back."""
    lo = p & 0xF
    hi = (p >> 4) & 0xF
    out = jnp.stack([lo, hi], axis=-1)
    return out.reshape(*p.shape[:-1], p.shape[-1] * 2)


# ---------------------------------------------------------------------------
# Quantize / dequantize
# ---------------------------------------------------------------------------

def _pad_last(x: jax.Array, block: int) -> jax.Array:
    last = x.shape[-1]
    pad = (-last) % block
    if pad:
        widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
        x = jnp.pad(x, widths)
    return x


def _block_view(x: jax.Array, block: int) -> jax.Array:
    return x.reshape(*x.shape[:-1], x.shape[-1] // block, block)


def quantize_blockwise(
    x: jax.Array,
    bits: int = 8,
    block: int = DEFAULT_BLOCK,
    symmetric: bool = False,
    stochastic_key: Optional[jax.Array] = None,
) -> QTensor:
    """Block-wise uniform quantization along the last axis.

    With ``stochastic_key`` the rounding is stochastic (unbiased); otherwise
    round-to-nearest. Scales/zeros are float32.
    """
    assert bits in (2, 4, 8), bits
    orig_last = x.shape[-1]
    dtype = str(x.dtype)
    xf = _pad_last(x.astype(jnp.float32), block)
    xb = _block_view(xf, block)
    qmin, qmax = _qrange(bits)

    if symmetric:
        absmax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
        scale = jnp.maximum(absmax / qmax, _EPS)
        zero = None
        t = xb / scale
    else:
        mx = jnp.max(xb, axis=-1, keepdims=True)
        mn = jnp.min(xb, axis=-1, keepdims=True)
        scale = jnp.maximum((mx - mn) / (qmax - qmin), _EPS)
        zero = qmin - mn / scale           # float zero-point
        t = xb / scale + zero

    if stochastic_key is not None:
        with profile.scope("repro.sr_randoms"):
            u = jax.random.uniform(stochastic_key, t.shape,
                                   dtype=jnp.float32)
        codes = jnp.floor(t + u)
    else:
        codes = jnp.round(t)
    codes = jnp.clip(codes, qmin, qmax)

    flat_codes = codes.reshape(*xf.shape)
    scale_out = scale[..., 0]
    zero_out = None if zero is None else zero[..., 0]

    if bits == 8:
        q = flat_codes.astype(jnp.int8)
    else:
        u8 = (flat_codes - qmin).astype(jnp.uint8)   # 0 .. 2^bits-1
        q = pack_int4(u8) if bits == 4 else u8
    return QTensor(q, scale_out, zero_out, bits, block, orig_last, dtype)


def dequantize(qt: QTensor, dtype=None) -> jax.Array:
    """Inverse transform; returns (q - z) * s cropped to the original shape."""
    out_dtype = dtype or jnp.dtype(qt.dtype)
    qmin, _ = _qrange(qt.bits)
    if qt.bits == 8:
        codes = qt.q.astype(jnp.float32)
    elif qt.bits == 4:
        codes = unpack_int4(qt.q).astype(jnp.float32) + qmin
    else:
        codes = qt.q.astype(jnp.float32) + qmin
    cb = _block_view(codes, qt.block)
    if qt.zero is None:
        xb = cb * qt.scale[..., None]
    else:
        xb = (cb - qt.zero[..., None]) * qt.scale[..., None]
    x = xb.reshape(*codes.shape)
    if x.shape[-1] != qt.orig_last:
        x = x[..., : qt.orig_last]
    return x.astype(out_dtype)


def requantize_sr(
    qt: QTensor, update: jax.Array, key: jax.Array,
    symmetric: Optional[bool] = None,
) -> QTensor:
    """The Q-GaLore weight update: W' = SR_quant(dequant(W) + update).

    Recomputes per-block scales from the updated values (the weight
    distribution drifts over training) and requantizes with stochastic
    rounding so sub-quantum gradient contributions survive in expectation.
    """
    w = dequantize(qt, jnp.float32) + update.astype(jnp.float32)
    sym = qt.symmetric if symmetric is None else symmetric
    return quantize_blockwise(
        w, bits=qt.bits, block=qt.block, symmetric=sym, stochastic_key=key)


# ---------------------------------------------------------------------------
# Plain stochastic rounding (used for bf16 casts and tests)
# ---------------------------------------------------------------------------

def stochastic_round(x: jax.Array, key: jax.Array) -> jax.Array:
    """SR to integers: floor(x + u)."""
    with profile.scope("repro.sr_randoms"):
        u = jax.random.uniform(key, x.shape, dtype=jnp.float32)
    return jnp.floor(x.astype(jnp.float32) + u)


# ---------------------------------------------------------------------------
# Pytree helpers
# ---------------------------------------------------------------------------

def tree_quantize(tree, bits=8, block=DEFAULT_BLOCK, symmetric=True,
                  predicate=None):
    """Quantize every array leaf for which ``predicate(path, leaf)`` holds."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    treedef = jax.tree_util.tree_structure(tree)
    leaves = []
    for path, leaf in flat:
        if predicate is None or predicate(path, leaf):
            leaves.append(quantize_blockwise(leaf, bits, block, symmetric))
        else:
            leaves.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def tree_dequantize(tree, dtype=None):
    return jax.tree_util.tree_map(
        lambda l: dequantize(l, dtype) if is_qtensor(l) else l,
        tree, is_leaf=is_qtensor)


def quantized_nbytes(tree) -> int:
    """Total bytes of a (possibly mixed) params tree."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree, is_leaf=is_qtensor):
        if is_qtensor(leaf):
            total += leaf.nbytes()
        else:
            total += int(np.prod(leaf.shape)) * leaf.dtype.itemsize
    return total
