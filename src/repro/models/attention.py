"""Attention blocks: GQA (with qk-norm, RoPE, GeGLU head dims) and
Multi-head Latent Attention (DeepSeek-V3), with train / prefill / decode
variants and memory-bounded chunked (flash-style) computation.

Chunked attention scans over query chunks with an online-softmax over KV
chunks, keeping the transient score tensor at ``chunk_q × chunk_kv`` — this
is what makes 32k-token prefill lowerable within VMEM/HBM budgets (XLA does
not rewrite naive attention into flash form by itself).
"""
from __future__ import annotations

import functools
import math
import os
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import MLAConfig, ModelConfig
from repro.kernels import profile
from repro.models import layers
from repro.models.layers import apply_rope, dense, dense_init, rmsnorm, \
    rmsnorm_init, rope_angles

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Core softmax-attention with chunking
# ---------------------------------------------------------------------------

def _attend_dense(q, k, v, *, causal: bool, q_offset, softcap: float = 0.0):
    """q (B,Sq,H,dh), k/v (B,Skv,KH,dh) — one dense block of scores.

    GQA: H must be a multiple of KH; kv heads are repeated via reshape.
    """
    B, Sq, H, dh = q.shape
    KH = k.shape[2]
    dv = v.shape[-1]            # may differ from dh (MLA)
    G = H // KH
    qf = q.astype(jnp.float32) / math.sqrt(dh)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qg = qf.reshape(B, Sq, KH, G, dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kf)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    if causal:
        Skv = k.shape[1]
        qpos = q_offset + jnp.arange(Sq)
        kpos = jnp.arange(Skv)
        mask = kpos[None, :] <= qpos[:, None]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - jax.lax.stop_gradient(m))
    z = jnp.sum(e, axis=-1, keepdims=True)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", e / z, vf)
    return o.reshape(B, Sq, H, dv)


def _flash_eligible(q, k, causal, q_offset, softcap) -> bool:
    """The dispatch-routed flash kernel covers the self-attention core
    only: causal, no soft-cap, queries aligned with keys (full sequence,
    no offset). Decode, cross-attention and ragged prefill keep the
    chunked path."""
    return (os.environ.get("REPRO_FLASH_ATTENTION", "") == "1"
            and causal and not softcap and q_offset == 0
            and q.shape[1] == k.shape[1] and q.shape[1] > 1
            and q.shape[2] % k.shape[2] == 0)


def chunked_attention(q, k, v, *, causal: bool = True, q_chunk: int = 1024,
                      q_offset: int = 0, softcap: float = 0.0) -> jax.Array:
    """Memory-bounded attention: scan over query chunks (scores stay
    (chunk, Skv)); falls back to a single dense block for short sequences.

    With ``REPRO_FLASH_ATTENTION=1`` eligible calls route through the
    kernel registry instead (``kernels.ops.flash_attention`` — Pallas
    flash kernel or its ref oracle per ``REPRO_KERNEL_BACKEND``), with kv
    heads repeated to fold GQA. Default OFF: the chunked path is the
    numerics the golden-trajectory fixtures pin."""
    B, Sq, H, dh = q.shape
    if _flash_eligible(q, k, causal, q_offset, softcap):
        from repro.kernels import ops
        G = H // k.shape[2]
        kf = jnp.repeat(k, G, axis=2) if G > 1 else k
        vf = jnp.repeat(v, G, axis=2) if G > 1 else v
        return ops.flash_attention(q, kf, vf, causal=True).astype(q.dtype)
    with profile.scope("repro.attention"):
        if Sq <= q_chunk:
            return _attend_dense(q, k, v, causal=causal, q_offset=q_offset,
                                 softcap=softcap).astype(q.dtype)
        pad = (-Sq) % q_chunk
        if pad:
            # ragged tail (e.g. VLM prefix + text): pad queries, crop
            # outputs — padded rows still see valid causal keys, results
            # are discarded.
            out = chunked_attention(
                jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))), k, v,
                causal=causal, q_chunk=q_chunk, q_offset=q_offset,
                softcap=softcap)
            return out[:, :Sq]
        nq = Sq // q_chunk
        qs = q.reshape(B, nq, q_chunk, H, dh)

        def body(carry, inp):
            qc, i = inp
            off = q_offset + i * q_chunk
            o = _attend_dense(qc, k, v, causal=causal, q_offset=off,
                              softcap=softcap)
            return carry, o

        _, outs = jax.lax.scan(
            body, 0, (qs.transpose(1, 0, 2, 3, 4), jnp.arange(nq)))
        dv = v.shape[-1]
        return outs.transpose(1, 0, 2, 3, 4).reshape(
            B, Sq, H, dv).astype(q.dtype)


def append_attention(q, k_cache, v_cache, q_positions) -> jax.Array:
    """Chunk-append attention: C queries at absolute positions
    ``q_positions`` (B, C) against a (B, Smax, KH, dh) cache that already
    holds every position ``<= q_positions`` (this chunk's K/V included).

    Mirrors :func:`_attend_dense` op-for-op (same einsum contraction, same
    max/exp/sum order) with the causal mask taken against absolute
    positions — masked keys contribute an exact 0 to the softmax sums, so
    appending a prompt in chunks is bit-identical to one dense prefill
    block over the unpadded prompt (asserted by ``tests/test_paged.py``).
    """
    B, C, H, dh = q.shape
    KH = k_cache.shape[2]
    dv = v_cache.shape[-1]
    G = H // KH
    qf = q.astype(jnp.float32) / math.sqrt(dh)
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    qg = qf.reshape(B, C, KH, G, dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kf)
    kpos = jnp.arange(k_cache.shape[1])
    mask = kpos[None, None] <= q_positions[:, :, None]        # (B, C, Skv)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - jax.lax.stop_gradient(m))
    z = jnp.sum(e, axis=-1, keepdims=True)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", e / z, vf)
    return o.reshape(B, C, H, dv).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, length) -> jax.Array:
    """Single-token decode: q (B,1,H,dh) against a (B,S,KH,dh) cache with
    ``length`` valid positions (per batch, int32 (B,))."""
    B, _, H, dh = q.shape
    KH = k_cache.shape[2]
    G = H // KH
    qf = q.astype(jnp.float32) / math.sqrt(dh)
    qg = qf.reshape(B, KH, G, dh)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache.astype(jnp.float32))
    pos = jnp.arange(k_cache.shape[1])
    mask = pos[None] < length[:, None]              # (B, S)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", w, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, H, v_cache.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention layer
# ---------------------------------------------------------------------------

def gqa_init(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    d, H, KH = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
    hd = cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": dense_init(k1, d, H * hd, dtype=dtype),
        "wk": dense_init(k2, d, KH * hd, dtype=dtype),
        "wv": dense_init(k3, d, KH * hd, dtype=dtype),
        "wo": dense_init(k4, H * hd, d, scale=1.0 / math.sqrt(H * hd),
                         dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd)
        p["k_norm"] = rmsnorm_init(hd)
    return p


def _qkv(p, x, cfg: ModelConfig, positions, dtype):
    B, S, _ = x.shape
    H, KH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = dense(x, p["wq"], dtype).reshape(B, S, H, hd)
    k = dense(x, p["wk"], dtype).reshape(B, S, KH, hd)
    v = dense(x, p["wv"], dtype).reshape(B, S, KH, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.rmsnorm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.rmsnorm_eps)
    sin, cos = rope_angles(positions, hd, cfg.rope_theta)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)
    return q, k, v


def gqa_apply(p: dict, x: jax.Array, cfg: ModelConfig, *,
              positions: jax.Array, causal: bool = True,
              q_chunk: int = 1024, dtype=jnp.bfloat16) -> jax.Array:
    """Full-sequence (train / encoder) attention."""
    q, k, v = _qkv(p, x, cfg, positions, dtype)
    o = chunked_attention(q, k, v, causal=causal, q_chunk=q_chunk)
    B, S = x.shape[:2]
    return dense(o.reshape(B, S, -1), p["wo"], dtype)


def gqa_prefill(p, x, cfg: ModelConfig, *, positions, q_chunk=1024,
                dtype=jnp.bfloat16):
    """Like gqa_apply but also returns the (k, v) cache."""
    q, k, v = _qkv(p, x, cfg, positions, dtype)
    o = chunked_attention(q, k, v, causal=True, q_chunk=q_chunk)
    B, S = x.shape[:2]
    return dense(o.reshape(B, S, -1), p["wo"], dtype), (k, v)


def gqa_decode(p, x, cfg: ModelConfig, *, cache: Tuple, length,
               dtype=jnp.bfloat16):
    """x (B,1,D); cache (k,v) each (B,Smax,KH,hd); length (B,) — writes the
    new token at ``length`` and attends over ``length+1`` positions."""
    k_cache, v_cache = cache
    B = x.shape[0]
    H, KH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = dense(x, p["wq"], dtype).reshape(B, 1, H, hd)
    k = dense(x, p["wk"], dtype).reshape(B, 1, KH, hd)
    v = dense(x, p["wv"], dtype).reshape(B, 1, KH, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.rmsnorm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.rmsnorm_eps)
    sin, cos = rope_angles(length[:, None].astype(jnp.float32), hd,
                           cfg.rope_theta)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)
    # scatter the new kv at position `length` (per batch row)
    oh = jax.nn.one_hot(length, k_cache.shape[1], dtype=k.dtype)  # (B,S)
    k_cache = k_cache * (1 - oh[..., None, None]) + oh[..., None, None] * k
    v_cache = v_cache * (1 - oh[..., None, None]) + oh[..., None, None] * v
    o = decode_attention(q, k_cache, v_cache, length + 1)
    return dense(o.reshape(B, 1, -1), p["wo"], dtype), (k_cache, v_cache)


def gqa_append(p, x, cfg: ModelConfig, *, cache: Tuple, positions, mask,
               dtype=jnp.bfloat16):
    """Chunk-append: x (B,C,D) holds the next C prompt tokens at absolute
    ``positions`` (B,C); ``mask`` (B,C) marks valid (non-pad-tail) tokens.
    Valid tokens write their K/V at their position; padded tail positions
    write NOTHING (the cache stays bit-exact — a later chunk or decode
    step owns those slots). Queries attend the whole cache under the
    absolute causal mask, so chunked prefill reproduces one-shot prefill
    bit-for-bit (see :func:`append_attention`)."""
    k_cache, v_cache = cache
    B, C, _ = x.shape
    q, k, v = _qkv(p, x, cfg, positions, dtype)
    S = k_cache.shape[1]
    # disjoint one-hot scatter of the chunk's K/V at its positions; padded
    # chunk positions are masked OUT (no garbage ever enters the cache)
    oh = jax.nn.one_hot(positions, S, dtype=k.dtype) \
        * mask[..., None].astype(k.dtype)                     # (B, C, S)
    written = oh.sum(axis=1)                                  # (B, S) 0/1
    k_cache = k_cache * (1 - written[..., None, None]) \
        + jnp.einsum("bcs,bchd->bshd", oh, k.astype(k_cache.dtype))
    v_cache = v_cache * (1 - written[..., None, None]) \
        + jnp.einsum("bcs,bchd->bshd", oh, v.astype(v_cache.dtype))
    o = append_attention(q, k_cache, v_cache, positions)
    return dense(o.reshape(B, C, -1), p["wo"], dtype), (k_cache, v_cache)


def gqa_cache_spec(cfg: ModelConfig, batch: int, max_len: int, dtype):
    KH, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    shape = (batch, max_len, KH, hd)
    return (jax.ShapeDtypeStruct(shape, dtype),
            jax.ShapeDtypeStruct(shape, dtype))


# ---------------------------------------------------------------------------
# Cross attention (encoder-decoder)
# ---------------------------------------------------------------------------

def cross_apply(p: dict, x: jax.Array, memory: jax.Array, cfg: ModelConfig,
                dtype=jnp.bfloat16) -> jax.Array:
    """Decoder cross-attention over encoder memory (no mask, no rope)."""
    B, S, _ = x.shape
    Sm = memory.shape[1]
    H, KH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = dense(x, p["wq"], dtype).reshape(B, S, H, hd)
    k = dense(memory, p["wk"], dtype).reshape(B, Sm, KH, hd)
    v = dense(memory, p["wv"], dtype).reshape(B, Sm, KH, hd)
    o = chunked_attention(q, k, v, causal=False)
    return dense(o.reshape(B, S, -1), p["wo"], dtype)


# ---------------------------------------------------------------------------
# Multi-head Latent Attention (DeepSeek-V3)
# ---------------------------------------------------------------------------

def mla_init(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    m: MLAConfig = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq_a": dense_init(ks[0], d, m.q_lora_rank, dtype=dtype),
        "q_a_norm": rmsnorm_init(m.q_lora_rank),
        "wq_b": dense_init(ks[1], m.q_lora_rank, H * qk_dim, dtype=dtype),
        "wkv_a": dense_init(ks[2], d, m.kv_lora_rank + m.qk_rope_head_dim,
                            dtype=dtype),
        "kv_a_norm": rmsnorm_init(m.kv_lora_rank),
        "wkv_b": dense_init(ks[3], m.kv_lora_rank,
                            H * (m.qk_nope_head_dim + m.v_head_dim),
                            dtype=dtype),
        "wo": dense_init(ks[4], H * m.v_head_dim, d,
                         scale=1.0 / math.sqrt(H * m.v_head_dim),
                         dtype=dtype),
    }


def _mla_qkv(p, x, cfg: ModelConfig, positions, dtype):
    m: MLAConfig = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    # queries through the low-rank bottleneck
    q_c = rmsnorm(dense(x, p["wq_a"], dtype), p["q_a_norm"], cfg.rmsnorm_eps)
    q = dense(q_c, p["wq_b"], dtype).reshape(
        B, S, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    # compressed kv latent + shared rope key
    kv_a = dense(x, p["wkv_a"], dtype)
    c_kv, k_rope = jnp.split(kv_a, [m.kv_lora_rank], axis=-1)
    c_kv = rmsnorm(c_kv, p["kv_a_norm"], cfg.rmsnorm_eps)
    sin, cos = rope_angles(positions, m.qk_rope_head_dim, cfg.rope_theta)
    q_rope = apply_rope(q_rope, sin, cos)
    k_rope = apply_rope(k_rope[:, :, None, :], sin, cos)  # single shared head
    return q_nope, q_rope, c_kv, k_rope


def _mla_expand_kv(p, c_kv, cfg: ModelConfig, dtype):
    m: MLAConfig = cfg.mla
    B, S, _ = c_kv.shape
    H = cfg.num_heads
    kv = dense(c_kv, p["wkv_b"], dtype).reshape(
        B, S, H, m.qk_nope_head_dim + m.v_head_dim)
    return jnp.split(kv, [m.qk_nope_head_dim], axis=-1)  # k_nope, v


def mla_apply(p: dict, x: jax.Array, cfg: ModelConfig, *, positions,
              q_chunk: int = 1024, dtype=jnp.bfloat16) -> jax.Array:
    m: MLAConfig = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, x, cfg, positions, dtype)
    k_nope, v = _mla_expand_kv(p, c_kv, cfg, dtype)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, S, H, m.qk_rope_head_dim))],
        axis=-1)
    o = chunked_attention(q, k, v, causal=True, q_chunk=q_chunk)
    return dense(o.reshape(B, S, -1), p["wo"], dtype)


def mla_prefill(p, x, cfg: ModelConfig, *, positions, q_chunk=1024,
                dtype=jnp.bfloat16):
    """Returns output and the *compressed* cache (c_kv, k_rope) — the
    memory-defining feature of MLA."""
    m: MLAConfig = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, x, cfg, positions, dtype)
    k_nope, v = _mla_expand_kv(p, c_kv, cfg, dtype)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, S, H, m.qk_rope_head_dim))],
        axis=-1)
    o = chunked_attention(q, k, v, causal=True, q_chunk=q_chunk)
    return dense(o.reshape(B, S, -1), p["wo"], dtype), \
        (c_kv, k_rope[:, :, 0, :])


def mla_decode(p, x, cfg: ModelConfig, *, cache, length,
               dtype=jnp.bfloat16):
    """Decode with the compressed cache using the **absorbed-matmul** form:
    the up-projections W_uk / W_uv are folded into the query/output sides so
    attention runs directly over the (rank + rope)-dim latents — the K/V
    expansion (B,S,H,·) is never materialized (it would be TBs at 32k).

    cache: (c_kv (B,Smax,rank), k_rope (B,Smax,rope_dim)); length (B,).
    """
    m: MLAConfig = cfg.mla
    c_cache, r_cache = cache
    B = x.shape[0]
    H = cfg.num_heads
    pos = length[:, None].astype(jnp.float32)
    q_nope, q_rope, c_kv_new, k_rope_new = _mla_qkv(p, x, cfg, pos, dtype)
    oh = jax.nn.one_hot(length, c_cache.shape[1], dtype=c_cache.dtype)
    c_cache = c_cache * (1 - oh[..., None]) + oh[..., None] * c_kv_new
    r_cache = r_cache * (1 - oh[..., None]) + oh[..., None] * \
        k_rope_new[:, :, 0, :]
    # absorb W_uk into q, W_uv into the context read-out. This is the ONE
    # decode-path weight materialization left: the absorbed form needs
    # wkv_b reshaped to (rank, H, nope+v), which the 2-D INT8-streaming
    # quantized_dense cannot express; with QVirtual weights the gradient
    # still routes to the virtual-weight shadow.
    w_ukv = layers.materialize(p["wkv_b"], dtype).reshape(
        m.kv_lora_rank, H, m.qk_nope_head_dim + m.v_head_dim)
    w_uk = w_ukv[..., : m.qk_nope_head_dim]      # (rank, H, nope)
    w_uv = w_ukv[..., m.qk_nope_head_dim:]       # (rank, H, v)
    q_abs = jnp.einsum("bshn,rhn->bshr", q_nope, w_uk)[:, 0]  # (B,H,rank)
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    s = (jnp.einsum("bhr,bkr->bhk", q_abs.astype(jnp.float32),
                    c_cache.astype(jnp.float32))
         + jnp.einsum("bshr,bkr->bhk", q_rope.astype(jnp.float32),
                      r_cache.astype(jnp.float32))) * scale
    kpos = jnp.arange(c_cache.shape[1])
    s = jnp.where(kpos[None, None] < (length + 1)[:, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhk,bkr->bhr", w,
                     c_cache.astype(jnp.float32))   # (B,H,rank)
    o = jnp.einsum("bhr,rhv->bhv", ctx, w_uv.astype(jnp.float32))
    o = o.reshape(B, 1, H * m.v_head_dim).astype(dtype)
    return dense(o, p["wo"], dtype), (c_cache, r_cache)


def mla_cache_spec(cfg: ModelConfig, batch: int, max_len: int, dtype):
    m: MLAConfig = cfg.mla
    return (jax.ShapeDtypeStruct((batch, max_len, m.kv_lora_rank), dtype),
            jax.ShapeDtypeStruct((batch, max_len, m.qk_rope_head_dim), dtype))
