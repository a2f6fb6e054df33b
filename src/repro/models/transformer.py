"""Decoder-only transformer family: dense GQA, MLA, MoE, VLM-backbone.

Covers: llama-* (paper's own), mistral-nemo-12b, qwen3-32b, gemma-7b, yi-9b,
internvl2-2b (vlm), qwen3-moe-30b-a3b, deepseek-v3-671b (MLA + MoE + MTP).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, ShapeCell
from repro.kernels import profile
from repro.models import attention, layers, moe as moe_lib
from repro.models.base import ModelBundle, SegmentDef
from repro.models.layers import cross_entropy, dense, dense_init, \
    embed_init, ffn_apply, ffn_init, rmsnorm, rmsnorm_init, softcap


# ---------------------------------------------------------------------------
# Block init / apply
# ---------------------------------------------------------------------------

def _attn_init(key, cfg: ModelConfig, dtype):
    if cfg.attention == "mla":
        return attention.mla_init(key, cfg, dtype)
    return attention.gqa_init(key, cfg, dtype)


def block_init(key, cfg: ModelConfig, *, moe_layer: bool, d_ff: int,
               dtype=jnp.float32) -> dict:
    k1, k2 = jax.random.split(key)
    p = {
        "attn_norm": rmsnorm_init(cfg.d_model),
        "attn": _attn_init(k1, cfg, dtype),
        "ffn_norm": rmsnorm_init(cfg.d_model),
    }
    if moe_layer:
        p["moe"] = moe_lib.moe_init(k2, cfg, dtype)
    else:
        p["ffn"] = ffn_init(k2, cfg.d_model, d_ff, dtype=dtype)
    return p


@profile.scope("repro.layer")
def block_apply(lp, carry, ctx, cfg: ModelConfig, *, moe_layer: bool,
                q_chunk: int, dtype, ep_axis=None) -> dict:
    h = carry["h"]
    x = rmsnorm(h, lp["attn_norm"], cfg.rmsnorm_eps)
    if cfg.attention == "mla":
        a = attention.mla_apply(lp["attn"], x, cfg,
                                positions=ctx["positions"],
                                q_chunk=q_chunk, dtype=dtype)
    else:
        a = attention.gqa_apply(lp["attn"], x, cfg,
                                positions=ctx["positions"],
                                q_chunk=q_chunk, dtype=dtype)
    h = h + a
    x = rmsnorm(h, lp["ffn_norm"], cfg.rmsnorm_eps)
    if moe_layer:
        if ep_axis is not None:
            f, aux = moe_lib.moe_apply_ep(lp["moe"], x, cfg,
                                          ep_axis=ep_axis, dtype=dtype)
        else:
            f, aux = moe_lib.moe_apply(lp["moe"], x, cfg, dtype=dtype)
        carry = {**carry, "aux": carry["aux"] + aux}
    else:
        f = ffn_apply(lp["ffn"], x, cfg.ffn_activation, dtype)
    return {**carry, "h": h + f}


def block_prefill(lp, carry, ctx, cfg: ModelConfig, *, moe_layer: bool,
                  q_chunk: int, dtype):
    h = carry["h"]
    x = rmsnorm(h, lp["attn_norm"], cfg.rmsnorm_eps)
    if cfg.attention == "mla":
        a, cache = attention.mla_prefill(lp["attn"], x, cfg,
                                         positions=ctx["positions"],
                                         q_chunk=q_chunk, dtype=dtype)
    else:
        a, cache = attention.gqa_prefill(lp["attn"], x, cfg,
                                         positions=ctx["positions"],
                                         q_chunk=q_chunk, dtype=dtype)
    if "max_len" in ctx:
        # grow the cache to the serving window (time axis = 1)
        pad = ctx["max_len"] - cache[0].shape[1]
        cache = tuple(
            jnp.pad(c, ((0, 0), (0, pad)) + ((0, 0),) * (c.ndim - 2))
            for c in cache)
    h = h + a
    x = rmsnorm(h, lp["ffn_norm"], cfg.rmsnorm_eps)
    if moe_layer:
        f, aux = moe_lib.moe_apply(lp["moe"], x, cfg, dtype=dtype)
        carry = {**carry, "aux": carry["aux"] + aux}
    else:
        f = ffn_apply(lp["ffn"], x, cfg.ffn_activation, dtype)
    return {**carry, "h": h + f}, cache


def block_append(lp, carry, cache, ctx, cfg: ModelConfig, *,
                 q_chunk: int, dtype):
    """Chunk-append (paged / chunked prefill): carry["h"] is a (B, C, D)
    chunk of prompt tokens at absolute ``ctx["positions"]``; the cache
    already holds every earlier position. Dense GQA only — MoE routing
    capacity depends on the tokens routed together (chunking would change
    which tokens drop), and MLA's absorbed decode contracts in a different
    order than its prefill, so neither can promise the chunked==one-shot
    bit-identity this path is gated on (``SegmentDef.append`` stays None
    there)."""
    h = carry["h"]
    x = rmsnorm(h, lp["attn_norm"], cfg.rmsnorm_eps)
    a, cache = attention.gqa_append(lp["attn"], x, cfg, cache=cache,
                                    positions=ctx["positions"],
                                    mask=ctx["chunk_mask"], dtype=dtype)
    h = h + a
    x = rmsnorm(h, lp["ffn_norm"], cfg.rmsnorm_eps)
    f = ffn_apply(lp["ffn"], x, cfg.ffn_activation, dtype)
    return {**carry, "h": h + f}, cache


def block_decode(lp, carry, cache, ctx, cfg: ModelConfig, *,
                 moe_layer: bool, dtype):
    h = carry["h"]                              # (B, 1, D)
    x = rmsnorm(h, lp["attn_norm"], cfg.rmsnorm_eps)
    if cfg.attention == "mla":
        a, cache = attention.mla_decode(lp["attn"], x, cfg, cache=cache,
                                        length=ctx["length"], dtype=dtype)
    else:
        a, cache = attention.gqa_decode(lp["attn"], x, cfg, cache=cache,
                                        length=ctx["length"], dtype=dtype)
    h = h + a
    x = rmsnorm(h, lp["ffn_norm"], cfg.rmsnorm_eps)
    if moe_layer:
        # decode: drop-free capacity (T is just the batch size)
        f, _ = moe_lib.moe_apply(lp["moe"], x, cfg, dtype=dtype,
                                 capacity=x.shape[0] * x.shape[1])
    else:
        f = ffn_apply(lp["ffn"], x, cfg.ffn_activation, dtype)
    return {**carry, "h": h + f}, cache


def _cache_spec(cfg: ModelConfig, batch: int, max_len: int, dtype):
    if cfg.attention == "mla":
        return attention.mla_cache_spec(cfg, batch, max_len, dtype)
    return attention.gqa_cache_spec(cfg, batch, max_len, dtype)


# ---------------------------------------------------------------------------
# Bundle assembly
# ---------------------------------------------------------------------------

def _embed_tokens(params, tokens, cfg: ModelConfig, dtype):
    # INT8 tables: per-token row gather + dequant, never the full table
    h = layers.embed_lookup(params["embedding"], tokens, dtype)
    if cfg.name.startswith("gemma"):
        h = h * math.sqrt(cfg.d_model)
    return h


def _head_logits(params, h, cfg: ModelConfig, dtype):
    h = rmsnorm(h, params["final_norm"], cfg.rmsnorm_eps)
    if cfg.tie_embeddings:
        # tied head: h @ W_emb^T — streams the same INT8 blocks transposed
        logits = layers.dense_t(h, params["embedding"], dtype)
    else:
        logits = dense(h, params["head"], dtype)
    return softcap(logits, cfg.logit_softcap)


def build(cfg: ModelConfig, *, q_chunk: int = 1024,
          dtype=jnp.bfloat16, ep_axis=None,
          split_layers: int = 0) -> ModelBundle:
    """Decoder-only LM bundle (dense / moe / vlm families).

    ``ep_axis``: manual mesh axis name for expert-parallel MoE — only valid
    when the TRAIN step runs inside a shard_map over that axis (serving
    paths stay GSPMD-auto).

    ``split_layers``: split the (dense) block stack into two segments after
    the first N layers — ``seg0_dense`` (layers 0..N-1) and ``seg1_dense``
    (the rest). Numerically identical to the single-segment model; it
    exists so param-group rules (``repro.core.rules``) can address layer
    RANGES at leaf granularity — e.g. the fine-tune entrypoint freezes
    ``seg0_`` (early layers) while Q-GaLore trains ``seg1_``. MoE models
    already split at ``first_dense_layers``; combining both is unsupported.
    """
    mc = cfg.moe
    is_vlm = cfg.family == "vlm"
    if split_layers and not (0 < split_layers < cfg.num_layers):
        # a silently-ignored split would leave ONE segment named
        # seg0_dense — and freeze-by-"seg0_" patterns would then freeze
        # every block
        raise ValueError(
            f"split_layers={split_layers} out of range for "
            f"num_layers={cfg.num_layers} (need 0 < split < num_layers)")

    # ---- segment layout ----
    if mc is not None and mc.first_dense_layers:
        if split_layers:
            raise ValueError("split_layers unsupported for MoE models with "
                             "first_dense_layers (already two segments)")
        segs = [("dense", mc.first_dense_layers, False),
                ("moe", cfg.num_layers - mc.first_dense_layers, True)]
    elif mc is not None:
        if split_layers:
            raise ValueError("split_layers unsupported for MoE models")
        segs = [("moe", cfg.num_layers, True)]
    elif split_layers:
        segs = [("dense", split_layers, False),
                ("dense", cfg.num_layers - split_layers, False)]
    else:
        segs = [("dense", cfg.num_layers, False)]

    def init_params(key):
        ks = jax.random.split(key, 8 + len(segs))
        params = {
            "embedding": embed_init(ks[0], cfg.vocab_size, cfg.d_model),
            "final_norm": rmsnorm_init(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            params["head"] = dense_init(
                ks[1], cfg.d_model, cfg.vocab_size,
                scale=1.0 / math.sqrt(cfg.d_model))
        d_ff_dense = (mc.dense_ff or cfg.d_ff) if mc else cfg.d_ff
        for i, (name, n, is_moe) in enumerate(segs):
            params[f"seg{i}_{name}"] = layers.stacked_init(
                functools.partial(block_init, cfg=cfg, moe_layer=is_moe,
                                  d_ff=(cfg.d_ff if is_moe else d_ff_dense)),
                ks[2 + i], n)
        if cfg.mtp_depth:
            params["mtp_block"] = block_init(
                ks[7], cfg, moe_layer=False,
                d_ff=(mc.dense_ff or cfg.d_ff) if mc else cfg.d_ff)
            params["mtp_norm"] = rmsnorm_init(cfg.d_model)
            params["mtp_proj"] = dense_init(ks[6], 2 * cfg.d_model,
                                            cfg.d_model)
        return params

    def embed(params, batch):
        tokens = batch["tokens"]
        h = _embed_tokens(params, tokens, cfg, dtype)
        if is_vlm and "patch_embeds" in batch:
            h = jnp.concatenate(
                [batch["patch_embeds"].astype(dtype), h], axis=1)
        B, S = h.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                     (B, S))
        carry = {"h": h, "aux": jnp.zeros((), jnp.float32)}
        ctx = {"positions": positions}
        return carry, ctx

    segments = tuple(
        SegmentDef(
            name=name, n_layers=n,
            apply=functools.partial(block_apply, cfg=cfg, moe_layer=is_moe,
                                    q_chunk=q_chunk, dtype=dtype,
                                    ep_axis=ep_axis if is_moe else None),
            prefill=functools.partial(block_prefill, cfg=cfg,
                                      moe_layer=is_moe, q_chunk=q_chunk,
                                      dtype=dtype),
            decode=functools.partial(block_decode, cfg=cfg, moe_layer=is_moe,
                                     dtype=dtype),
            append=(functools.partial(block_append, cfg=cfg,
                                      q_chunk=q_chunk, dtype=dtype)
                    if not is_moe and cfg.attention != "mla" else None),
            cache_spec=functools.partial(_cache_spec, cfg),
        )
        for (name, n, is_moe) in segs)

    @profile.scope("repro.head_loss")
    def head_loss(params, carry, batch):
        h = carry["h"]
        labels = batch["labels"]
        if is_vlm:
            n_img = h.shape[1] - labels.shape[1]
            h = h[:, n_img:]
        logits = _head_logits(params, h, cfg, dtype)
        # next-token prediction: logits[t] predicts labels[t]
        loss, metrics = cross_entropy(logits[:, :-1], labels[:, 1:])
        if cfg.mtp_depth:
            # DeepSeek-style multi-token prediction: one extra block predicts
            # t+2 from [h_t ; emb(label_{t+1})].
            emb_next = _embed_tokens(params, batch["labels"], cfg, dtype)
            hm = jnp.concatenate([carry["h"][:, :-1] if not is_vlm
                                  else h[:, :-1], emb_next[:, 1:]], axis=-1)
            hm = dense(hm, params["mtp_proj"], dtype)
            B, S = hm.shape[:2]
            pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                   (B, S))
            mtp_carry = {"h": hm, "aux": jnp.zeros((), jnp.float32)}
            mtp_carry = block_apply(params["mtp_block"], mtp_carry,
                                    {"positions": pos}, cfg,
                                    moe_layer=False, q_chunk=q_chunk,
                                    dtype=dtype)
            hm = rmsnorm(mtp_carry["h"], params["mtp_norm"], cfg.rmsnorm_eps)
            mtp_logits = _head_logits(params, hm, cfg, dtype)
            mtp_loss, _ = cross_entropy(mtp_logits[:, :-1], labels[:, 2:])
            loss = loss + 0.3 * mtp_loss
            metrics = {**metrics, "mtp_loss": mtp_loss}
        total = loss + carry["aux"]
        return total, {**metrics, "ce_loss": loss, "aux_loss": carry["aux"]}

    def head_logits(params, carry):
        return _head_logits(params, carry["h"][:, -1:], cfg, dtype)

    def input_specs(cell: ShapeCell):
        B, S = cell.global_batch, cell.seq_len
        spec = {
            "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
            "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
        }
        if is_vlm and cfg.num_prefix_embeddings:
            spec["patch_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.num_prefix_embeddings, cfg.d_model), dtype)
        return spec

    # Ragged (right-padded) prefill is exact only when rows can't interact:
    # causal attention qualifies, but capacity-limited MoE routing couples
    # rows through the shared expert buffers once T·k exceeds the drop-free
    # threshold (pads of one row can evict valid tokens of another) — MoE
    # bundles therefore keep the one-request-at-a-time unpadded admission.
    return ModelBundle(cfg=cfg, init_params=init_params, embed=embed,
                       segments=segments, head_loss=head_loss,
                       head_logits=head_logits, input_specs=input_specs,
                       ragged_prefill_ok=(mc is None))
