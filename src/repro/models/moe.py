"""Mixture-of-Experts FFN with sort-based capacity dispatch.

TPU-native design: token→expert routing is realized as a *sort + static
scatter* into per-expert buffers of fixed capacity ``C = ceil(T·k/E · cf)``
(static shapes — XLA requirement), followed by a batched expert matmul
``(E, C, d) × (E, d, f)``. Expert-stacked weights shard on ``E`` over the
``model`` axis (expert parallelism); the scatter/gather lowers to
all-to-all-style collectives under GSPMD.

Overflowing tokens (beyond capacity) fall into a garbage slot and contribute
zero — the standard capacity-factor trade-off; a load-balance auxiliary loss
keeps overflow rare.

Supports DeepSeek-style shared experts (always-on dense path added to the
routed output).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, MoEConfig
from repro.models import layers
from repro.models.layers import dense, dense_init


def moe_init(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    mc: MoEConfig = cfg.moe
    d, f, E = cfg.d_model, mc.expert_ff, mc.num_experts
    ks = jax.random.split(key, 8)
    std = 1.0 / math.sqrt(d)

    def expert_stack(k, din, dout):
        return (jax.random.truncated_normal(
            k, -2.0, 2.0, (E, din, dout), jnp.float32)
            * (1.0 / math.sqrt(din))).astype(dtype)

    p = {
        "router": dense_init(ks[0], d, E, scale=std, dtype=jnp.float32),
        "experts_wi": expert_stack(ks[1], d, f),
        "experts_wg": expert_stack(ks[2], d, f),
        "experts_wd": expert_stack(ks[3], f, d),
    }
    if mc.num_shared_experts:
        fs = f * mc.num_shared_experts
        p["shared"] = layers.ffn_init(ks[4], d, fs, dtype=dtype)
    return p


def _route(router_w, x, mc: MoEConfig):
    """Top-k routing. x (T, d) → (weights (T,k), experts (T,k), aux_loss)."""
    logits = dense(x, router_w, jnp.float32)          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, mc.top_k)
    weights = weights / jnp.maximum(
        weights.sum(-1, keepdims=True), 1e-9)         # renormalize over k
    # Switch-style load-balance loss
    E = logits.shape[-1]
    density = jnp.mean(jax.nn.one_hot(experts[..., 0], E), axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * E * mc.router_aux_coef
    return weights.astype(x.dtype), experts, aux


def moe_apply(p: dict, x: jax.Array, cfg: ModelConfig, *,
              capacity_factor: float = 1.25,
              capacity: Optional[int] = None,
              dtype=jnp.bfloat16) -> Tuple[jax.Array, jax.Array]:
    """x (B, S, d) → (out (B, S, d), aux_loss scalar).

    ``capacity`` overrides the factor-derived per-expert buffer (decode uses
    ``capacity=T`` — drop-free, exact)."""
    mc: MoEConfig = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, k = mc.num_experts, mc.top_k
    xt = x.reshape(T, d)

    weights, experts, aux = _route(p["router"], xt, mc)

    # --- sort-based dispatch -------------------------------------------------
    if capacity is not None:
        C = capacity
    elif T * k <= 4096:
        C = T          # tiny workloads (tests / decode): drop-free, exact
    else:
        C = max(1, int(math.ceil(T * k / E * capacity_factor)))
    e_flat = experts.reshape(-1)                    # (T*k,)
    tok_flat = jnp.repeat(jnp.arange(T), k)         # (T*k,)
    w_flat = weights.reshape(-1)
    order = jnp.argsort(e_flat)                     # group by expert
    e_sorted = e_flat[order]
    tok_sorted = tok_flat[order]
    w_sorted = w_flat[order]
    # position within each expert group: index − start-of-group
    group_start = jnp.searchsorted(e_sorted, jnp.arange(E), side="left")
    pos_in_group = jnp.arange(T * k) - group_start[e_sorted]
    keep = pos_in_group < C
    slot = jnp.where(keep, e_sorted * C + pos_in_group, E * C)  # garbage slot

    # scatter tokens into (E*C+1, d) buffers
    buf = jnp.zeros((E * C + 1, d), dtype)
    buf = buf.at[slot].set(xt[tok_sorted].astype(dtype), mode="drop",
                           unique_indices=True)
    expert_in = buf[: E * C].reshape(E, C, d)

    # --- batched expert FFN (INT8 expert stacks stay INT8 per expert) -------
    h = jax.nn.silu(layers.dense_batched(expert_in, p["experts_wg"], dtype)) \
        * layers.dense_batched(expert_in, p["experts_wi"], dtype)
    expert_out = layers.dense_batched(h, p["experts_wd"], dtype)  # (E, C, d)

    # --- combine --------------------------------------------------------------
    out_flat = expert_out.reshape(E * C, d)
    gathered = jnp.where(
        keep[:, None],
        out_flat[jnp.minimum(slot, E * C - 1)], 0.0)          # (T*k, d)
    y = jnp.zeros((T, d), jnp.float32)
    y = y.at[tok_sorted].add(gathered.astype(jnp.float32)
                             * w_sorted[:, None].astype(jnp.float32))
    out = y.astype(dtype)

    if mc.num_shared_experts:
        out = out + layers.ffn_apply(p["shared"], xt,
                                     cfg.ffn_activation, dtype)
    return out.reshape(B, S, d), aux


def moe_apply_ep(p: dict, x: jax.Array, cfg: ModelConfig, *,
                 ep_axis: str, capacity_factor: float = 1.25,
                 dtype=jnp.bfloat16) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE for use INSIDE a shard_map whose manual axis
    ``ep_axis`` shards both the batch (tokens) and the expert dim of the
    expert weights (E_loc = E / n_shards per shard).

    Flow per shard: route local tokens against the (replicated) router →
    sort-dispatch into per-expert buffers for ALL experts → ``all_to_all``
    ships each expert's tokens to its owner shard → local expert FFN →
    reverse ``all_to_all`` → weighted combine. Expert grads then live
    entirely on the owner shard (no cross-data reduction at all), and the
    activation payload on the wire is 2 × T·k·d instead of GSPMD's
    weight/activation all-gathers.
    """
    mc: MoEConfig = cfg.moe
    B, S, d = x.shape                       # LOCAL batch
    T = B * S
    E, k = mc.num_experts, mc.top_k
    n_shards = jax.lax.axis_size(ep_axis)
    assert E % n_shards == 0, (E, n_shards)
    E_loc = E // n_shards
    xt = x.reshape(T, d)

    weights, experts, aux = _route(p["router"], xt, mc)

    if T * k <= 4096:
        C = T
    else:
        C = max(1, int(math.ceil(T * k / E * capacity_factor)))
    e_flat = experts.reshape(-1)
    tok_flat = jnp.repeat(jnp.arange(T), k)
    w_flat = weights.reshape(-1)
    order = jnp.argsort(e_flat)
    e_sorted = e_flat[order]
    tok_sorted = tok_flat[order]
    w_sorted = w_flat[order]
    group_start = jnp.searchsorted(e_sorted, jnp.arange(E), side="left")
    pos_in_group = jnp.arange(T * k) - group_start[e_sorted]
    keep = pos_in_group < C
    slot = jnp.where(keep, e_sorted * C + pos_in_group, E * C)

    buf = jnp.zeros((E * C + 1, d), dtype)
    buf = buf.at[slot].set(xt[tok_sorted].astype(dtype), mode="drop",
                           unique_indices=True)
    send = buf[: E * C].reshape(E, C, d)

    # ---- EP exchange: (E, C, d) → (E_loc, n_shards·C, d) ----
    recv = jax.lax.all_to_all(send, ep_axis, split_axis=0, concat_axis=1,
                              tiled=True)

    # local (E_loc, d, f) expert stacks, consumed in INT8 per expert
    h = jax.nn.silu(layers.dense_batched(recv, p["experts_wg"], dtype)) \
        * layers.dense_batched(recv, p["experts_wi"], dtype)
    expert_out = layers.dense_batched(h, p["experts_wd"], dtype)

    # ---- reverse exchange: back to (E, C, d) on the token-owner shard ----
    back = jax.lax.all_to_all(expert_out, ep_axis, split_axis=1,
                              concat_axis=0, tiled=True)

    out_flat = back.reshape(E * C, d)
    gathered = jnp.where(keep[:, None],
                         out_flat[jnp.minimum(slot, E * C - 1)], 0.0)
    y = jnp.zeros((T, d), jnp.float32)
    y = y.at[tok_sorted].add(gathered.astype(jnp.float32)
                             * w_sorted[:, None].astype(jnp.float32))
    out = y.astype(dtype)
    if mc.num_shared_experts:
        out = out + layers.ffn_apply(p["shared"], xt,
                                     cfg.ffn_activation, dtype)
    return out.reshape(B, S, d), aux


def moe_ep_sharded(p: dict, x: jax.Array, cfg: ModelConfig, *, mesh,
                   ep_axis: str, capacity_factor: float = 1.25,
                   dtype=jnp.bfloat16) -> Tuple[jax.Array, jax.Array]:
    """Top-level expert-parallel entry: wraps :func:`moe_apply_ep` in a
    ``jax.shard_map`` manual over ``ep_axis``.

    Expert stacks shard on their leading E dim over ``ep_axis``; router and
    shared-expert weights enter replicated; the token batch shards on dim 0.
    The aux loss is pmeaned over the shards (per-shard top-1 densities).

    The region is manual over ALL mesh axes: the body only issues
    ``ep_axis`` collectives, and XLA:CPU miscompiles partial-manual
    subgroups for this program — non-EP inputs therefore enter
    replicated (gathered) over the other axes."""
    from jax.sharding import PartitionSpec as P

    def inner(pp, xb):
        out, aux = moe_apply_ep(pp, xb, cfg, ep_axis=ep_axis,
                                capacity_factor=capacity_factor, dtype=dtype)
        return out, jax.lax.pmean(aux, ep_axis)

    pspecs = {k: (P(ep_axis) if k.startswith("experts_") else P())
              for k in p}
    f = jax.shard_map(inner, mesh=mesh, in_specs=(pspecs, P(ep_axis)),
                      out_specs=(P(ep_axis), P()), check_vma=False)
    return f(p, x)


def moe_ref(p: dict, x: jax.Array, cfg: ModelConfig,
            dtype=jnp.float32) -> jax.Array:
    """Oracle: dense per-token loop over experts (no capacity drops).
    Used by tests to validate the sort-based dispatch."""
    mc: MoEConfig = cfg.moe
    B, S, d = x.shape
    xt = x.reshape(-1, d)
    weights, experts, _ = _route(p["router"], xt, mc)
    wi = layers.materialize(p["experts_wi"], dtype)
    wg = layers.materialize(p["experts_wg"], dtype)
    wd = layers.materialize(p["experts_wd"], dtype)

    def per_token(xv, ws, es):
        def per_choice(w, e):
            h = jax.nn.silu(xv @ wg[e]) * (xv @ wi[e])
            return w * (h @ wd[e])
        return sum(per_choice(ws[i], es[i]) for i in range(mc.top_k))

    out = jax.vmap(per_token)(xt.astype(dtype), weights.astype(dtype),
                              experts)
    if mc.num_shared_experts:
        out = out + layers.ffn_apply(p["shared"], xt, cfg.ffn_activation,
                                     dtype)
    return out.reshape(B, S, d)
