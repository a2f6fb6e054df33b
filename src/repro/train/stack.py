"""Fused projected-backward over segment stacks — the JAX-native realization
of the paper's fused-backward + low-rank projection (§3.5).

The forward scan saves only each layer's *input* carry (full per-layer
activation remat). The backward scan then, per layer:

  1. recomputes the layer forward and its VJP (``jax.vjp``),
  2. obtains the full-rank weight cotangents **transiently**,
  3. immediately projects every GaLore-eligible cotangent into its rank-r
     subspace (``P^T G`` / ``G P``) and emits only the low-rank tensor.

Consequences (matching the paper's memory story):
  * the full-rank gradient of the whole stack never co-resides — at any
    moment only ONE layer's (m, n) cotangent exists;
  * the emitted per-stack gradient is (L, r, n) / (L, m, r): 8-32× smaller;
  * under data parallelism the cross-replica reduction runs on the low-rank
    payload (gradient compression for free — see train.step).

Quantized (INT8 QTensor) parameters are *virtualized* per layer inside the
scan bodies (``quant.tree_virtualize``): the model consumes the INT8 codes
directly through the ``quantized_dense`` custom-VJP op — forward and the
``dL/dx`` backward stream INT8 blocks, and no full-precision weight view
exists even transiently. The ``QVirtual`` shadow (a dead zeros array of the
virtual shape) is what ``jax.vjp`` differentiates; its cotangent IS the
virtual-weight gradient, which the backward scan then projects low-rank as
before.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import projector, quant
from repro.kernels import profile
from repro.models.base import ModelBundle, SegmentDef

_FLOAT0 = jax.dtypes.float0


def _virt(tree):
    """QTensor leaves → QVirtual: INT8 stays the compute format, gradients
    land on the (virtual-shaped) shadow cotangent."""
    return quant.tree_virtualize(tree)


def _is_float(x) -> bool:
    return hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating) \
        and x.dtype != _FLOAT0


def _zero_cotangent_carry(tree):
    """Zeros for float leaves; scalar dummies for non-differentiable leaves
    (so the tree can ride a scan carry — float0 arrays cannot)."""
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, x.dtype) if _is_float(x)
        else jnp.zeros((), jnp.float32), tree)


def _to_float0_cotangent(acc_tree, primal_tree):
    """Rebuild a proper vjp cotangent: float0 zeros at non-float primal
    positions, accumulated values elsewhere."""
    return jax.tree_util.tree_map(
        lambda acc, p: acc if _is_float(p)
        else np.zeros(p.shape, _FLOAT0), acc_tree, primal_tree)


def _tree_add(a, b):
    """a (accumulator with dummies) += b (vjp output, may contain float0)."""
    def add(x, y):
        if y is None or not _is_float(y):
            return x
        if not _is_float(x):
            return x
        return x + y
    return jax.tree_util.tree_map(add, a, b,
                                  is_leaf=lambda x: x is None)


def project_leaf(g, P, side: Optional[str] = None):
    """Project one (possibly stacked) full-rank gradient leaf into the
    rank-r subspace of ``P`` (QTensor or array; leading batch dims ride the
    einsum). Shared by the backward-scan low-rank emission here and the
    distributed refresh in ``train.step`` (which projects the freshly
    reduced gradient slices with the just-recomputed P).

    ``side`` defaults to ``galore_side(g.shape)``, which is only valid on
    GLOBAL (logical) shapes — inside a manual region over the model axis
    a TP shard's local shape can flip the m>=n test, so shard-level
    callers must pass the spec's side explicitly (the distributed refresh
    does; ``projector.project_sharded`` is the shard-aware variant)."""
    if P is None:
        return g
    with profile.scope("repro.wgrad"):
        Pd = projector.maybe_dequantize(P, jnp.float32)
        side = side or projector.galore_side(g.shape)
        return projector.project(g.astype(jnp.float32), Pd, side)


def _project_cotangents(g_lp, P_lp):
    """Per-leaf: if a projection matrix is provided, emit the low-rank
    projection of the cotangent; else the full cotangent."""
    return jax.tree_util.tree_map(
        project_leaf, g_lp, P_lp,
        is_leaf=lambda x: x is None or quant.is_qtensor(x))


def segment_forward(seg: SegmentDef, seg_params, carry, ctx):
    """Forward scan saving per-layer input carries."""
    def body(c, lp):
        return seg.apply(_virt(lp), c, ctx), c
    from repro.models.base import scan_layers
    return scan_layers(body, carry, seg_params)


def segment_backward(seg: SegmentDef, seg_params, saved, g_carry, ctx,
                     P_tree: Optional[Any]):
    """Reverse scan: recompute + vjp + project. Returns
    (g_seg_params, g_carry_in, g_ctx_acc)."""
    g_ctx0 = _zero_cotangent_carry(ctx)
    g_carry0 = _zero_cotangent_carry(g_carry)
    # normalize incoming carry cotangent (may contain float0 from upstream)
    g_carry = _tree_add(g_carry0, g_carry)

    if P_tree is None:
        P_tree = jax.tree_util.tree_map(lambda _: None, seg_params,
                                        is_leaf=quant.is_qtensor)

    def body(state, inp):
        g_c, g_ctx = state
        lp, c_in, P_l = inp

        lp_v = _virt(lp)
        _, vjp = jax.vjp(lambda p, c, x: seg.apply(p, c, x),
                         lp_v, c_in, ctx)
        g_lp, g_cin, g_ctx_l = vjp(g_c)
        # collapse QVirtual cotangents to the shadow (= dL/dW virtual):
        # restores the plain per-QTensor gradient leaf and drops the
        # float0 code cotangents before they hit the scan ys.
        g_lp = quant.tree_devirtualize_grads(g_lp)
        g_lp = _project_cotangents(g_lp, P_l)
        g_cin = _tree_add(_zero_cotangent_carry(c_in), g_cin)
        return (g_cin, _tree_add(g_ctx, g_ctx_l)), g_lp

    from repro.models.base import scan_layers
    (g_carry_in, g_ctx), g_params = scan_layers(
        body, (g_carry, g_ctx0), (seg_params, saved, P_tree), reverse=True)
    return g_params, g_carry_in, g_ctx


def fused_value_and_grad(bundle: ModelBundle, params, batch,
                         proj_trees: Dict[str, Any]):
    """Loss + gradients with per-layer fused backward and in-scan projection.

    ``proj_trees``: {segment_key: pytree matching that segment's params with
    stacked P (or None per leaf)} — segment cotangents project INSIDE the
    backward scan; entries under NON-segment keys (``head``, ``embedding``
    when ``galore_embeddings``) project right after the head/embed vjps, so
    every GaLore leaf leaves this function low-rank and the DP reduction
    payload is low-rank across the board (the unembedding gradient otherwise
    dominates bytes-on-wire at small-model shapes). Pass {} to get full-rank
    grads everywhere (e.g. at subspace-refresh steps or for non-GaLore
    baselines).

    Returns ((loss, metrics), grads) where grads for projected leaves are
    low-rank (spec.low_shape) and full-rank elsewhere. Grad leaves for
    quantized params are w.r.t. the dequantized (virtual) weights.
    """
    seg_keys = [bundle.seg_key(i) for i in range(len(bundle.segments))]
    nonseg = {k: v for k, v in params.items() if k not in seg_keys}
    nonseg_v = _virt(nonseg)

    # ---- forward ----
    (carry, ctx), vjp_embed = jax.vjp(
        lambda ns: bundle.embed({**params, **ns}, batch), nonseg_v)

    saved_per_seg = []
    pre_vjps = []
    for i, seg in enumerate(bundle.segments):
        if seg.pre is not None:
            carry, vjp_pre = jax.vjp(
                lambda ns, c, x, _seg=seg: _seg.pre({**params, **ns}, c, x),
                nonseg_v, carry, ctx)
            pre_vjps.append(vjp_pre)
        else:
            pre_vjps.append(None)
        carry, saved = segment_forward(seg, params[seg_keys[i]], carry, ctx)
        saved_per_seg.append(saved)

    loss_and_metrics, vjp_head, metrics = jax.vjp(
        lambda ns, c: bundle.head_loss({**params, **ns}, c, batch),
        nonseg_v, carry, has_aux=True)
    loss = loss_and_metrics

    # ---- backward ----
    g_nonseg, g_carry = vjp_head(jnp.ones((), loss.dtype))
    g_nonseg = _tree_add(_zero_cotangent_carry(nonseg_v), g_nonseg)
    g_ctx_total = _zero_cotangent_carry(ctx)
    g_segs: Dict[str, Any] = {}
    for i in reversed(range(len(bundle.segments))):
        seg = bundle.segments[i]
        g_seg, g_carry, g_ctx = segment_backward(
            seg, params[seg_keys[i]], saved_per_seg[i], g_carry, ctx,
            proj_trees.get(seg_keys[i]))
        g_segs[seg_keys[i]] = g_seg
        g_ctx_total = _tree_add(g_ctx_total, g_ctx)
        if pre_vjps[i] is not None:
            g_ns_pre, g_carry, g_ctx_pre = pre_vjps[i](g_carry)
            g_carry = _tree_add(_zero_cotangent_carry(carry), g_carry) \
                if not isinstance(g_carry, dict) else g_carry
            g_nonseg = _tree_add(g_nonseg, g_ns_pre)
            g_ctx_total = _tree_add(g_ctx_total, g_ctx_pre)

    g_ns_embed, = vjp_embed(
        (g_carry, _to_float0_cotangent(g_ctx_total, ctx)))
    g_nonseg = _tree_add(g_nonseg, g_ns_embed)

    grads = {**g_nonseg, **g_segs}
    grads = {k: grads[k] for k in params.keys()}
    grads = quant.tree_devirtualize_grads(grads)
    for k, P_sub in proj_trees.items():
        if k not in g_segs and k in grads:      # nonseg galore leaves
            grads[k] = _project_cotangents(grads[k], P_sub)
    return (loss, metrics), grads


def simple_value_and_grad(bundle: ModelBundle, params, batch):
    """Oracle path: one vjp through the scanned forward (full-rank grads;
    higher peak memory). Used for tests and small baselines.

    Uses ``jax.vjp`` rather than ``value_and_grad`` because the virtualized
    params tree carries the (non-differentiable) INT8 code arrays alongside
    the float shadows; their float0 cotangents are dropped on extraction.
    """
    from repro.models import base

    virt = _virt(params)

    def loss_of(v):
        loss, metrics = base.loss_fn(bundle, v, batch)
        return loss, metrics

    loss, vjp, metrics = jax.vjp(loss_of, virt, has_aux=True)
    grads, = vjp(jnp.ones((), loss.dtype))
    grads = quant.tree_devirtualize_grads(grads)
    return (loss, metrics), grads
