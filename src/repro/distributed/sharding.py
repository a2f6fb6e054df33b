"""Logical-axis sharding rules: parameter / activation / optimizer-state
PartitionSpecs derived from leaf path names, with divisibility-aware fallback
(a dim is only sharded if the mesh axis divides it — e.g. 8 KV heads on a
16-way model axis fall back to replication, matching Megatron's handling of
narrow GQA).

QTensor leaves: ``q`` gets the weight's spec; ``scale``/``zero`` inherit the
leading-dim specs with the block-group dim sharded only when divisible.

Optimizer state (Q-GaLore): low-rank Adam moments keep the *surviving*
gradient dim (m for right-projection, n for left), so they inherit that dim's
sharding from the parent weight; the INT4 projection P (d, r) inherits the
*projected-away* dim's sharding on d. This keeps the deepseek-671b expert
moments (~27 GB INT8) sharded 16-way rather than replicated.

ZeRO sharding (``opt_state_sharding(..., zero_axes=...)``): on top of the
model-axis rules, the low-rank Adam moments and INT4 projections are
partitioned over the data-parallel axes — each DP rank owns a 1/D slice of
the quantized optimizer state and the slice is gathered (by GSPMD, at the
point of use) only inside the fused update. Dim choice is divisibility-aware
and composes with an existing model-axis sharding when the combined product
still divides the dim; leaves where nothing divides stay as-is (graceful
fallback, mirroring the narrow-GQA rule above). See docs/distributed.md.
"""
from __future__ import annotations

import re
from typing import List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import QGaLoreConfig
from repro.core import quant
from repro.core.adam8bit import Adam8bitState
from repro.core.quant import QTensor
from repro.core import qgalore
from repro.core.qgalore import LeafSpec

# (regex on normalized path, (row_logical, col_logical)) for the LAST TWO dims
_MATMUL_RULES = [
    (r"(wq|wk|wv|wq_b|wkv_b)$", (None, "tp")),
    (r"wo$", ("tp", None)),
    (r"(wq_a|wkv_a)$", (None, None)),        # MLA down-proj: small, replicate
    (r"(wi|wg|w_up|in_proj|w_gates|fuse|mtp_proj)$", (None, "tp")),
    (r"(wd|w_down|out_proj|site_out)$", ("tp", None)),
    (r"embedding$", (None, "tp")),
    (r"head$", (None, "tp")),
    (r"(router|conv_w|r_gates)$", (None, None)),
    (r"lora_[qo]/(A|B)$", (None, None)),
]


def _norm_path_str(s: str) -> str:
    """keystr-format path string → '/a/b/c'."""
    return "/" + re.sub(r"\['([^']*)'\]", r"\1/", s).rstrip("/") \
        .replace("][", "/").replace("[", "").replace("]", "")


def norm_path(path) -> str:
    """jax key-path → '/a/b/c' string."""
    return _norm_path_str(jax.tree_util.keystr(path))


def logical_axes(path: str, ndim: int) -> Tuple[Optional[str], ...]:
    if ndim == 0:
        return ()
    axes: List[Optional[str]] = [None] * ndim
    if ndim >= 2:
        for pat, (row, col) in _MATMUL_RULES:
            if re.search(pat, path):
                axes[-2], axes[-1] = row, col
                break
    if "experts_" in path and ndim >= 3:
        axes[-3] = "ep"
    return tuple(axes)


def _mesh_axis(logical: Optional[str], mesh: Mesh) -> Optional[str]:
    if logical in ("tp", "ep"):
        return "model" if "model" in mesh.axis_names else None
    return None


_EP_FULL_MESH = False


def set_ep_full_mesh(value: bool) -> None:
    """Full-mesh expert sharding requires the manual-EP all-to-all MoE path
    (moe_apply_ep inside the dp_compress shard_map): with plain GSPMD it
    degenerates into activation/weight all-gathers (measured — EXPERIMENTS
    §Perf iteration 3). The launcher enables it only alongside that path."""
    global _EP_FULL_MESH
    _EP_FULL_MESH = value


def _ep_axes(dim: int, mesh: Mesh):
    """Expert dim: shard over as much of the mesh as divides it (deepseek's
    256 experts → one per chip on 16×16; kills both replication and the
    expert grad all-reduce), else model only."""
    if _EP_FULL_MESH:
        avail = tuple(a for a in ("pod", "data", "model")
                      if a in mesh.axis_names)
        for cand in (avail, avail[1:], avail[2:]):
            if not cand:
                break
            total = 1
            for a in cand:
                total *= mesh.shape[a]
            if total > 1 and dim % total == 0 and dim >= total:
                return cand
    if "model" in mesh.axis_names and dim % mesh.shape["model"] == 0:
        return ("model",)
    return None


def spec_for(shape, logical, mesh: Mesh) -> P:
    """Each mesh axis may shard at most one dim: 'ep' (expert) takes
    precedence over 'tp' when both want the model axis (EP supersedes
    intra-matrix TP for expert-stacked weights)."""
    order = sorted(range(len(logical)),
                   key=lambda i: 0 if logical[i] == "ep" else 1)
    used = set()
    parts = [None] * len(logical)
    for i in order:
        dim, log = shape[i], logical[i]
        if log == "ep":
            axes = _ep_axes(dim, mesh)
            if axes and not (set(axes) & used):
                parts[i] = axes if len(axes) > 1 else axes[0]
                used.update(axes)
            continue
        ax = _mesh_axis(log, mesh)
        if ax is not None and ax not in used and dim > 0 \
                and dim % mesh.shape[ax] == 0:
            parts[i] = ax
            used.add(ax)
    return P(*parts)


def _extend_with_zero(spec: P, shape, mesh: Mesh, zero_axes,
                      skip_last: bool = False) -> P:
    """Add DP-axis (ZeRO) partitioning to an existing spec.

    Picks the largest dim that can absorb ``zero_axes`` — either free and
    divisible by their product, or already sharded with the combined product
    still dividing — and appends the zero axes to that dim's sharding. Leaves
    the spec unchanged when nothing divides. ``skip_last`` protects the
    quantized last axis of QTensor inner arrays (codes vs per-block scales
    disagree on its size, so sharding it would desynchronize them).
    """
    if not zero_axes:
        return spec
    ztot = int(np.prod([mesh.shape[a] for a in zero_axes]))
    if ztot <= 1:
        return spec
    parts = list(spec) + [None] * (len(shape) - len(spec))
    ndims = len(shape) - (1 if skip_last else 0)
    for i in sorted(range(ndims), key=lambda j: (-shape[j], j)):
        cur = parts[i]
        cur_t = () if cur is None else (
            (cur,) if isinstance(cur, str) else tuple(cur))
        if set(zero_axes) & set(cur_t):
            continue
        combined = ztot * int(np.prod([mesh.shape[a] for a in cur_t]) or 1)
        if shape[i] > 0 and shape[i] % combined == 0:
            new = cur_t + tuple(zero_axes)
            parts[i] = new if len(new) > 1 else new[0]
            return P(*parts)
    return spec


def _qtensor_sharding(qt: QTensor, logical, mesh: Mesh,
                      zero_axes=()) -> QTensor:
    qspec = _extend_with_zero(spec_for(qt.q.shape, logical, mesh),
                              qt.q.shape, mesh, zero_axes, skip_last=True)
    sspec = _extend_with_zero(spec_for(qt.scale.shape, logical, mesh),
                              qt.scale.shape, mesh, zero_axes,
                              skip_last=True)
    return QTensor(
        NamedSharding(mesh, qspec), NamedSharding(mesh, sspec),
        None if qt.zero is None else NamedSharding(mesh, sspec),
        qt.bits, qt.block, qt.orig_last, qt.dtype)


def param_sharding(params, mesh: Mesh):
    """Pytree of NamedShardings matching ``params`` (QTensor-aware)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=quant.is_qtensor)
    out = []
    for path, leaf in flat:
        pstr = norm_path(path)
        logical = logical_axes(pstr, len(leaf.shape))
        if quant.is_qtensor(leaf):
            out.append(_qtensor_sharding(leaf, logical, mesh))
        else:
            out.append(NamedSharding(mesh, spec_for(leaf.shape, logical,
                                                    mesh)))
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Tensor-parallel leaf-spec annotation
# ---------------------------------------------------------------------------

def annotate_tp(specs: List[LeafSpec], mesh: Optional[Mesh]
                ) -> List[LeafSpec]:
    """Stamp each spec with its TP shard annotation (``shard_dim``/``tp``)
    derived from the SAME matmul rules + divisibility / EP-precedence
    checks that place the parameters (:func:`spec_for`) — so the
    annotation can never disagree with the actual weight layout. No-op
    (annotations keep their replicated defaults) without a mesh or with a
    size-1 model axis, keeping DP-only and single-device specs
    bit-identical to the pre-TP contract."""
    if mesh is None or "model" not in mesh.axis_names \
            or mesh.shape["model"] <= 1:
        return specs
    import dataclasses
    tp = int(mesh.shape["model"])
    out = []
    for spec in specs:
        if len(spec.shape) < 2:
            out.append(spec)
            continue
        pstr = _norm_path_str(spec.path)
        parts = spec_for(spec.shape, logical_axes(pstr, len(spec.shape)),
                         mesh)
        shard_dim = None
        for d in (0, 1):
            part = parts[len(spec.shape) - 2 + d] \
                if len(parts) >= len(spec.shape) - 1 + d else None
            names = (part,) if isinstance(part, str) else tuple(part or ())
            if "model" in names:
                shard_dim = d
                break
        if shard_dim is None:
            out.append(spec)
        else:
            out.append(dataclasses.replace(spec, shard_dim=shard_dim,
                                           tp=tp))
    return out


# ---------------------------------------------------------------------------
# Optimizer-state sharding
# ---------------------------------------------------------------------------

def _galore_state_logicals(spec: LeafSpec, logical):
    """(moment_logical, proj_logical) for a galore leaf."""
    lead = logical[:-2]
    row, col = logical[-2], logical[-1]
    m, n = spec.mat_shape
    if spec.side == "right":       # low (…, m, r); P (…, n, r)
        mom = lead + (row, None)
        proj = lead + (col, None)
    else:                          # low (…, r, n); P (…, m, r)
        mom = lead + (None, col)
        proj = lead + (row, None)
    return mom, proj


def _shard_like(leaf, logical, mesh, zero_axes=()):
    if quant.is_qtensor(leaf):
        return _qtensor_sharding(leaf, logical, mesh, zero_axes)
    if leaf is None:
        return None
    spec = _extend_with_zero(spec_for(leaf.shape, logical, mesh),
                             leaf.shape, mesh, zero_axes)
    return NamedSharding(mesh, spec)


def zero_axes_for(mesh: Mesh) -> Tuple[str, ...]:
    """The DP axes a ZeRO-sharded optimizer state partitions over."""
    return batch_axes(mesh)


def opt_state_sharding(params, opt_state, cfg, mesh: Mesh,
                       zero_axes: Tuple[str, ...] = (), specs=None):
    """Sharding pytree for a QGaLoreState aligned with ``params``.

    ``cfg``: QGaLoreConfig or ParamRules — per-leaf galore/rank decisions
    (and therefore moment/projection layouts) resolve through the param
    groups; frozen-group leaves hold no state (None stays None).

    ``zero_axes``: DP mesh axes to additionally partition the Adam moments
    and projection matrices over (ZeRO-style optimizer-state sharding).
    Empty tuple = the pre-existing model-axis-only behavior.

    ``specs``: pre-resolved (possibly rank-overridden) leaf specs; the
    divisibility-aware ZeRO dim choice re-runs against the actual (shrunk)
    state shapes, so a rank transition re-shards cleanly.
    """
    if specs is None:
        specs = qgalore.leaf_specs(params, cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=quant.is_qtensor)
    inner_flat = jax.tree_util.tree_flatten(
        opt_state.inner, is_leaf=qgalore._is_inner_leaf)[0]
    proj_flat = jax.tree_util.tree_flatten(
        opt_state.proj,
        is_leaf=lambda x: quant.is_qtensor(x) or x is None)[0]

    inner_out, proj_out = [], []
    for (path, leaf), spec, inner, proj in zip(flat, specs, inner_flat,
                                               proj_flat):
        pstr = norm_path(path)
        logical = logical_axes(pstr, len(spec.shape))
        if spec.galore:
            mom_log, proj_log = _galore_state_logicals(spec, logical)
        else:
            mom_log, proj_log = logical, None
        inner_out.append(None if inner is None else Adam8bitState(
            _shard_like(inner.m, mom_log, mesh, zero_axes),
            _shard_like(inner.v, mom_log, mesh, zero_axes)))
        proj_out.append(None if proj is None
                        else _shard_like(proj, proj_log, mesh, zero_axes))

    from repro.core.qgalore import QGaLoreState
    return QGaLoreState(
        inner=jax.tree_util.tree_unflatten(treedef, inner_out),
        proj=jax.tree_util.tree_unflatten(treedef, proj_out),
        count=NamedSharding(mesh, P()),
    )


def lowrank_shardings(specs: List[LeafSpec], mesh: Mesh,
                      zero_axes: Tuple[str, ...] = ()):
    """Per-leaf layout hints for LOW-RANK values (projected gradients /
    Adam directions), keyed by ``LeafSpec.path``.

    Each galore leaf gets its MOMENT layout — the surviving weight dim
    model-sharded exactly when the TP placement shards that dim of the
    weight, the rank dim never sharded, optionally ZeRO-extended over
    ``zero_axes``. The transform chain applies these between its stages
    (``shardings=`` on ``chain(...).update``) so a 2-D mesh keeps the
    low-rank flow on the TP layout instead of re-replicating it at every
    stage boundary."""
    out = {}
    for spec in specs:
        if not spec.galore:
            continue
        logical = logical_axes(_norm_path_str(spec.path), len(spec.shape))
        mom_log, _ = _galore_state_logicals(spec, logical)
        pspec = _extend_with_zero(
            spec_for(spec.low_shape, mom_log, mesh), spec.low_shape, mesh,
            zero_axes)
        out[spec.path] = NamedSharding(mesh, pspec)
    return out


def zero2_scatter_dims(opt_sharding, specs: List[LeafSpec],
                       zero_axes: Tuple[str, ...]):
    """{leaf index: low-rank-gradient dim} for the ZeRO-2 gradient
    reduce-scatter (ROADMAP item): for each galore leaf whose ZeRO moment
    shard partitions some dim over EXACTLY the zero (DP) axes, return that
    dim — the steady-state low-rank gradient is then ``psum_scatter``ed
    along it (train/step.py), so each DP rank receives only the reduced
    slice that feeds the moment shard it owns, instead of a replicated
    ``pmean``. Leaves whose moments the ZeRO pass left unsharded (nothing
    divides) are omitted and keep the pmean."""
    if not zero_axes:
        return {}
    inner_flat = jax.tree_util.tree_flatten(
        opt_sharding.inner, is_leaf=qgalore._is_inner_leaf)[0]
    out = {}
    for i, (spec, ish) in enumerate(zip(specs, inner_flat)):
        if not spec.galore or ish is None:
            continue
        m_sh = ish.m.q if quant.is_qtensor(ish.m) else ish.m
        if not isinstance(m_sh, NamedSharding):
            continue
        for d, part in enumerate(m_sh.spec):
            parts = (part,) if isinstance(part, str) else tuple(part or ())
            # the dim carrying the zero axes (it may additionally be
            # model-sharded: the scatter is manual over the DP axes only,
            # GSPMD keeps handling the model factor outside the region)
            if set(zero_axes) <= set(parts) and d < len(spec.low_shape):
                out[i] = d
                break
    return out


# ---------------------------------------------------------------------------
# Batch / activation sharding
# ---------------------------------------------------------------------------

def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def data_sharding(batch_specs, mesh: Mesh):
    """Shard every batch input on its leading (batch) dim over pod+data."""
    dp = batch_axes(mesh)
    total = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1

    def one(spec):
        b = spec.shape[0]
        rest = [None] * (len(spec.shape) - 1)
        if dp and b % total == 0:
            return NamedSharding(mesh, P(dp, *rest))
        # fall back to the largest prefix of dp axes that divides b
        for sub in (dp[:1],):
            t = int(np.prod([mesh.shape[a] for a in sub]))
            if b % t == 0:
                return NamedSharding(mesh, P(sub, *rest))
        return NamedSharding(mesh, P(None, *rest))

    return jax.tree_util.tree_map(one, batch_specs)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())
