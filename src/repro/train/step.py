"""The pjit-able training step: fused projected backward + Q-GaLore update.

INT8 (QTensor) weights are the native compute format throughout: the
forward/backward consume them through the ``quantized_dense`` custom-VJP op
(`repro.kernels.ops`), so the training step never materializes a
full-precision weight — the per-layer dL/dW appears transiently, is
projected low-rank inside the backward scan, and the fused Q-GaLore update
kernel writes the new INT8 codes without leaving VMEM.

Two compiled variants per run:
  * ``refresh=False`` — steady state: grads for GaLore leaves are emitted
    low-rank straight out of the backward scan (never materializing the
    full-rank gradient), then the 8-bit Adam / SR weight update applies.
  * ``refresh=True``  — subspace-refresh steps: full-rank grads are
    materialized for GaLore leaves so the masked per-layer SVD can run
    in-graph (lax.cond inside a layer scan, §3.2).

Gradient accumulation scans over microbatches; with the fused path the
accumulated payload is the LOW-RANK gradient, which is also what crosses the
data-parallel axis — the paper-beyond gradient-compression effect.

The optimizer half of the step (``qgalore.apply_updates``) batches
same-shaped leaves through one scanned program and runs eligible leaves
through the fused update kernel (Adam + INT4 back-projection + SR requant
in one pass); the kernel backend is chosen per platform by
``repro.kernels.dispatch`` (pallas-tpu on TPU, pure-XLA ref elsewhere,
``REPRO_KERNEL_BACKEND`` to override).
"""
from __future__ import annotations

import functools
import logging
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.config import QGaLoreConfig, TrainConfig
from repro.core import qgalore, quant, transform
from repro.core.qgalore import QGaLoreState
from repro.core.rules import as_rules
from repro.kernels import profile
from repro.models.base import ModelBundle
from repro.train import stack


class TrainState(NamedTuple):
    params: Any
    opt: QGaLoreState


def prepare_params(params, qcfg, param_dtype=jnp.bfloat16):
    """Quantize eligible weights to INT8 (Q-GaLore) or cast to the param
    dtype (baselines). Norm scales / small vectors stay float32.

    ``qcfg`` may be a ``QGaLoreConfig`` or a ``ParamRules``: each leaf's
    ``weight_bits`` comes from its resolved param group, so a rule-set can
    keep an INT8 frozen base under fp trainable groups (or vice versa)."""
    rules = as_rules(qcfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        eff = rules.config_for(jax.tree_util.keystr(path))
        if eff.weight_bits == 8:
            if leaf.ndim >= 2 and leaf.shape[-1] >= 32:
                out.append(quant.quantize_blockwise(
                    leaf, bits=8, block=eff.quant_block, symmetric=True))
            else:
                out.append(leaf)
        elif leaf.ndim >= 2 and jnp.issubdtype(leaf.dtype, jnp.floating):
            out.append(leaf.astype(param_dtype))
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def init_state(bundle: ModelBundle, qcfg, key,
               param_dtype=jnp.bfloat16, specs=None) -> TrainState:
    params = prepare_params(bundle.init_params(key), qcfg, param_dtype)
    opt = qgalore.init(params, qcfg, jax.random.fold_in(key, 1),
                       specs=specs)
    return TrainState(params, opt)


def abstract_state(bundle: ModelBundle, qcfg,
                   param_dtype=jnp.bfloat16, specs=None) -> TrainState:
    """eval_shape'd TrainState (no allocation) — for sharding and dry-run.
    ``specs`` carries runtime rank overrides (dynamic rank adaptation), so
    the abstract low-rank state matches a shrunk checkpoint."""
    return jax.eval_shape(
        lambda k: init_state(bundle, qcfg, k, param_dtype, specs),
        jax.random.PRNGKey(0))


def _specs_for(bundle, qcfg, param_dtype):
    params_abs = abstract_state(bundle, qcfg, param_dtype).params
    return qgalore.leaf_specs(params_abs, qcfg)


def _microbatches(batch, accum: int):
    def split(x):
        b = x.shape[0]
        return x.reshape(accum, b // accum, *x.shape[1:])
    return jax.tree_util.tree_map(split, batch)


def build_train_step(bundle: ModelBundle, qcfg,
                     tcfg: TrainConfig, *, impl: str = "fused",
                     accum: int = 1, param_dtype=jnp.bfloat16,
                     mesh=None, dp_compress: bool = False,
                     moe_ep_axis=None, state_shardings=None,
                     zero2_dims=None, specs=None):
    """Returns ``step(state, batch, lr, rng, refresh_masks) -> (state,
    metrics)`` with ``refresh`` a static flag baked per variant via
    functools.partial before jit.

    ``qcfg`` may be a plain ``QGaLoreConfig`` or a ``ParamRules`` rule-set
    (``repro.core.rules``): per-leaf recipes resolve through the param
    groups, frozen-group leaves are excluded from the grad-norm clip and
    pass through the optimizer untouched. The optimizer half of the step
    is the canonical transform chain
    (``repro.core.transform.qgalore_transform`` — project → quantized_adam
    → backproject → sr_requant), whose fused/batched executor is
    ``qgalore.apply_updates``.

    ``state_shardings``: the TrainState sharding pytree (mesh runs) —
    forwarded to the optimizer so the batched-leaf scan operands carry
    explicit layouts (quiets GSPMD's involuntary-rematerialization
    warnings under ZeRO sharding).

    ``zero2_dims``: {leaf index: scatter dim} from
    ``sharding.zero2_scatter_dims`` — steady-state low-rank gradients for
    these leaves are reduce-scattered over the DP axes along the SAME dim
    their ZeRO moment shard uses (each rank receives only its owned slice
    of the reduced gradient: (D-1)/D of the pmean's bytes and no
    replicated low-rank grads), instead of the replicated ``pmean``.

    ``dp_compress`` (beyond-paper): run the gradient phase under a
    partial-manual ``shard_map`` over the data(+pod) axes — the backward scan
    projects each layer's cotangent to rank r *before* any cross-replica
    communication, and ONE explicit ``pmean`` at the end reduces the
    LOW-RANK payload (≈ min(m,n)/r smaller, once per step instead of once
    per microbatch). The model axis stays auto (GSPMD). GSPMD alone places
    the DP all-reduce at the full-rank dW einsum — this is the fix.

    Refresh steps in this mode run the DISTRIBUTED subspace refresh
    (``qcfg.dist_refresh``): for each stacked GaLore leaf whose layer dim
    divides the DP world size, the full-rank gradient is reduce-scattered
    over the layer-stack dim (each device receives the *reduced* gradient
    for only its owned layers — half the wire bytes of an all-reduce and no
    full-rank replica anywhere), the owning shard runs the mask-gated SVD
    for its layers, projects its slice low-rank with the new P, and
    all-gathers only the small results (low-rank grads + INT4 P + sims).
    ``apply_updates`` then sees those leaves as already-refreshed steady
    leaves. RNG folding uses global unit indices, so the distributed refresh
    draws the same randoms as the replicated one. Leaves that don't divide
    (or expert-parallel leaves) fall back to the replicated in-optimizer
    refresh. Note the gradient-clip norm at such refresh steps is computed
    on the LOW-RANK payload for distributed leaves (exactly as every
    steady-state compressed step already does), so plain-mode and
    dist-refresh trajectories agree only to clip-scale tolerance.
    """
    from repro.kernels import dispatch as kdispatch
    from repro.models import layers as _layers
    if mesh is not None and mesh.size > 1:
        # A Mosaic kernel compiles only inside a fully manual shard_map;
        # GSPMD refuses it ("cannot be automatically partitioned").
        if kdispatch.default_backend() == "pallas-tpu":
            raise ValueError(
                f"the pallas-tpu kernels do not run under a {mesh.size}-"
                "device mesh yet: the sharded step does not wrap them in a "
                "manual shard_map (ROADMAP Speed 7). Set "
                "REPRO_KERNEL_BACKEND=ref for multi-device runs.")
    rules = as_rules(qcfg)
    base = rules.base
    if specs is None:
        specs = _specs_for(bundle, rules, param_dtype)
        if mesh is not None:
            # shard-dim-aware contract: direct callers get the same
            # (shard_dim, tp) annotations the trainer derives, so the
            # batching signatures never mix differently-TP-sharded leaves
            from repro.distributed import sharding as _sh
            specs = _sh.annotate_tp(specs, mesh)
    tx = transform.qgalore_transform(rules, specs=specs)
    any_galore = any(s.galore for s in specs)
    seg_keys = {bundle.seg_key(i) for i in range(len(bundle.segments))}
    zero2_dims = dict(zero2_dims or {})

    logging.getLogger(__name__).info(
        "train step: kernel backend=%s quantized_dense=%s (backend=%s) "
        "fused_update=%s batch_leaves=%s groups=%s",
        kdispatch.default_backend("fused_qgalore_update"),
        _layers.QUANTIZED_DENSE,
        kdispatch.default_backend("quantized_dense"),
        base.fused_update, base.batch_leaves,
        sorted({s.group for s in specs}))

    def grad_phase(params, proj_trees, batch):
        """(loss, metrics, grads) on the (possibly shard-local) batch."""
        def one_micro(mb):
            if impl == "fused":
                return stack.fused_value_and_grad(bundle, params, mb,
                                                  proj_trees)
            return stack.simple_value_and_grad(bundle, params, mb)

        if accum > 1:
            micro = _microbatches(batch, accum)

            def body(acc, mb):
                (loss, metrics), g = one_micro(mb)
                acc_g, acc_loss = acc
                acc_g = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(a.dtype), acc_g, g)
                return (acc_g, acc_loss + loss), metrics

            zero_g = jax.eval_shape(lambda b: one_micro(b)[1],
                                    jax.tree_util.tree_map(
                                        lambda x: x[0], micro))
            zero_g = jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, jnp.float32), zero_g)
            from repro.models.base import scan_layers
            (g_sum, loss_sum), metrics = scan_layers(
                body, (zero_g, jnp.zeros((), jnp.float32)), micro)
            grads = jax.tree_util.tree_map(lambda g: g / accum, g_sum)
            loss = loss_sum / accum
            metrics = jax.tree_util.tree_map(lambda m: m[-1], metrics)
        else:
            (loss, metrics), grads = one_micro(batch)
        return loss, metrics, grads

    dp_axes: tuple = ()
    dp_size = 1
    refresh_axes: tuple = ()
    refresh_world = 1
    if dp_compress and mesh is not None:
        from jax.sharding import PartitionSpec as P
        dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        dp_size = int(np.prod([mesh.shape[a] for a in dp_axes])) \
            if dp_axes else 1
        # 2-D (data x model) mesh: the distributed refresh scatters the
        # layer stack over the COMBINED front — D*t ranks each own
        # L/(D*t) layers, so per-device refresh memory shrinks by the
        # model degree too and the per-layer SVD stays the bit-exact
        # replicated computation (no Gram/eigh numerics drift).
        refresh_axes, refresh_world = dp_axes, dp_size
        if dp_axes and "model" in mesh.axis_names \
                and int(mesh.shape["model"]) > 1:
            refresh_axes = dp_axes + ("model",)
            refresh_world = dp_size * int(mesh.shape["model"])

    # BF16 grad reduction (paper §3.1 keeps gradients BF16) halves the
    # residual full-rank payloads on the wire. It is OFF by default because
    # XLA:CPU cannot lower a bf16 psum inside a shard_map body — compilation
    # crashes with "Invalid binary instruction opcode copy"
    # (hlo_instruction.cc): the CPU emitter is missing the bf16<->f32
    # convert-around-reduce pattern the TPU backend inserts. The workaround
    # is simply to reduce in f32 on CPU (this flag) — numerics are a
    # superset of the bf16 reduction, so CI exercises the same code path at
    # higher precision. Set REPRO_BF16_REDUCE=1 on TPU backends, where the
    # cast is applied right before the pmean below. See EXPERIMENTS.md
    # §Perf iteration 4.
    import os as _os
    _BF16_REDUCE = _os.environ.get("REPRO_BF16_REDUCE", "0") == "1"

    def _is_expert(path: str) -> bool:
        return moe_ep_axis is not None and "experts_" in path

    def _manual_specs(tree):
        """Per-leaf specs over the MANUAL axes: expert leaves ride the
        shard_map sharded on their E dim (index 1: stacks are (L, E, ...)),
        everything else enters replicated."""
        from jax.sharding import PartitionSpec as P
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        specs = []
        for path, leaf in flat:
            pstr = jax.tree_util.keystr(path)
            nd = getattr(leaf, "ndim", 0)
            if _is_expert(pstr) and nd >= 3:
                parts = [None] * nd
                parts[1] = moe_ep_axis
                specs.append(P(*parts))
            else:
                specs.append(P())
        return jax.tree_util.tree_unflatten(treedef, specs)

    # Leaves eligible for the distributed refresh: stacked GaLore leaves
    # whose layer-stack dim divides the DP world size (so psum_scatter can
    # tile it), excluding expert-parallel leaves (their gradients are owned
    # per EP shard and never cross the DP front whole).
    dist_refresh_ok = set()
    if dp_axes and any_galore and base.dist_refresh:
        for i, sp in enumerate(specs):
            if (sp.galore and sp.batch and sp.batch[0] % dp_size == 0
                    and not _is_expert(sp.path)):
                dist_refresh_ok.add(i)

    # Per-leaf refresh front: on a 2-D (data x model) mesh, leaves whose
    # layer stack also divides D*t scatter over the COMBINED front (each
    # of the D*t ranks owns L/(D*t) layers); everything else keeps the
    # DP-only front. The per-layer SVD is the same bit-exact computation
    # either way — only the ownership map changes.
    dist_front = {
        i: ((refresh_axes, refresh_world)
            if refresh_world > dp_size
            and specs[i].batch[0] % refresh_world == 0
            else (dp_axes, dp_size))
        for i in dist_refresh_ok}

    # ZeRO-2 gradient reduce-scatter only applies where the steady-state
    # gradient is LOW-RANK (fused backward) and the leaf's moments are
    # actually DP-sharded; drop anything else defensively.
    if impl != "fused" or not dp_axes:
        zero2_dims = {}
    zero2_dims = {i: d for i, d in zero2_dims.items()
                  if specs[i].galore and not _is_expert(specs[i].path)
                  and specs[i].low_shape[d] % dp_size == 0}

    def grad_phase_dp(params, proj_trees, batch, refresh_proj=None,
                      refresh_masks=None, rng=None):
        """The manual-DP gradient phase.

        Steady state (``refresh_proj is None``): one pmean on the low-rank
        payload. Refresh steps: additionally runs the distributed subspace
        refresh for the leaves in ``refresh_proj`` (keys = str(leaf index))
        and returns their new projections + similarities; those leaves'
        gradients come back LOW-RANK.
        """
        from jax.sharding import PartitionSpec as P
        other_axes = tuple(a for a in dp_axes if a != moe_ep_axis)
        dist_now = sorted(int(k) for k in refresh_proj) \
            if refresh_proj is not None else []
        # steady state with low-rank emission only: at refresh steps (or
        # with the fused backward off) galore grads are full-rank
        zero2_now = dict(zero2_dims) \
            if refresh_proj is None and proj_trees else {}

        def inner(p, pt, b):
            loss, metrics, grads = grad_phase(p, pt, b)
            # paper §3.1 keeps gradients in BF16 — reduce in BF16 too
            # (halves the remaining full-rank payloads, e.g. gemma's 256k-
            # vocab embedding grad); ONE reduction, on the low-rank payload.
            # Expert-parallel leaves are OWNED per shard (the all_to_all
            # already routed every token to the owner) — no reduction over
            # the EP axis at all.
            flat, treedef = jax.tree_util.tree_flatten_with_path(grads)
            out = []
            for i, (path, g) in enumerate(flat):
                pstr = jax.tree_util.keystr(path)
                if specs[i].frozen:
                    # frozen-group leaves never reach the optimizer —
                    # don't pay the cross-replica reduce for a gradient
                    # that is discarded (the frozen embedding is the
                    # dominant wire payload in the fine-tune workload);
                    # zeros keep the replicated out-spec truthful.
                    out.append(jnp.zeros_like(g))
                    continue
                if i in dist_now:
                    # distributed refresh, phase 1: reduce-scatter the
                    # full-rank gradient over the layer stack — each shard
                    # leaves this region holding the REDUCED gradient of
                    # its owned layers only ((D-1)/D of an all-reduce's
                    # bytes, and no device ever holds a full-rank replica).
                    out.append(jax.lax.psum_scatter(
                        g.astype(jnp.float32), dp_axes,
                        scatter_dimension=0, tiled=True) / dp_size)
                    continue
                if i in zero2_now and tuple(g.shape) == specs[i].low_shape:
                    # ZeRO-2: the low-rank gradient is reduce-scattered
                    # along the SAME dim the leaf's ZeRO moment shard uses
                    # — each DP rank leaves with only its owned slice of
                    # the reduced gradient, aligned with the state it
                    # updates (no replicated low-rank grads on the wire).
                    out.append(jax.lax.psum_scatter(
                        g.astype(jnp.float32), dp_axes,
                        scatter_dimension=zero2_now[i], tiled=True)
                        / dp_size)
                    continue
                if _BF16_REDUCE and g.dtype == jnp.float32:
                    g = g.astype(jnp.bfloat16)
                if _is_expert(pstr):
                    if other_axes:
                        g = jax.lax.pmean(g, other_axes)
                else:
                    g = jax.lax.pmean(g, dp_axes)
                out.append(g)
            grads = jax.tree_util.tree_unflatten(treedef, out)
            loss = jax.lax.pmean(loss, dp_axes)
            metrics = jax.tree_util.tree_map(
                lambda m: jax.lax.pmean(m.astype(jnp.float32), dp_axes),
                metrics)
            return loss, metrics, grads

        batch_specs = jax.tree_util.tree_map(
            lambda x: P(dp_axes, *([None] * (x.ndim - 1))), batch)

        # grads have the params' tree structure but ONE (virtual) leaf per
        # QTensor — build their out_specs at that granularity
        from repro.core import quant as _q
        gflat, gtreedef = jax.tree_util.tree_flatten_with_path(
            params, is_leaf=_q.is_qtensor)
        gspecs = []
        for i, (path, leaf) in enumerate(gflat):
            pstr = jax.tree_util.keystr(path)
            nd = len(leaf.shape)
            if i in dist_now:
                # reduced full-rank gradient leaves the region layer-
                # sharded over the DP front (psum_scatter tiling)
                gspecs.append(P(dp_axes, *([None] * (nd - 1))))
            elif i in zero2_now:
                # ZeRO-2: low-rank gradient leaves sharded on its moment
                # dim (same rank count as the virtual shape)
                parts = [None] * len(specs[i].low_shape)
                parts[zero2_now[i]] = dp_axes
                gspecs.append(P(*parts))
            elif _is_expert(pstr) and nd >= 3:
                parts = [None] * nd
                parts[1] = moe_ep_axis
                gspecs.append(P(*parts))
            else:
                gspecs.append(P())
        grads_specs = jax.tree_util.tree_unflatten(gtreedef, gspecs)

        loss, metrics, grads = jax.shard_map(
            inner, mesh=mesh, axis_names=set(dp_axes),
            in_specs=(_manual_specs(params), _manual_specs(proj_trees),
                      batch_specs),
            out_specs=(P(), P(), grads_specs),
            check_vma=False)(params, proj_trees, batch)
        if not dist_now:
            return loss, metrics, grads, {}, {}, {}

        # ---- distributed refresh, phase 2: per-owner SVD + broadcast ----
        # A SECOND region, manual over ALL mesh axes: the mask-gated SVD
        # scan lowers to custom calls the partial-manual SPMD partitioner
        # cannot propagate shardings through (same XLA limitation the
        # manual-EP MoE documents in models/moe.py) — in a fully-manual
        # region they are plain local ops. Only the small refresh state
        # enters (layer-sharded reduced grads, P, masks); params and batch
        # stay out, so the model axes simply see replicated copies.
        g_flat2, g_treedef2 = jax.tree_util.tree_flatten(grads)
        gd = {}
        for i in dist_now:
            g = g_flat2[i]
            front, world = dist_front[i]
            if world > dp_size:
                # re-tile the layer-sharded reduced gradient over the
                # combined (data x model) front BEFORE the fully-manual
                # region: each of the D*t ranks owns L/(D*t) layers, so no
                # rank re-materializes even the DP-front shard, let alone
                # a full-rank replica.
                g = jax.lax.with_sharding_constraint(
                    g, jax.sharding.NamedSharding(
                        mesh, P(front, *([None] * (g.ndim - 1)))))
            gd[str(i)] = g

        def refresh_inner(gd, pd, md, key, sid, sid_all):
            new_low, new_proj, sims, ratios = {}, {}, {}, {}
            for i in dist_now:
                sp = specs[i]
                front, world = dist_front[i]
                # sid enters sharded over its front: the local element IS
                # this shard's flat index (lax.axis_index lowers to
                # PartitionId, which XLA:CPU rejects — see repro.compat).
                sidx = sid_all[0] if world > dp_size else sid[0]
                b_loc = sp.nbatch // world
                m, n = sp.mat_shape
                g_loc = gd[str(i)].reshape(b_loc, m, n)
                nlead = len(sp.batch)
                P_flat = jax.tree_util.tree_map(
                    lambda x: x.reshape((b_loc,) + x.shape[nlead:]),
                    pd[str(i)])
                mask_flat = md[str(i)].reshape(b_loc)
                idx = jnp.arange(b_loc, dtype=jnp.int32) + sidx * b_loc
                P_new_flat, sim_loc, ratio_loc = qgalore.refresh_slice(
                    g_loc, P_flat, mask_flat, idx,
                    qgalore._eff_cfg(sp, rules), sp.rank,
                    sp.side, jax.random.fold_in(key, i))
                low_loc = stack.project_leaf(g_loc, P_new_flat, sp.side)
                gather = functools.partial(
                    compat.all_gather_tiled, axes=front, axis=0,
                    world=world, index=sidx)
                new_low[str(i)] = gather(low_loc).reshape(sp.low_shape)
                new_proj[str(i)] = jax.tree_util.tree_map(
                    lambda x: gather(x).reshape(sp.batch + x.shape[1:]),
                    P_new_flat)
                sims[sp.path] = gather(sim_loc)
                if ratio_loc is not None:
                    ratios[sp.path] = gather(ratio_loc)
            return new_low, new_proj, sims, ratios

        front0 = lambda t: {
            k: jax.tree_util.tree_map(
                lambda x: P(dist_front[int(k)][0],
                            *([None] * (x.ndim - 1))), v)
            for k, v in t.items()}
        repl = lambda t: jax.tree_util.tree_map(lambda _: P(), t)
        sims_out_specs = {specs[i].path: P() for i in dist_now}
        ratios_out_specs = {
            specs[i].path: P() for i in dist_now
            if qgalore._eff_cfg(specs[i], rules).adaptive_rank}
        shard_ids = jnp.arange(dp_size, dtype=jnp.int32)
        shard_ids_all = jnp.arange(refresh_world, dtype=jnp.int32)
        new_low, new_proj, sims, ratios = jax.shard_map(
            refresh_inner, mesh=mesh,
            in_specs=(front0(gd), front0(refresh_proj),
                      front0(refresh_masks), P(), P(dp_axes),
                      P(refresh_axes)),
            out_specs=(repl(gd), repl(refresh_proj), sims_out_specs,
                       ratios_out_specs),
            check_vma=False)(gd, refresh_proj, refresh_masks, rng,
                             shard_ids, shard_ids_all)
        for i in dist_now:
            g_flat2[i] = new_low[str(i)]
        grads = jax.tree_util.tree_unflatten(g_treedef2, g_flat2)
        return loss, metrics, grads, new_proj, sims, ratios

    def step(state: TrainState, batch, lr, rng,
             refresh_masks: Optional[Dict[int, jax.Array]] = None,
             refresh: bool = False):
        params, opt = state

        # projection trees for the fused backward (low-rank emission) —
        # skipped at refresh steps (full-rank grads needed for SVD).
        # Non-segment galore leaves (head, embedding) ride along so their
        # cotangents also go low-rank before clip / DP reduction.
        proj_trees: Dict[str, Any] = {}
        if impl == "fused" and any_galore and not refresh:
            for k, sub in opt.proj.items():
                leaves = jax.tree_util.tree_leaves(
                    sub, is_leaf=lambda x: x is None or quant.is_qtensor(x))
                if k in seg_keys or any(l is not None for l in leaves):
                    proj_trees[k] = sub

        dist_sims: Dict[str, jax.Array] = {}
        dist_ratios: Dict[str, jax.Array] = {}
        if dp_axes:
            dist_idx = [i for i in sorted(dist_refresh_ok)
                        if refresh and refresh_masks and i in refresh_masks]
            if dist_idx:
                # distributed refresh: each owning shard recomputes its
                # layers' P inside the gradient shard_map; apply_updates
                # then treats these leaves as steady (low-rank grad, new P).
                pr_flat, pr_treedef = jax.tree_util.tree_flatten(
                    opt.proj,
                    is_leaf=lambda x: quant.is_qtensor(x) or x is None)
                rp = {str(i): pr_flat[i] for i in dist_idx}
                rm = {str(i): jnp.asarray(refresh_masks[i]).reshape(
                    specs[i].batch) for i in dist_idx}
                (loss, metrics, grads, new_proj, dist_sims,
                 dist_ratios) = grad_phase_dp(
                    params, proj_trees, batch, refresh_proj=rp,
                    refresh_masks=rm, rng=rng)
                for i in dist_idx:
                    pr_flat[i] = new_proj[str(i)]
                opt = opt._replace(proj=jax.tree_util.tree_unflatten(
                    pr_treedef, pr_flat))
                refresh_masks = {i: m for i, m in refresh_masks.items()
                                 if i not in set(dist_idx)}
            else:
                loss, metrics, grads, _, _, _ = grad_phase_dp(
                    params, proj_trees, batch)
        else:
            loss, metrics, grads = grad_phase(params, proj_trees, batch)

        with profile.scope("repro.optimizer"):
            grads, gnorm = transform.clip_by_global_norm(
                grads, tcfg.grad_clip, specs=specs)
            new_params, new_opt, opt_metrics = tx.update(
                grads, opt, params, lr=lr, rng=rng,
                refresh_masks=refresh_masks, refresh=refresh, specs=specs,
                shardings=state_shardings)
        if dist_sims:
            opt_metrics = {**opt_metrics,
                           "sims": {**dist_sims,
                                    **opt_metrics.get("sims", {})},
                           "ratios": {**dist_ratios,
                                      **opt_metrics.get("ratios", {})}}
        metrics = {**metrics, "loss": loss, "grad_norm": gnorm,
                   "lr": jnp.asarray(lr, jnp.float32)}
        return TrainState(new_params, new_opt), metrics, opt_metrics

    # introspection for tests / benchmarks: which front each dist-refresh
    # leaf scatters over, and the mesh-wide refresh geometry
    step.dist_front = dict(dist_front)
    step.refresh_axes = refresh_axes
    step.refresh_world = refresh_world
    step.dp_axes = dp_axes
    step.dp_size = dp_size
    return step, specs
