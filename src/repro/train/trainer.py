"""The training loop: adaptive subspace control, checkpoint/auto-resume,
fault tolerance, straggler detection.

Fault-tolerance contract (designed for 1000+-node operation, exercised at
container scale by tests):

* every step is replayable: data is a pure function of step, RNG keys are
  folded from (seed, step), the controller state is checkpointed — so a
  restart from step N reproduces the exact trajectory;
* ``run()`` retries a failed step after restoring the last checkpoint
  (``max_failures`` budget) — the single-process analogue of a coordinator
  restarting a pod after a node failure;
* a straggler monitor tracks the running median step time and flags steps
  slower than ``straggler_factor``× the median (on a real cluster the hook
  feeds preemption/re-scheduling; here it feeds metrics + logs).
"""
from __future__ import annotations

import functools
import json
import logging
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import QGaLoreConfig, TrainConfig
from repro.core import adaptive, optimizers, qgalore
from repro.core.rules import as_rules, group_assignment
from repro.data.synthetic import batch_for_bundle
from repro.kernels import profile
from repro.models.base import ModelBundle
from repro.train import checkpoint as ckpt_lib
from repro.train import step as step_lib

log = logging.getLogger("repro.trainer")


@dataclass
class StragglerMonitor:
    factor: float = 3.0
    window: int = 50
    times: List[float] = field(default_factory=list)
    events: List[Dict] = field(default_factory=list)

    def observe(self, step: int, dt: float) -> bool:
        self.times.append(dt)
        if len(self.times) > self.window:
            self.times.pop(0)
        med = float(np.median(self.times))
        if len(self.times) >= 10 and dt > self.factor * med:
            self.events.append({"step": step, "dt": dt, "median": med})
            log.warning("straggler step %d: %.3fs vs median %.3fs",
                        step, dt, med)
            return True
        return False


class Trainer:
    def __init__(self, bundle: ModelBundle, tcfg: TrainConfig,
                 qcfg, *, cell=None, impl: str = "fused",
                 param_dtype=jnp.float32, accum: int = 1,
                 mesh=None, zero_shard: bool = False,
                 zero2: Optional[bool] = None,
                 fault_hook: Optional[Callable[[int], None]] = None):
        """``qcfg``: a plain ``QGaLoreConfig`` or a ``ParamRules`` rule-set
        (``repro.core.rules``) — per-group ranks / intervals / bits /
        frozen groups resolve through the param-group rules; a plain
        config is the single-default-group case (bit-identical to the
        pre-rules trainer).

        ``mesh``: run the step distributed — params/optimizer state are
        placed with the ``distributed.sharding`` rules, batches are sharded
        over the DP axes, and the jitted steps pin state in/out shardings
        so the layout survives every step. ``zero_shard`` additionally
        partitions the quantized optimizer state (low-rank Adam moments +
        INT4 projections) over the DP axes — ZeRO-style, each DP rank owns
        a 1/D slice, gathered only where the fused update consumes it.
        ``zero2`` (default: follows ``zero_shard``) reduce-scatters the
        steady-state low-rank gradients along each leaf's moment-shard dim
        instead of ``pmean``-replicating them (requires
        ``compress_dp_grads``)."""
        self.rules = as_rules(qcfg)
        self.qcfg = self.rules.base
        self.bundle = bundle
        self.tcfg = tcfg
        self.impl = impl
        self.param_dtype = param_dtype
        from repro.config import ShapeCell
        self.cell = cell or ShapeCell("train", tcfg.seq_len,
                                      tcfg.global_batch, "train")
        self.fault_hook = fault_hook          # tests inject failures here
        self.stragglers = StragglerMonitor()
        self.mesh = mesh
        self.zero_shard = zero_shard
        self.zero2 = zero_shard if zero2 is None else zero2
        dp_compress = self.qcfg.compress_dp_grads and mesh is not None
        if zero2 and not (mesh is not None and zero_shard and dp_compress):
            # an explicit force-on that cannot take effect must not
            # silently fall back to the replicated pmean
            raise ValueError(
                "zero2=True requires a mesh, zero_shard=True, and "
                "compress_dp_grads=True (the reduce-scatter dims come "
                "from the ZeRO moment sharding inside the compressed-DP "
                f"shard_map); got mesh={mesh is not None}, "
                f"zero_shard={zero_shard}, "
                f"compress_dp_grads={self.qcfg.compress_dp_grads}")

        self._accum = accum
        self._dp_compress = dp_compress
        self._rank_overrides: Dict[str, int] = {}
        self._build_execution()

        self.controller = adaptive.SubspaceController(self._base_specs,
                                                      self.rules)
        self.mgr = None
        if tcfg.checkpoint_dir:
            self.mgr = ckpt_lib.CheckpointManager(
                tcfg.checkpoint_dir, max_to_keep=tcfg.keep_checkpoints,
                async_save=tcfg.async_checkpoint)

        self.state = step_lib.init_state(
            bundle, self.rules, jax.random.PRNGKey(tcfg.seed), param_dtype,
            specs=self.specs)
        if self.state_sharding is not None:
            self.state = jax.device_put(self.state, self.state_sharding)
        self.start_step = 0
        self.history: List[Dict[str, float]] = []

    # ------------------------------------------------------------------
    def _build_execution(self):
        """(Re)derive specs / shardings / the compiled step pair under the
        current rank overrides. Called at construction (no overrides), when
        a restore brings in a shrunk checkpoint's overrides, and after each
        runtime rank migration — a rank change alters state shapes, the
        batching signatures, the ZeRO layout, and the DP wire payload, so
        the whole execution stack is rebuilt (two fresh jit variants)."""
        self._base_specs = step_lib._specs_for(self.bundle, self.rules,
                                               self.param_dtype)
        self.specs = qgalore.apply_rank_overrides(self._base_specs,
                                                  self._rank_overrides)
        mesh, tcfg = self.mesh, self.tcfg
        if mesh is not None:
            # shard-dim annotation BEFORE anything consumes the specs: the
            # batching signatures, the optimizer-state placement and the
            # TP-aware refresh fronts must all see the same (shard_dim, tp)
            # a leaf's weight actually gets from the placement rules.
            from repro.distributed import sharding as _sh
            self.specs = _sh.annotate_tp(self.specs, mesh)
        self.state_sharding = None
        self._batch_sharding = None
        zero2_dims = None
        if mesh is not None:
            from repro.distributed import sharding as sh
            abs_state = self._abstract_state()
            zaxes = sh.zero_axes_for(mesh) if self.zero_shard else ()
            self.state_sharding = step_lib.TrainState(
                sh.param_sharding(abs_state.params, mesh),
                sh.opt_state_sharding(abs_state.params, abs_state.opt,
                                      self.rules, mesh, zero_axes=zaxes,
                                      specs=self.specs))
            if self.zero2 and zaxes and self._dp_compress:
                zero2_dims = sh.zero2_scatter_dims(
                    self.state_sharding.opt, self.specs, zaxes)
            elif self.zero2 and zaxes and not self._dp_compress:
                # zero_shard-implied default that can't take effect —
                # say so rather than silently keeping the pmean path
                log.info("zero2 inactive: compress_dp_grads is off (the "
                         "reduce-scatter lives in the compressed-DP "
                         "shard_map); pass --compress / "
                         "compress_dp_grads=True to enable it")

        raw_step, _ = step_lib.build_train_step(
            self.bundle, self.rules, tcfg, impl=self.impl,
            accum=self._accum, param_dtype=self.param_dtype, mesh=mesh,
            dp_compress=self._dp_compress,
            state_shardings=self.state_sharding, zero2_dims=zero2_dims,
            specs=self.specs)
        self._raw_step = raw_step

        if mesh is not None:
            from repro.distributed import sharding as sh
            batch_abs = jax.eval_shape(
                lambda: batch_for_bundle(self.bundle, self.cell, 0,
                                         tcfg.seed))
            self._batch_sharding = sh.data_sharding(batch_abs, mesh)
            rep = sh.replicated(mesh)
            # positional wrappers: jit in_shardings rejects kwargs, and the
            # out sharding pins the (ZeRO) state layout across steps
            self._step_normal = jax.jit(
                lambda st, b, lr, rng: raw_step(
                    st, b, lr, rng, refresh_masks=None, refresh=False),
                in_shardings=(self.state_sharding, self._batch_sharding,
                              rep, rep),
                out_shardings=(self.state_sharding, None, None))
            self._step_refresh = jax.jit(
                lambda st, b, lr, rng, masks: raw_step(
                    st, b, lr, rng, refresh_masks=masks, refresh=True),
                in_shardings=(self.state_sharding, self._batch_sharding,
                              rep, rep, rep),
                out_shardings=(self.state_sharding, None, None))
        else:
            self._step_normal = jax.jit(
                functools.partial(raw_step, refresh=False,
                                  refresh_masks=None))
            self._step_refresh = jax.jit(
                functools.partial(raw_step, refresh=True),
                static_argnames=())

    def _abstract_state(self):
        return step_lib.abstract_state(self.bundle, self.rules,
                                       self.param_dtype, specs=self.specs)

    def _adaptive_rank_enabled(self) -> bool:
        return self.qcfg.adaptive_rank or any(
            g.adaptive_rank for g in self.rules.groups)

    def maybe_restore(self) -> int:
        if self.mgr is None or self.mgr.latest_step() is None:
            return 0
        # group-metadata compatibility FIRST (meta only, no arrays): a
        # checkpoint written under different param-group rules (or holding
        # rank-shrunk state this run cannot adapt to) has differently-
        # shaped (or missing) optimizer state per leaf — fail with the
        # loud meta-mismatch error, not a shape error from the array
        # restore.
        meta = self.mgr.read_meta()
        ckpt_lib.check_rules_compat(meta, self.rules.fingerprint(),
                                    group_assignment(self._base_specs),
                                    adaptive_rank=
                                    self._adaptive_rank_enabled())
        # adopt the checkpoint's rank overrides before touching arrays:
        # the abstract state / shardings / compiled steps must describe
        # the SHRUNK shapes the checkpoint actually holds
        overrides = {str(k): int(v)
                     for k, v in (meta.get("rank_overrides") or {}).items()}
        if overrides != self._rank_overrides:
            self._rank_overrides = overrides
            self._build_execution()
            self.controller.update_specs(self.specs)
        # state_sharding may describe a different mesh than the checkpoint
        # was saved on — restore is elastic (arrays are host-gathered at
        # save; device_put here re-places them under the current rules)
        state, meta = self.mgr.restore(None, self._abstract_state(),
                                       self.state_sharding)
        self.state = state
        if meta.get("controller"):
            self.controller.from_json(meta["controller"])
        self.start_step = int(meta["step"]) + 1
        log.info("restored checkpoint at step %d", meta["step"])
        return self.start_step

    def save(self, step: int):
        if self.mgr is None:
            return
        self.mgr.save(step, self.state,
                      {"controller": self.controller.to_json(),
                       "rules_fingerprint": self.rules.fingerprint(),
                       "groups": group_assignment(self._base_specs),
                       "rank_overrides": self.controller.current_ranks(),
                       # provenance only — restore is mesh-elastic and
                       # never requires the saving layout (checkpoint.py)
                       "mesh": None if self.mesh is None else
                       {a: int(self.mesh.shape[a])
                        for a in self.mesh.axis_names}})

    # ------------------------------------------------------------------
    def _run_one(self, step: int):
        if self.fault_hook is not None:
            self.fault_hook(step)             # may raise (simulated failure)
        with profile.span("repro.batch"):     # the step's inputs
            batch = batch_for_bundle(self.bundle, self.cell, step,
                                     self.tcfg.seed)
            if self._batch_sharding is not None:
                batch = jax.device_put(batch, self._batch_sharding)
            lr = optimizers.lr_at(step, self.tcfg)
            rng = jax.random.fold_in(
                jax.random.PRNGKey(self.tcfg.seed + 17), step)
        with profile.span("repro.controller"):
            masks = self.controller.masks_for_step(step) \
                if self.controller.units else {}
        if masks:
            # pass masks for EVERY galore leaf (False where not due) so the
            # refresh variant compiles exactly once
            jmasks = {
                i: jnp.asarray(masks[i]) if i in masks
                else jnp.zeros((s.nbatch,), bool)
                for i, s in enumerate(self.specs) if s.galore}
            with profile.span("repro.dispatch"):
                state, metrics, opt_metrics = self._step_refresh(
                    self.state, batch, lr, rng, jmasks)
            sims = {k: np.asarray(v)
                    for k, v in opt_metrics.get("sims", {}).items()}
            ratios = {k: np.asarray(v)
                      for k, v in opt_metrics.get("ratios", {}).items()}
            self.controller.observe(step, masks, sims, ratios)
            decisions = self.controller.take_rank_decisions()
        else:
            with profile.span("repro.dispatch"):
                state, metrics, _ = self._step_normal(self.state, batch, lr,
                                                      rng)
            decisions = []
        self.state = state
        if decisions:
            self._migrate_ranks(step, decisions)
        return metrics

    def _migrate_ranks(self, step: int, decisions):
        """Apply pending rank-shrink decisions from the controller:
        truncate the live low-rank state (INT8 moments + INT4 projection,
        deterministic), swap in rank-overridden specs, and rebuild the
        compiled steps / shardings around the new shapes."""
        i_flat, i_tree = jax.tree_util.tree_flatten(
            self.state.opt.inner, is_leaf=qgalore._is_inner_leaf)
        pr_flat, pr_tree = jax.tree_util.tree_flatten(
            self.state.opt.proj,
            is_leaf=lambda x: qgalore.quant.is_qtensor(x) or x is None)
        for idx, old, new in decisions:
            spec = self.specs[idx]
            i_flat[idx], pr_flat[idx] = qgalore.migrate_rank_state(
                i_flat[idx], pr_flat[idx], spec, new, self.rules)
            self._rank_overrides[spec.path] = new
            log.info("rank transition at step %d: %s %d -> %d "
                     "(explained-variance threshold held %d refreshes)",
                     step, spec.path, old, new,
                     self.controller._cfg_for(idx).rank_patience)
        self.state = step_lib.TrainState(
            self.state.params,
            qgalore.QGaLoreState(
                inner=jax.tree_util.tree_unflatten(i_tree, i_flat),
                proj=jax.tree_util.tree_unflatten(pr_tree, pr_flat),
                count=self.state.opt.count))
        self._build_execution()
        self.controller.update_specs(self.specs)
        if self.state_sharding is not None:
            # ZeRO re-shard: the shrunk arrays re-place under the sharding
            # derived from the NEW shapes (divisibility re-checked)
            self.state = jax.device_put(self.state, self.state_sharding)

    def run(self, steps: Optional[int] = None, max_failures: int = 3):
        steps = steps if steps is not None else self.tcfg.steps
        failures = 0
        step = self.start_step
        while step < steps:
            t0 = time.monotonic()
            try:
                metrics = self._run_one(step)
            except Exception as e:   # noqa: BLE001 — fault-tolerance path
                failures += 1
                log.warning("step %d failed (%s); recovering (%d/%d)",
                            step, e, failures, max_failures)
                if failures > max_failures:
                    raise
                if self.mgr is not None and self.mgr.latest_step() is not None:
                    self.maybe_restore()
                    step = self.start_step
                continue
            # reading the scalars waits for the step program to finish on
            # the device, so dt is the step's time and not its dispatch
            row = {k: float(v) for k, v in metrics.items()
                   if np.ndim(v) == 0}
            dt = time.monotonic() - t0
            self.stragglers.observe(step, dt)
            row["step"] = step
            row["dt"] = dt
            self.history.append(row)
            if self.tcfg.log_every and step % self.tcfg.log_every == 0:
                log.info("step %d loss %.4f (%.2fs)", step,
                         row.get("loss", float("nan")), dt)
            if (self.tcfg.checkpoint_every
                    and step % self.tcfg.checkpoint_every == 0
                    and step > 0):
                self.save(step)
            step += 1
        if self.mgr is not None:
            self.save(steps - 1)
            self.mgr.wait()
        return self.history

    # ------------------------------------------------------------------
    def eval_loss(self, n_batches: int = 4, offset: int = 10_000) -> float:
        """Held-out loss on batches the training never sees."""
        from repro.models import base
        losses = []
        # INT8 params are consumed natively by the model (quantized_dense)
        fn = jax.jit(lambda p, b: base.loss_fn(self.bundle, p, b)[0])
        for i in range(n_batches):
            batch = batch_for_bundle(self.bundle, self.cell, offset + i,
                                     self.tcfg.seed + 1)
            losses.append(float(fn(self.state.params, batch)))
        return float(np.mean(losses))
