"""End-to-end trainer tests: learning, checkpoint/restore determinism,
fault recovery, straggler accounting, adaptive subspace behavior."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import QGaLoreConfig, ShapeCell, TrainConfig, replace
from repro.core.optimizers import preset
from repro.models import model_zoo
from repro.train.trainer import Trainer

CELL = ShapeCell("tiny", seq_len=32, global_batch=4, kind="train")


def make_trainer(tmp_path=None, optimizer="qgalore", steps=12, impl="fused",
                 fault_hook=None, ckpt_every=0, seed=0, lr=1e-2):
    bundle = model_zoo.build_arch("llama-60m", smoke=True, dtype=jnp.float32)
    qcfg = preset(optimizer, QGaLoreConfig(
        rank=8, min_dim=32, update_interval=4, adaptive_k=1,
        cos_threshold=0.3))
    tcfg = TrainConfig(
        seed=seed, global_batch=4, seq_len=32, steps=steps,
        learning_rate=lr, warmup_steps=2, grad_clip=1.0,
        checkpoint_dir=str(tmp_path) if tmp_path else "",
        checkpoint_every=ckpt_every, log_every=0,
        async_checkpoint=False)
    return Trainer(bundle, tcfg, qcfg, cell=CELL, impl=impl,
                   param_dtype=jnp.float32, fault_hook=fault_hook)


class TestLearning:
    def test_loss_decreases_qgalore(self):
        tr = make_trainer(steps=55)
        hist = tr.run()
        first = np.mean([h["loss"] for h in hist[:5]])
        last = np.mean([h["loss"] for h in hist[-5:]])
        assert last < first - 0.15, (first, last)

    def test_loss_decreases_full_baseline(self):
        tr = make_trainer(steps=30, optimizer="full", lr=3e-3)
        hist = tr.run()
        first = np.mean([h["loss"] for h in hist[:5]])
        last = np.mean([h["loss"] for h in hist[-5:]])
        assert last < first - 0.1

    def test_qgalore_tracks_full_adam(self):
        """Paper Table 1 claim at micro scale: Q-GaLore stays in the same
        loss regime as Full Adam (GaLore's α=0.25 slows the very early
        trajectory; parity at convergence is shown in benchmarks)."""
        losses = {}
        for opt in ("full", "qgalore"):
            tr = make_trainer(steps=55, optimizer=opt)
            hist = tr.run()
            losses[opt] = np.mean([h["loss"] for h in hist[-5:]])
        assert losses["qgalore"] < losses["full"] + 0.8, losses

    def test_svd_calls_saved_by_adaptive(self):
        tr = make_trainer(steps=30)
        tr.run()
        used = tr.controller.total_svd_count()
        base = tr.controller.baseline_svd_count(30)
        assert 0 < used <= base


class TestCheckpointRestore:
    def test_resume_reproduces_trajectory(self, tmp_path):
        # full run
        tr_a = make_trainer(tmp_path=tmp_path / "a", steps=12, ckpt_every=5)
        hist_a = tr_a.run()
        # interrupted run: 0..7, then a fresh trainer resumes from ckpt
        tr_b = make_trainer(tmp_path=tmp_path / "b", steps=12, ckpt_every=5)
        tr_b.run(steps=8)
        tr_c = make_trainer(tmp_path=tmp_path / "b", steps=12, ckpt_every=5)
        resumed_at = tr_c.maybe_restore()
        assert resumed_at > 0
        hist_c = tr_c.run()
        last_a = [h["loss"] for h in hist_a][-3:]
        last_c = [h["loss"] for h in hist_c][-3:]
        np.testing.assert_allclose(last_a, last_c, rtol=2e-3, atol=2e-3)

    def test_resume_bit_identical(self, tmp_path):
        """Save mid-run, restore, continue: the tail must be BIT-identical
        to the uninterrupted run — same losses (exact float equality), same
        final params/optimizer state (exact array equality), same
        SubspaceController intervals and per-layer SVD counts, same SR RNG
        stream (keys are folded from (seed, step), so a restored step N
        draws the randoms step N always draws)."""
        tr_a = make_trainer(tmp_path=tmp_path / "a", steps=14, ckpt_every=5)
        hist_a = tr_a.run()

        tr_b = make_trainer(tmp_path=tmp_path / "b", steps=14, ckpt_every=5)
        tr_b.run(steps=8)                     # interrupted at step 8
        tr_c = make_trainer(tmp_path=tmp_path / "b", steps=14, ckpt_every=5)
        resumed_at = tr_c.maybe_restore()
        assert resumed_at == 8
        hist_c = tr_c.run()

        by_step = {h["step"]: h["loss"] for h in hist_a}
        for h in hist_c:
            assert h["loss"] == by_step[h["step"]], (
                f"step {h['step']}: resumed loss {h['loss']} != "
                f"uninterrupted {by_step[h['step']]}")
        for a, c in zip(jax.tree_util.tree_leaves(jax.device_get(tr_a.state)),
                        jax.tree_util.tree_leaves(jax.device_get(tr_c.state))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
        assert tr_a.controller.interval_summary() == \
            tr_c.controller.interval_summary()
        # svd counts differ by bookkeeping before the restore point only in
        # run B's prefix; totals per unit must match the uninterrupted run
        assert tr_a.controller.svd_count_summary() == \
            tr_c.controller.svd_count_summary()

    def test_fault_recovery(self, tmp_path):
        boom = {"armed": True}

        def fault(step):
            if step == 9 and boom["armed"]:
                boom["armed"] = False
                raise RuntimeError("simulated node failure")

        tr = make_trainer(tmp_path=tmp_path, steps=12, ckpt_every=4,
                          fault_hook=fault)
        hist = tr.run()
        steps_seen = [h["step"] for h in hist]
        assert 11 in steps_seen          # completed despite the failure
        assert not boom["armed"]

    def test_fault_budget_exhausted_raises(self, tmp_path):
        def always_fail(step):
            raise RuntimeError("permafail")

        tr = make_trainer(tmp_path=tmp_path, steps=4, ckpt_every=2,
                          fault_hook=always_fail)
        with pytest.raises(RuntimeError):
            tr.run(max_failures=2)


class TestStraggler:
    def test_straggler_detection(self):
        tr = make_trainer(steps=1)
        for i in range(20):
            tr.stragglers.observe(i, 0.1)
        assert tr.stragglers.observe(20, 1.0)     # 10x median
        assert tr.stragglers.events


class TestSpans:
    def test_run_marks_host_spans(self, tmp_path, monkeypatch):
        """The parts of ``_run_one`` that prepare a step (its inputs, the
        controller's masks, the dispatch) are host spans: written into a
        profiler trace, and counted by the wall-clock accumulator under
        ``REPRO_PROFILE=1``."""
        from repro.kernels import profile
        monkeypatch.setenv("REPRO_PROFILE", "1")
        profile.reset()
        tr = make_trainer(steps=2)
        jax.profiler.start_trace(str(tmp_path / "trace"))
        try:
            tr.run()
        finally:
            jax.profiler.stop_trace()
        calls = {k: v["wall_calls"] for k, v in profile.snapshot().items()
                 if k.startswith("repro.")}
        profile.reset()
        assert calls == {"repro.batch": 2, "repro.controller": 2,
                         "repro.dispatch": 2}
        import glob
        path, = glob.glob(str(tmp_path / "trace" / "plugins" / "profile"
                              / "*" / "*.xplane.pb"))
        names = {e.name for plane in
                 jax.profiler.ProfileData.from_file(path).planes
                 for line in plane.lines for e in line.events}
        assert set(calls) <= names

    def test_undeclared_scope_is_refused(self):
        from repro.kernels import profile
        with pytest.raises(ValueError, match="undeclared"):
            profile.scope("repro.nowhere")


class TestImplParity:
    def test_fused_and_simple_same_losses(self):
        h1 = make_trainer(steps=6, impl="fused", seed=3).run()
        h2 = make_trainer(steps=6, impl="simple", seed=3).run()
        l1 = [h["loss"] for h in h1]
        l2 = [h["loss"] for h in h2]
        np.testing.assert_allclose(l1, l2, rtol=5e-3, atol=5e-3)


def make_adarank_trainer(tmp_path=None, steps=12, ckpt_every=0,
                         adaptive_rank=True):
    """The adarank regression config: base trainer config +
    ``galore_embeddings`` + the adaptive-rank knobs, tuned so a rank-8 → 4
    shrink fires at step 8 (refresh observations at steps 0/4/8, patience
    3) — a 12-step run crosses exactly one transition."""
    bundle = model_zoo.build_arch("llama-60m", smoke=True, dtype=jnp.float32)
    qcfg = preset("qgalore", QGaLoreConfig(
        rank=8, min_dim=32, update_interval=4, adaptive_k=1,
        cos_threshold=0.3, galore_embeddings=True,
        adaptive_rank=adaptive_rank, rank_ladder=(4,),
        explained_ratio_threshold=0.45, rank_patience=3, min_rank=4))
    tcfg = TrainConfig(
        seed=0, global_batch=4, seq_len=32, steps=steps,
        learning_rate=1e-2, warmup_steps=2, grad_clip=1.0,
        checkpoint_dir=str(tmp_path) if tmp_path else "",
        checkpoint_every=ckpt_every, log_every=0, async_checkpoint=False)
    return Trainer(bundle, tcfg, qcfg, cell=CELL, impl="fused",
                   param_dtype=jnp.float32)


class TestRankTransitionResume:
    def _assert_states_equal(self, tr_a, tr_b):
        for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(tr_a.state)),
                        jax.tree_util.tree_leaves(jax.device_get(tr_b.state))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_resume_bit_identical_across_rank_transition(self, tmp_path):
        """The rank-transition extension of ``test_resume_bit_identical``:
        (a) a checkpoint saved AFTER a shrink (holding truncated state +
        the rank-override meta) restores into a freshly-built trainer —
        which must adopt the overrides BEFORE touching arrays — and the
        tail is bit-identical; (b) a checkpoint saved BEFORE the shrink
        replays the transition deterministically on resume (migration is
        SR-free round-to-nearest, so replay equals the original)."""
        tr_a = make_adarank_trainer(tmp_path / "a", steps=12, ckpt_every=5)
        hist_a = tr_a.run()
        trans_a = tr_a.controller.rank_transition_summary()
        assert [t["step"] for t in trans_a].count(8) == len(trans_a) > 0, (
            "config drifted: expected all transitions at step 8", trans_a)
        by_step = {h["step"]: h["loss"] for h in hist_a}

        # (a) interrupt after the transition: latest ckpt is step 10
        tr_b = make_adarank_trainer(tmp_path / "b", steps=12, ckpt_every=5)
        tr_b.run(steps=11)
        tr_c = make_adarank_trainer(tmp_path / "b", steps=12, ckpt_every=5)
        meta = tr_c.mgr.read_meta()
        assert meta["rank_overrides"], (
            "post-transition checkpoint must persist the override map")
        assert tr_c.maybe_restore() == 11
        # overrides adopted before array restore: specs already shrunk
        shrunk = {s.path: s.rank for s in tr_c.specs if s.galore}
        assert any(r == 4 for r in shrunk.values()), shrunk
        hist_c = tr_c.run()
        for h in hist_c:
            assert h["loss"] == by_step[h["step"]], h
        self._assert_states_equal(tr_a, tr_c)
        assert tr_c.controller.rank_transition_summary() == trans_a

        # (b) interrupt before the transition (run() saves its last step,
        # 6): resume from step 7 with two streak observations restored,
        # replay the step-8 shrink, land bit-identical
        tr_d = make_adarank_trainer(tmp_path / "d", steps=12, ckpt_every=5)
        tr_d.run(steps=7)
        assert tr_d.controller.rank_transition_summary() == []
        tr_e = make_adarank_trainer(tmp_path / "d", steps=12, ckpt_every=5)
        assert tr_e.maybe_restore() == 7
        assert not tr_e._rank_overrides        # pre-transition ckpt
        hist_e = tr_e.run()
        for h in hist_e:
            assert h["loss"] == by_step[h["step"]], h
        self._assert_states_equal(tr_a, tr_e)
        assert tr_e.controller.rank_transition_summary() == trans_a

    def test_restore_with_adaptive_off_fails_loudly(self, tmp_path):
        """A shrunk checkpoint restored by a run that cannot adapt
        (adaptive_rank off everywhere) must fail META-FIRST with an error
        naming the overridden leaves — not a shape error mid-array-restore."""
        tr = make_adarank_trainer(tmp_path, steps=10, ckpt_every=9)
        tr.run()
        assert tr.controller.current_ranks()
        tr2 = make_adarank_trainer(tmp_path, steps=10, ckpt_every=9,
                                   adaptive_rank=False)
        with pytest.raises(ValueError, match="rank_overrides"):
            tr2.maybe_restore()
