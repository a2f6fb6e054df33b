"""Distributed correctness at container scale: a real (2,2)/(2,4) host-device
mesh in a subprocess (the 512-device flag must be set before jax imports, so
these run out-of-process), exercising sharded train steps, sharded decode,
checkpoint save on one mesh + elastic restore onto another, and the dry-run
entry points."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(body: str, devices: int = 8, timeout: int = 600):
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={devices}"
        from repro.launch.mesh import make_mesh
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_sharded_train_step_runs_and_matches_single_device():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.config import QGaLoreConfig, ShapeCell, TrainConfig
        from repro.core.optimizers import preset
        from repro.distributed import sharding as sh
        from repro.models import model_zoo
        from repro.train import step as step_lib
        from repro.data.synthetic import batch_for_bundle

        mesh = make_mesh((2, 4), ("data", "model"))
        bundle = model_zoo.build_arch("llama-60m", smoke=True,
                                      dtype=jnp.float32)
        qcfg = preset("qgalore", QGaLoreConfig(rank=8, min_dim=32))
        tcfg = TrainConfig(global_batch=4, seq_len=32, grad_clip=1.0)
        cell = ShapeCell("t", 32, 4, "train")
        raw, specs = step_lib.build_train_step(
            bundle, qcfg, tcfg, impl="fused", param_dtype=jnp.float32)
        state = step_lib.init_state(bundle, qcfg, jax.random.PRNGKey(0),
                                    jnp.float32)
        batch = batch_for_bundle(bundle, cell, 0)

        p_sh = sh.param_sharding(state.params, mesh)
        o_sh = sh.opt_state_sharding(state.params, state.opt, qcfg, mesh)
        b_sh = sh.data_sharding(
            jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch),
            mesh)
        rep = sh.replicated(mesh)
        fn = jax.jit(lambda st, b, lr, rng: raw(st, b, lr, rng,
                                                refresh_masks=None,
                                                refresh=False),
                     in_shardings=(step_lib.TrainState(p_sh, o_sh),
                                   b_sh, rep, rep))
        st_sharded = jax.device_put(state, step_lib.TrainState(p_sh, o_sh))
        with mesh:
            new_state, metrics, _ = fn(st_sharded, batch, 1e-3,
                                       jax.random.PRNGKey(1))
        loss_sharded = float(metrics["loss"])

        # single-device oracle
        fn1 = jax.jit(lambda st, b, lr, rng: raw(st, b, lr, rng,
                                                 refresh_masks=None,
                                                 refresh=False))
        _, metrics1, _ = fn1(state, batch, 1e-3, jax.random.PRNGKey(1))
        loss1 = float(metrics1["loss"])
        assert abs(loss_sharded - loss1) < 5e-3, (loss_sharded, loss1)
        print("OK", loss_sharded, loss1)
    """)
    assert "OK" in out


def test_pallas_tpu_under_multi_device_mesh_names_the_cause():
    """The Mosaic kernels are not wrapped in a manual shard_map yet, so a
    multi-device step on pallas-tpu must stop at build time with the
    cause, not inside the compiler. One device is still allowed."""
    out = run_py("""
        import os
        os.environ["REPRO_KERNEL_BACKEND"] = "pallas-tpu"
        import jax.numpy as jnp, pytest
        from repro.config import QGaLoreConfig, TrainConfig
        from repro.models import model_zoo
        from repro.train import step as step_lib

        bundle = model_zoo.build_arch("llama-60m", smoke=True,
                                      dtype=jnp.float32)
        qcfg = QGaLoreConfig(rank=8, min_dim=32)
        tcfg = TrainConfig(global_batch=4, seq_len=32)
        step_lib.build_train_step(bundle, qcfg, tcfg,
                                  mesh=make_mesh((1, 1), ("data", "model")))
        with pytest.raises(ValueError, match="Speed 7"):
            step_lib.build_train_step(
                bundle, qcfg, tcfg, mesh=make_mesh((2, 1), ("data", "model")))
        print("OK")
    """, devices=2)
    assert "OK" in out


def test_elastic_checkpoint_reshard():
    """Save on a (4,2) mesh, restore onto (2,2) with different shardings —
    the elastic-scaling path."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from repro.config import QGaLoreConfig, ShapeCell, TrainConfig
        from repro.core.optimizers import preset
        from repro.distributed import sharding as sh
        from repro.models import model_zoo
        from repro.train import step as step_lib
        from repro.train.checkpoint import CheckpointManager

        bundle = model_zoo.build_arch("llama-60m", smoke=True,
                                      dtype=jnp.float32)
        qcfg = preset("qgalore", QGaLoreConfig(rank=8, min_dim=32))
        state = step_lib.init_state(bundle, qcfg, jax.random.PRNGKey(0),
                                    jnp.float32)

        mesh_a = make_mesh((4, 2), ("data", "model"))
        p_sh_a = sh.param_sharding(state.params, mesh_a)
        o_sh_a = sh.opt_state_sharding(state.params, state.opt, qcfg,
                                       mesh_a)
        st_a = jax.device_put(state, step_lib.TrainState(p_sh_a, o_sh_a))

        d = tempfile.mkdtemp()
        mgr = CheckpointManager(d, async_save=False)
        mgr.save(7, st_a, {"note": "elastic"})

        # restore on a DIFFERENT mesh shape
        mesh_b = make_mesh((2, 2), ("data", "model"))
        abs_state = step_lib.abstract_state(bundle, qcfg, jnp.float32)
        p_sh_b = sh.param_sharding(abs_state.params, mesh_b)
        o_sh_b = sh.opt_state_sharding(abs_state.params, abs_state.opt,
                                       qcfg, mesh_b)
        restored, meta = mgr.restore(
            None, abs_state, step_lib.TrainState(p_sh_b, o_sh_b))
        assert meta["step"] == 7

        a = jax.tree_util.tree_leaves(jax.device_get(st_a))
        b = jax.tree_util.tree_leaves(jax.device_get(restored))
        assert len(a) == len(b)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        print("OK elastic reshard", meta)
    """)
    assert "OK elastic reshard" in out


def test_sharded_decode_runs():
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.config import QGaLoreConfig
        from repro.distributed import sharding as sh
        from repro.models import model_zoo
        from repro.serve import engine, shard as sshard
        from repro.train import step as step_lib

        mesh = make_mesh((2, 4), ("data", "model"))
        bundle = model_zoo.build_arch("yi-9b", smoke=True,
                                      dtype=jnp.float32)
        params = step_lib.prepare_params(
            bundle.init_params(jax.random.PRNGKey(0)), QGaLoreConfig(),
            jnp.float32)
        B, maxlen = 4, 64
        batch = {"tokens": jnp.zeros((B, 8), jnp.int32)}
        prefill = jax.jit(engine.build_prefill(bundle, maxlen))
        logits, state = prefill(params, batch)

        s_abs = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
        s_sh = sshard.decode_state_sharding(
            engine.DecodeState(s_abs.caches, s_abs.lengths, s_abs.extras),
            mesh)
        p_sh = sh.param_sharding(params, mesh)
        decode = jax.jit(engine.build_decode(bundle),
                         in_shardings=(p_sh, s_sh, sh.replicated(mesh)))
        with mesh:
            params_s = jax.device_put(params, p_sh)
            state_s = jax.device_put(state, s_sh)
            lg, state2 = decode(params_s, state_s,
                                jnp.ones((B, 1), jnp.int32))
        import numpy as np
        assert np.isfinite(np.asarray(lg)).all()
        print("OK sharded decode", lg.shape)
    """)
    assert "OK sharded decode" in out


def test_sharded_cache_pool_continuous_decode():
    """Continuous batching on a real mesh: the slot pool sharded via
    pool_sharding (slot axis on data, KV time on model) must produce the
    same tokens as the unsharded scheduler."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models import model_zoo
        from repro.serve import shard as sshard
        from repro.serve.scheduler import Request, Scheduler

        bundle = model_zoo.build_arch("llama-60m", smoke=True,
                                      dtype=jnp.float32)
        params = bundle.init_params(jax.random.PRNGKey(0))
        V = bundle.cfg.vocab_size
        rng = np.random.default_rng(0)
        reqs = [Request(rid=r,
                        tokens=rng.integers(1, V, size=int(
                            rng.integers(3, 10))).astype(np.int32),
                        max_new_tokens=int(rng.integers(2, 6)))
                for r in range(6)]

        mesh = make_mesh((2, 2), ("data", "model"))
        sh = sshard.pool_sharding(bundle, num_slots=4, max_len=32,
                                  mesh=mesh, dtype=jnp.float32)
        with mesh:
            sched = Scheduler(bundle, params, num_slots=4, max_len=32,
                              dtype=jnp.float32, prompt_bucket=8,
                              shardings=sh)
            comps = {c.rid: c.tokens for c in sched.run(list(reqs))}

        plain = Scheduler(bundle, params, num_slots=4, max_len=32,
                          dtype=jnp.float32, prompt_bucket=8)
        ref = {c.rid: c.tokens for c in plain.run(list(reqs))}
        assert comps == ref, (comps, ref)
        print("OK sharded pool", sched.stats)
    """)
    assert "OK sharded pool" in out


def test_sharded_paged_pool_continuous_decode():
    """Paged serving on a real mesh: the block pool sharded via
    paged_pool_sharding (block axis on data, KV time WITHIN blocks on
    model) must produce the same tokens as the unsharded paged scheduler
    AND the slot scheduler — traced-index block gathers/scatters become
    collectives under GSPMD without changing a single token."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models import model_zoo
        from repro.serve import shard as sshard
        from repro.serve.paged import PagedScheduler
        from repro.serve.scheduler import Request, Scheduler

        bundle = model_zoo.build_arch("llama-60m", smoke=True,
                                      dtype=jnp.float32)
        params = bundle.init_params(jax.random.PRNGKey(0))
        V = bundle.cfg.vocab_size
        rng = np.random.default_rng(0)
        shared = rng.integers(1, V, size=8)
        def reqs():
            out = []
            for r in range(6):
                p = rng2.integers(1, V, size=int(
                    rng2.integers(3, 10))).astype(np.int32)
                if r % 2 == 0:
                    p = np.concatenate([shared.astype(np.int32), p])
                out.append(Request(rid=r, tokens=p.tolist(),
                                   max_new_tokens=int(
                                       rng2.integers(2, 6))))
            return out

        # num_blocks divisible by the data axis (2) for block sharding
        kw = dict(num_slots=4, max_len=32, block_size=8, num_blocks=18,
                  prefill_chunk=8, dtype=jnp.float32)
        mesh = make_mesh((2, 2), ("data", "model"))
        sh = sshard.paged_pool_sharding(bundle, kw["num_blocks"],
                                        kw["block_size"], mesh,
                                        dtype=jnp.float32)
        rng2 = np.random.default_rng(1)
        with mesh:
            sched = PagedScheduler(bundle, params, shardings=sh, **kw)
            comps = {c.rid: c.tokens for c in sched.run(reqs())}

        rng2 = np.random.default_rng(1)
        plain = PagedScheduler(bundle, params, **kw)
        ref = {c.rid: c.tokens for c in plain.run(reqs())}
        assert comps == ref, (comps, ref)

        rng2 = np.random.default_rng(1)
        slot = Scheduler(bundle, params, num_slots=4, max_len=32,
                         dtype=jnp.float32, prompt_bucket=8)
        slot_ref = {c.rid: c.tokens for c in slot.run(reqs())}
        assert comps == slot_ref, (comps, slot_ref)
        assert sched.stats["radix_hit_blocks"] > 0
        print("OK sharded paged pool", sched.stats)
    """)
    assert "OK sharded paged pool" in out


@pytest.mark.slow
def test_dryrun_entry_small():
    """The dryrun module itself (512 devices) on the smallest arch/cell."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    out_dir = "/tmp/dryrun_test_out"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "xlstm-125m", "--cell", "decode_32k", "--out", out_dir],
        env=env, capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    with open(os.path.join(out_dir, "16x16",
                           "xlstm-125m__decode_32k.json")) as f:
        art = json.load(f)
    assert art["ok"]
    assert art["cost_analysis"]["flops"] > 0


def test_dp_compress_parity_1dev_vs_8dev():
    """Golden parity case: the SAME distributed mode (dp_compress +
    distributed refresh) on a 1-device vs an 8-device DP mesh must produce
    the same trajectory — the only allowed difference is floating-point
    reduction order (which SR turns into sub-quantum code flips), so the
    loss band is tight. 8 layers so the layer stack divides both worlds
    and refresh-step eligibility is identical."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.config import QGaLoreConfig, ShapeCell, TrainConfig
        from repro.core.optimizers import preset
        from repro.models import model_zoo
        from repro.config import replace as cfg_replace
        from repro.models.model_zoo import build, get_config
        from repro.train import step as step_lib
        from repro.data.synthetic import batch_for_bundle

        cfg = cfg_replace(get_config("llama-60m", smoke=True), num_layers=8)
        bundle = build(cfg, dtype=jnp.float32)
        qcfg = preset("qgalore", QGaLoreConfig(rank=8, min_dim=32))
        tcfg = TrainConfig(global_batch=8, seq_len=32, grad_clip=1.0)
        cell = ShapeCell("t", 32, 8, "train")

        def run(d):
            mesh = make_mesh((d, 1), ("data", "model"))
            raw, specs = step_lib.build_train_step(
                bundle, qcfg, tcfg, impl="fused", param_dtype=jnp.float32,
                mesh=mesh, dp_compress=True)
            state = step_lib.init_state(bundle, qcfg,
                                        jax.random.PRNGKey(0), jnp.float32)
            galore = [i for i, s in enumerate(specs) if s.galore]
            masks = {i: jnp.ones((specs[i].nbatch,), bool) for i in galore}
            fr = jax.jit(lambda st, b, lr, rng, m: raw(
                st, b, lr, rng, refresh_masks=m, refresh=True))
            fn = jax.jit(lambda st, b, lr, rng: raw(
                st, b, lr, rng, refresh_masks=None, refresh=False))
            losses = []
            with mesh:
                for s in range(5):
                    batch = batch_for_bundle(bundle, cell, s)
                    if s % 3 == 0:
                        state, met, _ = fr(state, batch, 1e-2,
                                           jax.random.PRNGKey(s), masks)
                    else:
                        state, met, _ = fn(state, batch, 1e-2,
                                           jax.random.PRNGKey(s))
                    losses.append(float(met["loss"]))
            return losses

        l1, l8 = run(1), run(8)
        np.testing.assert_allclose(l1, l8, rtol=1e-3, atol=1e-3)
        print("OK parity", l1, l8)
    """, timeout=900)
    assert "OK parity" in out


def test_dist_refresh_matches_replicated():
    """The distributed subspace refresh (reduce-scatter + per-owner SVD +
    broadcast) must reproduce the replicated in-optimizer refresh: same
    similarities, same new projections, same next-step loss. grad_clip=0
    so the (documented) low-rank-vs-full-rank clip-norm difference at
    refresh steps doesn't enter."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.config import QGaLoreConfig, ShapeCell, TrainConfig
        from repro.core.optimizers import preset
        from repro.core import quant
        from repro.models import model_zoo
        from repro.train import step as step_lib
        from repro.data.synthetic import batch_for_bundle

        mesh = make_mesh((2, 4), ("data", "model"))
        bundle = model_zoo.build_arch("llama-60m", smoke=True,
                                      dtype=jnp.float32)
        tcfg = TrainConfig(global_batch=8, seq_len=32, grad_clip=0.0)
        cell = ShapeCell("t", 32, 8, "train")

        results = {}
        for dist in (True, False):
            qcfg = preset("qgalore", QGaLoreConfig(
                rank=8, min_dim=32, dist_refresh=dist))
            raw, specs = step_lib.build_train_step(
                bundle, qcfg, tcfg, impl="fused", param_dtype=jnp.float32,
                mesh=mesh, dp_compress=True)
            state = step_lib.init_state(bundle, qcfg,
                                        jax.random.PRNGKey(0), jnp.float32)
            galore = [i for i, s in enumerate(specs) if s.galore]
            masks = {i: jnp.ones((specs[i].nbatch,), bool) for i in galore}
            fr = jax.jit(lambda st, b, lr, rng, m: raw(
                st, b, lr, rng, refresh_masks=m, refresh=True))
            with mesh:
                batch = batch_for_bundle(bundle, cell, 0)
                state, met, om = fr(state, batch, 1e-2,
                                    jax.random.PRNGKey(7), masks)
                sims = {k: np.asarray(v) for k, v in om["sims"].items()}
                proj = jax.device_get(state.opt.proj)
                results[dist] = (float(met["loss"]), sims, proj)

        l_d, s_d, p_d = results[True]
        l_r, s_r, p_r = results[False]
        assert abs(l_d - l_r) < 1e-4, (l_d, l_r)
        assert set(s_d) == set(s_r)
        for k in s_d:
            np.testing.assert_allclose(s_d[k], s_r[k], atol=1e-3, err_msg=k)
        for a, b in zip(jax.tree_util.tree_leaves(p_d),
                        jax.tree_util.tree_leaves(p_r)):
            a, b = np.asarray(a), np.asarray(b)
            if np.issubdtype(a.dtype, np.integer):
                frac = (a != b).mean()
                assert frac < 0.02, frac     # INT4 codes: rare edge flips
            else:
                np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-3)
        print("OK dist refresh parity", l_d, l_r)
    """, timeout=900)
    assert "OK dist refresh parity" in out


def test_zero_sharded_state_matches_and_reshards():
    """ZeRO-sharded optimizer state: (a) the sharded step matches the
    replicated-state step, (b) per-device optimizer bytes shrink ~D-fold,
    (c) a ZeRO checkpoint saved on an (8,1) data mesh restores bit-exactly
    onto a (2,2) mesh with different zero axes (elastic reshard)."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from repro.config import QGaLoreConfig, ShapeCell, TrainConfig
        from repro.core.optimizers import preset
        from repro.distributed import sharding as sh
        from repro.models import model_zoo
        from repro.train import step as step_lib
        from repro.train.checkpoint import CheckpointManager
        from repro.data.synthetic import batch_for_bundle

        bundle = model_zoo.build_arch("llama-60m", smoke=True,
                                      dtype=jnp.float32)
        qcfg = preset("qgalore", QGaLoreConfig(rank=8, min_dim=32,
                                               compress_dp_grads=True))
        tcfg = TrainConfig(global_batch=8, seq_len=32, grad_clip=1.0)
        cell = ShapeCell("t", 32, 8, "train")
        mesh = make_mesh((8, 1), ("data", "model"))
        raw, specs = step_lib.build_train_step(
            bundle, qcfg, tcfg, impl="fused", param_dtype=jnp.float32,
            mesh=mesh, dp_compress=True)
        state = step_lib.init_state(bundle, qcfg, jax.random.PRNGKey(0),
                                    jnp.float32)
        batch = batch_for_bundle(bundle, cell, 0)

        p_sh = sh.param_sharding(state.params, mesh)
        o_rep = sh.opt_state_sharding(state.params, state.opt, qcfg, mesh)
        o_zero = sh.opt_state_sharding(state.params, state.opt, qcfg,
                                       mesh, zero_axes=("data",))
        b_sh = sh.data_sharding(jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch), mesh)
        rep = sh.replicated(mesh)

        losses = {}
        states = {}
        for name, o_sh in (("rep", o_rep), ("zero", o_zero)):
            ss = step_lib.TrainState(p_sh, o_sh)
            fn = jax.jit(lambda st, b, lr, rng: raw(
                st, b, lr, rng, refresh_masks=None, refresh=False),
                in_shardings=(ss, b_sh, rep, rep),
                out_shardings=(ss, None, None))
            with mesh:
                st = jax.device_put(state, ss)
                for s in range(2):
                    st, met, _ = fn(st, batch, 1e-3, jax.random.PRNGKey(s))
                losses[name] = float(met["loss"])
            states[name] = st
        # (a) numerics identical up to reduction order
        assert abs(losses["rep"] - losses["zero"]) < 1e-5, losses
        for a, b in zip(jax.tree_util.tree_leaves(
                            jax.device_get(states["rep"])),
                        jax.tree_util.tree_leaves(
                            jax.device_get(states["zero"]))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        # (b) per-device bytes of the big moment leaves shrink
        def per_dev(st):
            tot = dev = 0
            for l in jax.tree_util.tree_leaves(st.opt.inner):
                if hasattr(l, "addressable_shards") and l.nbytes > 4096:
                    tot += l.nbytes
                    dev += max(s.data.nbytes for s in l.addressable_shards)
            return tot, dev
        tot, dev = per_dev(states["zero"])
        assert dev * 4 <= tot, (tot, dev)   # >= 4x sharded overall

        # (c) elastic ZeRO reshard through a checkpoint
        d = tempfile.mkdtemp()
        mgr = CheckpointManager(d, async_save=False)
        mgr.save(3, states["zero"], {"note": "zero"})
        mesh_b = make_mesh((2, 2), ("data", "model"))
        abs_state = step_lib.abstract_state(bundle, qcfg, jnp.float32)
        ss_b = step_lib.TrainState(
            sh.param_sharding(abs_state.params, mesh_b),
            sh.opt_state_sharding(abs_state.params, abs_state.opt, qcfg,
                                  mesh_b, zero_axes=("data",)))
        restored, meta = mgr.restore(None, abs_state, ss_b)
        assert meta["step"] == 3
        for a, b in zip(jax.tree_util.tree_leaves(
                            jax.device_get(states["zero"])),
                        jax.tree_util.tree_leaves(
                            jax.device_get(restored))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("OK zero shard", losses, tot, dev)
    """, timeout=900)
    assert "OK zero shard" in out


def test_zero2_reduce_scatter_matches_pmean():
    """ZeRO-2 gradient reduce-scatter (ROADMAP item): the steady-state
    low-rank gradients are psum_scattered along each leaf's moment-shard
    dim instead of pmean-replicated. Trajectory must match the pmean path
    (identical psum values, only the layout of the result differs), and
    the scatter dims must align with the ZeRO moment sharding."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.config import QGaLoreConfig, ShapeCell, TrainConfig
        from repro.core.optimizers import preset
        from repro.core import qgalore
        from repro.distributed import sharding as sh
        from repro.models import model_zoo
        from repro.train import step as step_lib
        from repro.data.synthetic import batch_for_bundle

        mesh = make_mesh((8, 1), ("data", "model"))
        bundle = model_zoo.build_arch("llama-60m", smoke=True,
                                      dtype=jnp.float32)
        qcfg = preset("qgalore", QGaLoreConfig(rank=8, min_dim=32,
                                               compress_dp_grads=True))
        tcfg = TrainConfig(global_batch=8, seq_len=32, grad_clip=1.0)
        cell = ShapeCell("t", 32, 8, "train")

        abs_state = step_lib.abstract_state(bundle, qcfg, jnp.float32)
        specs = qgalore.leaf_specs(abs_state.params, qcfg)
        o_zero = sh.opt_state_sharding(abs_state.params, abs_state.opt,
                                       qcfg, mesh, zero_axes=("data",))
        dims = sh.zero2_scatter_dims(o_zero, specs, ("data",))
        assert dims, "no ZeRO-2 scatterable leaves found"
        # alignment: the scatter dim carries the data axis in the moment
        # sharding and divides the low-rank shape by the DP world size
        inner_flat = jax.tree_util.tree_flatten(
            o_zero.inner, is_leaf=qgalore._is_inner_leaf)[0]
        for i, d in dims.items():
            m_sh = inner_flat[i].m
            spec_p = (m_sh.q if hasattr(m_sh, 'q') else m_sh).spec
            part = spec_p[d]
            parts = (part,) if isinstance(part, str) else tuple(part)
            assert "data" in parts, (specs[i].path, d, spec_p)
            assert specs[i].low_shape[d] % 8 == 0

        p_sh = sh.param_sharding(abs_state.params, mesh)
        b_abs = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            batch_for_bundle(bundle, cell, 0))
        b_sh = sh.data_sharding(b_abs, mesh)
        rep = sh.replicated(mesh)
        ss = step_lib.TrainState(p_sh, o_zero)

        losses = {}
        for name, z2 in (("pmean", None), ("zero2", dims)):
            raw, _ = step_lib.build_train_step(
                bundle, qcfg, tcfg, impl="fused", param_dtype=jnp.float32,
                mesh=mesh, dp_compress=True,
                state_shardings=step_lib.TrainState(p_sh, o_zero),
                zero2_dims=z2)
            state = step_lib.init_state(bundle, qcfg,
                                        jax.random.PRNGKey(0), jnp.float32)
            fn = jax.jit(lambda st, b, lr, rng: raw(
                st, b, lr, rng, refresh_masks=None, refresh=False),
                in_shardings=(ss, b_sh, rep, rep),
                out_shardings=(ss, None, None))
            ls = []
            with mesh:
                st = jax.device_put(state, ss)
                for s in range(3):
                    st, met, _ = fn(st, batch_for_bundle(bundle, cell, s),
                                    1e-2, jax.random.PRNGKey(s))
                    ls.append(float(met["loss"]))
            losses[name] = ls
        np.testing.assert_allclose(losses["pmean"], losses["zero2"],
                                   rtol=1e-4, atol=1e-4)
        print("OK zero2 parity", losses)
    """, timeout=900)
    assert "OK zero2 parity" in out


def test_dp_compress_matches_plain():
    """The shard_map-compressed gradient path must produce the same update
    as the plain GSPMD path (same loss trajectory over steps)."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.config import QGaLoreConfig, ShapeCell, TrainConfig
        from repro.core.optimizers import preset
        from repro.models import model_zoo
        from repro.train import step as step_lib
        from repro.data.synthetic import batch_for_bundle

        mesh = make_mesh((4, 2), ("data", "model"))
        bundle = model_zoo.build_arch("llama-60m", smoke=True,
                                      dtype=jnp.float32)
        qcfg = preset("qgalore", QGaLoreConfig(rank=8, min_dim=32))
        tcfg = TrainConfig(global_batch=8, seq_len=32, grad_clip=1.0)
        cell = ShapeCell("t", 32, 8, "train")

        losses = {}
        for mode in ("plain", "compress"):
            raw, _ = step_lib.build_train_step(
                bundle, qcfg, tcfg, impl="fused", param_dtype=jnp.float32,
                mesh=mesh, dp_compress=(mode == "compress"))
            state = step_lib.init_state(bundle, qcfg, jax.random.PRNGKey(0),
                                        jnp.float32)
            fn = jax.jit(lambda st, b, lr, rng: raw(
                st, b, lr, rng, refresh_masks=None, refresh=False))
            ls = []
            with mesh:
                for s in range(3):
                    batch = batch_for_bundle(bundle, cell, s)
                    state, metrics, _ = fn(state, batch, 1e-3,
                                           jax.random.PRNGKey(s))
                    ls.append(float(metrics["loss"]))
            losses[mode] = ls
        np.testing.assert_allclose(losses["plain"], losses["compress"],
                                   rtol=5e-3, atol=5e-3)
        print("OK dp_compress", losses)
    """, timeout=900)
    assert "OK dp_compress" in out


def test_adarank_zero_compressed_forced_transition():
    """The adarank-smoke CI gate: dynamic rank adaptation under the FULL
    distributed stack — compressed-DP shard_map + distributed refresh (the
    explained-variance profiles are computed on the scattered owners and
    gathered) + ZeRO-sharded optimizer state on an 8-device DP mesh, with
    a forced rank transition at the first refresh. Asserts (a) 1-dev vs
    8-dev parity of the loss trajectory AND the exact transition schedule,
    (b) the migrated (truncated + re-sharded) state keeps stepping, (c) a
    post-shrink ZeRO checkpoint restores bit-identically onto a different
    mesh, adopting the rank overrides meta-first."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from repro.config import QGaLoreConfig, ShapeCell, TrainConfig
        from repro.config import replace as cfg_replace
        from repro.core.optimizers import preset
        from repro.models.model_zoo import build, get_config
        from repro.train.trainer import Trainer

        cfg = cfg_replace(get_config("llama-60m", smoke=True), num_layers=8)
        qcfg = preset("qgalore", QGaLoreConfig(
            rank=8, min_dim=32, update_interval=4, adaptive_k=1,
            cos_threshold=0.3, compress_dp_grads=True,
            galore_embeddings=True, adaptive_rank=True, rank_ladder=(4,),
            explained_ratio_threshold=0.05, rank_patience=1, min_rank=4))
        cell = ShapeCell("t", 32, 8, "train")

        def make(d, ckpt_dir="", mesh=None):
            bundle = build(cfg, dtype=jnp.float32)
            tcfg = TrainConfig(seed=0, global_batch=8, seq_len=32, steps=6,
                               learning_rate=1e-2, warmup_steps=2,
                               grad_clip=1.0, log_every=0,
                               checkpoint_dir=ckpt_dir,
                               async_checkpoint=False)
            mesh = mesh or make_mesh((d, 1), ("data", "model"))
            return Trainer(bundle, tcfg, qcfg, cell=cell, impl="fused",
                           param_dtype=jnp.float32, mesh=mesh,
                           zero_shard=True)

        d8 = tempfile.mkdtemp()
        tr8 = make(8, ckpt_dir=d8)
        hist8 = tr8.run()
        trans8 = tr8.controller.rank_transition_summary()
        assert trans8 and all(t["step"] == 0 for t in trans8), trans8
        assert all(t["new"] == 4 for t in trans8), trans8
        # the live state really shrank: every galore moment's rank dim is 4
        for i, s in enumerate(tr8.specs):
            if s.galore:
                assert s.rank == 4, s

        tr1 = make(1)
        hist1 = tr1.run()
        assert tr1.controller.rank_transition_summary() == trans8
        np.testing.assert_allclose([h["loss"] for h in hist1],
                                   [h["loss"] for h in hist8],
                                   rtol=1e-3, atol=1e-3)

        # (c) elastic post-shrink restore onto a (2,2) mesh
        mesh_b = make_mesh((2, 2), ("data", "model"))
        trb = make(2, ckpt_dir=d8, mesh=mesh_b)
        assert trb.mgr.read_meta()["rank_overrides"]
        assert trb.maybe_restore() == 6
        assert {s.path: s.rank for s in trb.specs if s.galore} == \
            {s.path: s.rank for s in tr8.specs if s.galore}
        for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(tr8.state)),
                        jax.tree_util.tree_leaves(jax.device_get(trb.state))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("OK adarank zero", [round(h["loss"], 4) for h in hist8])
    """, timeout=900)
    assert "OK adarank zero" in out
