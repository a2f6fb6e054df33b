"""Tensor-parallel Q-GaLore on a 2-D (data x model) mesh.

The tentpole contract (ISSUE 8): every GaLore quantity follows the
weight's TP shard dim —

  side   shard_dim   P (d, r)         low-rank / moments
  right  0 (m)       replicated       sharded on m  (local project)
  right  1 (n)       sliced on d = n  replicated    (psum on low)
  left   0 (m)       sliced on d = m  replicated    (psum on low)
  left   1 (n)       replicated       sharded on n  (local project)

— and the subspace refresh runs on shards over the COMBINED
(data x model) front (train/step.py scatters the layer stack over all
D*t ranks), so no full-rank GaLore tensor is ever gathered: the thing
ColossalAI's distributed_galore does on every refresh. Mesh tests run in
subprocesses (the forced-host-device flag must be set before jax
imports); the pure shard-algebra checks run in-process.
"""
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

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


# ---------------------------------------------------------------------------
# Golden parity on the full stack + elastic (2,4) <-> (8,1) restore
# ---------------------------------------------------------------------------

def test_tp_adarank_parity_2x4_vs_1dev_and_elastic_restore():
    """The TP acceptance gate: the FULL distributed stack (compressed-DP
    shard_map + combined-front distributed refresh + ZeRO-sharded state +
    a forced adaptive-rank transition with live state migration) on a
    (2,4) data x model mesh must match the 1-device run — same loss
    trajectory, same transition schedule — and a post-shrink checkpoint
    saved on (2,4) must restore bit-exactly onto an (8,1) mesh (the
    elastic TP <-> DP reshard)."""
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

        def make(mesh, ckpt_dir=""):
            bundle = build(cfg, dtype=jnp.float32)
            tcfg = TrainConfig(seed=0, global_batch=8, seq_len=32, steps=6,
                               learning_rate=1e-2, warmup_steps=2,
                               grad_clip=1.0, log_every=0,
                               checkpoint_dir=ckpt_dir,
                               async_checkpoint=False)
            return Trainer(bundle, tcfg, qcfg, cell=cell, impl="fused",
                           param_dtype=jnp.float32, mesh=mesh,
                           zero_shard=True)

        d = tempfile.mkdtemp()
        mesh_tp = make_mesh((2, 4), ("data", "model"))
        tr_tp = make(mesh_tp, ckpt_dir=d)
        # the TP annotation really landed on the specs
        ann = {s.path: (s.shard_dim, s.tp) for s in tr_tp.specs
               if s.galore and len(s.mat_shape) == 2}
        assert any(t == 4 for _, t in ann.values()), ann
        hist_tp = tr_tp.run()
        trans_tp = tr_tp.controller.rank_transition_summary()
        assert trans_tp and all(t["step"] == 0 for t in trans_tp), trans_tp
        assert all(t["new"] == 4 for t in trans_tp), trans_tp
        for s in tr_tp.specs:
            if s.galore:
                assert s.rank == 4, s      # live migration really shrank

        mesh_1 = make_mesh((1, 1), ("data", "model"))
        tr1 = make(mesh_1)
        hist1 = tr1.run()
        assert tr1.controller.rank_transition_summary() == trans_tp
        np.testing.assert_allclose([h["loss"] for h in hist1],
                                   [h["loss"] for h in hist_tp],
                                   rtol=1e-3, atol=1e-3)

        # elastic: the (2,4) ZeRO+TP checkpoint restores onto (8,1)
        mesh_dp = make_mesh((8, 1), ("data", "model"))
        tr_dp = make(mesh_dp, ckpt_dir=d)
        assert tr_dp.mgr.read_meta()["rank_overrides"]
        assert tr_dp.maybe_restore() == 6
        assert {s.path: s.rank for s in tr_dp.specs if s.galore} == \
            {s.path: s.rank for s in tr_tp.specs if s.galore}
        for a, b in zip(
                jax.tree_util.tree_leaves(jax.device_get(tr_tp.state)),
                jax.tree_util.tree_leaves(jax.device_get(tr_dp.state))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("OK tp adarank", [round(h["loss"], 4) for h in hist_tp])
    """, timeout=900)
    assert "OK tp adarank" in out


# ---------------------------------------------------------------------------
# No full-rank GaLore tensor materializes during a TP refresh
# ---------------------------------------------------------------------------

def test_tp_refresh_no_full_rank_materialization():
    """Compile a refresh step on a (2,4) mesh and scan the HLO: the only
    collectives allowed to touch full-rank stacked-leaf shapes are the
    phase-1 reduce-scatters (each rank RECEIVING its owned layer slice);
    any all-reduce / all-gather producing a full-rank stacked buffer —
    global (L, m, n) or per-front (L/D, m, n) / (L/(D*t), m, n) — means a
    rank gathered gradients it does not own, i.e. the ColossalAI-style
    full-rank refresh the combined-front design exists to avoid. Also
    asserts the structural contract: every stacked galore leaf scatters
    over the combined ('data','model') front of 8 ranks."""
    out = run_py("""
        import re, jax, jax.numpy as jnp, numpy as np
        from repro.config import QGaLoreConfig, ShapeCell, TrainConfig
        from repro.config import replace as cfg_replace
        from repro.core.optimizers import preset
        from repro.models.model_zoo import build, get_config
        from repro.train import step as step_lib
        from repro.data.synthetic import batch_for_bundle

        cfg = cfg_replace(get_config("llama-60m", smoke=True), num_layers=8)
        bundle = build(cfg, dtype=jnp.float32)
        qcfg = preset("qgalore", QGaLoreConfig(rank=8, min_dim=32,
                                               compress_dp_grads=True))
        tcfg = TrainConfig(global_batch=8, seq_len=32, grad_clip=0.0)
        cell = ShapeCell("t", 32, 8, "train")
        mesh = make_mesh((2, 4), ("data", "model"))
        raw, specs = step_lib.build_train_step(
            bundle, qcfg, tcfg, impl="fused", param_dtype=jnp.float32,
            mesh=mesh, dp_compress=True)
        state = step_lib.init_state(bundle, qcfg, jax.random.PRNGKey(0),
                                    jnp.float32)
        galore = [i for i, s in enumerate(specs) if s.galore]
        masks = {i: jnp.ones((specs[i].nbatch,), bool) for i in galore}

        # structural contract: combined front over all 8 ranks
        assert raw.refresh_axes == ("data", "model"), raw.refresh_axes
        assert raw.refresh_world == 8 and raw.dp_size == 2
        stacked = [i for i in galore if specs[i].batch]
        assert stacked
        mats = set()
        for i in stacked:
            assert raw.dist_front[i] == (("data", "model"), 8), \\
                (i, raw.dist_front[i])
            assert specs[i].nbatch % 8 == 0      # each rank owns L/(D*t)
            assert specs[i].tp == 4 and specs[i].shard_dim in (0, 1)
            mats.add(specs[i].mat_shape)

        fr = jax.jit(lambda st, b, lr, rng, m: raw(
            st, b, lr, rng, refresh_masks=m, refresh=True))
        with mesh:
            batch = batch_for_bundle(bundle, cell, 0)
            txt = fr.lower(state, batch, 1e-2, jax.random.PRNGKey(7),
                           masks).compile().as_text()
            st2, met, om = fr(state, batch, 1e-2, jax.random.PRNGKey(7),
                              masks)
        assert np.isfinite(float(met["loss"]))
        assert len(om.get("sims", {})) == len(galore)

        pat = re.compile(r"=\\s+(\\w+)\\[([\\d,]*)\\][^=]*?"
                         r"\\b(all-gather|all-reduce|reduce-scatter)\\b")
        L = specs[stacked[0]].nbatch               # 8 stacked layers
        forbidden = {",".join(map(str, (lead,) + m))
                     for m in mats for lead in (L, L // 2, L // 8)}
        hits = []
        gathered_lowrank = False
        for m_ in pat.finditer(txt):
            dtype, shape, op = m_.group(1), m_.group(2), m_.group(3)
            if op == "reduce-scatter":
                continue                           # phase-1 reduce: exempt
            if shape in forbidden:
                hits.append((op, dtype, shape))
            dims = tuple(int(x) for x in shape.split(",") if x)
            if len(dims) == 3 and dims[0] == L and dims[-1] <= 8:
                gathered_lowrank = True            # e.g. (8, 64, 8) low
        assert not hits, f"full-rank gather in TP refresh: {hits}"
        assert gathered_lowrank, "no low-rank gather found - wrong scan?"
        print("OK no full-rank", sorted(forbidden))
    """, timeout=900)
    assert "OK no full-rank" in out


# ---------------------------------------------------------------------------
# Per-device optimizer-state bytes shrink ~tp-fold on model-sharded leaves
# ---------------------------------------------------------------------------

def test_tp_per_device_state_bytes():
    """On a (2,4) mesh (ZeRO off, so the model axis does all the work)
    every 2-D galore leaf keeps exactly one of {moments, projection} on
    the model axis per the shard-dim table; that component's max
    per-device bytes must drop ~4x vs the (8,1) mesh where the model axis
    is trivial."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.config import QGaLoreConfig
        from repro.core.optimizers import preset
        from repro.core import projector, qgalore, quant
        from repro.distributed import sharding as sh
        from repro.models import model_zoo
        from repro.train import step as step_lib

        bundle = model_zoo.build_arch("llama-60m", smoke=True,
                                      dtype=jnp.float32)
        qcfg = preset("qgalore", QGaLoreConfig(rank=8, min_dim=32))
        state = step_lib.init_state(bundle, qcfg, jax.random.PRNGKey(0),
                                    jnp.float32)
        specs = qgalore.leaf_specs(state.params, qcfg)

        def place(mesh):
            o_sh = sh.opt_state_sharding(state.params, state.opt, qcfg,
                                         mesh)
            with mesh:
                opt = jax.device_put(state.opt, o_sh)
            inner = jax.tree_util.tree_flatten(
                opt.inner, is_leaf=qgalore._is_inner_leaf)[0]
            proj = jax.tree_util.tree_flatten(
                opt.proj,
                is_leaf=lambda x: quant.is_qtensor(x) or x is None)[0]
            return inner, proj

        mesh_tp = make_mesh((2, 4), ("data", "model"))
        mesh_dp = make_mesh((8, 1), ("data", "model"))
        specs_tp = sh.annotate_tp(specs, mesh_tp)
        inner_tp, proj_tp = place(mesh_tp)
        inner_dp, proj_dp = place(mesh_dp)

        def nbytes(tree):
            arrs = jax.tree_util.tree_leaves(tree)
            dev = sum(max(s.data.nbytes for s in a.addressable_shards)
                      for a in arrs)
            return dev, sum(a.nbytes for a in arrs)

        checked = 0
        for i, sp in enumerate(specs_tp):
            if not sp.galore or sp.shard_dim is None:
                continue
            if projector.proj_dim_sharded(sp.side, sp.shard_dim):
                tgt_tp, tgt_dp = proj_tp[i], proj_dp[i]      # P sliced on d
            else:
                tgt_tp, tgt_dp = inner_tp[i], inner_dp[i]    # moments
            dev_tp, tot_tp = nbytes(tgt_tp)
            dev_dp, tot_dp = nbytes(tgt_dp)
            assert tot_tp == tot_dp                          # same state
            assert dev_dp == tot_dp, (sp.path, dev_dp, tot_dp)
            # ~tp-fold: INT4/INT8 codes split exactly 4x, per-block
            # scales may stay replicated when they don't divide
            assert dev_tp * 4 <= tot_tp * 1.3, \\
                (sp.path, dev_tp, tot_tp)
            checked += 1
        assert checked >= 6, checked
        print("OK tp bytes", checked)
    """, timeout=600)
    assert "OK tp bytes" in out


# ---------------------------------------------------------------------------
# Sharded subspace math on a real 1-axis mesh
# ---------------------------------------------------------------------------

def test_tp_sharded_subspace_collectives():
    """projector.sharded_subspace / explained_ratio_sharded inside a real
    shard_map over a 4-device axis: both sides x both shard dims, the
    Gram-accumulated subspace must match the SVD subspace (compared via
    subspace similarity — eigen vs SVD differ elementwise at fp32 noise)
    and the sharded explained-variance profile must match the replicated
    one to float tolerance."""
    out = run_py("""
        import functools, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import projector

        mesh = make_mesh((4,), ("x",))
        psum = functools.partial(jax.lax.psum, axis_name="x")
        G = jax.random.normal(jax.random.PRNGKey(0), (48, 64), jnp.float32)
        rank = 8
        for side in ("right", "left"):
            P_ref = projector.compute_subspace(G, rank, side)
            Pq = projector.quantize_projection(P_ref, bits=4, block=8)
            Pf = projector.maybe_dequantize(Pq)
            ratio_ref = np.asarray(
                projector.explained_ratio(G, Pf, side))
            for shard_dim in (0, 1):
                g_spec = P("x", None) if shard_dim == 0 else P(None, "x")
                sliced = projector.proj_dim_sharded(side, shard_dim)
                p_spec = P("x", None) if sliced else P(None, None)

                f = functools.partial(projector.sharded_subspace,
                                      rank=rank, side=side,
                                      shard_dim=shard_dim, psum=psum)
                P_sh = jax.shard_map(
                    f, mesh=mesh, in_specs=(g_spec,), out_specs=p_spec,
                    check_vma=False)(G)
                sim = float(projector.subspace_similarity(P_ref, P_sh))
                assert sim > 0.99, (side, shard_dim, sim)

                g = functools.partial(projector.explained_ratio_sharded,
                                      side=side, shard_dim=shard_dim,
                                      psum=psum)
                ratio_sh = jax.shard_map(
                    g, mesh=mesh, in_specs=(g_spec, p_spec),
                    out_specs=P(None), check_vma=False)(G, Pf)
                np.testing.assert_allclose(
                    np.asarray(ratio_sh), ratio_ref, rtol=1e-5, atol=1e-6,
                    err_msg=f"{side}/{shard_dim}")
        print("OK sharded subspace")
    """, devices=4, timeout=600)
    assert "OK sharded subspace" in out


# ---------------------------------------------------------------------------
# Host-side shard algebra (no mesh needed)
# ---------------------------------------------------------------------------

def test_projection_shard_reassemble_and_project():
    """Pure shard algebra: slicing an INT4 projection along d commutes
    with reassembly bit-exactly (codes AND scales), and per-shard
    projection recomposes the replicated low-rank product for every
    side x shard-dim combination."""
    from repro.core import projector

    world = 4
    for side, (m, n) in (("right", (64, 32)), ("left", (32, 64))):
        G = jax.random.normal(jax.random.PRNGKey(1), (m, n), jnp.float32)
        P_ = projector.compute_subspace(G, 8, side)
        Pq = projector.quantize_projection(P_, bits=4, block=8)
        Pf = projector.maybe_dequantize(Pq)
        low_full = projector.project(G, Pf, side)
        for shard_dim in (0, 1):
            shards = [projector.shard_projection(Pq, side, shard_dim, k,
                                                 world)
                      for k in range(world)]
            back = projector.reassemble_projection(shards, side, shard_dim)
            for a, b in zip(jax.tree_util.tree_leaves(Pq),
                            jax.tree_util.tree_leaves(back)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

            g_shards = [projector.shard_matrix(G, shard_dim, k, world)
                        for k in range(world)]
            lows = [projector.project_sharded(
                        g_shards[k],
                        projector.shard_projection(Pf, side, shard_dim, k,
                                                   world),
                        side, shard_dim, psum=lambda x: x)
                    for k in range(world)]
            if projector.proj_dim_sharded(side, shard_dim):
                low = sum(lows)                  # contracted dim: reduce
            else:                                # surviving dim: concat
                axis = -2 if side == "right" else -1
                low = jnp.concatenate(lows, axis=axis)
            np.testing.assert_allclose(np.asarray(low),
                                       np.asarray(low_full),
                                       rtol=1e-5, atol=1e-6,
                                       err_msg=f"{side}/{shard_dim}")
