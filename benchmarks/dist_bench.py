"""Distributed Q-GaLore DP training bench: bytes-on-wire + step time,
compressed (project-before-all-reduce, ``dp_compress``) vs full-rank
GSPMD data parallelism, on a forced 8-device host mesh.

Modes (same init state, same batch, replicated optimizer state so the
wire numbers isolate the DP gradient synchronization):

* ``fullrank``   — the textbook DP-GaLore baseline: ``impl="simple"``
  materializes full-rank dW, GSPMD all-reduces it, the optimizer projects
  AFTER the reduce (what a DDP gradient hook does).
* ``gspmd``      — fused projected backward (grads leave the step
  low-rank) but no manual collectives: GSPMD places the reduction where
  it likes, auto-compressing some leaves and not others.
* ``compressed`` — the production path: fused backward + ``dp_compress``
  shard_map, ONE explicit low-rank pmean.

Measurements per mode: (a) bytes-on-wire — the summed result bytes of
every collective op (all-reduce / reduce-scatter / all-gather /
collective-permute / all-to-all) in the compiled HLO of one step, plus the
analytic payload from the leaf specs; (b) wall-clock step time (median of
``--iters`` post-warmup).

All modes use the GaLore-2-style large-scale DP recipe
``galore_embeddings=True`` (the embedding/unembedding rows otherwise
dominate the wire at these shapes); the analytic section also reports the
paper-default ``galore_embeddings=False`` ratio for honesty.

A ``compressed_zero`` variant re-times the compressed step with the
quantized optimizer state ZeRO-sharded over the DP axes
(``opt_state_sharding(zero_axes=...)``) and reports global vs
max-per-device optimizer bytes — the memory axis of the same subsystem
(its gathers/scatters are GSPMD-inserted at the point of use and show up
in its wire column; they are state traffic, not gradient sync).

A ``tp`` section re-times the compressed step on a 2-D ``(D/tp, tp)``
data x model mesh vs the pure-DP ``(D, 1)`` mesh (ZeRO off, isolating
the model axis) and reports per-device Adam-moment / INT4-projection
bytes: per the shard-dim table in ``core/projector.py`` each 2-D galore
leaf keeps exactly one of {moments, projection} on the model axis, so
``tp_model_sharded_state_reduction_x`` lands ~tp.

    PYTHONPATH=src:. python benchmarks/dist_bench.py --out BENCH_dist.json
    PYTHONPATH=src:. python benchmarks/dist_bench.py --smoke   # CI
"""
from __future__ import annotations

import argparse
import json
import re
import time

from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import force_host_device_count, make_mesh

_BYTES = {"f32": 4, "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f64": 8}

_COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather",
                "collective-permute", "all-to-all")


def hlo_collective_bytes(compiled_text: str) -> dict:
    """Sum the result bytes of every collective in a compiled HLO dump.

    Handles both single-result ops (``= f32[8,512]{1,0} all-reduce(...)``)
    and the tuple-result form XLA's combiner passes emit when they merge
    per-leaf reductions (``= (f32[...]{...}, f32[...]{...}) all-reduce``)
    — every tuple element is counted.
    """
    out = {k: 0 for k in _COLLECTIVES}
    pat = re.compile(
        r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\]\S*))\s*("
        + "|".join(_COLLECTIVES) + r")\(")
    shape_pat = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
    for m in pat.finditer(compiled_text):
        op = m.group(2)
        for dt, dims in shape_pat.findall(m.group(1)):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            out[op] += n * _BYTES.get(dt, 4)
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def analytic_payload_bytes(specs) -> dict:
    """Per-step DP gradient-reduction payload (f32 words) from leaf specs.
    The compressed number is the canonical ``qgalore.dp_payload_bytes``
    counter (also what the adaptive-rank ablation asserts on), so rank
    overrides flow through here too."""
    import numpy as np
    from repro.core import qgalore
    full = 4 * sum(int(np.prod(s.shape)) for s in specs if not s.frozen)
    comp = qgalore.dp_payload_bytes(specs)
    return {"fullrank_bytes": full, "compressed_bytes": comp,
            "ratio": full / max(comp, 1)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--arch", default="llama-60m")
    ap.add_argument("--smoke", action="store_true",
                    help="smoke-config model (CI); full config otherwise")
    ap.add_argument("--rank", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--out", default="BENCH_dist.json")
    args = ap.parse_args()

    force_host_device_count(args.devices)
    import jax
    import jax.numpy as jnp
    import numpy as np
    enable_compile_cache()

    from repro.config import QGaLoreConfig, ShapeCell, TrainConfig, replace
    from repro.core.optimizers import preset
    from repro.data.synthetic import batch_for_bundle
    from repro.distributed import sharding as sh
    from repro.models import model_zoo
    from repro.train import step as step_lib

    mesh = make_mesh((args.devices, 1), ("data", "model"))
    bundle = model_zoo.build_arch(args.arch, smoke=args.smoke,
                                  dtype=jnp.float32)
    rank = min(args.rank, 8 if args.smoke else args.rank)
    min_dim = 32 if args.smoke else 128
    tcfg = TrainConfig(global_batch=args.batch, seq_len=args.seq,
                       grad_clip=1.0)
    cell = ShapeCell("bench", args.seq, args.batch, "train")
    batch = batch_for_bundle(bundle, cell, 0)

    modes = {
        "fullrank": dict(impl="simple", compress=False, zero=False),
        "gspmd": dict(impl="fused", compress=False, zero=False),
        "compressed": dict(impl="fused", compress=True, zero=False),
        "compressed_zero": dict(impl="fused", compress=True, zero=True),
    }
    report: dict = {
        "arch": args.arch, "smoke": args.smoke, "rank": rank,
        "devices": args.devices, "batch": args.batch, "seq": args.seq,
        "modes": {},
    }

    qcfg = preset("qgalore", QGaLoreConfig(
        rank=rank, min_dim=min_dim, galore_embeddings=True))
    for name, m in modes.items():
        mode_qcfg = replace(qcfg, compress_dp_grads=m["compress"])
        raw, specs = step_lib.build_train_step(
            bundle, mode_qcfg, tcfg, impl=m["impl"],
            param_dtype=jnp.float32, mesh=mesh, dp_compress=m["compress"])
        state = step_lib.init_state(bundle, mode_qcfg,
                                    jax.random.PRNGKey(0), jnp.float32)
        p_sh = sh.param_sharding(state.params, mesh)
        zaxes = sh.zero_axes_for(mesh) if m["zero"] else ()
        o_sh = sh.opt_state_sharding(state.params, state.opt, mode_qcfg,
                                     mesh, zero_axes=zaxes)
        b_sh = sh.data_sharding(
            jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch),
            mesh)
        rep = sh.replicated(mesh)
        ss = step_lib.TrainState(p_sh, o_sh)
        fn = jax.jit(lambda st, b, lr, rng: raw(
            st, b, lr, rng, refresh_masks=None, refresh=False),
            in_shardings=(ss, b_sh, rep, rep), out_shardings=(ss, None, None))

        with mesh:
            st = jax.device_put(state, ss)
            bt = jax.device_put(batch, b_sh)
            lowered = fn.lower(st, bt, 1e-3, jax.random.PRNGKey(1))
            compiled = lowered.compile()
            wire = hlo_collective_bytes(compiled.as_text())
            # warm + time
            st2, metrics, _ = fn(st, bt, 1e-3, jax.random.PRNGKey(1))
            jax.block_until_ready(st2)
            times = []
            for i in range(args.iters):
                t0 = time.monotonic()
                st2, metrics, _ = fn(st2, bt, 1e-3, jax.random.PRNGKey(i))
                jax.block_until_ready(st2)
                times.append(time.monotonic() - t0)
        opt_leaves = [l for l in jax.tree_util.tree_leaves(st2.opt)
                      if hasattr(l, "addressable_shards")]
        report["modes"][name] = {
            "loss": float(metrics["loss"]),
            "step_time_s_median": float(np.median(times)),
            "step_time_s_all": [round(t, 4) for t in times],
            "hlo_collective_bytes": wire,
            "opt_state_bytes_global": sum(l.nbytes for l in opt_leaves),
            "opt_state_bytes_max_per_device": sum(
                max(s.data.nbytes for s in l.addressable_shards)
                for l in opt_leaves),
        }
        print(f"{name:>16}: loss {report['modes'][name]['loss']:.4f}  "
              f"step {report['modes'][name]['step_time_s_median']:.3f}s  "
              f"wire {wire['total'] / 2**20:.1f} MiB")

    # ------------------------------------------------------------------
    # TP section: the same compressed step on a pure-DP (D,1) mesh vs a
    # 2-D (D/tp, tp) data x model mesh, ZeRO off so the model axis does
    # all the state-sharding work (the compressed_zero mode above covers
    # the DP/ZeRO axis). Per the shard-dim table in core/projector.py
    # every 2-D galore leaf keeps exactly ONE of {Adam moments, INT4
    # projection} on the model axis, so that component's per-device peak
    # drops ~tp-fold; the headline ratio below measures exactly those
    # components under both placements.
    # ------------------------------------------------------------------
    from repro.core import projector, qgalore, quant

    tp = 4 if args.devices % 4 == 0 else 2
    qcfg_tp = replace(qcfg, compress_dp_grads=True)
    tp_runs: dict = {}
    for shape in ((args.devices, 1), (args.devices // tp, tp)):
        dname = f"{shape[0]}x{shape[1]}"
        mesh_t = make_mesh(shape, ("data", "model"))
        raw, specs_t = step_lib.build_train_step(
            bundle, qcfg_tp, tcfg, impl="fused", param_dtype=jnp.float32,
            mesh=mesh_t, dp_compress=True)
        state = step_lib.init_state(bundle, qcfg_tp, jax.random.PRNGKey(0),
                                    jnp.float32)
        p_sh = sh.param_sharding(state.params, mesh_t)
        o_sh = sh.opt_state_sharding(state.params, state.opt, qcfg_tp,
                                     mesh_t)
        b_sh = sh.data_sharding(
            jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch),
            mesh_t)
        rep = sh.replicated(mesh_t)
        ss = step_lib.TrainState(p_sh, o_sh)
        fn = jax.jit(lambda st, b, lr, rng: raw(
            st, b, lr, rng, refresh_masks=None, refresh=False),
            in_shardings=(ss, b_sh, rep, rep),
            out_shardings=(ss, None, None))
        with mesh_t:
            st = jax.device_put(state, ss)
            bt = jax.device_put(batch, b_sh)
            wire = hlo_collective_bytes(
                fn.lower(st, bt, 1e-3, jax.random.PRNGKey(1))
                .compile().as_text())
            st2, metrics, _ = fn(st, bt, 1e-3, jax.random.PRNGKey(1))
            jax.block_until_ready(st2)
            times = []
            for i in range(args.iters):
                t0 = time.monotonic()
                st2, metrics, _ = fn(st2, bt, 1e-3, jax.random.PRNGKey(i))
                jax.block_until_ready(st2)
                times.append(time.monotonic() - t0)

        def split(tree):
            leaves = [l for l in jax.tree_util.tree_leaves(tree)
                      if hasattr(l, "addressable_shards")]
            return (sum(l.nbytes for l in leaves),
                    sum(max(s.data.nbytes for s in l.addressable_shards)
                        for l in leaves))

        mom_g, mom_d = split(st2.opt.inner)
        prj_g, prj_d = split(st2.opt.proj)
        tp_runs[dname] = {
            "specs": specs_t,
            "inner_flat": jax.tree_util.tree_flatten(
                st2.opt.inner, is_leaf=qgalore._is_inner_leaf)[0],
            "proj_flat": jax.tree_util.tree_flatten(
                st2.opt.proj,
                is_leaf=lambda x: quant.is_qtensor(x) or x is None)[0],
            "summary": {
                "loss": float(metrics["loss"]),
                "step_time_s_median": float(np.median(times)),
                "hlo_collective_bytes": wire,
                "moment_bytes_global": mom_g,
                "moment_bytes_max_per_device": mom_d,
                "projection_bytes_global": prj_g,
                "projection_bytes_max_per_device": prj_d,
            },
        }
        print(f"{dname:>16}: loss {metrics['loss']:.4f}  "
              f"step {float(np.median(times)):.3f}s  "
              f"state/dev {(mom_d + prj_d) / 2**20:.2f} MiB")

    dp_name = f"{args.devices}x1"
    tp_name = f"{args.devices // tp}x{tp}"
    # the model-sharded component of every 2-D galore leaf, measured
    # under BOTH placements (leaf order is mesh-independent)
    specs_2d = tp_runs[tp_name]["specs"]

    def sharded_component_device_bytes(run):
        total = 0
        for i, sp in enumerate(specs_2d):
            if not sp.galore or sp.shard_dim is None:
                continue
            tgt = run["proj_flat"][i] if projector.proj_dim_sharded(
                sp.side, sp.shard_dim) else run["inner_flat"][i]
            total += sum(
                max(s.data.nbytes for s in a.addressable_shards)
                for a in jax.tree_util.tree_leaves(tgt))
        return total

    report["tp"] = {
        "tp_degree": tp,
        "meshes": {k: v["summary"] for k, v in tp_runs.items()},
        "model_sharded_component_device_bytes": {
            k: sharded_component_device_bytes(v)
            for k, v in tp_runs.items()},
    }
    report["tp_model_sharded_state_reduction_x"] = (
        report["tp"]["model_sharded_component_device_bytes"][dp_name]
        / max(report["tp"]["model_sharded_component_device_bytes"][tp_name],
              1))
    report["tp_galore_state_device_reduction_x"] = (
        (tp_runs[dp_name]["summary"]["moment_bytes_max_per_device"]
         + tp_runs[dp_name]["summary"]["projection_bytes_max_per_device"])
        / max(tp_runs[tp_name]["summary"]["moment_bytes_max_per_device"]
              + tp_runs[tp_name]["summary"]
              ["projection_bytes_max_per_device"], 1))

    # analytic payloads for both embedding recipes (no step build needed)
    specs_emb = step_lib._specs_for(bundle, qcfg, jnp.float32)
    specs_noemb = step_lib._specs_for(
        bundle, replace(qcfg, galore_embeddings=False), jnp.float32)
    report["analytic"] = {
        "galore_embeddings": analytic_payload_bytes(specs_emb),
        "paper_default": analytic_payload_bytes(specs_noemb),
    }

    full = report["modes"]["fullrank"]
    comp = report["modes"]["compressed"]
    zero = report["modes"]["compressed_zero"]
    # headline: bytes a DDP-style full-rank gradient sync ships (every
    # grad leaf at full shape — what torch-DDP GaLore all-reduces) over
    # the bytes the compressed step MEASURABLY ships (compiled HLO)
    report["wire_reduction_x_vs_ddp"] = (
        report["analytic"]["galore_embeddings"]["fullrank_bytes"]
        / max(comp["hlo_collective_bytes"]["total"], 1))
    # vs the measured GSPMD baseline, which already auto-compresses some
    # leaves by sinking its all-reduce past projection dots
    report["wire_reduction_x_hlo"] = (
        full["hlo_collective_bytes"]["total"]
        / max(comp["hlo_collective_bytes"]["total"], 1))
    report["wire_reduction_x_analytic"] = \
        report["analytic"]["galore_embeddings"]["ratio"]
    # production TPU recipe: REPRO_BF16_REDUCE=1 reduces the low-rank
    # payload in bf16 (paper §3.1 keeps grads bf16) — half the bytes of
    # the f32 reduction measured above, vs a DDP stack shipping f32
    # master grads. (CPU CI reduces in f32 — see the XLA:CPU note in
    # train/step.py — so this cell is analytic, not HLO-measured.)
    report["wire_reduction_x_bf16_reduce_vs_ddp_f32"] = 2 * \
        report["analytic"]["galore_embeddings"]["ratio"]
    report["steptime_ratio_compressed_over_fullrank"] = (
        comp["step_time_s_median"] / full["step_time_s_median"])
    # the production configuration (launch/train --compress --zero)
    report["steptime_ratio_compressed_zero_over_fullrank"] = (
        zero["step_time_s_median"] / full["step_time_s_median"])
    report["zero_shard_reduction_x"] = (
        zero["opt_state_bytes_global"]
        / max(zero["opt_state_bytes_max_per_device"], 1))
    print(json.dumps({k: v for k, v in report.items()
                      if not isinstance(v, dict)}, indent=2))
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print("wrote", args.out)


if __name__ == "__main__":
    main()
