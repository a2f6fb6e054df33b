import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
# ^^ MUST precede any jax-touching import: jax locks the device count at
# first backend init. Everything below may import jax.

import argparse          # noqa: E402
import json              # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.analysis import hlo as hlo_lib                    # noqa: E402
from repro.config import QGaLoreConfig, TrainConfig, cells_for_arch  # noqa: E402
from repro.core.optimizers import preset                     # noqa: E402
from repro.distributed import sharding as shard_lib          # noqa: E402
from repro.launch.mesh import make_production_mesh           # noqa: E402
from repro.models import model_zoo                           # noqa: E402
from repro.serve import engine as serve_engine               # noqa: E402
from repro.serve import shard as serve_shard                 # noqa: E402
from repro.train import step as step_lib                     # noqa: E402

"""Multi-pod dry-run: ``.lower().compile()`` for every
(architecture × input-shape × mesh) cell, recording cost/memory analysis and
collective payloads for the roofline (EXPERIMENTS.md §Dry-run / §Roofline).

Runs each cell in-process via ``run_cell`` or as a fleet of subprocesses via
``--all`` (isolation: one bad cell cannot take down the sweep; the 512
host-device flag is per-process)."""

QCFG = QGaLoreConfig(rank=128)   # paper's production optimizer settings


def _qchunk(cell) -> int:
    # memory-bounded attention chunking for long sequences
    return 1024 if cell.seq_len >= 8192 else max(cell.seq_len, 256)


def _accum(arch: str, cell) -> int:
    """Microbatch (gradient-accumulation) factor for the train cell —
    bounds the per-step activation footprint on big models."""
    if cell.kind != "train":
        return 1
    big = {"deepseek-v3-671b": 8, "qwen3-32b": 4, "qwen3-moe-30b-a3b": 4,
           "mistral-nemo-12b": 4, "yi-9b": 4, "gemma-7b": 4,
           "zamba2-2.7b": 2, "llama-7b": 4}
    return big.get(arch, 1)


def run_cell(arch: str, cell_name: str, multi_pod: bool,
             refresh: bool = False, compress: bool = False):
    """Lower + compile one (arch × cell × mesh); returns the artifact dict."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = model_zoo.get_config(arch)
    cell = next(c for c in cells_for_arch(arch) if c.name == cell_name)
    # compress mode: MoE experts ride the shard_map sharded over 'data' with
    # manual all-to-all dispatch (moe_apply_ep); otherwise GSPMD-auto EP.
    moe_ep_axis = None
    if (compress and cell.kind == "train" and cfg.moe is not None
            and cfg.moe.num_experts % mesh.shape["data"] == 0):
        moe_ep_axis = "data"
    shard_lib.set_ep_full_mesh(moe_ep_axis is not None)
    build_kw = {}
    if moe_ep_axis and cfg.family == "moe":
        build_kw["ep_axis"] = moe_ep_axis
    bundle = model_zoo.build(cfg, q_chunk=_qchunk(cell), dtype=jnp.bfloat16,
                             **build_kw)

    art = {
        "arch": arch, "cell": cell_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        # the chip this host-emulated mesh stands for (roofline peaks)
        "device_kind": "TPU v5 lite",
        "kind": cell.kind, "refresh": refresh, "compress": compress,
        "params": model_zoo.count_params_analytic(cfg),
        "active_params": model_zoo.count_active_params(cfg),
        "ok": False,
    }
    t0 = time.time()

    if cell.kind == "train":
        qcfg = QCFG
        tcfg = TrainConfig(global_batch=cell.global_batch,
                           seq_len=cell.seq_len, grad_clip=1.0)
        accum = _accum(arch, cell)
        raw_step, specs = step_lib.build_train_step(
            bundle, qcfg, tcfg, impl="fused", accum=accum,
            param_dtype=jnp.bfloat16, mesh=mesh, dp_compress=compress,
            moe_ep_axis=moe_ep_axis)
        state_abs = step_lib.abstract_state(bundle, qcfg, jnp.bfloat16)
        batch_abs = bundle.input_specs(cell)

        p_shard = shard_lib.param_sharding(state_abs.params, mesh)
        o_shard = shard_lib.opt_state_sharding(state_abs.params,
                                               state_abs.opt, qcfg, mesh)
        b_shard = shard_lib.data_sharding(batch_abs, mesh)
        state_shard = step_lib.TrainState(p_shard, o_shard)
        rep = shard_lib.replicated(mesh)

        if refresh:
            masks_abs = {
                i: jax.ShapeDtypeStruct((s.nbatch,), jnp.bool_)
                for i, s in enumerate(specs) if s.galore}
            fn = jax.jit(
                lambda st, b, lr, rng, masks: raw_step(
                    st, b, lr, rng, refresh_masks=masks, refresh=True),
                in_shardings=(state_shard, b_shard, rep, rep,
                              {i: rep for i in masks_abs}),
                donate_argnums=(0,))
            args = (state_abs, batch_abs,
                    jax.ShapeDtypeStruct((), jnp.float32),
                    jax.ShapeDtypeStruct((2,), jnp.uint32), masks_abs)
        else:
            fn = jax.jit(
                lambda st, b, lr, rng: raw_step(st, b, lr, rng,
                                                refresh_masks=None,
                                                refresh=False),
                in_shardings=(state_shard, b_shard, rep, rep),
                donate_argnums=(0,))
            args = (state_abs, batch_abs,
                    jax.ShapeDtypeStruct((), jnp.float32),
                    jax.ShapeDtypeStruct((2,), jnp.uint32))
        tokens = cell.global_batch * cell.seq_len
        art["model_flops"] = 6.0 * art["active_params"] * tokens

    elif cell.kind == "prefill":
        params_abs = jax.eval_shape(
            lambda k: step_lib.prepare_params(bundle.init_params(k), QCFG),
            jax.random.PRNGKey(0))
        batch_abs = bundle.input_specs(cell)
        p_shard = shard_lib.param_sharding(params_abs, mesh)
        b_shard = shard_lib.data_sharding(batch_abs, mesh)
        # VLM: the KV window must cover prefix embeddings + prompt
        prefill = serve_engine.build_prefill(
            bundle, max_len=cell.seq_len + cfg.num_prefix_embeddings)
        fn = jax.jit(prefill, in_shardings=(p_shard, b_shard))
        args = (params_abs, batch_abs)
        art["model_flops"] = 2.0 * art["active_params"] \
            * cell.global_batch * cell.seq_len

    else:  # decode
        params_abs = jax.eval_shape(
            lambda k: step_lib.prepare_params(bundle.init_params(k), QCFG),
            jax.random.PRNGKey(0))
        p_shard = shard_lib.param_sharding(params_abs, mesh)
        state_abs = serve_engine.abstract_decode_state(
            bundle, cell.global_batch, cell.seq_len, jnp.bfloat16)
        s_shard = serve_shard.decode_state_sharding(state_abs, mesh)
        tok_abs = jax.ShapeDtypeStruct((cell.global_batch, 1), jnp.int32)
        t_shard = shard_lib.data_sharding({"t": tok_abs}, mesh)["t"]
        decode = serve_engine.build_decode(bundle)
        fn = jax.jit(decode, in_shardings=(p_shard, s_shard, t_shard),
                     donate_argnums=(1,))
        args = (params_abs, state_abs, tok_abs)
        art["model_flops"] = 2.0 * art["active_params"] * cell.global_batch

    with mesh:
        lowered = fn.lower(*args)
        compiled = lowered.compile()

    art["compile_s"] = time.time() - t0
    try:
        mem = compiled.memory_analysis()
        art["memory_analysis"] = {
            k: int(getattr(mem, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)}
        print("memory_analysis:", art["memory_analysis"])
    except Exception as e:  # noqa: BLE001 — backend-dependent
        art["memory_analysis"] = {"error": str(e)}
    try:
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        art["cost_analysis"] = {
            k: float(v) for k, v in cost.items()
            if isinstance(v, (int, float)) and k in
            ("flops", "bytes accessed", "optimal_seconds")}
        print("cost_analysis flops=%.3e bytes=%.3e" % (
            art["cost_analysis"].get("flops", 0),
            art["cost_analysis"].get("bytes accessed", 0)))
    except Exception as e:  # noqa: BLE001
        art["cost_analysis"] = {"error": str(e)}
    try:
        text = compiled.as_text()
        art["collectives"] = hlo_lib.parse_collectives(text)
        art["hlo_ops"] = hlo_lib.count_ops(text)
        art["hlo_chars"] = len(text)
    except Exception as e:  # noqa: BLE001
        art["collectives"] = {"error": str(e)}
    art["ok"] = True
    return art


def _out_path(out_dir, arch, cell, multi_pod, refresh):
    mesh = "2x16x16" if multi_pod else "16x16"
    sfx = "__refresh" if refresh else ""
    return os.path.join(out_dir, mesh, f"{arch}__{cell}{sfx}.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--cell")
    ap.add_argument("--multi-pod", type=int, default=0)
    ap.add_argument("--refresh", type=int, default=0)
    ap.add_argument("--compress", type=int, default=0,
                    help="DP low-rank gradient compression (beyond-paper)")
    ap.add_argument("--unroll", type=int, default=0,
                    help="unroll layer scans for exact FLOP/collective "
                         "accounting (XLA cost_analysis counts loop bodies "
                         "once)")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--all", action="store_true",
                    help="run every cell in subprocesses")
    ap.add_argument("--skip-existing", type=int, default=1)
    args = ap.parse_args()

    if args.all:
        import subprocess
        archs = [a for a in model_zoo.ARCH_IDS if not a.startswith("llama-")]
        jobs = []
        for mp in (0, 1):
            for arch in archs:
                for cell in cells_for_arch(arch):
                    jobs.append((arch, cell.name, mp, 0))
        # refresh-variant proof for one representative arch
        jobs.append(("yi-9b", "train_4k", 0, 1))
        failures = []
        for arch, cell, mp, rf in jobs:
            path = _out_path(args.out, arch, cell, mp, rf)
            if args.skip_existing and os.path.exists(path):
                print(f"[skip] {path}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--cell", cell, "--multi-pod", str(mp),
                   "--refresh", str(rf), "--out", args.out]
            print("[run]", " ".join(cmd), flush=True)
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=7200)
            if r.returncode != 0:
                failures.append((arch, cell, mp))
                os.makedirs(os.path.dirname(path), exist_ok=True)
                with open(path, "w") as f:
                    json.dump({"arch": arch, "cell": cell, "ok": False,
                               "error": r.stderr[-2000:]}, f, indent=1)
                print(r.stderr[-800:], flush=True)
        print(f"done; {len(failures)} failures: {failures}")
        sys.exit(1 if failures else 0)

    if args.unroll:
        os.environ["REPRO_SCAN_UNROLL"] = "full"
    art = None
    try:
        art = run_cell(args.arch, args.cell, bool(args.multi_pod),
                       bool(args.refresh), bool(args.compress))
        art["unroll"] = bool(args.unroll)
    except Exception:
        art = {"arch": args.arch, "cell": args.cell, "ok": False,
               "error": traceback.format_exc()[-3000:]}
        raise
    finally:
        if art is not None:
            path = _out_path(args.out, args.arch, args.cell,
                             bool(args.multi_pod), bool(args.refresh))
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(path, "w") as f:
                json.dump(art, f, indent=1)
            print("wrote", path)


if __name__ == "__main__":
    main()
