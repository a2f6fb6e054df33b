"""Production training launcher: any --arch on any mesh.

On a real TPU slice this is the per-host entry point (jax.distributed
initializes from the TPU environment); on the CPU container pass
``--devices N --mesh dxm`` to emulate a small mesh, or nothing for
single-device smoke runs.

    PYTHONPATH=src python -m repro.launch.train --arch llama-60m --smoke \
        --steps 50
    PYTHONPATH=src python -m repro.launch.train --arch yi-9b \
        --mesh 16x16 --batch 256 --seq 4096 --compress --zero  # on hardware
    PYTHONPATH=src python -m repro.launch.train --arch llama-60m --smoke \
        --devices 8 --mesh 8x1 --compress --zero   # distributed mode on CPU
"""
import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama-60m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--rank", type=int, default=128)
    ap.add_argument("--adaptive-rank", action="store_true",
                    help="dynamic per-layer rank adaptation: shrink a "
                         "leaf's rank down the --rank-ladder when its "
                         "explained-variance ratio holds above "
                         "--rank-threshold for --rank-patience refreshes")
    ap.add_argument("--rank-ladder", default="",
                    help="comma-separated shrink rungs, e.g. 64,32 "
                         "(empty = halve)")
    ap.add_argument("--rank-threshold", type=float, default=0.95)
    ap.add_argument("--rank-patience", type=int, default=2)
    ap.add_argument("--min-rank", type=int, default=8)
    ap.add_argument("--optimizer", default="qgalore")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--compress", action="store_true",
                    help="DP low-rank gradient compression + distributed "
                         "subspace refresh (shard_map)")
    ap.add_argument("--zero", action="store_true",
                    help="ZeRO-shard the quantized optimizer state over "
                         "the DP axes (combined with --compress this also "
                         "turns on the ZeRO-2 gradient reduce-scatter; "
                         "see --zero2)")
    ap.add_argument("--zero2", type=int, default=-1, choices=(-1, 0, 1),
                    help="force the ZeRO-2 low-rank-gradient "
                         "reduce-scatter on (1) or off (0); default -1 "
                         "follows --zero")
    ap.add_argument("--mesh", default="",
                    help="dxm, e.g. 4x2 (data x model); empty = single dev")
    ap.add_argument("--tp", type=int, default=0,
                    help="tensor-parallel degree: builds a (devices/tp, "
                         "tp) (data x model) mesh. Mutually exclusive "
                         "with --mesh; must divide the device count")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices (CPU emulation)")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--multihost", action="store_true",
                    help="initialize jax.distributed (real clusters)")
    args = ap.parse_args()

    if args.devices:
        from repro.launch.mesh import force_host_device_count
        force_host_device_count(args.devices)
    import jax
    if args.multihost:
        jax.distributed.initialize()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    import logging
    import jax.numpy as jnp
    from repro.config import QGaLoreConfig, ShapeCell, TrainConfig
    from repro.core.optimizers import preset
    from repro.launch.mesh import make_mesh, make_tp_mesh
    from repro.models import model_zoo
    from repro.train.trainer import Trainer

    logging.basicConfig(level=logging.INFO)
    mesh = None
    if args.mesh and args.tp:
        raise SystemExit("--mesh and --tp both fix the mesh shape — "
                         "pass one or the other")
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        if d * m != len(jax.devices()):
            raise SystemExit(
                f"--mesh {args.mesh} needs {d * m} devices, have "
                f"{len(jax.devices())} (use --devices on CPU)")
        mesh = make_mesh((d, m), ("data", "model"))
    elif args.tp:
        mesh = make_tp_mesh(args.tp)

    bundle = model_zoo.build_arch(args.arch, smoke=args.smoke,
                                  dtype=jnp.float32 if args.smoke
                                  else jnp.bfloat16)
    ladder = tuple(int(x) for x in args.rank_ladder.split(",") if x)
    qcfg = preset(args.optimizer, QGaLoreConfig(
        rank=args.rank, min_dim=64 if args.smoke else 128,
        compress_dp_grads=args.compress,
        adaptive_rank=args.adaptive_rank, rank_ladder=ladder,
        explained_ratio_threshold=args.rank_threshold,
        rank_patience=args.rank_patience, min_rank=args.min_rank))
    tcfg = TrainConfig(global_batch=args.batch, seq_len=args.seq,
                       steps=args.steps, learning_rate=args.lr,
                       warmup_steps=max(args.steps // 20, 1), log_every=10,
                       checkpoint_dir=args.checkpoint_dir,
                       checkpoint_every=args.checkpoint_every)
    cell = ShapeCell("train", args.seq, args.batch, "train")
    trainer = Trainer(bundle, tcfg, qcfg, cell=cell, accum=args.accum,
                      mesh=mesh, zero_shard=args.zero and mesh is not None,
                      zero2=None if args.zero2 < 0 else bool(args.zero2),
                      param_dtype=jnp.float32 if args.smoke
                      else jnp.bfloat16)
    if mesh is not None:
        leaves = [l for l in jax.tree_util.tree_leaves(trainer.state.opt)
                  if hasattr(l, "addressable_shards")]
        tot = sum(l.nbytes for l in leaves)
        per_dev = sum(max(s.data.nbytes for s in l.addressable_shards)
                      for l in leaves)
        logging.getLogger("repro.launch").info(
            "optimizer state: %.1f MB global, %.1f MB max/device "
            "(zero_shard=%s)", tot / 2**20, per_dev / 2**20, args.zero)
    trainer.maybe_restore()
    hist = trainer.run()
    print(f"final loss {hist[-1]['loss']:.4f}; "
          f"SVD used {trainer.controller.total_svd_count()} / "
          f"{trainer.controller.baseline_svd_count(args.steps)} baseline")
    if args.adaptive_rank:
        from repro.core import qgalore
        for t in trainer.controller.rank_transition_summary():
            print(f"rank transition: step {t['step']} {t['path']} "
                  f"{t['old']} -> {t['new']}")
        bytes_now = qgalore.optimizer_state_bytes(
            trainer.state.params, trainer.rules, specs=trainer.specs)
        print(f"optimizer state {bytes_now / 2**20:.2f} MB; "
              f"DP payload {qgalore.dp_payload_bytes(trainer.specs)} B/step")


if __name__ == "__main__":
    main()
