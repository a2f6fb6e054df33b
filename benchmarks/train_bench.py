"""End-to-end train-step and decode-token throughput: quantized-dense
(INT8-native compute) vs the dequantize-then-einsum baseline.

For each model the SAME quantized parameters run through two traced
variants of the full pipeline (fused projected-backward train step +
Q-GaLore update; serve prefill + per-token decode):

* ``quantized`` — ``layers.QUANTIZED_DENSE = True`` (default): every
  QTensor matmul streams INT8 blocks through the dispatch-registered
  ``quantized_dense`` op; no full-precision weight view exists.
* ``dequant``   — the legacy baseline: materialize (dequantize) each
  weight, einsum in full precision; autodiff saves the dequantized copy,
  and decode re-dequantizes the stacked layer pytree per token.

Both variants are compiled up front and then timed with **interleaved
paired rounds** (see ``benchmarks/common.paired_times``), phase-major:
within each round the two modes of one phase run back-to-back, and the headline
``*_speedup_x`` fields are the trimmed means of the per-round ratios
(with ``*_speedup_sem`` standard errors alongside). The old sequential
A/B (all quantized iters, then all dequant iters) is what manufactured
the phantom 0.76x prefill "regression" on a noisy box — drift between
the two timing windows, not a real kernel gap.

Emits the repo-standard ``name,us_per_call,derived`` CSV rows and writes
``BENCH_train.json`` — the seed of the perf trajectory (CI uploads it per
PR; compare the ``*_speedup_x`` fields across commits).

    PYTHONPATH=src:. python benchmarks/train_bench.py            # full
    PYTHONPATH=src:. python benchmarks/train_bench.py --smoke    # CI smoke
"""
from __future__ import annotations

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, paired_ratio
from repro.config import QGaLoreConfig, ShapeCell, TrainConfig
from repro.data.synthetic import batch_for_bundle
from repro.kernels import dispatch, profile
from repro.launch.cache import enable_compile_cache
from repro.models import layers, model_zoo
from repro.serve import engine
from repro.train import step as step_lib

MODELS = {"llama_60m": "llama-60m", "llama_130m": "llama-130m"}

PHASES = ("train_step", "prefill", "decode_token")


def build_variant(arch_id: str, mode: str, *, seq: int, batch: int,
                  smoke: bool) -> dict:
    """Compile the full pipeline (train step, prefill, decode) for one
    mode and return zero-arg timed callables. QUANTIZED_DENSE is a
    trace-time global, so compilation happens HERE, while it is set; the
    returned jitted programs keep the mode baked in."""
    qcfg = QGaLoreConfig(rank=32, min_dim=64, update_interval=100_000)
    tcfg = TrainConfig(global_batch=batch, seq_len=seq, steps=2)
    cell = ShapeCell("bench", seq_len=seq, global_batch=batch, kind="train")
    layers.QUANTIZED_DENSE = (mode == "quantized")
    try:
        bundle = model_zoo.build_arch(arch_id, smoke=smoke,
                                      dtype=jnp.float32)
        state = step_lib.init_state(bundle, qcfg, jax.random.PRNGKey(0),
                                    param_dtype=jnp.float32)
        raw_step, _ = step_lib.build_train_step(
            bundle, qcfg, tcfg, impl="fused", param_dtype=jnp.float32)
        step = jax.jit(functools.partial(raw_step, refresh=False,
                                         refresh_masks=None))
        b = batch_for_bundle(bundle, cell, 0)
        rng = jax.random.PRNGKey(1)

        def step_fn():
            return step(state, b, 1e-3, rng)[0]

        jax.block_until_ready(step_fn())            # compile under mode

        # serving: prefill on the first half, decode token by token
        prompt = {k: (v[:, : seq // 2]
                      if v.ndim >= 2 and v.shape[1] == seq else v)
                  for k, v in b.items()}
        prefill = jax.jit(engine.build_prefill(bundle, max_len=seq + 4))
        decode = jax.jit(engine.build_decode(bundle))

        def prefill_fn():
            return prefill(state.params, prompt)

        logits, dstate = prefill_fn()
        jax.block_until_ready(logits)
        tok = engine.sample(logits, jax.random.PRNGKey(2))

        def decode_fn(st):
            return decode(state.params, st, tok[:, None])

        jax.block_until_ready(decode_fn(dstate)[0])  # compile under mode
        return {"step": step_fn, "prefill": prefill_fn,
                "decode": decode_fn, "dstate": dstate}
    finally:
        layers.QUANTIZED_DENSE = True


def bench_model(arch_id: str, *, seq: int, batch: int, iters: int,
                decode_tokens: int, rounds: int, smoke: bool) -> dict:
    """Paired-rounds A/B of the two modes; returns the per-mode phase
    times (medians), the trimmed-ratio speedups, and their sems."""
    variants = {mode: build_variant(arch_id, mode, seq=seq, batch=batch,
                                    smoke=smoke)
                for mode in ("quantized", "dequant")}

    # Phase-major interleaving: within a round, the two modes of ONE
    # phase run back-to-back before moving on. Mode-major rounds (all
    # three phases of mode A, then all of mode B) separate the paired
    # measurements of each phase by whole train-step bursts, and the
    # allocator/cache wake these leave behind skews the short phases —
    # measured ~8% phantom deficit on llama-130m prefill vs parity when
    # the same programs are timed adjacently.
    times = {p: {m: [] for m in variants} for p in PHASES}
    modes = list(variants)
    for r in range(rounds):
        order = modes if r % 2 == 0 else list(reversed(modes))
        for m in order:
            v = variants[m]
            t0 = time.perf_counter()
            for _ in range(iters):
                out = v["step"]()
            jax.block_until_ready(out)
            times["train_step"][m].append(
                (time.perf_counter() - t0) / iters * 1e6)

        for m in order:
            v = variants[m]
            t0 = time.perf_counter()
            for _ in range(max(iters // 2, 1)):
                logits, _ = v["prefill"]()
            jax.block_until_ready(logits)
            times["prefill"][m].append(
                (time.perf_counter() - t0) / max(iters // 2, 1) * 1e6)

        for m in order:
            v = variants[m]
            st = v["dstate"]
            t0 = time.perf_counter()
            for _ in range(decode_tokens):
                logits, st = v["decode"](st)
            jax.block_until_ready(logits)
            times["decode_token"][m].append(
                (time.perf_counter() - t0) / decode_tokens * 1e6)

    results: dict = {m: {f"{p}_us": float(np.median(times[p][m]))
                         for p in PHASES} for m in variants}
    for p, name in (("train_step", "train"), ("decode_token", "decode"),
                    ("prefill", "prefill")):
        stat = paired_ratio(times[p], "dequant", "quantized")
        results[f"{name}_speedup_x"] = stat["ratio_x"]
        results[f"{name}_speedup_sem"] = stat["sem"]
        results[f"{name}_speedup_median_x"] = stat["median_x"]
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", default="llama_60m,llama_130m")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--iters", type=int, default=2,
                    help="calls per variant per round")
    ap.add_argument("--rounds", type=int, default=8,
                    help="interleaved A/B rounds per model")
    ap.add_argument("--decode-tokens", type=int, default=8)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shape-preserving configs (CI)")
    ap.add_argument("--out", default="BENCH_train.json")
    args = ap.parse_args(argv)
    enable_compile_cache()

    report = {
        "meta": {
            "platform": dispatch.platform(),
            "backend": dispatch.default_backend("quantized_dense"),
            "seq": args.seq, "batch": args.batch, "iters": args.iters,
            "rounds": args.rounds, "decode_tokens": args.decode_tokens,
            "smoke": args.smoke, "measurement": "interleaved-paired-rounds",
        },
        "results": {},
    }
    for name in args.models.split(","):
        arch = MODELS[name.strip()]
        r = bench_model(arch, seq=args.seq, batch=args.batch,
                        iters=args.iters, decode_tokens=args.decode_tokens,
                        rounds=args.rounds, smoke=args.smoke)
        for mode in ("quantized", "dequant"):
            for k, v in r[mode].items():
                emit(f"train_bench/{name}_{mode}_{k}", v,
                     f"seq={args.seq};batch={args.batch};mode={mode}")
        emit(f"train_bench/{name}_train_speedup", r["train_speedup_x"],
             f"unit=x;baseline=dequant-dense;sem={r['train_speedup_sem']:.4f}")
        emit(f"train_bench/{name}_decode_speedup", r["decode_speedup_x"],
             f"unit=x;baseline=dequant-dense;sem={r['decode_speedup_sem']:.4f}")
        emit(f"train_bench/{name}_prefill_speedup", r["prefill_speedup_x"],
             f"unit=x;baseline=dequant-dense;sem={r['prefill_speedup_sem']:.4f}")
        report["results"][name] = r

    profile.maybe_attach(report)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}", flush=True)
    return report


if __name__ == "__main__":
    main()
