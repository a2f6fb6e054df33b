"""Measured block-size autotune sweep for the Pallas kernel wrappers.

Replaces the hand-guessed ``_DEFAULT_BLOCKS`` numbers with data: for every
(op, problem shape) the serving/training hot path actually hits — the
shape grid comes from ``engine.matmul_shape_grid`` over the model zoo's
bench configs (prefill and one-token decode) — each tile candidate is
registered as an in-process table entry, run through the REAL ``ops``
wrapper (so padding, tile clamping, and the custom-VJP plumbing are all
inside the timed region), and timed best-of-``repeats``. The winner per
(op, bucketed shape, dtype) becomes a ``"source": "measured"`` entry.

Where the entries go:

* always: the ``--out`` report JSON (CI uploads it as an artifact);
* ``REPRO_REGEN_AUTOTUNE=1``: merged over the committed table at
  ``dispatch.table_path()`` (seed entries for shapes the sweep did not
  cover are kept) — this is the workflow for refreshing
  ``src/repro/kernels/autotune_table.json`` in place;
* ``--table PATH``: merged into an arbitrary table file instead.

Block kwargs only reach the Pallas backends — the pure-XLA ``ref``
backend drops them — so sweeping under ``ref`` would measure noise. The
sweep refuses to run there unless ``--backend`` names a Pallas backend
explicitly (CI smoke uses ``pallas-interpret``; real numbers come from
``pallas-tpu`` on the accelerator).

    PYTHONPATH=src:. python benchmarks/autotune_blocks.py \
        --backend pallas-interpret --smoke --out BENCH_autotune.json
    REPRO_REGEN_AUTOTUNE=1 PYTHONPATH=src:. \
        python benchmarks/autotune_blocks.py        # on-TPU refresh
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.core import projector
from repro.core.quant import quantize_blockwise
from repro.kernels import dispatch, ops, profile
from repro.launch.cache import enable_compile_cache
from repro.models import model_zoo
from repro.serve import engine

MODELS = ("llama-60m", "llama-130m")

# Raw tile candidates per op; the wrapper's pick_tile/fit_block clamps
# turn these into the effective tiles, so distinct candidates that clamp
# to the same effective tuple are deduplicated before timing.
CANDIDATES = {
    "int8_matmul": [
        {"bm": bm, "bn": bn, "bk": bk}
        for bm in (64, 128, 256) for bn in (256, 512, 1024)
        for bk in (256, 512, 1024)
    ],
    "int8_matmul_t": [
        {"bm": bm, "bn": bn, "bk": bk}
        for bm in (64, 128, 256) for bn in (256, 512, 1024)
        for bk in (128, 256, 512)
    ],
    "fused_qgalore_update": [{"bm": bm, "bn": 1024}
                             for bm in (128, 256, 512)],
}

SMOKE_CANDIDATES = {
    "int8_matmul": [{"bm": bm, "bn": 256, "bk": 128} for bm in (8, 64)],
    "int8_matmul_t": [{"bm": bm, "bn": 256, "bk": 64} for bm in (8, 64)],
    "fused_qgalore_update": [{"bm": bm, "bn": 256} for bm in (32, 64)],
}


def _bestof(f, args, *, iters: int, repeats: int) -> float:
    """Best-of-``repeats`` mean wall time (us) of ``iters`` calls."""
    jax.block_until_ready(f(*args))            # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = f(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best * 1e6


def _effective_i8(op: str, M: int, K: int, n_pad: int, qblock: int,
                  cand: Dict[str, int]) -> Tuple[int, ...]:
    """The tile tuple the wrapper will actually run for a raw candidate
    (dedup key: candidates that clamp identically time identically)."""
    return (dispatch.pick_tile(M, cand["bm"]),
            dispatch.fit_block(n_pad, cand["bn"], qblock),
            dispatch.fit_block(K, cand["bk"], 128))


def sweep_int8(shapes, backend: str, *, iters: int, repeats: int,
               qblock: int, rows: List[dict]) -> None:
    key = jax.random.PRNGKey(0)
    for (M, K, N) in shapes:
        x = jax.random.normal(key, (M, K), jnp.float32)
        w = jax.random.normal(jax.random.fold_in(key, 1), (K, N)) * 0.1
        qt = quantize_blockwise(w, bits=8, block=qblock, symmetric=True)
        n_pad = qt.q.shape[-1]
        g = jax.random.normal(jax.random.fold_in(key, 2),
                              (M, n_pad), jnp.float32)
        for op, run in (
            ("int8_matmul", lambda c: jax.jit(
                lambda a: ops.int8_matmul(a, qt, backend=backend))),
            ("int8_matmul_t", lambda c: jax.jit(
                lambda a: ops._i8t_call(backend, a, qt.q, qt.scale,
                                        qt.block))),
        ):
            operand = x if op == "int8_matmul" else g
            shape_key = (M, K)           # what the wrapper queries with
            seen: Dict[Tuple[int, ...], Dict[str, int]] = {}
            for cand in CANDIDATES[op]:
                eff = _effective_i8(op, M, K, n_pad, qt.block, cand)
                seen.setdefault(eff, cand)
            timings = []
            for cand in seen.values():
                dispatch.register_tuned(op, backend, shape_key, cand,
                                        str(operand.dtype))
                us = _bestof(run(cand), (operand,), iters=iters,
                             repeats=repeats)
                timings.append((us, cand))
            us, best = min(timings, key=lambda t: t[0])
            rows.append(_row(op, backend, shape_key, operand.dtype, best,
                             us, (M, K, N)))
            emit(f"autotune/{op}", us,
                 f"M={M};K={K};N={N};blocks={_fmt(best)};backend={backend}")


def sweep_fused(weight_shapes, backend: str, *, iters: int, repeats: int,
                rank: int, qblock: int, rows: List[dict]) -> None:
    key = jax.random.PRNGKey(3)
    for (m, n) in weight_shapes:
        W = jax.random.normal(key, (m, n)) * 0.02
        qt = quantize_blockwise(W, bits=8, block=qblock, symmetric=True)
        n_pad = qt.q.shape[-1]
        P = jnp.linalg.qr(jax.random.normal(
            jax.random.fold_in(key, 4), (n, rank)))[0]
        qp = projector.quantize_projection(P, 4, 256)
        low = jax.random.normal(jax.random.fold_in(key, 5), (m, rank))
        m32 = jnp.zeros((m, rank))
        v32 = jnp.zeros((m, rank))
        rng = jax.random.PRNGKey(6)
        shape_key = (m, n_pad)           # what the wrapper queries with

        def make(c):
            @jax.jit
            def f(low, m32, v32, rng):
                new_qt, mn, vn = ops.fused_qgalore_update(
                    qt, low, m32, v32, qp, jnp.float32(1), 1e-2, rng,
                    side="right", gscale=0.25, backend=backend)
                return new_qt.q, mn, vn
            return f

        timings = []
        seen = set()
        for cand in CANDIDATES["fused_qgalore_update"]:
            eff = min(cand["bm"], m)
            if eff in seen:
                continue
            seen.add(eff)
            dispatch.register_tuned("fused_qgalore_update", backend,
                                    shape_key, cand)
            us = _bestof(make(cand), (low, m32, v32, rng), iters=iters,
                         repeats=repeats)
            timings.append((us, cand))
        us, best = min(timings, key=lambda t: t[0])
        rows.append(_row("fused_qgalore_update", backend, shape_key,
                         None, best, us, (m, n)))
        emit("autotune/fused_qgalore_update", us,
             f"m={m};n={n};r={rank};blocks={_fmt(best)};backend={backend}")


def _row(op, backend, shape_key, dtype, blocks, us, problem) -> dict:
    return {
        "op": op, "backend": backend,
        "shape": [dispatch._bucket(int(d)) for d in shape_key],
        "dtype": str(dtype) if dtype is not None else "",
        "blocks": dict(blocks), "source": "measured",
        "us": round(us, 1), "problem": list(problem),
    }


def _fmt(blocks: Dict[str, int]) -> str:
    return "/".join(f"{k}{v}" for k, v in sorted(blocks.items()))


def shape_grid(batch: int, prompt: int):
    """Dedup (M, K, N) problems over the zoo's bench models: full-seq and
    half-seq prefill plus one-token decode."""
    shapes = set()
    weights = set()
    for arch in MODELS:
        bundle = model_zoo.build_arch(arch, dtype=jnp.float32)
        for plen in (prompt, max(prompt // 2, 1)):
            shapes.update(engine.matmul_shape_grid(bundle, batch, plen))
        shapes.update(engine.matmul_shape_grid(bundle, batch, prompt,
                                               decode=True))
        weights.update((K, N) for (_, K, N)
                       in engine.matmul_shape_grid(bundle, batch, prompt))
    return sorted(shapes), sorted(weights)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default=None,
                    help="pallas-tpu | pallas-interpret (default: dispatch "
                         "default; refuses ref)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=128)
    ap.add_argument("--rank", type=int, default=32)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes + 2 candidates/op (CI artifact run)")
    ap.add_argument("--out", default="BENCH_autotune.json")
    ap.add_argument("--table", default=None,
                    help="merge measured entries into this table file")
    args = ap.parse_args(argv)
    enable_compile_cache()

    backend = args.backend or dispatch.default_backend("int8_matmul")
    if backend == "ref":
        print("autotune_blocks: dispatch default is 'ref' — block kwargs "
              "are dropped there, nothing to tune. Pass --backend "
              "pallas-interpret (smoke) or run on TPU.", flush=True)
        with open(args.out, "w") as f:
            json.dump({"meta": {"backend": "ref", "skipped": True},
                       "entries": []}, f, indent=2)
        return None

    if args.smoke:
        CANDIDATES.clear()
        CANDIDATES.update(SMOKE_CANDIDATES)
        shapes = [(8, 64, 128), (16, 128, 96)]
        weights = [(64, 128)]
        qblock, iters, repeats = 64, 1, 1
        rank = 16
    else:
        shapes, weights = shape_grid(args.batch, args.prompt)
        qblock, iters, repeats = 256, args.iters, args.repeats
        rank = args.rank

    rows: List[dict] = []
    with profile.span("autotune/sweep"):
        sweep_int8(shapes, backend, iters=iters, repeats=repeats,
                   qblock=qblock, rows=rows)
        sweep_fused(weights, backend, iters=iters, repeats=repeats,
                    rank=rank, qblock=qblock, rows=rows)
    dispatch._RUNTIME_TABLE.clear()      # drop sweep candidates

    # keep the best measurement per table key (two problems can bucket
    # to the same entry)
    best: Dict[tuple, dict] = {}
    for r in rows:
        k = (r["op"], r["backend"], tuple(r["shape"]), r["dtype"])
        if k not in best or r["us"] < best[k]["us"]:
            best[k] = r
    entries = [best[k] for k in sorted(best)]

    report = {
        "meta": {"backend": backend, "platform": dispatch.platform(),
                 "smoke": args.smoke, "batch": args.batch,
                 "prompt": args.prompt, "iters": iters,
                 "repeats": repeats, "n_shapes": len(shapes)},
        "entries": entries,
    }
    profile.maybe_attach(report)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out} ({len(entries)} entries)", flush=True)

    table = args.table
    if os.environ.get("REPRO_REGEN_AUTOTUNE", "0") == "1" and not table:
        table = dispatch.table_path()
    if table:
        merged = dispatch.load_table_entries(table) + entries
        dispatch.save_table_entries(merged, table)
        print(f"merged {len(entries)} measured entries into {table}",
              flush=True)
    return report


if __name__ == "__main__":
    main()
