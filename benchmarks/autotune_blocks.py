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

``--train CONFIG TRAFFIC`` sweeps the INT8 matmuls of one training step
instead: M is the rows per update of the traffic file (``batch * seq``),
(K, N) each quantized weight of the configuration file (the benchmark's
``bench/configs`` and ``bench/traffic`` layout), activations bf16 as the
step runs them. The wrappers look tiles up by ``(M, K)`` alone, so each
bucket keeps the tile set with the least time summed over its problems,
each weighted by how many of the model's weights have that shape.

    PYTHONPATH=src:. python benchmarks/autotune_blocks.py \
        --backend pallas-interpret --smoke --out BENCH_autotune.json
    REPRO_REGEN_AUTOTUNE=1 PYTHONPATH=src:. \
        python benchmarks/autotune_blocks.py        # on-TPU refresh
    REPRO_REGEN_AUTOTUNE=1 PYTHONPATH=src:. \
        python benchmarks/autotune_blocks.py --train \
        bench/configs/internlm2-1.8b.json bench/traffic/pretrain-s256.json
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List, Optional, Tuple

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

# The training step's shapes (M in the thousands): taller row tiles and
# wider column / contraction tiles, each candidate compiled and timed.
TRAIN_CANDIDATES = {
    op: [{"bm": bm, "bn": bn, "bk": bk}
         for bm in (256, 512, 1024) for bn in (512, 1024, 2048)
         for bk in (512, 1024, 2048)]
    for op in ("int8_matmul", "int8_matmul_t")
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
               qblock: int, rows: List[dict], dtype=jnp.float32,
               calls: Optional[Dict[Tuple[int, int, int], int]] = None
               ) -> None:
    """Time every distinct effective tile set of both INT8 kernels on each
    (M, K, N) through the training path's wrappers. The wrappers query
    the table with ``(M, K)``, so per op and ``(M, K)`` bucket the
    candidate with the least time summed over the bucket's problems wins,
    each problem weighted by ``calls`` (default 1). Weights go in as
    arguments (not baked-in constants); a tile set the compiler refuses
    is reported, and rules its candidates out of that bucket."""
    key = jax.random.PRNGKey(0)
    # (op, bucketed (M, K)) -> problem -> candidate label -> (us, cand)
    timed: Dict[tuple, Dict[tuple, Dict[str, tuple]]] = {}
    for (M, K, N) in shapes:
        x = jax.random.normal(key, (M, K), dtype)
        w = jax.random.normal(jax.random.fold_in(key, 1), (K, N)) * 0.1
        qt = quantize_blockwise(w, bits=8, block=qblock, symmetric=True)
        n_pad = qt.q.shape[-1]
        g = jax.random.normal(jax.random.fold_in(key, 2), (M, n_pad), dtype)
        for op, operand, call in (
            ("int8_matmul", x, lambda a, q, s: ops._i8_call(
                backend, a, q, s, qt.block, n_pad)),
            ("int8_matmul_t", g, lambda a, q, s: ops._i8t_call(
                backend, a, q, s, qt.block)),
        ):
            shape_key = (M, K)           # what the wrapper queries with
            seen: Dict[Tuple[int, ...], List[Dict[str, int]]] = {}
            for cand in CANDIDATES[op]:
                eff = _effective_i8(op, M, K, n_pad, qt.block, cand)
                seen.setdefault(eff, []).append(cand)
            bucket = (op, tuple(dispatch._bucket(d) for d in shape_key))
            got = timed.setdefault(bucket, {}).setdefault((M, K, N), {})
            for cands in seen.values():
                dispatch.register_tuned(op, backend, shape_key, cands[0],
                                        str(operand.dtype))
                try:
                    us = _bestof(jax.jit(lambda a, q, s: call(a, q, s)),
                                 (operand, qt.q, qt.scale), iters=iters,
                                 repeats=repeats)
                except Exception as e:  # noqa: BLE001 — compiler refusal
                    emit(f"autotune/{op}/refused", 0.0,
                         f"M={M};K={K};N={N};blocks={_fmt(cands[0])};"
                         f"{str(e).splitlines()[0][:120]}")
                    continue
                emit(f"autotune/{op}/candidate", us,
                     f"M={M};K={K};N={N};blocks={_fmt(cands[0])}")
                for cand in cands:
                    got[_fmt(cand)] = (us, cand)

    for (op, bshape), problems in timed.items():
        usable = set.intersection(*(set(t) for t in problems.values()))
        if not usable:
            continue

        def weighted(label):
            return sum((calls or {}).get(p, 1) * t[label][0]
                       for p, t in problems.items())

        label = min(sorted(usable), key=weighted)
        # the entry records the problem that weighs most at the winner
        top = max(problems, key=lambda p: (calls or {}).get(p, 1)
                  * problems[p][label][0])
        us, best = problems[top][label]
        rows.append(_row(op, backend, top[:2], jnp.dtype(dtype), best, us,
                         top))
        emit(f"autotune/{op}", weighted(label),
             f"M={top[0]};K={top[1]};bucket={bshape[0]}x{bshape[1]};"
             f"problems={len(problems)};blocks={label};backend={backend}")


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


def train_shape_grid(config_path: str, traffic_path: str):
    """(M, K, N) of every INT8 matmul of one training step of a benchmark
    cell, and how many of the model's weights have each: M = ``batch *
    seq`` of the traffic, (K, N) the configuration's attention projections
    (q, k, v, o), FFN (gate, up, down), each in every layer, and the
    untied head once."""
    with open(config_path) as f:
        cfg = json.load(f)["model"]
    with open(traffic_path) as f:
        traffic = json.load(f)
    M = traffic["batch"] * traffic["seq"]
    d, ff, v = cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    hd = cfg["head_dim"] or d // cfg["num_heads"]
    per_layer = [(d, cfg["num_heads"] * hd), (d, cfg["num_kv_heads"] * hd),
                 (d, cfg["num_kv_heads"] * hd), (cfg["num_heads"] * hd, d),
                 (d, ff), (d, ff), (ff, d)]
    calls: Dict[Tuple[int, int, int], int] = {(M, d, v): 1}
    for K, N in per_layer:
        calls[(M, K, N)] = calls.get((M, K, N), 0) + cfg["num_layers"]
    return sorted(calls), calls


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
    ap.add_argument("--train", nargs=2, metavar=("CONFIG", "TRAFFIC"),
                    help="sweep the INT8 matmuls of one training step of "
                         "this configuration and traffic file (bf16)")
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

    dtype, calls = jnp.float32, None
    if args.smoke:
        CANDIDATES.clear()
        CANDIDATES.update(SMOKE_CANDIDATES)
        shapes = [(8, 64, 128), (16, 128, 96)]
        weights = [(64, 128)]
        qblock, iters, repeats = 64, 1, 1
        rank = 16
    elif args.train:
        CANDIDATES.clear()
        CANDIDATES.update(TRAIN_CANDIDATES)
        (shapes, calls), weights = train_shape_grid(*args.train), []
        qblock, iters, repeats = 256, args.iters, args.repeats
        rank = args.rank
        dtype = jnp.bfloat16
    else:
        shapes, weights = shape_grid(args.batch, args.prompt)
        qblock, iters, repeats = 256, args.iters, args.repeats
        rank = args.rank

    rows: List[dict] = []
    with profile.span("autotune/sweep"):
        sweep_int8(shapes, backend, iters=iters, repeats=repeats,
                   qblock=qblock, rows=rows, dtype=dtype, calls=calls)
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
                 "smoke": args.smoke, "train": args.train,
                 "batch": args.batch,
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
