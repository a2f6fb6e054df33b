"""Serving throughput/latency: lockstep vs slot continuous batching vs the
paged runtime (block pool + radix prefix cache + chunked prefill).

Two traffic mixes run through every engine on the SAME quantized-weight
decode path:

* ``uniform`` — the original mix: uniform prompt/output lengths, no
  sharing (the slot scheduler's home turf);
* ``shared_prefix`` — the serving-v2 target: a fraction
  (``--share-ratio``) of requests carry one common system prompt of
  ``--prefix-len`` tokens, and private prompt lengths are heavy-tailed
  (lognormal, clipped to ``--prompt-max``) — long prompts + re-prefilled
  prefixes are exactly what paging fixes.

Engines:

* ``lockstep`` — FIFO groups padded to the group max (pre-scheduler);
* ``slot`` — ``serve.scheduler.Scheduler`` continuous batching;
* ``paged`` — ``serve.paged.PagedScheduler``. Memory-matched to the slot
  pool (same block bytes: ``num_blocks = num_slots·MB + 1``) but with
  ``2×`` the slots — the capacity the block pool buys on mixed-length
  traffic (see ``tests/test_paged.py``).

All engines are verified TOKEN-IDENTICAL on each request set before
timing. Latency metrics add **TTFT** (time-to-first-token) p50/p99 —
the number chunked prefill moves. Timing is best-of-``--rounds`` warm
runs, engines INTERLEAVED per round (machine drift hits all evenly;
compiled programs reused via ``reset()``).

    PYTHONPATH=src:. python benchmarks/serve_bench.py            # full
    PYTHONPATH=src:. python benchmarks/serve_bench.py --smoke    # CI smoke
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.config import QGaLoreConfig
from repro.kernels import dispatch
from repro.launch.cache import enable_compile_cache
from repro.models import model_zoo
from repro.serve import engine
from repro.serve.paged import PagedScheduler
from repro.serve.scheduler import Request, Scheduler, _bucket
from repro.train import step as step_lib

MODELS = {"llama_60m": "llama-60m", "llama_130m": "llama-130m"}
PAD = 0


# ---------------------------------------------------------------------------
# Traffic mixes
# ---------------------------------------------------------------------------

def make_requests(n: int, *, prompt_lo: int, prompt_hi: int, out_lo: int,
                  out_hi: int, vocab: int, seed: int = 0):
    """The original uniform mix."""
    rng = np.random.default_rng(seed)
    reqs = []
    for rid in range(n):
        L = int(rng.integers(prompt_lo, prompt_hi + 1))
        N = int(rng.integers(out_lo, out_hi + 1))
        toks = rng.integers(1, vocab, size=L).astype(np.int32)
        reqs.append(Request(rid=rid, tokens=toks, max_new_tokens=N))
    return reqs


def make_shared_prefix_requests(n: int, *, prefix_len: int,
                                share_ratio: float, prompt_lo: int,
                                prompt_hi: int, out_lo: int, out_hi: int,
                                vocab: int, seed: int = 0):
    """Long-prompt + shared-prefix mix: ``share_ratio`` of requests start
    with ONE common prefix; private lengths are heavy-tailed (lognormal
    clipped to [prompt_lo, prompt_hi])."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(1, vocab, size=prefix_len).astype(np.int32)
    reqs = []
    for rid in range(n):
        L = int(np.clip(rng.lognormal(mean=np.log(max(prompt_lo, 2)),
                                      sigma=0.8),
                        prompt_lo, prompt_hi))
        N = int(rng.integers(out_lo, out_hi + 1))
        toks = rng.integers(1, vocab, size=L).astype(np.int32)
        if rng.random() < share_ratio:
            toks = np.concatenate([prefix, toks])
        reqs.append(Request(rid=rid, tokens=toks, max_new_tokens=N))
    return reqs


# ---------------------------------------------------------------------------
# Engine runners — run_once() -> (outputs, wall_s, latencies, ttfts[, stats])
# ---------------------------------------------------------------------------

def make_lockstep_runner(bundle, params, reqs, *, num_slots: int,
                         max_len: int, bucket: int):
    """FIFO groups of ``num_slots``; shares one jitted prefill/decode
    across groups. TTFT for every rid in a group is the group's
    prefill+first-sample completion (all rids in a group stall together —
    the baseline chunked prefill improves on)."""
    prefill = jax.jit(engine.build_prefill(bundle, max_len, pad_id=None))
    decode = jax.jit(engine.build_decode(bundle))

    def run_once():
        outputs, latencies, ttfts = {}, {}, {}
        t0 = time.monotonic()
        for g in range(0, len(reqs), num_slots):
            group = reqs[g: g + num_slots]
            B = len(group)
            Lp = _bucket(max(len(r.tokens) for r in group), bucket)
            toks = np.full((B, Lp), PAD, np.int32)
            for i, r in enumerate(group):
                toks[i, : len(r.tokens)] = r.tokens
            lengths = jnp.asarray([len(r.tokens) for r in group], jnp.int32)
            batch = {"tokens": jnp.asarray(toks), "lengths": lengths}
            steps = max(r.max_new_tokens for r in group)

            logits, state = prefill(params, batch)
            tok = engine.sample(logits, jax.random.PRNGKey(0))
            emitted = [np.asarray(tok)]          # sync: TTFT is real
            t_first = time.monotonic() - t0
            for _ in range(steps - 1):
                logits, state = decode(params, state, tok[:, None])
                tok = engine.sample(logits, jax.random.PRNGKey(0))
                emitted.append(np.asarray(tok))
            out = np.stack(emitted, axis=1)
            t_done = time.monotonic() - t0
            for i, r in enumerate(group):
                outputs[r.rid] = out[i, : r.max_new_tokens].tolist()
                latencies[r.rid] = t_done
                ttfts[r.rid] = t_first
        return outputs, time.monotonic() - t0, latencies, ttfts

    return run_once


def make_sched_runner(sched, reqs, arrivals=None):
    """Runner over a reused scheduler (``reset()`` keeps the compiled
    programs) — works for both the slot and the paged backend."""

    def run_once():
        sched.reset()
        t0 = time.monotonic()
        comps = sched.run(reqs, arrivals=arrivals)
        wall = time.monotonic() - t0
        outputs = {c.rid: list(c.tokens) for c in comps}
        latencies = {c.rid: c.latency for c in comps}
        ttfts = {c.rid: c.ttft for c in comps}
        return outputs, wall, latencies, ttfts, dict(sched.stats)

    return run_once


def _best(old, new):
    return new if old is None or new[1] < old[1] else old


def _metrics(outputs, wall, latencies, ttfts):
    total = sum(len(v) for v in outputs.values())
    lats = np.asarray(sorted(latencies.values()))
    tf = np.asarray(sorted(ttfts.values()))
    return {
        "tokens": total,
        "wall_s": wall,
        "tokens_per_s": total / wall if wall > 0 else float("inf"),
        "p50_latency_ms": float(np.percentile(lats, 50) * 1e3),
        "p99_latency_ms": float(np.percentile(lats, 99) * 1e3),
        "p50_ttft_ms": float(np.percentile(tf, 50) * 1e3),
        "p99_ttft_ms": float(np.percentile(tf, 99) * 1e3),
    }


# ---------------------------------------------------------------------------
# One model × one mix
# ---------------------------------------------------------------------------

def bench_mix(bundle, params, reqs, *, engines, num_slots: int,
              max_len: int, bucket: int, block_size: int,
              prefill_chunk: int, rates, rounds: int) -> dict:
    MB = -(-max_len // block_size)
    runners = {}
    if "lockstep" in engines:
        runners["lockstep"] = make_lockstep_runner(
            bundle, params, reqs, num_slots=num_slots, max_len=max_len,
            bucket=bucket)
    slot_sched = paged_sched = None
    if "slot" in engines:
        slot_sched = Scheduler(bundle, params, num_slots=num_slots,
                               max_len=max_len, pad_id=PAD,
                               prompt_bucket=bucket, dtype=jnp.float32)
        runners["slot"] = make_sched_runner(slot_sched, reqs)
    if "paged" in engines:
        # memory-matched to the slot pool (same block bytes + scratch);
        # the >= 2x concurrency-at-fixed-memory win is asserted separately
        # (tests/test_paged.py) — equal slots here so the comparison
        # isolates paging + radix sharing + chunked prefill
        paged_sched = PagedScheduler(
            bundle, params, num_slots=num_slots, max_len=max_len,
            block_size=block_size, num_blocks=num_slots * MB + 1,
            prefill_chunk=prefill_chunk, pad_id=PAD, dtype=jnp.float32)
        runners["paged"] = make_sched_runner(paged_sched, reqs)

    best = {name: None for name in runners}
    for name in runners:
        runners[name]()                          # compile
    for _ in range(rounds):                      # interleaved rounds
        for name in runners:
            best[name] = _best(best[name], runners[name]())

    # token parity gate before any number is reported
    ref_name = next(iter(best))
    ref_out = best[ref_name][0]
    for name, b in best.items():
        for r in reqs:
            assert b[0][r.rid] == ref_out[r.rid], (
                f"rid {r.rid}: {name} {b[0][r.rid]} != "
                f"{ref_name} {ref_out[r.rid]}")

    result = {"token_parity": True}
    for name, b in best.items():
        m = _metrics(b[0], b[1], b[2], b[3])
        if len(b) > 4:
            m["scheduler_stats"] = b[4]
        result[name] = m
    if "slot" in result and "lockstep" in result:
        result["slot_speedup_x"] = (result["slot"]["tokens_per_s"]
                                    / result["lockstep"]["tokens_per_s"])
    if "paged" in result and "slot" in result:
        result["paged_vs_slot_tokens_per_s_x"] = (
            result["paged"]["tokens_per_s"]
            / result["slot"]["tokens_per_s"])

    # finite offered rates: latency under load (slot + paged)
    result["rates"] = {}
    for rate in rates:
        arrivals = [i / rate for i in range(len(reqs))]
        entry = {}
        for name, sched in (("slot", slot_sched), ("paged", paged_sched)):
            if sched is None:
                continue
            rr = make_sched_runner(sched, reqs, arrivals=arrivals)
            rr()                                 # warm at this schedule
            out_r, wall_r, lat_r, tf_r, _ = rr()
            entry[name] = _metrics(out_r, wall_r, lat_r, tf_r)
        result["rates"][f"{rate:g}_rps"] = entry
    return result


def bench_model(arch_id: str, *, engines, num_slots: int, n_requests: int,
                prompt_lo: int, prompt_hi: int, out_lo: int, out_hi: int,
                prefix_len: int, share_ratio: float, bucket: int,
                block_size: int, prefill_chunk: int, rates, smoke: bool,
                seed: int, rounds: int = 2) -> dict:
    bundle = model_zoo.build_arch(arch_id, smoke=smoke, dtype=jnp.float32)
    # INT8-native weights — the serving format (PR 2)
    params = step_lib.prepare_params(
        bundle.init_params(jax.random.PRNGKey(0)), QGaLoreConfig(),
        jnp.float32)
    V = bundle.cfg.vocab_size

    mixes = {
        "uniform": (
            make_requests(n_requests, prompt_lo=prompt_lo,
                          prompt_hi=prompt_hi, out_lo=out_lo,
                          out_hi=out_hi, vocab=V, seed=seed),
            _bucket(prompt_hi + out_hi + 1, bucket)),
        "shared_prefix": (
            make_shared_prefix_requests(
                n_requests, prefix_len=prefix_len, share_ratio=share_ratio,
                prompt_lo=prompt_lo, prompt_hi=prompt_hi, out_lo=out_lo,
                out_hi=out_hi, vocab=V, seed=seed),
            _bucket(prefix_len + prompt_hi + out_hi + 1, bucket)),
    }
    out = {}
    for mix_name, (reqs, max_len) in mixes.items():
        out[mix_name] = bench_mix(
            bundle, params, reqs, engines=engines, num_slots=num_slots,
            max_len=max_len, bucket=bucket, block_size=block_size,
            prefill_chunk=prefill_chunk, rates=rates, rounds=rounds)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", default="llama_60m,llama_130m")
    ap.add_argument("--engines", default="lockstep,slot,paged",
                    help="comma-separated: lockstep,slot,paged")
    ap.add_argument("--paged", action="store_true",
                    help="shortcut: only the slot-vs-paged comparison "
                    "(CI paged-smoke)")
    ap.add_argument("--num-slots", type=int, default=8)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--prompt-min", type=int, default=8)
    ap.add_argument("--prompt-max", type=int, default=48)
    ap.add_argument("--out-min", type=int, default=4)
    ap.add_argument("--out-max", type=int, default=48)
    ap.add_argument("--prefix-len", type=int, default=64,
                    help="shared system-prompt length (shared_prefix mix)")
    ap.add_argument("--share-ratio", type=float, default=0.75,
                    help="fraction of requests carrying the shared prefix")
    ap.add_argument("--bucket", type=int, default=16)
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged pool block size (tokens)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="paged chunked-prefill width (tokens)")
    ap.add_argument("--rates", default="8",
                    help="comma-separated offered request rates (req/s)")
    ap.add_argument("--rounds", type=int, default=2,
                    help="interleaved timed rounds per engine (best-of)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shape-preserving configs (CI)")
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.paged:
        args.engines = "slot,paged"
    if args.smoke:
        args.num_slots = min(args.num_slots, 4)
        args.requests = min(args.requests, 12)
        args.prompt_min = min(args.prompt_min, 4)
        args.prompt_max = min(args.prompt_max, 16)
        args.out_min = min(args.out_min, 2)
        args.out_max = min(args.out_max, 16)
        args.prefix_len = min(args.prefix_len, 32)
        args.bucket = min(args.bucket, 8)
        args.block_size = min(args.block_size, 8)

    engines = [e.strip() for e in args.engines.split(",") if e.strip()]
    rates = [float(r) for r in args.rates.split(",") if r.strip()]
    report = {
        "meta": {
            "platform": dispatch.platform(),
            "backend": dispatch.default_backend("quantized_dense"),
            "engines": engines,
            "num_slots": args.num_slots, "requests": args.requests,
            "prompt_len": [args.prompt_min, args.prompt_max],
            "out_len": [args.out_min, args.out_max],
            "prefix_len": args.prefix_len, "share_ratio": args.share_ratio,
            "bucket": args.bucket, "block_size": args.block_size,
            "prefill_chunk": args.prefill_chunk, "rates_rps": rates,
            "paged_memory_matched_to_slots": args.num_slots,
            "paged_num_slots": args.num_slots,
            "smoke": args.smoke, "seed": args.seed,
        },
        "results": {},
    }
    for name in args.models.split(","):
        arch = MODELS[name.strip()]
        r = bench_model(arch, engines=engines, num_slots=args.num_slots,
                        n_requests=args.requests,
                        prompt_lo=args.prompt_min, prompt_hi=args.prompt_max,
                        out_lo=args.out_min, out_hi=args.out_max,
                        prefix_len=args.prefix_len,
                        share_ratio=args.share_ratio, bucket=args.bucket,
                        block_size=args.block_size,
                        prefill_chunk=args.prefill_chunk, rates=rates,
                        smoke=args.smoke, seed=args.seed,
                        rounds=args.rounds)
        for mix, rm in r.items():
            for eng in engines:
                if eng not in rm:
                    continue
                m = rm[eng]
                emit(f"serve_bench/{name}_{mix}_{eng}_tokens_per_s",
                     m["wall_s"] * 1e6,
                     f"{m['tokens_per_s']:.1f} tok/s;"
                     f"p99={m['p99_latency_ms']:.0f}ms;"
                     f"ttft_p99={m['p99_ttft_ms']:.0f}ms")
            if "paged_vs_slot_tokens_per_s_x" in rm:
                emit(f"serve_bench/{name}_{mix}_paged_vs_slot",
                     rm["paged"]["wall_s"] * 1e6,
                     f"{rm['paged_vs_slot_tokens_per_s_x']:.2f}x")
        report["results"][name] = r

    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}", flush=True)
    return report


if __name__ == "__main__":
    main()
