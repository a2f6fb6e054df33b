#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics read from
a profiler trace of the window), ``device``, with ``--trace 1`` a
``breakdown``, and last ``check``: every number the correctness check
compared, beside its limit. The same numbers end standard error.

Exits 2, printing no result, when JAX finds no TPU, fewer chips than the
cell asks for, a chip missing from ``bench/peaks.json``, or no program to
run (a directory holding only the benchmark). JAX's persistent
compilation cache is ``<checkout>/.jax_cache``, whatever
``$JAX_COMPILATION_CACHE_DIR`` said: the benchmark gives the program that
directory, so that runs from one checkout share their compiled programs
and runs from two checkouts share nothing.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# read by JAX when it is imported, and by the program's cache set-up
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")


def fail(msg: str) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness, spec
    try:
        cell = spec.load(args.workload)
    except (KeyError, FileNotFoundError) as e:
        return fail(str(e))
    try:
        import repro.launch.cache as cache
    except ImportError as e:
        return fail(f"no program to run ({e})")

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return fail(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < cell.chips:
        return fail(f"{cell.name} asks for {cell.chips} chips, JAX sees "
                    f"{len(devs)}")
    try:
        peaks = spec.peaks(devs[0].device_kind)
    except KeyError as e:
        return fail(str(e))

    harness.log(f"[setup] compile cache {cache.enable_compile_cache()}")
    # every program, however quick to compile, from the cache after the
    # first run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = harness.CompileClock(jax)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START,
                              clock=clock, device=device, peaks=peaks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
