#!/usr/bin/env python3
"""Readings that the limits of a cell's correctness check are set from.

    python bench/calibrate.py --workload <cell> --mode <mode> --seeds 1,2,3 [--out f.jsonl]

Modes, each over the listed seeds in one process (set-up is long):

* ``program``: the program's first steps against the reference, as a run
  compares them (no measured window): the lower readings;
* ``control``: the reference computed in float8 e4m3 (per-tensor scaled
  operands), put in the program's place: the upper readings;
* ``half_batch``: the reference put in the program's place on half of each
  batch (half the rows; with one row, the first half of its text), the
  mean taken over the rest: a fault that the check has to catch;
* ``unchanged``: the reference put in the program's place at learning
  rate 0, so that each step leaves the weights as they were: the loss
  readings of a step that returns its state unchanged;
* ``randoms``: the float32 reference itself, with the control's random
  stream, put in the program's place: how far apart the randoms of the
  refresh and of stochastic rounding alone set two sound runs.

Each seed prints one JSON line: the mode, the seed, the compared numbers
and the seconds the seed took. Not run by the benchmark's own runs.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mode", required=True,
                    choices=("program", "control", "half_batch",
                             "unchanged", "randoms"))
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default="")
    ap.add_argument("--expect-backend", default="pallas-tpu")
    args = ap.parse_args(argv)

    import jax

    from bench import compare, harness, seeds, spec
    from bench.runners import train
    from repro.launch import cache
    cache.enable_compile_cache()
    clock = harness.CompileClock(jax)
    cell = spec.load(args.workload)
    out = open(args.out, "a") if args.out else None
    ref = other = None
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        run = train.build(cell.config, cell.traffic, seed, clock=clock,
                          expect_backend=args.expect_backend)
        ref = ref or run.reference()
        if args.mode == "program":
            run.setup()
            run.free()
            prog = run.program_readings(ref)
            extra = {"guards": run.guards({"compiles": 0})}
        else:
            other = other or (run.reference("fp8")
                              if args.mode == "control" else ref)
            # the control's stream: randoms other than the reference's
            prog = run.reference_readings(
                other, halved=args.mode == "half_batch", stream=seeds.CONTROL,
                still=args.mode == "unchanged")
            extra = {}
        nums = compare.numbers(prog, run.reference_readings(ref))
        line = {"mode": args.mode, "workload": cell.name, "seed": seed,
                **{k: nums[k] for k in compare.NUMBERS},
                "losses": prog["losses"], **extra,
                "seconds": time.monotonic() - t0}
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
        del run, prog
        gc.collect()
    harness.log(f"[calibrate] {time.monotonic() - T_START:.1f} s, backend "
                f"compiles {clock.count} ({clock.seconds:.1f} s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
