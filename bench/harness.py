"""One run of one cell: set-up, the measured window, the traced metrics
and the correctness check, as one JSON line.

``run.py`` calls :func:`run_cell` after it has found the chip; the
benchmark's own tests call it on the CPU at a tiny size.
"""
from __future__ import annotations

import importlib
import shutil
import sys
import tempfile
import time
from typing import Optional

from bench import compare, spec, trace


class CompileClock:
    """Backend compiles (XLA and Mosaic, or a fetch from the persistent
    cache on a hit), from ``jax.monitoring`` events: their count and
    seconds. Tracing and lowering are left out: their events nest."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool, *,
             t_start: float, clock: CompileClock, device: dict,
             peaks: Optional[dict], expect_backend: str = "pallas-tpu",
             fault: Optional[str] = None) -> dict:
    """Run the cell once; return the result line's object."""
    import jax

    runner = importlib.import_module(
        f"bench.runners.{cell.traffic['runner']}")
    run = runner.build(cell.config, cell.traffic, seed, clock=clock,
                       expect_backend=expect_backend, fault=fault)
    run.setup()
    setup_s = time.monotonic() - t_start
    log(f"[setup] {setup_s:.3f} s, backend compiles {clock.count} "
        f"({clock.seconds:.3f} s), persistent-cache hits {clock.cache_hits}")

    tdir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    if traced:
        # device ops and the host's TraceMe spans; no Python call tracing
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tdir, profiler_options=opts)
        # the profiler stalls the first step it sees; keep that step out
        # of the traced window
        run.run_step()
    win = run.window(seconds)
    if traced:
        jax.profiler.stop_trace()
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    counters = {"opt_state_bytes": run.opt_state_bytes()}
    if traced:
        counters["step_program_bytes"] = run.step_program_bytes()
    log(f"[window] {win['steps']} steps in {win['seconds']:.3f} s, "
        f"{win['compiles']} backend compiles, peak_bytes_in_use {peak}")
    run.free()

    dev = dict(device, memory_peak_bytes=peak)
    out = {"attempted": win["steps"], "failed": win["failed"]}
    if traced:
        tr = trace.load(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        lo, hi = tr.window("bench.window")
        ctx = trace.Context(trace=tr, lo=lo, hi=hi, window=win, cell=cell,
                            peaks=peaks, counters=counters)
        metrics = {}
        for m in cell.per_layer:
            v = spec.reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        busy = trace.busy_seconds(tr.device_ops, lo, hi)
        dev.update(busy_s=busy, window_s=(hi - lo) / 1e9)
        out["breakdown"] = trace.breakdown(tr, lo, hi)
    else:
        values = run.end_to_end(win, setup_s, peak)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    check = dict(run.guards(win))
    if cell.limits is None:
        raise FileNotFoundError(f"no bench/limits/{cell.name}.json")
    t0 = time.monotonic()
    ref = run.reference()
    nums = compare.numbers(run.program_readings(ref),
                           run.reference_readings(ref))
    for k in compare.NUMBERS:
        if k in cell.limits:
            check[k] = dict(nums[k], limit=cell.limits[k])
        else:
            log(f"[check] {k} {nums[k]['value']!r} (not compared)")
    log(f"[check] reference and comparison {time.monotonic() - t0:.3f} s")
    correct = all(c["value"] <= c["limit"] for c in check.values())
    for k, c in check.items():
        log(f"[check] {k} {c['value']!r} limit {c['limit']!r}"
            + (f" at {c['at']}" if c.get("at") else ""))
    return {"correct": correct, **out, "metrics": metrics, "device": dev,
            "check": check}
