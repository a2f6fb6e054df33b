"""Profiling hooks: trace-time op counters, named device scopes and host
spans.

The quantized hot paths run inside ``jit``, so per-call wall time cannot
be observed from Python without defeating the fusion being measured.
What CAN be recorded cheaply and without perturbing the compiled graph:

* **trace-time counters** — every ``ops`` wrapper calls :func:`record`
  while tracing, logging how many times each kernel op is baked into a
  compiled program and the HBM bytes / FLOPs one execution of that call
  moves. Re-traces count again (that is itself a useful signal: an
  unexpected recount means shape churn → recompiles). Each string in a
  call's ``meta`` is tallied too (``meta_calls``, e.g. how many INT8
  matmul calls run on measured tiles). Only under
  ``REPRO_PROFILE=1``.
* **device scopes** — :func:`scope` is ``jax.named_scope`` for one of the
  names in :data:`SCOPES`. It sets the ``op_name`` metadata of every HLO
  instruction traced inside it, so a profiler trace can sum device time
  per scope; it changes nothing that runs. Scopes wrap XLA work: the
  Pallas kernels are found by their own instruction names.
* **host spans** — :func:`span` wraps a host-side region in a
  ``jax.profiler.TraceAnnotation``, which is written only while a
  profiler session runs, so device idle gaps can be set against what the
  host was doing. Under ``REPRO_PROFILE=1`` it also keeps a wall-clock
  accumulator (``wall_us``, ``wall_calls``) beside the counters.

The benches attach :func:`snapshot` to their JSON artifacts
(:func:`maybe_attach`) so the next perf gap is diagnosable from CI output
instead of rerunning A/B sweeps by hand.
"""
from __future__ import annotations

import contextlib
import os
import time
from collections import defaultdict
from typing import Any, Dict, Optional

import jax

# Device scopes of the training step, innermost wins where they nest:
#   repro.attention   scores, mask, softmax and PV (models/attention.py)
#   repro.wgrad       weight gradients x^T g and their rank-r projection
#   repro.layer       the rest of a decoder block: norms, RoPE, SwiGLU gate,
#                     residuals
#   repro.head_loss   final norm, logits and cross-entropy
#   repro.optimizer   gradient clipping and the optimizer update around the
#                     fused kernel: dense 8-bit Adam, moment (de)quantization
#   repro.sr_randoms  the uniform draws that feed stochastic rounding
SCOPES = ("repro.attention", "repro.wgrad", "repro.layer",
          "repro.head_loss", "repro.optimizer", "repro.sr_randoms")


def enabled() -> bool:
    return os.environ.get("REPRO_PROFILE", "0") == "1"


def _new_row() -> Dict[str, float]:
    return {"trace_calls": 0, "bytes_per_call": 0, "flops_per_call": 0,
            "wall_us": 0.0, "wall_calls": 0}


_COUNTS: Dict[str, Dict[str, float]] = defaultdict(_new_row)


def record(op: str, *, nbytes: int = 0, flops: int = 0,
           meta: Optional[Dict[str, Any]] = None) -> None:
    """Trace-time hook: count one baked-in call of ``op`` and the HBM
    bytes / FLOPs a single execution of it moves. ``meta`` (e.g. the
    problem shape) is kept from the most recent call; its string values
    are tallied over all calls as ``meta_calls["key=value"]``."""
    if not enabled():
        return
    row = _COUNTS[op]
    row["trace_calls"] += 1
    row["bytes_per_call"] = int(nbytes)
    row["flops_per_call"] = int(flops)
    if meta:
        row["meta"] = dict(meta)
        tally = row.setdefault("meta_calls", {})
        for k, v in meta.items():
            if isinstance(v, str):
                tally[f"{k}={v}"] = tally.get(f"{k}={v}", 0) + 1


def scope(name: str):
    """``jax.named_scope(name)`` for a name declared in :data:`SCOPES`."""
    if name not in SCOPES:
        raise ValueError(f"undeclared device scope {name!r}; have {SCOPES}")
    return jax.named_scope(name)


@contextlib.contextmanager
def span(name: str):
    """A named host-side region: a profiler ``TraceAnnotation`` always,
    and the wall-clock accumulator under ``REPRO_PROFILE=1``."""
    with jax.profiler.TraceAnnotation(name):
        if not enabled():
            yield
            return
        t0 = time.monotonic()
        try:
            yield
        finally:
            row = _COUNTS[name]
            row["wall_us"] += (time.monotonic() - t0) * 1e6
            row["wall_calls"] += 1


def reset() -> None:
    _COUNTS.clear()


def snapshot() -> Dict[str, Dict[str, float]]:
    return {op: dict(row) for op, row in sorted(_COUNTS.items())}


def maybe_attach(report: Dict[str, Any]) -> None:
    """Attach the current snapshot to a bench report dict (in place) when
    profiling is on; no key is added otherwise."""
    if enabled():
        report["profile"] = snapshot()
