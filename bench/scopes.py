"""Device time per named scope of the program, and host time per program
span: the reduction that the per-layer metrics of the training step's
scopes use.

The program marks its XLA work with ``jax.named_scope`` names that start
with ``repro.`` (``repro.kernels.profile.SCOPES``). A scope reaches the
``op_name`` metadata of each HLO instruction traced inside it, under
whatever transform wraps it (``jvp(repro.layer)/repro.attention/...``,
``transpose(jvp(repro.layer))/repro.wgrad/...``). A TPU trace names a
device operation by its instruction and keeps no ``op_name`` with it, so
the scopes come from the compiled text of the plain step program, which
the window runs (:func:`op_names`), looked up by instruction name. The
first reader builds that program again from the cell's configuration and
traffic as the program's ``Trainer`` builds it on one chip
(:func:`plain_step`) and compiles it, which the persistent compilation
cache answers (:func:`step_op_names`); the map is kept for the other
readers in the run's counters (:func:`names_for`).

An operation belongs to the innermost scope of its ``op_name``. An
instruction the compiler made, with no ``op_name`` of its own (a copy, a
relayout, an asynchronous copy, a fusion), takes the one of its fused
computation's root, else of its first operand that has one: the scope of
the value it moves. An operation with no scope, or not in the map
(another program's), is unscoped. The measured kernels (the patterns of
their own readers) and control-flow envelopes are left out, so the kernel
times and the times per scope add up to the summed time of the operations
that are not envelopes.

The program's host spans (``repro.batch``, ``repro.dispatch``, ...) are
``TraceAnnotation`` events of the host plane, as the benchmark's own
``bench.*`` spans are.
"""
from __future__ import annotations

import re
import sys
from typing import Dict, Iterable, Optional, Sequence

from bench import spec, trace

# each per-layer metric of a scope, and the scope it reads; ``unscoped``
# is the remainder, so that work outside every scope, or a scope lost in a
# refactor, shows
METRICS = {"attention.ms_per_step": "repro.attention",
           "wgrad.ms_per_step": "repro.wgrad",
           "layer_other.ms_per_step": "repro.layer",
           "head_loss.ms_per_step": "repro.head_loss",
           "optimizer.ms_per_step": "repro.optimizer",
           "sr_randoms.ms_per_step": "repro.sr_randoms",
           "unscoped.ms_per_step": "unscoped"}
UNSCOPED = "unscoped"
# the program's spans of host work before the device can start a step
HOST_PREP = ("repro.batch", "repro.controller", "repro.dispatch")

SCOPE = re.compile(r"repro\.[A-Za-z_]+")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# the opcode and its operand list: the first ``word(`` whose parenthesis
# holds instruction names (a layout's ``T(8,128)`` holds numbers)
_OPERANDS = re.compile(r"\s([a-z][a-z0-9\-]*)\(((?:%[^)]*)?)\)")
_CALLS = re.compile(r"\bcalls=%([^\s,]+)")


def kernel_patterns() -> tuple:
    """The instruction-name patterns of the measured kernels, as their
    own readers pick them."""
    return (spec.reader("int8_matmul.ms_per_step").PATTERNS
            + spec.reader("fused_update.ms_per_step").PATTERNS)


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` of every instruction of a compiled
    program's text (``compiled.as_text()``) that has one of its own or
    can take one: a fusion from its computation's root, any other
    instruction from its first operand that has one."""
    own: Dict[str, str] = {}
    operands: Dict[str, list] = {}
    calls: Dict[str, str] = {}
    roots: Dict[str, str] = {}
    comp = None
    for line in hlo_text.splitlines():
        c = _COMPUTATION.match(line)
        if c:
            comp = c.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(2)
        if m.group(1) and comp is not None:
            roots[comp] = name
        o = _OP_NAME.search(line)
        if o:
            own[name] = o.group(1)
            continue
        rest = line[m.end():].split(", metadata=", 1)[0]
        a = _OPERANDS.search(rest)
        if a:
            operands[name] = re.findall(r"%([^\s,)]+)", a.group(2))
        k = _CALLS.search(rest)
        if k:
            calls[name] = k.group(1)

    out: Dict[str, Optional[str]] = dict(own)

    def resolve(name: str) -> Optional[str]:
        if name in out:
            return out[name]
        out[name] = None          # a cycle, or nothing found, reads None
        nxt = ([roots[calls[name]]] if calls.get(name) in roots else []) \
            + operands.get(name, [])
        for n in nxt:
            found = resolve(n)
            if found:
                out[name] = found
                break
        return out[name]

    limit = sys.getrecursionlimit()
    sys.setrecursionlimit(max(limit, 20000))
    try:
        for name in set(operands) | set(calls):
            resolve(name)
    finally:
        sys.setrecursionlimit(limit)
    return {k: v for k, v in out.items() if v}


def scope_of(op_name: str) -> str:
    """The innermost ``repro.*`` scope of an op_name, or ``unscoped``."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else UNSCOPED


def seconds_by_scope(ops: Iterable[trace.Event], names: Dict[str, str],
                     kernels: Optional[Sequence[str]] = None
                     ) -> Dict[str, float]:
    """Summed seconds of ``ops`` per scope, the op_name of each looked up
    in ``names``; the kernels and control-flow envelopes left out."""
    rx = [re.compile(p) for p in (kernel_patterns() if kernels is None
                                  else kernels)]
    out: Dict[str, float] = {}
    for e in ops:
        if e.kind in trace.ENVELOPES or any(r.fullmatch(e.kind) for r in rx):
            continue
        s = scope_of(names.get(e.op, ""))
        out[s] = out.get(s, 0.0) + e.dur / 1e9
    return out


def ms_per_step(ops: Iterable[trace.Event], names: Dict[str, str],
                steps: int) -> Optional[Dict[str, float]]:
    """Each metric of :data:`METRICS` in milliseconds per step. None where
    no operation carries a scope: a program without scopes, or no map."""
    by = seconds_by_scope(ops, names)
    if not steps or set(by) <= {UNSCOPED}:
        return None
    return {m: 1e3 * by.get(s, 0.0) / steps for m, s in METRICS.items()}


def span_ms_per_step(spans: Iterable[trace.Event], which: Sequence[str],
                     lo: int, hi: int, steps: int) -> Optional[float]:
    """Summed milliseconds per step of the host spans named in ``which``
    inside [lo, hi]; None where there is none."""
    inside = [e for e in trace.clip(spans, lo, hi) if e.name in which]
    if not inside or not steps:
        return None
    return 1e3 * sum(e.dur for e in inside) / 1e9 / steps


# ---------------------------------------------------------------------------
# the plain step program of a training cell
# ---------------------------------------------------------------------------

def plain_step(config: dict, traffic: dict, sharding=None):
    """(jitted plain step, its abstract arguments) of a training cell,
    built as ``bench/runners/train.py`` has the program's ``Trainer``
    build it on one chip: the model in bfloat16 on the reference's
    weights, the traffic's Q-GaLore settings, clipping and batch.
    ``sharding`` places every argument (the chip's device, or one device
    of a described chip)."""
    import dataclasses
    import functools
    import importlib

    import jax
    import jax.numpy as jnp

    from bench import traffic_gen
    from repro.config import ModelConfig, QGaLoreConfig, TrainConfig
    from repro.models import model_zoo
    from repro.train import step as step_lib

    m, t = config["model"], traffic
    ref = importlib.import_module(f"bench.reference.{config['reference']}")
    bundle = model_zoo.build(ModelConfig(**m), dtype=jnp.bfloat16)
    bundle = dataclasses.replace(bundle, init_params=lambda k: ref.nest(
        ref.init_weights(m, config["initializer_range"], k)))
    qcfg = QGaLoreConfig(**t["qgalore"])
    tcfg = TrainConfig(
        seed=0, global_batch=t["batch"], seq_len=traffic_gen.text_len(t),
        steps=1, learning_rate=t["train"]["learning_rate"], warmup_steps=0,
        lr_schedule="constant", grad_clip=t["train"]["grad_clip"],
        log_every=0)
    step, _ = step_lib.build_train_step(bundle, qcfg, tcfg,
                                        param_dtype=jnp.bfloat16)

    def put(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)

    state = jax.tree_util.tree_map(put, step_lib.abstract_state(
        bundle, qcfg, jnp.bfloat16))
    make = traffic_gen.batch_fn(t, m["vocab_size"], m["d_model"])
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    batch = jax.tree_util.tree_map(put, jax.eval_shape(make, key, 0))
    # the learning rate goes in as the Python number the trainer passes
    args = (state, batch, 0.0, put(key))
    return jax.jit(functools.partial(step, refresh=False,
                                     refresh_masks=None)), args


def step_op_names(cell) -> Optional[Dict[str, str]]:
    """The op_name map of ``cell``'s plain step program, compiled for the
    first device. None, with the reason on stderr, where the program
    cannot build it."""
    import time
    import traceback

    import jax
    from jax.sharding import SingleDeviceSharding
    t0 = time.monotonic()
    try:
        fn, args = plain_step(cell.config, cell.traffic,
                              SingleDeviceSharding(jax.devices()[0]))
        text = fn.lower(*args).compile().as_text()
    except Exception:  # noqa: BLE001 — a program that predates the scopes
        print("[metric] scopes: no plain step program to read\n"
              + traceback.format_exc(), file=sys.stderr)
        return None
    names = op_names(text)
    print(f"[metric] scopes: {len(names)} instructions named in "
          f"{time.monotonic() - t0:.3f} s", file=sys.stderr)
    return names


def names_for(ctx) -> Optional[Dict[str, str]]:
    """The op_name map of the traced cell's plain step, built by the first
    reader that asks and kept for the others in the run's counters."""
    if "op_names" not in ctx.counters:
        ctx.counters["op_names"] = step_op_names(ctx.cell)
    return ctx.counters["op_names"]


def read(ctx, scope: str) -> Optional[float]:
    """Milliseconds per step of one scope (or ``unscoped``) in the traced
    window; None where no operation carries a scope of the program."""
    names = names_for(ctx)
    steps = ctx.window["steps"]
    if not names or not steps:
        return None
    by = seconds_by_scope(ctx.ops(), names)
    if set(by) <= {UNSCOPED}:
        return None
    return 1e3 * by.get(scope, 0.0) / steps
