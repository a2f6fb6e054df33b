#!/usr/bin/env python3
"""Bring-up check of Q-GaLore on TPU: llama-1b at full width (24 layers,
d_model 2048, 32 heads, d_ff 5461, vocab 32000; random weights from a
seed) through the normal entry points.

    python chip_smoke.py             # one chip: train, check, serve
    python chip_smoke.py --chips 4   # four chips: the multi-chip meshes only

One chip runs three phases:

* train -- Q-GaLore steps (bf16 params, INT8 weights, INT4 projections,
  fused projected backward, fused update) through ``Trainer`` at the
  paper's pre-training length, SVD refreshes included;
* check -- the same computations under the ``ref`` backend on the same
  chip: step-0 loss, and the two fused-update kernels on a real layer;
* serve -- requests with a shared prefix through the paged scheduler,
  greedy, and the first decode step's logits against ``ref``.

``--chips 4`` runs only llama-1b's step 0 under compressed DP + ZeRO on a
(4, 1) mesh and on a (2, 2) data x model mesh, each against a
single-device run in this process. These runs request the ``ref``
kernel backend: a Mosaic kernel cannot be partitioned automatically
across devices, and the sharded step does not yet wrap its kernels in a
fully manual ``shard_map``.

Everything runs in this one process: a chip belongs to one process. The
script exits non-zero, printing no result, when JAX finds no TPU or any
phase fails. The last stdout line is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import gc
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "llama-1b"
SEQ = 256            # the paper's pre-training sequence length (Table 1/2)
# Global batch for one v5e chip (16 GiB HBM), from compiled.memory_analysis()
# of the step programs compiled for a described v5e: the refresh step
# (full-rank gradients for the subspace) needs 9.3 GiB at batch 8, 9.5 at
# 16, 10.2 at 32 and 14.3 at 64; the plain step 5.2 GiB at 8 and at 16.
# At seq 256 the state fills the chip, not the activations. 32 leaves
# room for the check phase's copy of the initial weights (1.3 GiB).
BATCH = 32
STEPS = 5
REFRESH_EVERY = 3    # SVD refresh at steps 0 and 3
SEED = 0
# Every kernel op of the main path, as profile.record names them.
MAIN_PATH_OPS = ("quantized_dense/fwd", "quantized_dense/dx",
                 "fused_qgalore_update")

# Tolerances against the ``ref`` backend, which runs under
# default_matmul_precision("highest") as the f32 reference. Both sides
# read the same INT8 codes and scales and accumulate in f32; they differ
# in the order of the adds, in where the block scale multiplies
# (activation side in the kernel, weight side in ref) and in the MXU
# passes an f32 dot takes in the kernel: up to ~2^-9 relative per matmul.
# The bf16 activations between layers round that on, through 24 layers.
# A wrong scale group, tile or transpose moves every element by about its
# own size, and so the logits by O(1) in norm.
LOSS_RTOL = 1e-3          # |loss - loss_ref| <= LOSS_RTOL * loss_ref
LOGITS_RTOL = 2e-2        # ||l - l_ref||_2 <= LOGITS_RTOL * ||l_ref||_2
# Fused update: the same SR randoms, so INT8 codes differ only where the
# kernel's f32 back-projection (a different add order) puts a value on the
# other side of a rounding boundary: at most one code step, rarely.
FUSED_MIN_CODE_MATCH = 0.999
MOMENT_RTOL, MOMENT_ATOL = 1e-5, 1e-6

# Serving: requests sharing one prefix (radix hits), distinct suffixes.
SERVE_REQUESTS = 6
SERVE_PREFIX = 64
SERVE_NEW_TOKENS = 16


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds of backend compilation (XLA and Mosaic, or the fetch from
    the persistent cache on a hit), from jax.monitoring events. Tracing
    and lowering are left out: their events nest (an inner jit is traced
    inside its caller), so a sum of them would count time twice."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@contextlib.contextmanager
def reference():
    """Trace what runs inside as the f32 reference: the ``ref`` kernel
    backend (the ``REPRO_KERNEL_BACKEND`` override of
    ``repro.kernels.dispatch``) at full f32 matmul precision."""
    import jax
    old = os.environ.get("REPRO_KERNEL_BACKEND")
    os.environ["REPRO_KERNEL_BACKEND"] = "ref"
    try:
        with jax.default_matmul_precision("highest"):
            yield
    finally:
        if old is None:
            del os.environ["REPRO_KERNEL_BACKEND"]
        else:
            os.environ["REPRO_KERNEL_BACKEND"] = old


def require(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {what}")


def train_config(batch: int, steps: int):
    from repro.config import QGaLoreConfig, TrainConfig
    # Paper defaults (rank 128, INT8 weights, INT4 projection, 8-bit
    # Adam, stochastic rounding, fused update) except the subspace
    # method: XLA takes 5-7 minutes per shape to compile the exact
    # jnp.linalg.svd for v5e at d=2048 (described-chip compile), which
    # is four shapes here; the randomized range finder compiles in
    # under 20 s per shape and still refreshes every due layer.
    qcfg = QGaLoreConfig(update_interval=REFRESH_EVERY,
                         subspace_method="randomized")
    tcfg = TrainConfig(seed=SEED, global_batch=batch, seq_len=SEQ,
                       steps=steps, learning_rate=1e-3, warmup_steps=1,
                       log_every=0)
    return qcfg, tcfg


def state_bytes_per_device(state) -> dict:
    import jax
    per_dev: dict = {}
    for leaf in jax.tree_util.tree_leaves(state):
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) \
                + sh.data.nbytes
    return dict(sorted(per_dev.items()))


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_train(bundle, clock):
    import jax
    import jax.numpy as jnp

    from repro.kernels import dispatch, profile
    from repro.train.trainer import Trainer

    log(f"[train] {ARCH}: layers={bundle.cfg.num_layers} "
        f"d_model={bundle.cfg.d_model} heads={bundle.cfg.num_heads} "
        f"d_ff={bundle.cfg.d_ff} vocab={bundle.cfg.vocab_size} "
        f"seq={SEQ} batch={BATCH} steps={STEPS} refresh_every="
        f"{REFRESH_EVERY} param_dtype=bfloat16")
    qcfg, tcfg = train_config(BATCH, STEPS)
    os.environ["REPRO_PROFILE"] = "1"      # trace-time op/backend record
    profile.reset()
    trainer = Trainer(bundle, tcfg, qcfg, param_dtype=jnp.bfloat16)
    params0 = trainer.state.params          # kept for the check phase
    c0 = clock.seconds
    hist = trainer.run(max_failures=0)
    jax.block_until_ready(trainer.state)
    compile_s = clock.seconds - c0
    for h in hist:
        log(f"[train] step {h['step']} loss {h['loss']:.6f} "
            f"seconds {h['dt']:.3f} "
            f"svd_refresh={h['step'] % REFRESH_EVERY == 0}")
    # steps 0 and 1 are the first calls of the two step programs
    steady = {r: [f"{h['dt']:.3f}" for h in hist[2:]
                  if (h["step"] % REFRESH_EVERY == 0) == r]
              for r in (False, True)}
    log(f"[train] compile seconds {compile_s:.1f} (backend compile, "
        f"persistent-cache hits so far {clock.cache_hits})")
    log(f"[train] steady step seconds: plain {' '.join(steady[False])}; "
        f"SVD refresh {' '.join(steady[True])}")
    log(f"[train] SVD used {trainer.controller.total_svd_count()} / "
        f"{trainer.controller.baseline_svd_count(STEPS)} baseline")
    snap = profile.snapshot()
    os.environ["REPRO_PROFILE"] = "0"
    for op in MAIN_PATH_OPS:
        got = snap.get(op, {}).get("meta", {}).get("backend")
        log(f"[train] op {op}: backend {got} "
            f"(traced {snap.get(op, {}).get('trace_calls', 0)} calls)")
        require(got == "pallas-tpu", f"{op} resolved to {got}")
    for op in ("int8_matmul", "int8_matmul_t", "fused_qgalore_update"):
        got = dispatch.resolve(op)[0]
        log(f"[train] dispatch.resolve({op!r}) -> {got}")
        require(got == "pallas-tpu", f"{op} resolves to {got}")
    losses = [h["loss"] for h in hist]
    require(all(map(math.isfinite, losses)), f"finite losses {losses}")
    require(trainer.controller.total_svd_count() > 0, "an SVD refresh ran")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"[train] peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    return trainer, params0, hist


def phase_check(bundle, trainer, params0, hist):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.config import ShapeCell
    from repro.core import adam8bit, qgalore
    from repro.data.synthetic import batch_for_bundle
    from repro.kernels import ops
    from repro.models import base

    # step-0 loss: the trainer's (pallas-tpu) against ref on the same
    # init params and the same step-0 batch
    cell = ShapeCell("train", SEQ, BATCH, "train")
    batch = batch_for_bundle(bundle, cell, 0, SEED)
    with reference():
        ref_loss = float(jax.jit(
            lambda p, b: base.loss_fn(bundle, p, b)[0])(params0, batch))
    tpu_loss = hist[0]["loss"]
    rel = abs(tpu_loss - ref_loss) / abs(ref_loss)
    log(f"[check] step-0 loss pallas-tpu {tpu_loss:.6f} ref {ref_loss:.6f} "
        f"rel diff {rel:.2e} (tolerance {LOSS_RTOL:.0e})")
    require(rel <= LOSS_RTOL, "step-0 loss vs ref")

    # the fused update on one real layer of each side, both backends,
    # same inputs (the trained layer-0 weights and projections)
    specs = trainer.specs
    p_flat = jax.tree_util.tree_leaves(
        trainer.state.params, is_leaf=qgalore.quant.is_qtensor)
    i_flat = jax.tree_util.tree_leaves(
        trainer.state.opt.inner, is_leaf=qgalore._is_inner_leaf)
    pr_flat = jax.tree_util.tree_leaves(
        trainer.state.opt.proj,
        is_leaf=lambda x: qgalore.quant.is_qtensor(x) or x is None)
    layer0 = lambda t: jax.tree_util.tree_map(lambda x: x[0], t)
    done = set()
    for idx, spec in enumerate(specs):
        if not spec.galore or not spec.batch or spec.side in done:
            continue
        done.add(spec.side)
        w, P = layer0(p_flat[idx]), layer0(pr_flat[idx])
        m32, v32 = adam8bit.moments_fp32(layer0(i_flat[idx]))
        low = jax.random.normal(jax.random.PRNGKey(7), m32.shape) * 1e-3
        fused = functools.partial(
            ops.fused_qgalore_update, w, low, m32, v32, P, jnp.float32(4),
            1e-3, jax.random.PRNGKey(11), side=spec.side, gscale=0.25)
        qa, ma, va = jax.block_until_ready(fused())
        with reference():
            qb, mb, vb = jax.block_until_ready(fused())
        n = qa.orig_last
        codes = np.asarray(qa.q)[:, :n] == np.asarray(qb.q)[:, :n]
        step = np.abs(np.asarray(qa.q, np.int32)
                      - np.asarray(qb.q, np.int32)).max()
        log(f"[check] fused update {spec.path}[0] side={spec.side} "
            f"W{tuple(w.shape)} low-rank {tuple(m32.shape)}: codes equal "
            f"{codes.mean():.6f} (min {FUSED_MIN_CODE_MATCH}), max code "
            f"step {step}")
        require(codes.mean() >= FUSED_MIN_CODE_MATCH and step <= 1,
                f"fused update {spec.side} codes")
        np.testing.assert_allclose(np.asarray(qa.scale), np.asarray(qb.scale),
                                   rtol=1e-2, atol=0)
        np.testing.assert_allclose(np.asarray(ma), np.asarray(mb),
                                   rtol=MOMENT_RTOL, atol=MOMENT_ATOL)
        np.testing.assert_allclose(np.asarray(va), np.asarray(vb),
                                   rtol=MOMENT_RTOL, atol=MOMENT_ATOL)
    require(done == {"left", "right"}, f"both fused sides checked: {done}")


def phase_serve(bundle, params, clock):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serve import engine
    from repro.serve.scheduler import Request, make_scheduler

    rng = np.random.default_rng(SEED)
    vocab = bundle.cfg.vocab_size
    prefix = rng.integers(1, vocab, SERVE_PREFIX).astype(np.int32)
    reqs = [Request(rid=i, tokens=np.concatenate([
        prefix, rng.integers(1, vocab, 8 + 8 * i).astype(np.int32)]),
        max_new_tokens=SERVE_NEW_TOKENS) for i in range(SERVE_REQUESTS)]
    max_len = max(len(r.tokens) for r in reqs) + SERVE_NEW_TOKENS + 1
    sched = make_scheduler(bundle, params, backend="paged", num_slots=4,
                           max_len=max_len, dtype=jnp.bfloat16)
    c0 = clock.seconds
    t0 = time.monotonic()
    comps = sched.run(reqs)
    dt = time.monotonic() - t0
    n_tok = sum(len(c.tokens) for c in comps)
    st = sched.stats
    log(f"[serve] paged: {len(comps)}/{len(reqs)} requests, {n_tok} tokens "
        f"generated in {dt:.2f}s (incl. {clock.seconds - c0:.1f}s compile); "
        f"admitted={st['admitted']} retired={st['retired']} "
        f"radix_hit_blocks={st['radix_hit_blocks']}")
    require(len(comps) == len(reqs), "every request completed")
    require(st["admitted"] == st["retired"] == len(reqs),
            "admitted == retired")
    require(n_tok == SERVE_REQUESTS * SERVE_NEW_TOKENS, "tokens generated")

    # first decode step's logits: pallas-tpu vs ref from the same
    # (pallas-tpu) prefill state and the same tokens
    plen = SERVE_PREFIX + 16
    batch = {"tokens": jnp.asarray(np.stack([r.tokens[:plen]
                                             for r in reqs[1:5]]))}
    logits, state = jax.jit(engine.build_prefill(bundle, plen + 2))(
        params, batch)
    tok = engine.sample(logits, None)[:, None]
    decode = lambda: np.asarray(jax.jit(engine.build_decode(bundle))(
        params, state, tok)[0], np.float32)
    got = decode()
    with reference():
        want = decode()
    rel = np.linalg.norm(got - want) / np.linalg.norm(want)
    log(f"[serve] first decode logits {want.shape}: ||diff||/||ref|| "
        f"{rel:.2e} (tolerance {LOGITS_RTOL:.0e}), max|diff| "
        f"{np.abs(got - want).max():.4e}, max|ref| {np.abs(want).max():.4e}")
    require(np.isfinite(got).all(), "finite logits")
    require(rel <= LOGITS_RTOL, "first decode logits vs ref")


def run_one_chip(clock):
    import jax.numpy as jnp

    from repro.models import model_zoo

    bundle = model_zoo.build_arch(ARCH, dtype=jnp.bfloat16)
    trainer, params0, hist = phase_train(bundle, clock)
    phase_check(bundle, trainer, params0, hist)
    params = trainer.state.params
    del trainer, params0
    gc.collect()
    phase_serve(bundle, params, clock)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def run_four_chips(clock):
    import jax
    import jax.numpy as jnp

    from repro.config import replace
    from repro.launch.mesh import make_dp_mesh, make_tp_mesh
    from repro.models import model_zoo
    from repro.train.trainer import Trainer

    bundle = model_zoo.build_arch(ARCH, dtype=jnp.bfloat16)
    qcfg, tcfg = train_config(BATCH, 1)
    qcfg = replace(qcfg, compress_dp_grads=True)
    # explicit: every run here, the single-device one too, is traced on
    # the ref backend (see the module docstring)
    os.environ["REPRO_KERNEL_BACKEND"] = "ref"
    log("[4chip] kernel backend ref, requested explicitly: Mosaic kernels "
        "cannot be partitioned automatically across devices")

    def step0(mesh, label):
        c0 = clock.seconds
        tr = Trainer(bundle, tcfg, qcfg, param_dtype=jnp.bfloat16,
                     mesh=mesh, zero_shard=mesh is not None)
        per_dev = state_bytes_per_device(tr.state)
        opt_dev = state_bytes_per_device(tr.state.opt)
        hist = tr.run(max_failures=0)
        log(f"[4chip] {label}: step-0 loss {hist[0]['loss']:.6f} "
            f"seconds {hist[0]['dt']:.2f} (compile "
            f"{clock.seconds - c0:.1f})")
        log(f"[4chip] {label}: state bytes per device "
            + " ".join(f"{d}:{b}" for d, b in per_dev.items())
            + "; optimizer state " + " ".join(
                f"{d}:{b}" for d, b in opt_dev.items()))
        del tr
        gc.collect()
        return hist[0]["loss"], per_dev

    ref, _ = step0(None, "single device")
    for mesh, label in ((make_dp_mesh(4), "(4,1) compressed DP + ZeRO"),
                        (make_tp_mesh(2), "(2,2) data x model, "
                                          "compressed DP + ZeRO")):
        loss, per_dev = step0(mesh, label)
        rel = abs(loss - ref) / abs(ref)
        log(f"[4chip] {label}: loss vs single device rel diff {rel:.2e} "
            f"(tolerance {LOSS_RTOL:.0e})")
        require(rel <= LOSS_RTOL, f"{label} step-0 loss")
        require(len(per_dev) == 4 and max(per_dev.values())
                < 0.75 * sum(per_dev.values()),
                f"{label} state spread over 4 devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)}",
              file=sys.stderr)
        return 2

    from repro.launch.cache import enable_compile_cache
    log(f"[setup] compile cache {enable_compile_cache()}")
    clock = CompileClock(jax)
    t0 = time.monotonic()
    if args.chips == 4:
        run_four_chips(clock)
    else:
        run_one_chip(clock)
    log(f"[setup] total seconds {time.monotonic() - t0:.1f}, compile "
        f"seconds {clock.seconds:.1f}, cache hits {clock.cache_hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
