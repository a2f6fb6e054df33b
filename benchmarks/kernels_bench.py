"""Kernel microbenchmarks (CPU wall time of the jnp reference paths +
interpret-mode Pallas correctness cost; real-TPU numbers come from the
roofline, not this box) and the quantized-vs-dequant A/B gate.

Measurement discipline
----------------------
Sequential A/B timing (run all iters of A, then all of B) is what
produced the phantom "quantized prefill regression" this box once
reported: scheduler drift between the two windows shows up as a fake
ratio. Every ratio here is measured with **interleaved paired rounds**
instead — each round times one short burst of every variant
back-to-back (alternating order round to round), the per-round ratios
are trimmed (drop the top/bottom 20%), and the trimmed mean ± standard
error is reported. A real effect survives trimming; a scheduler hiccup
lands in one round and gets dropped.

The ``--gate-out`` mode writes a machine-readable no-regression verdict
for CI: quantized_dense forward must not be slower than the
dequantize-then-einsum baseline. On the ``ref`` backend the two compile
to near-identical XLA programs, so the gate passes when the trimmed
ratio is ≥ 1.0 **or** is within 2 standard errors of 1.0 (a hard ≥ 1.0
on a noisy shared box would flake on a true ratio of exactly 1.0).

The fused-update section times the Q-GaLore per-step weight update both
ways (unfused-interpret / unfused-same-backend / fused) and emits both
speedup ratios.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, paired_ratio, paired_times
from repro.core import projector, quant
from repro.core.quant import quantize_blockwise
from repro.kernels import dispatch, ops, profile
from repro.launch.cache import enable_compile_cache


def _time(fn, *args, iters=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.monotonic()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.monotonic() - t0) / iters * 1e6


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=12)
    ap.add_argument("--inner", type=int, default=4)
    ap.add_argument("--gate-out", default=None,
                    help="write the quantized>=dequant gate verdict JSON")
    args = ap.parse_args(argv)
    enable_compile_cache()

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (512, 1024), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (1024, 2048))
    qt = quantize_blockwise(w, bits=8, symmetric=True)

    f_deq = jax.jit(lambda a, q: a @ quant.dequantize(q, jnp.float32))
    us = _time(f_deq, x, qt)
    emit("kernels/int8_dense_jnp", us, "M=512;K=1024;N=2048")

    P = jax.random.normal(jax.random.fold_in(key, 2), (1024, 128)) * 0.1
    qp = quantize_blockwise(P, bits=4, block=128, symmetric=False)
    f_proj = jax.jit(lambda g, q: g @ quant.dequantize(q, jnp.float32))
    us = _time(f_proj, x, qp)
    emit("kernels/int4_project_jnp", us, "M=512;K=1024;R=128")

    f_q = jax.jit(lambda a: quantize_blockwise(a, bits=8, symmetric=True).q)
    us = _time(f_q, w)
    emit("kernels/blockwise_quant_jnp", us, "K=1024;N=2048")

    upd = jax.random.normal(jax.random.fold_in(key, 3), w.shape) * 1e-3
    f_sr = jax.jit(lambda q, u, k: quant.requantize_sr(q, u, k).q)
    us = _time(f_sr, qt, upd, jax.random.PRNGKey(9))
    emit("kernels/sr_requant_jnp", us, "K=1024;N=2048")

    # Pallas interpret-mode parity cost (correctness harness, not perf)
    t0 = time.monotonic()
    out = ops.int8_matmul(x[:128, :256], quantize_blockwise(
        w[:256, :512], bits=8, symmetric=True), interpret=True)
    jax.block_until_ready(out)
    emit("kernels/int8_pallas_interpret", (time.monotonic() - t0) * 1e6,
         "M=128;K=256;N=512;mode=interpret")

    gate = quantized_dense_bench(key, rounds=args.rounds, inner=args.inner)
    fused_update_bench(key)

    if args.gate_out:
        with open(args.gate_out, "w") as f:
            json.dump(gate, f, indent=2)
        print(f"wrote {args.gate_out} (pass={gate['pass']})", flush=True)
    return gate


# (M, K, N) problems for the quantized-vs-dequant gate: a generic square-
# ish matmul, a 1-row decode shape, and a llama-60m FFN-up prefill slice
# (N=1376 exercises the quant-block column padding / tail scale group).
GATE_SHAPES = ((512, 1024, 2048), (8, 512, 512), (256, 512, 1376))


def quantized_dense_bench(key, *, rounds=12, inner=4) -> dict:
    """quantized_dense fwd + fwd/bwd vs the dequantize-then-einsum
    baseline on the dispatch default backend, measured with interleaved
    paired rounds over GATE_SHAPES. Returns the gate verdict dict."""
    backend = dispatch.default_backend("quantized_dense")
    gate = {"backend": backend, "rounds": rounds, "inner": inner,
            "criterion": "ratio_x >= 1.0 or ratio_x + 2*sem >= 1.0",
            "shapes": [], "pass": True}

    for si, (m, k, n) in enumerate(GATE_SHAPES):
        x = jax.random.normal(jax.random.fold_in(key, si), (m, k),
                              jnp.float32)
        w = jax.random.normal(jax.random.fold_in(key, 20 + si),
                              (k, n)) * 0.1
        qt = quantize_blockwise(w, bits=8, symmetric=True)
        shape = f"M={m};K={k};N={n}"

        f_q = jax.jit(lambda a=x: ops.quantized_dense(
            a, qt, dtype=jnp.float32, backend=backend))
        f_d = jax.jit(lambda a=x: a @ quant.dequantize(qt, jnp.float32))
        times = paired_times({"dequant": f_d, "quantized": f_q},
                             rounds=rounds, inner=inner)
        stat = paired_ratio(times, "dequant", "quantized")
        us_q = float(np.median(times["quantized"]))
        us_d = float(np.median(times["dequant"]))
        emit("kernels/quantized_dense_fwd", us_q,
             shape + f";backend={backend}")
        emit("kernels/dequant_dense_fwd", us_d, shape)
        emit("kernels/quantized_dense_fwd_speedup", stat["ratio_x"],
             shape + f";unit=x;baseline=dequant-einsum;sem={stat['sem']:.4f}"
             f";rounds={stat['rounds']}")
        ok = (stat["ratio_x"] >= 1.0
              or stat["ratio_x"] + 2.0 * stat["sem"] >= 1.0)
        gate["shapes"].append({"shape": [m, k, n], **stat,
                               "us_quantized": us_q, "us_dequant": us_d,
                               "pass": ok})
        gate["pass"] = gate["pass"] and ok

    # fwd + bwd (dL/dx and dL/dW) through the custom VJP vs autodiff of
    # the dequant einsum — training path, QVirtual weight (shadow dL/dW)
    m, k, n = GATE_SHAPES[0]
    x = jax.random.normal(key, (m, k), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 20), (k, n)) * 0.1
    qt = quantize_blockwise(w, bits=8, symmetric=True)
    shape = f"M={m};K={k};N={n}"
    wv = quant.virtualize(qt)

    @jax.jit
    def g_q(a, shadow):
        def f(aa, sh):
            out = ops.quantized_dense(
                aa, quant.QVirtual(qt, sh), dtype=jnp.float32,
                backend=backend)
            return jnp.sum(out * out)
        return jax.grad(f, argnums=(0, 1))(a, shadow)

    @jax.jit
    def g_d(a, wfull):
        def f(aa, ww):
            out = aa @ ww
            return jnp.sum(out * out)
        return jax.grad(f, argnums=(0, 1))(a, wfull)

    wd = quant.dequantize(qt, jnp.float32)
    times = paired_times(
        {"dequant": lambda: g_d(x, wd),
         "quantized": lambda: g_q(x, wv.shadow)},
        rounds=rounds, inner=max(inner // 2, 1))
    stat = paired_ratio(times, "dequant", "quantized")
    emit("kernels/quantized_dense_fwdbwd",
         float(np.median(times["quantized"])),
         shape + f";backend={backend}")
    emit("kernels/dequant_dense_fwdbwd",
         float(np.median(times["dequant"])), shape)
    emit("kernels/quantized_dense_fwdbwd_speedup", stat["ratio_x"],
         shape + f";unit=x;baseline=dequant-einsum;sem={stat['sem']:.4f}")
    gate["fwdbwd"] = {"shape": [m, k, n], **stat}
    profile.maybe_attach(gate)
    return gate


def fused_update_bench(key, m=2048, n=1024, r=128, iters=3):
    """Fused vs unfused Q-GaLore step update (acceptance: >= 1.5x).

    Both variants are jitted end-to-end over a llama-130m-sized layer so
    the comparison measures the update pipeline, not Python dispatch.
    """
    W = jax.random.normal(jax.random.fold_in(key, 10), (m, n)) * 0.02
    qt = quantize_blockwise(W, bits=8, symmetric=True)
    P = jnp.linalg.qr(
        jax.random.normal(jax.random.fold_in(key, 11), (n, r)))[0]
    qp = projector.quantize_projection(P, 4, 256)
    grad = jax.random.normal(jax.random.fold_in(key, 12), (m, n))
    m32 = jnp.zeros((m, r))
    v32 = jnp.zeros((m, r))
    b1, b2, eps, gscale, lr = 0.9, 0.999, 1e-8, 0.25, 1e-2
    rng = jax.random.PRNGKey(5)

    backend = dispatch.default_backend("fused_qgalore_update")

    def make_unfused(op_backend):
        @jax.jit
        def unfused(grad, m32, v32, rng):
            # three separate op calls, as the per-leaf loop ran them:
            # project, Adam (jnp), SR-requant
            low = ops.int4_project(grad, qp, backend=op_backend)
            m_new = b1 * m32 + (1 - b1) * low
            v_new = b2 * v32 + (1 - b2) * low * low
            dirn = (m_new / (1 - b1)) / (jnp.sqrt(v_new / (1 - b2)) + eps)
            upd = gscale * projector.project_back(
                dirn, projector.maybe_dequantize(qp), "right")
            new_qt = ops.sr_requant_update(qt, -lr * upd, rng,
                                           backend=op_backend)
            return new_qt.q, m_new, v_new
        return unfused

    @jax.jit
    def fused(grad, m32, v32, rng):
        low = projector.project(
            grad, projector.maybe_dequantize(qp), "right")
        new_qt, m_new, v_new = ops.fused_qgalore_update(
            qt, low, m32, v32, qp, jnp.float32(1), lr, rng, side="right",
            gscale=gscale, backend=backend)
        return new_qt.q, m_new, v_new

    us_interp = _time(make_unfused("pallas-interpret"), grad, m32, v32,
                      rng, iters=iters)
    us_same = _time(make_unfused(backend), grad, m32, v32, rng,
                    iters=iters)
    us_fused = _time(fused, grad, m32, v32, rng, iters=iters)
    shape = f"M={m};N={n};r={r}"
    emit("kernels/step_update_unfused_interpret", us_interp,
         shape + ";ops=3;mode=interpret")
    emit("kernels/step_update_unfused", us_same,
         shape + f";ops=3;backend={backend}")
    emit("kernels/step_update_fused", us_fused,
         shape + f";backend={backend}")
    # vs the old per-leaf loop on CPU containers (interpret-mode ops)
    emit("kernels/step_update_fused_speedup", us_interp / us_fused,
         shape + ";unit=x;baseline=interpret")
    # vs the same backend unfused — the fusion benefit itself
    emit("kernels/step_update_fusion_speedup", us_same / us_fused,
         shape + ";unit=x;baseline=same-backend")


if __name__ == "__main__":
    main()
