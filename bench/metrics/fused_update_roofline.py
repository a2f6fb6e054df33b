"""fused_update_roofline (%): the least time the chip could take for one
step's fused Q-GaLore updates, over the time the kernel took.

One call per projected weight matrix per step: each layer's seven
matrices and the output head. Bytes are what the update itself needs:
the INT8 weight read and written with its f32 block scales (one per 256
along the last axis), the low-rank gradient read (f32), the two f32
moments read and written, and the INT4 projection (d * r / 2 bytes) with
an f32 scale and zero per row. The stochastic-rounding randoms are not
counted: they are how this implementation rounds, not what the update
needs. Operations are the back-projection's 2 * m * n * r.

What one step needs does not depend on how the program splits it into
kernel calls, so the least time per step is set against the kernel's
time per step, however many events that took.
"""
import sys

from bench import trace

PATTERNS = (r"fused_qgalore_update",)
BLOCK = 256


def calls(model: dict, qgalore: dict):
    """(m, n, r) of every fused update of one step."""
    D, F, L = model["d_model"], model["d_ff"], model["num_layers"]
    H, KV = model["num_heads"], model["num_kv_heads"]
    hd = model.get("head_dim") or D // H
    mats = [(D, H * hd), (D, KV * hd), (D, KV * hd), (H * hd, D),
            (D, F), (D, F), (F, D)]
    out = []
    for m, n in mats * L + [(D, model["vocab_size"])]:
        if min(m, n) >= qgalore["min_dim"]:
            out.append((m, n, min(qgalore["rank"], m, n)))
    return out


def flops(c) -> int:
    m, n, r = c
    return 2 * m * n * r


def nbytes(c) -> int:
    m, n, r = c
    low = r * max(m, n)
    d = min(m, n)
    blocks = m * -(-n // BLOCK)
    return (2 * m * n + 2 * blocks * 4      # INT8 W and scales, in and out
            + 4 * low + 2 * 2 * 4 * low     # gradient; moments in and out
            + d * r // 2 + 2 * d * 4)       # INT4 P, scale and zero per row


def read(ctx):
    ev = trace.pick(ctx.ops(), PATTERNS)
    steps = ctx.window["steps"]
    if not ev or not steps or not ctx.peaks:
        return None
    cs = calls(ctx.cell.config["model"], ctx.cell.traffic["qgalore"])
    t_c = sum(flops(c) for c in cs) / ctx.peaks["bf16_flops_per_s"]
    t_m = sum(nbytes(c) for c in cs) / ctx.peaks["hbm_bytes_per_s"]
    best = sum(max(flops(c) / ctx.peaks["bf16_flops_per_s"],
                   nbytes(c) / ctx.peaks["hbm_bytes_per_s"]) for c in cs)
    kernel_s = sum(e.dur for e in ev) / 1e9 / steps
    print(f"[metric] fused_update_roofline: bound by "
          f"{'compute' if t_c >= t_m else 'memory'} "
          f"({t_c * 1e3:.3f} ms of operations, {t_m * 1e3:.3f} ms of bytes "
          f"per step; kernel {kernel_s * 1e3:.3f} ms in {len(ev) / steps:g} "
          f"calls)", file=sys.stderr)
    return 100.0 * best / kernel_s
