"""int8_matmul_roofline (%): the least time the chip could take for the
INT8 matmul kernel calls of the traced window, over the time they took.

Each call's cost comes from its own event in the trace: a TPU trace names
a device op by its HLO instruction, which carries the shapes of the
result and of every operand. For a call of ``int8_matmul`` (forward) or
``int8_matmul_t`` (dx) the operations are 2 * rows of the result * the
elements of the INT8 weight operand (2*M*K*N, on the padded widths the
kernel runs), and the bytes are its operands and its result, each moved
once: the bf16 activations in, the INT8 codes, their f32 block scales,
the bf16 result. A call's least time is the larger of its operations over
the bf16 peak (the kernel widens the codes and runs the MXU on floats)
and its bytes over the HBM bandwidth; the least times are summed.

The instruction names of these kernels carry JAX's transform path
(``int8_matmul``, ``jvp_jit_int8_matmul__``,
``transpose_jvp_jit_int8_matmul_t___``): every name with ``int8_matmul``
in it. A kernel event whose text gives no INT8 operand leaves the metric
out.
"""
import re
import sys

from bench import trace

PATTERNS = (r".*int8_matmul.*",)
BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
         "f32": 4, "s32": 4, "u32": 4}
SHAPE = re.compile(r"\b(pred|[su]8|[su]16|[su]32|bf16|f16|f32)\[([\d,]*)\]")


def _size(dims: str) -> int:
    n = 1
    for d in filter(None, dims.split(",")):
        n *= int(d)
    return n


def cost(text: str):
    """(operations, bytes) of one kernel call from its instruction text,
    or None when the text does not show an INT8 weight operand."""
    head = text.split(", custom_call_target", 1)[0]
    shapes = SHAPE.findall(head)
    if len(shapes) < 3:
        return None
    (rdt, rdims), operands = shapes[0], shapes[1:]
    weights = [dims for dt, dims in operands if dt == "s8"]
    if not weights:
        return None
    rows = int(rdims.split(",")[0])
    flops = 2 * rows * _size(weights[0])
    nbytes = sum(BYTES[dt] * _size(dims) for dt, dims in shapes)
    return flops, nbytes


def read(ctx):
    ev = trace.pick(ctx.ops(), PATTERNS)
    if not ev or not ctx.peaks:
        return None
    costs = [cost(e.name) for e in ev]
    if any(c is None for c in costs):
        print("[metric] int8_matmul_roofline: a kernel event without INT8 "
              "operand shapes; left out", file=sys.stderr)
        return None
    peak, bw = ctx.peaks["bf16_flops_per_s"], ctx.peaks["hbm_bytes_per_s"]
    t_c = sum(f for f, _ in costs) / peak
    t_m = sum(b for _, b in costs) / bw
    best = sum(max(f / peak, b / bw) for f, b in costs)
    kernel_s = sum(e.dur for e in ev) / 1e9
    print(f"[metric] int8_matmul_roofline: {len(ev)} calls, bound by "
          f"{'compute' if t_c >= t_m else 'memory'} ({t_c:.4f} s of "
          f"operations, {t_m:.4f} s of bytes; kernels {kernel_s:.4f} s)",
          file=sys.stderr)
    return 100.0 * best / kernel_s
