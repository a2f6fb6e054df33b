"""int8_matmul.ms_per_step (ms/step): summed device time of the INT8
matmul kernels per step of the traced window: forward ``int8_matmul`` and
dx ``int8_matmul_t`` through ``kernels/ops.py::quantized_dense``, whose
instruction names carry JAX's transform path (``jvp_jit_int8_matmul__``,
``transpose_jvp_jit_int8_matmul_t___``)."""
from bench import trace

PATTERNS = (r".*int8_matmul.*",)


def read(ctx):
    ev = trace.pick(ctx.ops(), PATTERNS)
    if not ev or not ctx.window["steps"]:
        return None
    return 1e3 * sum(e.dur for e in ev) / 1e9 / ctx.window["steps"]
