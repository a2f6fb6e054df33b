"""fused_update.ms_per_step (ms/step): summed device time of the fused
Q-GaLore update kernel (``kernels/fused_update.py``) per step of the
traced window."""
from bench import trace

PATTERNS = (r"fused_qgalore_update",)


def read(ctx):
    ev = trace.pick(ctx.ops(), PATTERNS)
    if not ev or not ctx.window["steps"]:
        return None
    return 1e3 * sum(e.dur for e in ev) / 1e9 / ctx.window["steps"]
