"""idle_share (%): the share of the traced window in which no operation
ran on the device, 1 - (union of device-op intervals) / window."""
from bench import trace


def read(ctx):
    window = (ctx.hi - ctx.lo) / 1e9
    busy = trace.busy_seconds(ctx.trace.device_ops, ctx.lo, ctx.hi)
    if window <= 0 or busy <= 0:
        return None
    return 100.0 * (1.0 - busy / window)
