"""opt_state_gib (GiB): bytes of the optimizer state on the device after
the window (every array of ``TrainState.opt``: 8-bit moments, INT4
projections, the step count)."""


def read(ctx):
    b = ctx.counters.get("opt_state_bytes")
    return None if not b else b / 2**30
