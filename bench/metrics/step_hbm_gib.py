"""step_hbm_gib (GiB): the device memory the larger of the two training
step programs (the refresh and the plain step) needs by the compiler's
memory analysis: arguments, outputs not aliased to them, temporaries.
``peak_hbm_gib`` is the larger of this and the program's initialisation
peak; today the initialisation sets it (PERF.md section 5)."""


def read(ctx):
    b = ctx.counters.get("step_program_bytes")
    return None if not b else b / 2**30
