"""unscoped.ms_per_step (ms/step): device time per step of the traced
window's operations under no scope of the program's
(``repro.kernels.profile.SCOPES``), or of another program: the
remainder, so that work outside every scope, or a scope lost in a
refactor, shows.

The measured kernels and control-flow envelopes are left out; the
op_name of each operation comes from the plain step's compiled text
(``bench/scopes.py``). Nothing is read where the program carries no
scope.
"""
from bench import scopes

SCOPE = "unscoped"


def read(ctx):
    return scopes.read(ctx, SCOPE)
