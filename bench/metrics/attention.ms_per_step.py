"""attention.ms_per_step (ms/step): device time per step of the traced
window's operations under the program's ``repro.attention`` scope:
attention's scores, mask, softmax and PV, forward, remat recompute and
backward (``models/attention.py``).

The measured kernels and control-flow envelopes are left out; the
op_name of each operation comes from the plain step's compiled text
(``bench/scopes.py``). Nothing is read where the program carries no
scope.
"""
from bench import scopes

SCOPE = "repro.attention"


def read(ctx):
    return scopes.read(ctx, SCOPE)
