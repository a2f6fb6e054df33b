"""sr_randoms.ms_per_step (ms/step): device time per step of the traced
window's operations under the program's ``repro.sr_randoms`` scope: the
uniform draws that feed stochastic rounding (``kernels/ops.py``,
``core/quant.py``).

The measured kernels and control-flow envelopes are left out; the
op_name of each operation comes from the plain step's compiled text
(``bench/scopes.py``). Nothing is read where the program carries no
scope.
"""
from bench import scopes

SCOPE = "repro.sr_randoms"


def read(ctx):
    return scopes.read(ctx, SCOPE)
