"""layer_other.ms_per_step (ms/step): device time per step of the traced
window's operations under the program's ``repro.layer`` scope: what a
decoder block does outside attention, the weight gradients and the
kernels: norms, RoPE, the SwiGLU gate, residuals
(``models/transformer.py::block_apply``).

The measured kernels and control-flow envelopes are left out; the
op_name of each operation comes from the plain step's compiled text
(``bench/scopes.py``). Nothing is read where the program carries no
scope.
"""
from bench import scopes

SCOPE = "repro.layer"


def read(ctx):
    return scopes.read(ctx, SCOPE)
