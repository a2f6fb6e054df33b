"""wgrad.ms_per_step (ms/step): device time per step of the traced window's
operations under the program's ``repro.wgrad`` scope: the weight
gradients x^T g of the quantized matmuls' backward (``kernels/ops.py``)
and their projection to rank r (``train/stack.py``).

The measured kernels and control-flow envelopes are left out; the
op_name of each operation comes from the plain step's compiled text
(``bench/scopes.py``). Nothing is read where the program carries no
scope.
"""
from bench import scopes

SCOPE = "repro.wgrad"


def read(ctx):
    return scopes.read(ctx, SCOPE)
