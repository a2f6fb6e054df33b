"""head_loss.ms_per_step (ms/step): device time per step of the traced
window's operations under the program's ``repro.head_loss`` scope: the
final norm, the logits' cast and the cross-entropy, forward and backward
(``models/transformer.py::head_loss``).

The measured kernels and control-flow envelopes are left out; the
op_name of each operation comes from the plain step's compiled text
(``bench/scopes.py``). Nothing is read where the program carries no
scope.
"""
from bench import scopes

SCOPE = "repro.head_loss"


def read(ctx):
    return scopes.read(ctx, SCOPE)
