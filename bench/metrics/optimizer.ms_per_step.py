"""optimizer.ms_per_step (ms/step): device time per step of the traced
window's operations under the program's ``repro.optimizer`` scope:
gradient clipping and the optimizer update around the fused kernel:
dense 8-bit Adam of the norms and the embedding, moment
(de)quantization, low-rank glue (``train/step.py``).

The measured kernels and control-flow envelopes are left out; the
op_name of each operation comes from the plain step's compiled text
(``bench/scopes.py``). Nothing is read where the program carries no
scope.
"""
from bench import scopes

SCOPE = "repro.optimizer"


def read(ctx):
    return scopes.read(ctx, SCOPE)
