"""A cell of the chip benchmark cut to a size the CPU runs in seconds:
the text cell's configuration and traffic at two layers of width 128,
every projected matrix kept at or above the 128 of ``min_dim``."""
import copy
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness, spec  # noqa: E402

SEED = 2**33 + 5          # past 32 bits, as a run's --seed may be
# Limits of the tiny cell's check, from CPU runs at SEED: the program
# read loss gaps of 2.7e-6 / 2.4e-6 (steps 0 and 1, the same weights on
# both sides after a step at learning rate 0), grad_gap 3.9e-3, grad1_gap
# 1.8e-3 and change_gap 2.5e-2 (1.4e-5 / 7.5e-6, 5.3e-3, 2.5e-3, 1.1e-2
# with an image prefix); the float8 control read step-0 loss gaps of
# 5.2e-4 and 6.6e-4 and grad1_gap 8.4e-3 and 1.1e-2; half of each batch
# 1.4e-3 and 3.1e-3 and grad1_gap 0.14 and 0.16; a state left unchanged
# reads 1 on every norm. Step 2's loss is not compared, as in the chip
# cells: the randoms of the update, not the arithmetic, set its gap (two
# float32 references with different randoms differ as much).
LIMITS = {"loss_gap.0": 1e-4, "grad_gap": 0.03, "grad1_gap": 0.005,
          "change_gap": 0.06}


def cell(prefix: int = 0) -> spec.Cell:
    c = spec.load("internlm2-1.8b.pretrain-s256")
    c.config = copy.deepcopy(c.config)
    c.config["model"].update(num_layers=2, d_model=128, num_heads=4,
                             num_kv_heads=2, d_ff=256, vocab_size=512)
    c.traffic = copy.deepcopy(c.traffic)
    c.traffic.update(seq=32, batch=4)
    c.traffic["qgalore"]["rank"] = 16
    if prefix:
        c.config["model"].update(family="vlm", num_prefix_embeddings=prefix)
        c.traffic.update(seq=32 + prefix, prefix=prefix, prefix_std=0.02)
    c.limits = dict(LIMITS)
    return c


def run(c: spec.Cell, *, traced: bool = False, fault=None,
        seconds: float = 1.0) -> dict:
    """A whole run of the cell on the CPU: the look for a chip skipped,
    the kernels on the backend the CPU resolves them to."""
    import jax
    return harness.run_cell(c, SEED, seconds, traced,
                            t_start=time.monotonic(),
                            clock=harness.CompileClock(jax),
                            device={"platform": "cpu", "count": 1},
                            peaks=None, expect_backend="ref", fault=fault)
