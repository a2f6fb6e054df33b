"""Production meshes. Functions, not module constants — importing this module
never touches jax device state (``force_host_device_count`` must therefore be
called before anything else imports jax)."""
from __future__ import annotations

import math
import os


def force_host_device_count(n: int) -> None:
    """Emulate ``n`` devices on the host CPU platform (CI / laptops): appends
    the XLA flag, so it MUST run before jax initializes its backends. The
    distributed tests and ``benchmarks/dist_bench.py`` run their meshes this
    way; on real hardware it is a no-op (don't call it)."""
    if n and n > 0:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}")


def make_mesh(shape, axes):
    """Every mesh of the repo is built here, with ``Auto`` axes, over the
    first ``prod(shape)`` visible devices.

    JAX 0.9's ``jax.make_mesh`` makes ``Explicit`` axes by default, under
    which the sharding rules of ``repro.distributed`` (written for
    GSPMD's automatic propagation) stop on ``ShardingTypeError``."""
    import jax
    from jax.sharding import AxisType
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=jax.devices()[:math.prod(shape)])


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_dp_mesh(num_devices: int = 0):
    """Pure data-parallel mesh ``(D, 1)`` over ``("data", "model")`` — the
    shape the compressed-DP + ZeRO training mode runs on when the model
    fits one device (the Q-GaLore regime: INT8 weights + low-rank INT8
    state). ``num_devices`` defaults to every visible device. The model
    axis exists but has size 1, so nothing is tensor-parallel — use
    :func:`make_tp_mesh` to split devices between the two axes."""
    import jax
    d = num_devices or len(jax.devices())
    return make_mesh((d, 1), ("data", "model"))


def make_tp_mesh(tp: int, num_devices: int = 0):
    """2-D ``(D/tp, tp)`` mesh over ``("data", "model")``: ``tp``-way
    tensor parallelism, data parallelism over the rest. Validates that
    ``tp`` divides the device count — a ragged split would silently drop
    devices."""
    import jax
    n = num_devices or len(jax.devices())
    if tp <= 0 or n % tp != 0:
        raise ValueError(
            f"tensor-parallel degree {tp} must be a positive divisor of "
            f"the device count {n} (got remainder {n % tp if tp else n})")
    return make_mesh((n // tp, tp), ("data", "model"))
