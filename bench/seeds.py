"""Keys from the benchmark's ``--seed``, which may exceed 32 bits."""
from __future__ import annotations


def key(seed: int, stream: int):
    """A PRNG key for one named stream of one seed. ``PRNGKey`` keeps only
    the low 32 bits of its seed, so the high bits are folded in; distinct
    seeds and streams give distinct keys."""
    import jax
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    k = jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(k, stream)


def small(seed: int) -> int:
    """A non-negative int32 derived from ``seed``, for APIs that take a
    plain integer seed."""
    return (seed ^ (seed >> 31)) % (2**31 - 64)


# stream ids: each consumer of randomness draws from its own stream
WEIGHTS, TRAFFIC, REFERENCE, CONTROL = 1, 2, 3, 4
