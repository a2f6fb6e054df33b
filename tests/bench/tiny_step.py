"""The plain training step of the benchmark's tiny cell, jitted as
``Trainer`` jits it, with abstract arguments: for tests that compile the
step and read its instructions without running it."""
import bench_tiny  # noqa: F401  (puts the checkout on sys.path)


def plain_step(sharding=None):
    """(jitted plain step, its abstract arguments) of the tiny cell's
    configuration and traffic; ``sharding`` places every argument (one
    device of a described chip, for a compile for that chip)."""
    from bench import scopes

    c = bench_tiny.cell()
    return scopes.plain_step(c.config, c.traffic, sharding)
