"""Isolation shared by every test module."""
import pytest


@pytest.fixture(autouse=True, scope="module")
def _trainer_module_globals():
    """Give each test module the trainer's own batch source and
    learning-rate schedule. The benchmark's training runner
    (``bench/runners/train.py``) swaps both module globals of
    ``repro.train.trainer`` for its own and leaves them swapped, so a
    trainer test that ran after a benchmark test in the same process
    trained on the benchmark's batches."""
    from repro.core import optimizers
    from repro.data.synthetic import batch_for_bundle
    from repro.train import trainer

    trainer.batch_for_bundle = batch_for_bundle
    trainer.optimizers = optimizers
    yield
