"""The control of the training cells' check: the plain reference computed
with float8 (e4m3, per-tensor scaled) operands, one step below the
bfloat16 the configuration computes in, put in the program's place, has
to come out not correct against the float32 reference."""
import bench_tiny
import pytest

from bench import compare, seeds
from bench.runners import train


@pytest.mark.parametrize("prefix", [0, 8], ids=["text", "image-prefix"])
def test_float8_control_is_not_correct(prefix):
    c = bench_tiny.cell(prefix)
    run = train.build(c.config, c.traffic, bench_tiny.SEED,
                      expect_backend="ref")
    ref = run.reference()
    want = run.reference_readings(ref)
    got = run.reference_readings(run.reference("fp8"), stream=seeds.CONTROL)
    nums = compare.numbers(got, want)
    assert not compare.judge(nums, c.limits)
    assert nums["loss_gap.0"]["value"] > c.limits["loss_gap.0"]
    # the plain program's step is held to its own number
    assert nums["grad1_gap"]["value"] > c.limits["grad1_gap"]
    # the same reference with other randoms stays inside every limit
    same = compare.numbers(
        run.reference_readings(ref, stream=seeds.CONTROL), want)
    assert compare.judge(same, c.limits), same
