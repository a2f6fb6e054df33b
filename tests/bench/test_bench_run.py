"""A whole traced run of the benchmark's tiny cell on the CPU: the timed
path sound, the check passes and the per-layer metrics that a CPU trace
can hold are there."""
import bench_tiny
import pytest


@pytest.mark.parametrize("prefix", [0, 8], ids=["text", "image-prefix"])
def test_sound_run_is_correct(prefix):
    res = bench_tiny.run(bench_tiny.cell(prefix), traced=bool(prefix))
    check = res["check"]
    assert res["correct"], check
    assert list(res)[-1] == "check"
    assert check["window_compiles"]["value"] == 0
    assert res["attempted"] > 0 and res["failed"] == 0
    if prefix:
        assert {"idle_share", "opt_state_gib"} <= set(res["metrics"])
        assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
        assert res["breakdown"]["device_ops"]
    else:
        assert res["metrics"]["train_tokens_per_s"]["value"] > 0
        assert res["metrics"]["setup_s"]["unit"] == "s"
