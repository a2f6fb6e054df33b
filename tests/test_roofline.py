"""Roofline peaks come from a table keyed by ``device_kind``; a device
that is not in it is an error, never a default."""
import pytest

from repro.analysis import roofline


def test_v5e_peaks_are_the_published_values():
    p = roofline.peaks_for("TPU v5 lite")
    assert (p.bf16_flops, p.int8_ops, p.hbm_bw, p.ici_bw) == (
        197e12, 393e12, 819e9, 50e9)


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", None])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError):
        roofline.peaks_for(kind)


def test_artifact_roofline_uses_its_device_kind():
    art = {"device_kind": "TPU v5 lite", "mesh": "16x16",
           "cost_analysis": {"flops": 197e12, "bytes accessed": 819e9}}
    r = roofline.from_artifact(art, corrected=False)
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_s == pytest.approx(1.0)
    with pytest.raises(KeyError):
        roofline.from_artifact({k: v for k, v in art.items()
                                if k != "device_kind"}, corrected=False)
