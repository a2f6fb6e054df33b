"""The benchmark's operation and byte counts, against the model's FLOPs
and the program's own list of projected weights."""
import math

import bench_tiny
import pytest

from bench import spec

I8 = spec.reader("int8_matmul_roofline")
FU = spec.reader("fused_update_roofline")
MFU = spec.reader("step_mfu")
CELLS = ["internlm2-1.8b.pretrain-s256"]


def cells():
    out = [bench_tiny.cell(), bench_tiny.cell(prefix=8)]
    return out + [spec.load(c) for c in CELLS]


# instruction texts of INT8 matmul kernel events, as a TPU v5e trace of
# the text cell and of a 512-wide smoke model named them (measured on one
# v5e chip)
FWD = ("%int8_matmul.42 = bf16[1024,512]{1,0:T(8,128)(2,1)S(1)} custom-call("
       "bf16[1024,512]{1,0:T(8,128)(2,1)S(1)} %bitcast.1658, "
       "s8[512,512]{1,0:T(8,128)(4,1)S(1)} %dynamic-slice_bitcast_fusion.87, "
       "f32[2,1,512]{2,1,0:T(1,128)S(1)} %reshape.2060), "
       'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
DX = ("%transpose_jvp_jit_int8_matmul_t___.74 = bf16[8192,2048]{1,0:T(8,128)"
      "(2,1)} custom-call(bf16[8192,8192]{1,0:T(8,128)(2,1)} %fusion.3, "
      "s8[2048,8192]{1,0:T(8,128)(4,1)} %get-tuple-element.9, "
      "f32[16,1,2048]{2,1,0:T(1,128)} %reshape.11), "
      'custom_call_target="tpu_custom_call"')


def test_int8_matmul_cost_from_the_instruction():
    f, b = I8.cost(FWD)
    assert f == 2 * 1024 * 512 * 512
    assert b == 2 * 1024 * 512 * 2 + 512 * 512 + 2 * 512 * 4
    f, b = I8.cost(DX)
    assert f == 2 * 8192 * 2048 * 8192
    assert b == 8192 * 8192 * 2 + 2048 * 8192 + 16 * 2048 * 4 \
        + 8192 * 2048 * 2
    assert I8.cost("%int8_matmul.1 = bf16[8,8]{1,0} custom-call()") is None


@pytest.mark.parametrize("c", cells(), ids=["tiny", "tiny-vl", *CELLS])
def test_model_flops_count_each_matmul_parameter_six_times(c):
    """PaLM's count: 6 FLOPs per matmul parameter per position through it
    (the head over the text positions) plus causal attention."""
    m, t = c.config["model"], c.traffic
    p = MFU.matmul_params(m)
    B, T = t["batch"], t["seq"]
    text = T - t.get("prefix", 0)
    H = m["num_heads"]
    hd = m.get("head_dim") or m["d_model"] // H
    D, F, KV = m["d_model"], m["d_ff"], m["num_kv_heads"]
    assert p["layers"] == m["num_layers"] * (
        2 * D * H * hd + 2 * D * KV * hd + 3 * D * F)
    assert MFU.flops_per_step(m, t) == B * (
        6 * p["layers"] * T + 6 * p["head"] * text
        + 6 * m["num_layers"] * H * hd * T * T)


def _program_specs(c):
    import jax.numpy as jnp

    from repro.config import ModelConfig, QGaLoreConfig
    from repro.core import qgalore
    from repro.models import model_zoo
    from repro.train import step as step_lib
    bundle = model_zoo.build(ModelConfig(**c.config["model"]))
    q = QGaLoreConfig(**c.traffic["qgalore"])
    params = step_lib.abstract_state(bundle, q, jnp.bfloat16).params
    return qgalore.leaf_specs(params, q)


@pytest.mark.parametrize("c", cells()[:2] + [spec.load(CELLS[0])],
                         ids=["tiny", "tiny-vl", CELLS[0]])
def test_fused_update_calls_and_bytes_follow_the_program_leaves(c):
    specs = [s for s in _program_specs(c) if s.galore]
    want = sorted((*s.mat_shape, s.rank) for s in specs
                  for _ in range(s.nbatch))
    calls = FU.calls(c.config["model"], c.traffic["qgalore"])
    assert sorted(calls) == want
    total = 0
    for s in specs:
        m, n = s.mat_shape
        low = math.prod(s.low_shape) // s.nbatch
        d, r = s.proj_shape[-2:]
        total += s.nbatch * (2 * m * n + 8 * m * -(-n // 256) + 20 * low
                             + d * r // 2 + 8 * d)
    assert sum(FU.nbytes(x) for x in calls) == total


def test_one_recorded_step_of_int8_matmuls_is_the_model_matmul_term():
    """The INT8 matmul kernel events of one recorded step of the text cell
    (tests/bench/data): forward and dx of every layer matmul and of the
    head, plus the backward scan's recompute of each layer's forward, less
    the down projection (whose recomputed output nothing reads, so XLA
    drops it). In PaLM's terms: 6 FLOPs per matmul parameter per position,
    less the 2 of the weight-gradient dots (XLA's, not this kernel), plus
    the recompute; the head at its padded width."""
    import os
    path = os.path.join(os.path.dirname(__file__), "data",
                        "int8_matmul_step.txt")
    with open(path) as f:
        events = [x for x in f.read().splitlines() if x.startswith("%")]
    c = spec.load(CELLS[0])
    m, t = c.config["model"], c.traffic
    L, D, F = m["num_layers"], m["d_model"], m["d_ff"]
    TB = t["batch"] * t["seq"]
    assert len(events) == 20 * L + 2
    p = MFU.matmul_params(m)
    v_pad = -(-m["vocab_size"] // 256) * 256
    got = sum(I8.cost(e)[0] for e in events)
    assert got == 6 * p["layers"] * TB - 2 * L * F * D * TB \
        + 4 * D * v_pad * TB
