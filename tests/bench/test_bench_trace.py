"""The benchmark's trace reduction: busy-interval union and idle share,
kernel events picked by name, idle gaps set against host spans; on
hand-made events and on a trace recorded here on the CPU."""
import bench_tiny  # noqa: F401  (puts the checkout on sys.path)
import pytest

from bench import spans, trace
from bench.trace import Event


def ev(name, start, dur, device="d0", **stats):
    return Event(name, start, dur, dict(stats), device)


OPS = [ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 0, 10),
       ev("%int8_matmul_t.2 = bf16[256,128]{1,0} custom-call(bf16[256,512])",
          5, 10),
       ev("%get-tuple-element.3 = s8[8,8]{1,0} get-tuple-element("
          "%fused_qgalore_update.4), index=0", 30, 10),
       ev("%fused_qgalore_update.4 = (s8[8,8]{1,0}) custom-call(f32[8,8])",
          50, 20)]
SPANS = [ev("bench.window", 0, 100), ev("bench.dispatch", 14, 20),
         ev("bench.wait", 40, 15), ev("bench.batch", 15, 5)]


def test_union_merges_overlaps_and_touching():
    assert trace.union([(5, 15), (0, 10), (15, 20), (30, 40)]) == \
        [(0, 20), (30, 40)]


def test_busy_and_idle_share():
    # busy: [0, 15] + [30, 40] + [50, 70] = 45 of a 100 ns window
    assert trace.busy_seconds(OPS, 0, 100) == pytest.approx(45e-9)
    assert trace.idle_gaps(OPS, 0, 100) == [(15, 30), (40, 50), (70, 100)]
    # clipping to a window inside the trace
    assert trace.busy_seconds(OPS, 10, 35) == pytest.approx(10e-9)


def test_busy_averages_over_devices():
    ops = OPS + [ev("fusion.9", 0, 100, device="d1")]
    assert trace.busy_seconds(ops, 0, 100) == pytest.approx(72.5e-9)


def test_pick_kernel_events_by_instruction_name():
    assert [e.op for e in trace.pick(OPS, (r"int8_matmul(_t)?",))] == [
        "int8_matmul_t.2"]
    # an op that takes the kernel's result as operand is not the kernel
    assert [e.op for e in trace.pick(OPS, (r"fused_qgalore_update",))] \
        == ["fused_qgalore_update.4"]
    assert trace.pick(OPS, (r"int8_matmul",)) == []
    assert [e.kind for e in OPS] == ["fusion", "int8_matmul_t",
                                     "get-tuple-element",
                                     "fused_qgalore_update"]


def test_idle_gaps_go_to_the_span_covering_most_of_them():
    assert trace.attribute((15, 30), SPANS) == "bench.dispatch"
    assert trace.attribute((40, 50), SPANS) == "bench.wait"
    # the window span itself never names a gap
    assert trace.attribute((70, 100), SPANS) == "(no span)"
    # of spans covering a gap alike, the innermost
    assert trace.attribute((15, 20), SPANS) == "bench.batch"


def test_breakdown_lists_ops_and_gaps():
    tr = trace.Trace(OPS, SPANS)
    b = trace.breakdown(tr, 0, 100)
    assert b["device_ops"][0][1] == pytest.approx(20e-9)
    assert b["idle_gaps"][0] == ["(no span)", pytest.approx(30e-9)]
    assert len(b["idle_gaps"]) == 3


def test_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        return jnp.tanh(x @ x) + 1.0

    x = jnp.ones((128, 128))
    step(x).block_until_ready()
    rec = spans.Spans()
    jax.profiler.start_trace(str(tmp_path))
    with rec("bench.window"):
        for _ in range(3):
            with rec("bench.dispatch"):
                y = step(x)
            with rec("bench.wait"):
                y.block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.load(str(tmp_path))
    lo, hi = tr.window("bench.window")
    names = {s.name for s in tr.spans}
    assert {"bench.window", "bench.dispatch", "bench.wait"} <= names
    ops = trace.clip(tr.device_ops, lo, hi)
    assert ops and all("hlo_op" in e.stats for e in ops)
    assert trace.pick(ops, (r"dot.*",)), {e.kind for e in ops}
    busy = trace.busy_seconds(tr.device_ops, lo, hi)
    assert 0 < busy <= (hi - lo) / 1e9
    b = trace.breakdown(tr, lo, hi)
    assert b["device_ops"] and all(
        g[0] in names | {"(no span)"} for g in b["idle_gaps"])
    assert [n for n, *_ in rec.done].count("bench.dispatch") == 3
