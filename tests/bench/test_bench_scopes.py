"""Device time per named scope (``bench/scopes.py``): the innermost scope
names an operation, the kernels and envelopes are left out, the metrics
partition the operations' time; the program's plain step carries the
scopes the metrics read; program spans name idle gaps."""
import os
import re

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)
import pytest

from bench import scopes, spec, trace
from bench.trace import Event

KERNELS = ("int8_matmul.ms_per_step", "fused_update.ms_per_step")


def ev(name, start, dur):
    return Event(name, start, dur, {}, "/device:TPU:0")


def ctx(ops, steps=2):
    return trace.Context(trace=trace.Trace(ops, []), lo=0, hi=10**6,
                         window={"steps": steps, "seconds": 1.0,
                                 "tokens": 0},
                         cell=spec.load("internlm2-1.8b.pretrain-s256"),
                         peaks=None, counters={})


@pytest.mark.parametrize("op_name,scope", [
    ("jit(step)/while/body/closed_call/jvp(repro.layer)/repro.attention/"
     "bqhgd,bkhd->bhgqk/dot_general", "repro.attention"),
    ("jit(step)/while/body/closed_call/transpose(jvp(repro.layer))/"
     "repro.wgrad/mk,mn->kn/dot_general", "repro.wgrad"),
    ("jit(step)/while/body/closed_call/transpose(jvp(repro.layer))/"
     "repro.attention/bhgqk,bkhd->bqhgd/dot_general", "repro.attention"),
    ("jit(step)/while/body/checkpoint/rematted_computation/repro.layer/mul",
     "repro.layer"),
    ("jit(step)/transpose(jvp(repro.head_loss))/log_softmax/sub",
     "repro.head_loss"),
    ("jit(step)/repro.optimizer/while/body/closed_call/repro.sr_randoms/"
     "random_bits", "repro.sr_randoms"),
    ("jit(step)/repro.optimizer/add", "repro.optimizer"),
    ("jit(step)/jvp(embed)/gather", "unscoped"),
    ("", "unscoped"),
], ids=["attention-recompute", "wgrad-in-transpose", "attention-transposed",
        "remat", "head-loss-backward", "sr-in-optimizer", "optimizer",
        "no-scope", "no-op-name"])
def test_innermost_scope_names_the_op(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_op_names_from_compiled_text():
    text = "\n".join([
        "HloModule jit_f, is_scheduled=true",
        "%fused_computation (param_0.1: f32[8]) -> f32[8] {",
        "  %param_0.1 = f32[8]{0} parameter(0)",
        "  ROOT %tanh.0 = f32[8]{0} tanh(%param_0.1), "
        'metadata={op_name="jit(f)/repro.attention/tanh"}',
        "}",
        "ENTRY %main.1 (x.1: f32[8,8]) -> f32[8] {",
        '  %x.1 = f32[8,8]{1,0} parameter(0), metadata={op_name="x"}',
        "  %dot.2 = f32[8]{0} dot(%x.1, %x.1), metadata={op_name="
        '"jit(f)/repro.wgrad/dot_general" stack_frame_id=3}',
        "  ROOT %tanh_fusion = f32[8]{0} fusion(%dot.2), kind=kLoop, "
        "calls=%fused_computation, "
        'metadata={op_name="jit(f)/repro.attention/tanh"}',
        "}"])
    names = scopes.op_names(text)
    assert names["dot.2"] == "jit(f)/repro.wgrad/dot_general"
    assert names["tanh_fusion"] == "jit(f)/repro.attention/tanh"
    assert names["tanh.0"] == "jit(f)/repro.attention/tanh"
    assert "param_0.1" not in names
    # a TPU event names the op by its instruction text
    e = ev("%dot.2 = f32[8]{0:T(128)} dot(f32[8,8]{1,0} %x.1)", 0, 1)
    assert names[e.op] == "jit(f)/repro.wgrad/dot_general"


# one step of hand-made device ops (instruction -> op_name): the two
# measured kernels, whose op_names carry scopes too; a loop envelope around
# scoped ops; scoped and unscoped XLA work; an op of another program
NAMES = {
    "int8_matmul.4": "jit(s)/while/body/repro.layer/jit(int8_matmul)/"
                     "pallas_call",
    "transpose_jvp_jit_int8_matmul_t___.7":
        "jit(s)/transpose(jvp(jit(int8_matmul_t)))/pallas_call",
    "fused_qgalore_update.2": "jit(s)/repro.optimizer/"
                              "jit(fused_qgalore_update)/pallas_call",
    "while.3": "jit(s)/repro.optimizer/while",
    "fusion.5": "jit(s)/while/body/jvp(repro.layer)/repro.attention/exp",
    "convolution.6": "jit(s)/while/body/transpose(jvp(repro.layer))/"
                     "repro.wgrad/dot_general",
    "fusion.8": "jit(s)/while/body/transpose(jvp(repro.layer))/mul",
    "fusion.9": "jit(s)/transpose(jvp(repro.head_loss))/sub",
    "fusion.10": "jit(s)/repro.optimizer/add",
    "fusion.11": "jit(s)/repro.optimizer/repro.sr_randoms/random_bits",
    "gather.12": "jit(s)/jvp(embed)/gather",
}
STEP = [
    ev("%int8_matmul.4 = bf16[256,128] custom-call()", 0, 100),
    ev("%transpose_jvp_jit_int8_matmul_t___.7 = bf16[256,128] custom-call()",
       100, 60),
    ev("%fused_qgalore_update.2 = (s8[8,8]) custom-call()", 160, 40),
    ev("%while.3 = (s32[]) while()", 200, 90),
    ev("%fusion.5 = f32[8] fusion()", 200, 30),
    ev("%convolution.6 = f32[8,8] convolution()", 230, 25),
    ev("%fusion.8 = f32[8] fusion()", 255, 11),
    ev("%fusion.9 = f32[8] fusion()", 300, 7),
    ev("%fusion.10 = f32[8] fusion()", 307, 5),
    ev("%fusion.11 = f32[8] fusion()", 312, 13),
    ev("%gather.12 = bf16[8] gather()", 325, 3),
    ev("%copy.99 = s32[4] copy()", 328, 2),
]


def two_steps():
    return STEP + [Event(e.name, e.start + 1000, e.dur, e.stats, e.device)
                   for e in STEP]


def test_kernels_and_envelopes_are_left_out():
    by = scopes.seconds_by_scope(STEP, NAMES)
    assert by == pytest.approx({
        "repro.attention": 30e-9, "repro.wgrad": 25e-9,
        "repro.layer": 11e-9, "repro.head_loss": 7e-9,
        "repro.optimizer": 5e-9, "repro.sr_randoms": 13e-9,
        "unscoped": 5e-9})


def test_scope_metrics_per_step():
    got = scopes.ms_per_step(two_steps(), NAMES, steps=2)
    assert got == pytest.approx({
        "attention.ms_per_step": 30e-6, "wgrad.ms_per_step": 25e-6,
        "layer_other.ms_per_step": 11e-6, "head_loss.ms_per_step": 7e-6,
        "optimizer.ms_per_step": 5e-6, "sr_randoms.ms_per_step": 13e-6,
        "unscoped.ms_per_step": 5e-6})


def test_seven_scopes_and_two_kernels_partition_the_op_time(monkeypatch):
    """Through the metric files themselves: the seven scope readers and
    the two kernel readers add up to the summed time of the operations
    that are not envelopes."""
    monkeypatch.setattr(scopes, "names_for", lambda ctx: NAMES)
    ops = two_steps()
    c = ctx(ops)
    got = {m: spec.reader(m).read(c) for m in scopes.METRICS}
    assert got == pytest.approx(scopes.ms_per_step(ops, NAMES, 2))
    total = sum(got.values()) + sum(spec.reader(n).read(c) for n in KERNELS)
    busy = sum(e.dur for e in ops if e.kind not in trace.ENVELOPES)
    assert total == pytest.approx(1e3 * busy / 1e9 / 2)


def test_a_program_without_scopes_reads_nothing(monkeypatch):
    """The parent of the scopes: no op names a scope (or there is no map),
    so the scope metrics are left out, and the kernels read as before."""
    plain = {k: "jit(s)/add" for k in NAMES}
    assert scopes.ms_per_step(two_steps(), plain, 2) is None
    assert scopes.ms_per_step(two_steps(), {}, 2) is None
    c = ctx(two_steps())
    for names in (plain, {}, None):
        monkeypatch.setattr(scopes, "names_for", lambda ctx: names)
        assert all(spec.reader(m).read(c) is None for m in scopes.METRICS)
    assert spec.reader("int8_matmul.ms_per_step").read(c) == \
        pytest.approx(160e-6)
    assert spec.reader("fused_update.ms_per_step").read(c) == \
        pytest.approx(40e-6)


def test_recorded_step_reads_as_before():
    """The recorded TPU kernel events of one step of the text cell: the
    kernel readers read them whole, and with no scope in them the scope
    reduction reads nothing."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "int8_matmul_step.txt")
    with open(path) as f:
        names = [x for x in f.read().splitlines() if x.startswith("%")]
    ops = [ev(n, 100 * i, 90) for i, n in enumerate(names)]
    tr = trace.Trace(ops, [Event("bench.window", 0, 100 * len(names)),
                           Event("repro.dispatch", 5, 10)])
    lo, hi = tr.window("bench.window")
    c = trace.Context(trace=tr, lo=lo, hi=hi,
                      window={"steps": 1, "seconds": 1.0, "tokens": 0},
                      cell=spec.load("internlm2-1.8b.pretrain-s256"),
                      peaks=None, counters={})
    assert spec.reader("int8_matmul.ms_per_step").read(c) == \
        pytest.approx(1e3 * 90 * len(names) / 1e9)
    assert spec.reader("fused_update.ms_per_step").read(c) is None
    assert scopes.seconds_by_scope(c.ops(), {}) == {}
    assert scopes.ms_per_step(c.ops(), {}, 1) is None


def test_program_span_inside_dispatch_names_the_gap():
    """A chip step's idle gap: the device waits from the end of the last
    step's program, through the host's preparation of the next (its
    inputs, the controller's masks) and into the dispatch, until the
    program starts. The benchmark's ``bench.dispatch`` covers all of it
    and names it; of the program's spans, which cover it but for the
    moments between them, ``repro.dispatch`` covers most."""
    spans = [Event("bench.window", 0, 1000), Event("bench.wait", 0, 100),
             Event("bench.dispatch", 100, 70), Event("repro.batch", 101, 27),
             Event("repro.controller", 129, 1),
             Event("repro.dispatch", 131, 38), Event("bench.wait", 170, 800)]
    gap = (98, 160)
    assert trace.attribute(gap, spans) == "bench.dispatch"
    assert trace.attribute(gap, [s for s in spans if s.name.startswith(
        "repro.")]) == "repro.dispatch"
    # host preparation per step: the program's batch, controller and
    # dispatch spans inside the window
    assert scopes.span_ms_per_step(spans, scopes.HOST_PREP, 0, 1000, 2) == \
        pytest.approx(1e3 * 66 / 1e9 / 2)
    assert scopes.span_ms_per_step(spans[:1], scopes.HOST_PREP, 0, 1000,
                                   2) is None


def test_compiler_made_ops_take_the_scope_of_what_they_move():
    text = "\n".join([
        "HloModule jit_f, is_scheduled=true",
        "%fused_computation.3 (param_0.1: f32[8]) -> f32[8] {",
        "  %param_0.1 = f32[8]{0} parameter(0)",
        "  ROOT %exp.1 = f32[8]{0} exponential(%param_0.1), "
        'metadata={op_name="jit(f)/repro.attention/exp"}',
        "}",
        "ENTRY %main.1 (x.1: f32[8]) -> (f32[8], f32[8], f32[8]) {",
        "  %x.1 = f32[8]{0} parameter(0)",
        "  %copy.2 = f32[8]{0:T(128)} copy(%x.1)",
        "  %fusion.4 = f32[8]{0:T(128)S(1)} fusion(%copy.2), kind=kLoop, "
        "calls=%fused_computation.3",
        "  %add.5 = f32[8]{0} add(%fusion.4, %x.1), "
        'metadata={op_name="jit(f)/repro.wgrad/add"}',
        "  %tuple.6 = (f32[8]{0}, f32[8]{0}) tuple(%add.5, %fusion.4)",
        "  %get-tuple-element.7 = f32[8]{0} get-tuple-element(%tuple.6), "
        "index=0",
        "  %copy-start.8 = (f32[8]{0:T(128)}, f32[8]{0:T(128)S(1)}, "
        "u32[]{:S(2)}) copy-start(%get-tuple-element.7)",
        "  %copy-done.9 = f32[8]{0:T(128)S(1)} copy-done(%copy-start.8)",
        "  ROOT %tuple.10 = (f32[8], f32[8], f32[8]) tuple(%copy-done.9, "
        "%fusion.4, %copy.2)",
        "}"])
    names = scopes.op_names(text)
    # a fusion without an op_name: its computation's root
    assert names["fusion.4"] == "jit(f)/repro.attention/exp"
    # an asynchronous copy: the value it moves, through the tuple
    assert names["copy-start.8"] == names["copy-done.9"] == \
        "jit(f)/repro.wgrad/add"
    # a copy of an argument moves nothing a scope made
    assert "copy.2" not in names and "x.1" not in names


def test_metric_scopes_are_the_program_scopes():
    """Each scope metric's file reads the scope the reduction gives it,
    the files' scopes are the program's, and BENCHMARK.json lists every
    one: a refactor that drops a scope fails here, not on the chip."""
    import json

    from repro.kernels import profile
    files = {m: spec.reader(m).SCOPE for m in scopes.METRICS}
    assert files == scopes.METRICS
    assert set(files.values()) - {scopes.UNSCOPED} == set(profile.SCOPES)
    assert all(s.startswith("repro.") and scopes.scope_of(s) == s
               for s in profile.SCOPES)
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"] for m in json.load(f)["per_layer"]}
    assert set(scopes.METRICS) <= listed


@pytest.fixture
def compile_cache(tmp_path):
    """A persistent compilation cache of the test's own, which keeps every
    program, as ``bench/run.py`` sets one up on the chip."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    jax.config.update(keys[0], str(tmp_path / "jax_cache"))
    jax.config.update(keys[1], 0)
    jax.config.update(keys[2], 0)
    compilation_cache.reset_cache()
    yield
    for k, v in was.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_traced_run_reads_every_scope(monkeypatch, compile_cache):
    """A traced run of the tiny cell on the CPU: the scope readers read
    every scope, and with the two kernel readers they add up to the
    operations' time per step; the plain step built again after the
    window is answered by the persistent cache with the program the
    window ran."""
    import bench_tiny
    from bench.runners import train as runner

    seen = {}
    load, spb = trace.load, runner.TrainCell.step_program_bytes

    def keep_trace(d):
        seen["trace"] = load(d)
        return seen["trace"]

    def keep_text(self):
        fn, args = self.step_args["plain"]
        seen["text"] = fn.lower(*args).compile().as_text()
        return spb(self)

    monkeypatch.setattr(trace, "load", keep_trace)
    monkeypatch.setattr(runner.TrainCell, "step_program_bytes", keep_text)
    c = bench_tiny.cell()
    res = bench_tiny.run(c, traced=True)
    assert res["correct"], res["check"]
    assert scopes.step_op_names(c) == scopes.op_names(seen["text"])
    got = {m: res["metrics"][m]["value"] for m in scopes.METRICS}
    assert all(v > 0 for v in got.values()), got
    tr = seen["trace"]
    lo, hi = tr.window("bench.window")
    ops = trace.clip(tr.device_ops, lo, hi)
    busy = sum(e.dur for e in ops if e.kind not in trace.ENVELOPES)
    kernels = sum(res["metrics"].get(n, {}).get("value", 0.0)
                  for n in KERNELS)
    assert sum(got.values()) + kernels == pytest.approx(
        1e3 * busy / 1e9 / res["attempted"], rel=1e-9)


@pytest.fixture(scope="module")
def plain_step_text():
    import tiny_step
    fn, args = tiny_step.plain_step()
    return fn.lower(*args).compile().as_text()


def _ops(text, kinds):
    out = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = .*? (\w+)\(", line)
        if m and m.group(1) in kinds:
            name = re.search(r'op_name="([^"]*)"', line)
            out.append(name.group(1) if name else "")
    return out


def test_every_dot_of_the_plain_step_is_scoped(plain_step_text):
    """Compiled on the CPU, where the kernels run as XLA dots (the ``ref``
    backend): every dot and convolution carries a declared scope, and
    attention's dots, forward, recompute and transposed, carry
    ``repro.attention``."""
    from repro.kernels import profile
    dots = _ops(plain_step_text, ("dot", "convolution"))
    assert dots
    assert all(scopes.scope_of(n) in profile.SCOPES for n in dots), \
        [n for n in dots if scopes.scope_of(n) not in profile.SCOPES]
    attn = [n for n in dots if "bhgqk" in n]
    assert attn and all(scopes.scope_of(n) == "repro.attention"
                        for n in attn)
    assert any(n.count("repro.attention") and "transpose(" in n
               for n in attn)
    assert any("jvp(" in n and "transpose(" not in n for n in attn)
    used = {scopes.scope_of(n)
            for n in scopes.op_names(plain_step_text).values()}
    assert set(profile.SCOPES) <= used
