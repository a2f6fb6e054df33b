"""Compile-only checks: the main-path Pallas kernels compile for a TPU v5e
(described, not attached) at llama-1b widths -- d_model 2048, d_ff 5461
(padded to 5632 on the quantized axis), vocab 32000, rank 128 -- at the
prefill/train row tile (256 rows) and the decode row tile (8 rows).

Nothing runs: these catch what interpret mode cannot, such as a block
that is not (8, 128)-aligned, a cast Mosaic lacks, or a kernel that does
not fit the scoped VMEM. The kernels are called with an explicit
``backend="pallas-tpu"`` because ``jax.default_backend()`` is the CPU
here.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.quant import QTensor
from repro.kernels import dispatch, ops

D, FF, FF_PAD, VOCAB, RANK, BLOCK = 2048, 5461, 5632, 32000, 128, 256
BENCH_TESTS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "bench")


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 topology. Described here, never
    at import, so that every test worker collects the same tests."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but not read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(dev, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)


def _int8_weight(dev, k, n_pad, orig):
    return QTensor(_sds(dev, (k, n_pad), jnp.int8),
                   _sds(dev, (k, n_pad // BLOCK), jnp.float32),
                   None, 8, BLOCK, orig, "bfloat16")


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


# (K, padded N, virtual N) of every quantized matmul in a llama-1b layer
# and its head: attention projections, ffn up/gate, ffn down, lm head.
MATMULS = [(D, D, D), (D, FF_PAD, FF), (FF, D, D), (D, VOCAB, VOCAB)]


@pytest.mark.parametrize("rows", [256, 8], ids=["prefill", "decode"])
@pytest.mark.parametrize("k,n_pad,n", MATMULS,
                         ids=["attn", "ffn_up", "ffn_down", "head"])
def test_int8_matmul_compiles(one_chip, rows, k, n_pad, n):
    """Forward ``int8_matmul`` through ``quantized_dense``."""
    w = _int8_weight(one_chip, k, n_pad, n)
    x = _sds(one_chip, (rows, k), jnp.bfloat16)
    text = _compiled_text(
        lambda x, w: ops.quantized_dense(x, w, backend="pallas-tpu"), x, w)
    assert text.count("tpu_custom_call") >= 1


@pytest.mark.parametrize("rows", [256, 8], ids=["prefill", "decode"])
@pytest.mark.parametrize("k,n_pad,n", MATMULS,
                         ids=["attn", "ffn_up", "ffn_down", "head"])
def test_int8_matmul_t_compiles(one_chip, rows, k, n_pad, n):
    """Transposed ``int8_matmul_t``: the tied-head forward
    (``quantized_dense_t``), whose backward adds the plain kernel."""
    w = _int8_weight(one_chip, k, n_pad, n)
    x = _sds(one_chip, (rows, n), jnp.bfloat16)
    g = _sds(one_chip, (rows, k), jnp.bfloat16)

    def fwd_bwd(x, w, g):
        out, vjp = jax.vjp(
            lambda x: ops.quantized_dense_t(x, w, backend="pallas-tpu"), x)
        return out, vjp(g)

    text = _compiled_text(fwd_bwd, x, w, g)
    assert text.count("tpu_custom_call") >= 2


# (K, padded N, virtual N) of every quantized matmul of one training step
# of the benchmark's InternLM2-1.8B cell (hidden 2048, 8 KV heads of 128,
# d_ff 8192, vocab 92553), at its 32 x 256 rows per update.
TRAIN_ROWS = 8192
TRAIN_MATMULS = [(2048, 2048, 2048), (2048, 1024, 1024), (2048, 8192, 8192),
                 (8192, 2048, 2048), (2048, 92672, 92553)]


@pytest.mark.parametrize("k,n_pad,n", TRAIN_MATMULS,
                         ids=["attn_q_o", "attn_kv", "ffn_up", "ffn_down",
                              "head"])
def test_training_shapes_compile_at_measured_tiles(one_chip, k, n_pad, n):
    """``int8_matmul`` (forward) and ``int8_matmul_t`` (dx) through
    ``quantized_dense`` at the training step's shapes, with the measured
    tiles the committed table gives them: a tile that overflows the
    scoped VMEM fails here."""
    for op in ("int8_matmul", "int8_matmul_t"):
        assert dispatch.tuned_lookup(op, (TRAIN_ROWS, k), "bfloat16",
                                     backend="pallas-tpu")[1] == "measured"
    w = _int8_weight(one_chip, k, n_pad, n)
    x = _sds(one_chip, (TRAIN_ROWS, k), jnp.bfloat16)
    g = _sds(one_chip, (TRAIN_ROWS, n), jnp.bfloat16)

    def fwd_bwd(x, w, g):
        out, vjp = jax.vjp(
            lambda x: ops.quantized_dense(x, w, backend="pallas-tpu"), x)
        return out, vjp(g)

    text = _compiled_text(fwd_bwd, x, w, g)
    assert text.count("tpu_custom_call") >= 2


# (side, rows, padded cols, virtual cols) of every GaLore leaf of
# llama-1b: attention (right), ffn down (right), ffn up/gate (left), head
# (left).
FUSED = [("right", D, D, D), ("right", FF, D, D), ("left", D, FF_PAD, FF),
         ("left", D, VOCAB, VOCAB)]


@pytest.mark.parametrize("side,m,n_pad,n", FUSED,
                         ids=["attn", "ffn_down", "ffn_up", "head"])
def test_fused_update_compiles(one_chip, side, m, n_pad, n):
    w = _int8_weight(one_chip, m, n_pad, n)
    d = n if side == "right" else m
    proj = QTensor(_sds(one_chip, (d, RANK // 2), jnp.uint8),
                   _sds(one_chip, (d, 1), jnp.float32),
                   _sds(one_chip, (d, 1), jnp.float32),
                   4, RANK, RANK, "float32")
    low = _sds(one_chip, (m, RANK) if side == "right" else (RANK, n),
               jnp.float32)
    scalar = _sds(one_chip, (), jnp.float32)
    key = _sds(one_chip, (2,), jnp.uint32)

    def update(w, low, m32, v32, proj, count, lr, key):
        return ops.fused_qgalore_update(
            w, low, m32, v32, proj, count, lr, key, side=side, gscale=0.25,
            backend="pallas-tpu")

    text = _compiled_text(update, w, low, low, low, proj, scalar, scalar,
                          key)
    assert text.count("tpu_custom_call") >= 1


def test_plain_step_keeps_kernel_names_and_carries_scopes(one_chip,
                                                          monkeypatch):
    """The whole plain training step of the benchmark's tiny cell on the
    kernels: every Pallas call keeps an instruction name that the
    benchmark's kernel readers pick, and the device scopes reach the XLA
    ops' ``op_name`` metadata (and never a kernel's instruction name)."""
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas-tpu")
    monkeypatch.syspath_prepend(BENCH_TESTS)
    import tiny_step
    from bench import scopes, spec, trace
    from repro.kernels import profile

    fn, args = tiny_step.plain_step(one_chip)
    text = fn.lower(*args).compile().as_text()
    calls = [trace.Event(line.strip().removeprefix("ROOT "), 0, 1)
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    i8 = trace.pick(calls, spec.reader("int8_matmul.ms_per_step").PATTERNS)
    fu = trace.pick(calls, spec.reader("fused_update.ms_per_step").PATTERNS)
    assert calls and len(i8) + len(fu) == len(calls), \
        sorted({e.kind for e in calls})
    assert {re.sub(r"^.*(int8_matmul(_t)?).*$", r"\1", e.kind)
            for e in i8} == {"int8_matmul", "int8_matmul_t"}
    assert fu
    assert not any(s in e.op for e in calls for s in profile.SCOPES)
    names = scopes.op_names(text)
    assert set(profile.SCOPES) <= {scopes.scope_of(n)
                                   for n in names.values()}
