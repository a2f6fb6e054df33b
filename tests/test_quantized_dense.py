"""quantized_dense: forward + gradient parity vs the dequantize-then-einsum
reference across backends, including shapes where M/N/K are not tile
multiples and N is not a multiple of the quant block, plus serve
prefill/decode logits parity on a quantized model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.kernels import dispatch, ops
from repro.models import base, layers, model_zoo
from repro.serve import engine
from repro.train import stack, step as train_step

from test_models_smoke import make_batch

BACKENDS = ["ref", "pallas-interpret"]

# (lead..., K) x (K, N): includes non-tile-multiple M/K and N not a
# multiple of the 256-col quant block (the QTensor pads internally).
SHAPES = [((2, 37), 96, 300),
          ((128,), 512, 256),
          ((5,), 64, 192),
          ((3, 3, 7), 130, 515)]


def _rand(seed, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(jax.random.PRNGKey(seed), shape)
            * scale).astype(dtype)


def _maxerr(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return np.abs(a - b).max() / max(np.abs(b).max(), 1e-6)


class TestForwardParity:
    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize("lead,K,N", SHAPES)
    def test_matches_dequant_einsum(self, backend, lead, K, N):
        x = _rand(0, lead + (K,))
        w = _rand(1, (K, N), scale=0.1)
        qt = quant.quantize_blockwise(w, bits=8, symmetric=True)
        got = ops.quantized_dense(x, qt, dtype=jnp.float32, backend=backend)
        want = jnp.einsum("...d,df->...f", x,
                          quant.dequantize(qt, jnp.float32))
        assert got.shape == lead + (N,)
        assert _maxerr(got, want) < 1e-5

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_transposed_matches(self, backend):
        # tied-embedding head orientation: x (..., D) @ W (V, D)^T
        x = _rand(2, (3, 11, 200))
        w = _rand(3, (97, 200), scale=0.1)
        qt = quant.quantize_blockwise(w, bits=8, symmetric=True)
        got = ops.quantized_dense_t(x, qt, dtype=jnp.float32,
                                    backend=backend)
        want = jnp.einsum("...d,vd->...v", x,
                          quant.dequantize(qt, jnp.float32))
        assert got.shape == (3, 11, 97)
        assert _maxerr(got, want) < 1e-5

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_batched_matches(self, backend):
        x = _rand(4, (4, 9, 64))
        w = _rand(5, (4, 64, 300), scale=0.1)
        qt = quant.quantize_blockwise(w, bits=8, symmetric=True)
        got = ops.quantized_dense_batched(x, qt, dtype=jnp.float32,
                                          backend=backend)
        want = jnp.einsum("ecd,edf->ecf", x,
                          quant.dequantize(qt, jnp.float32))
        assert _maxerr(got, want) < 1e-5

    def test_bf16_activations(self):
        x = _rand(6, (32, 128), jnp.bfloat16)
        w = _rand(7, (128, 256), scale=0.1)
        qt = quant.quantize_blockwise(w, bits=8, symmetric=True)
        got = ops.quantized_dense(x, qt, dtype=jnp.bfloat16, backend="ref")
        want = jnp.einsum("...d,df->...f", x.astype(jnp.float32),
                          quant.dequantize(qt, jnp.float32))
        assert got.dtype == jnp.bfloat16
        assert _maxerr(got, want) < 1e-2


class TestGradientParity:
    @pytest.mark.parametrize("backend", BACKENDS)
    def test_dx_and_dw_match_reference(self, backend):
        x = _rand(8, (2, 17, 96))
        w = _rand(9, (96, 300), scale=0.1)
        qt = quant.quantize_blockwise(w, bits=8, symmetric=True)
        wd = quant.dequantize(qt, jnp.float32)
        g_out = _rand(10, (2, 17, 300))

        def f(shadow, xx):
            wv = quant.QVirtual(qt, shadow)
            out = ops.quantized_dense(xx, wv, dtype=jnp.float32,
                                      backend=backend)
            return jnp.sum(out * g_out)

        wv0 = quant.virtualize(qt)
        dw, dx = jax.grad(f, argnums=(0, 1))(wv0.shadow, x)

        def f_ref(wfull, xx):
            return jnp.sum(jnp.einsum("...d,df->...f", xx, wfull) * g_out)

        dw_ref, dx_ref = jax.grad(f_ref, argnums=(0, 1))(wd, x)
        assert _maxerr(dw, dw_ref) < 1e-5
        assert _maxerr(dx, dx_ref) < 1e-5

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_transposed_grads(self, backend):
        x = _rand(11, (13, 200))
        w = _rand(12, (97, 200), scale=0.1)
        qt = quant.quantize_blockwise(w, bits=8, symmetric=True)
        wd = quant.dequantize(qt, jnp.float32)
        g_out = _rand(13, (13, 97))

        def f(shadow, xx):
            wv = quant.QVirtual(qt, shadow)
            out = ops.quantized_dense_t(xx, wv, dtype=jnp.float32,
                                        backend=backend)
            return jnp.sum(out * g_out)

        dw, dx = jax.grad(f, argnums=(0, 1))(quant.virtualize(qt).shadow, x)

        def f_ref(wfull, xx):
            return jnp.sum(jnp.einsum("...d,vd->...v", xx, wfull) * g_out)

        dw_ref, dx_ref = jax.grad(f_ref, argnums=(0, 1))(wd, x)
        assert _maxerr(dw, dw_ref) < 1e-5
        assert _maxerr(dx, dx_ref) < 1e-5

    def test_embed_lookup_grads(self):
        emb = _rand(14, (96, 300), scale=0.1)
        qt = quant.quantize_blockwise(emb, bits=8, symmetric=True)
        tok = jax.random.randint(jax.random.PRNGKey(15), (2, 9), 0, 96)

        def f(shadow):
            out = layers.embed_lookup(quant.QVirtual(qt, shadow), tok,
                                      jnp.float32)
            return jnp.sum(out ** 2)

        got = jax.grad(f)(quant.virtualize(qt).shadow)
        want = jax.grad(
            lambda w: jnp.sum(jnp.take(w, tok, axis=0) ** 2))(
                quant.dequantize(qt, jnp.float32))
        assert _maxerr(got, want) < 1e-6


class TestDispatch:
    def test_registered_all_backends(self):
        assert set(dispatch.available_backends("quantized_dense")) == \
            {"pallas-tpu", "pallas-interpret", "ref"}
        assert set(dispatch.available_backends("int8_matmul_t")) == \
            {"pallas-tpu", "pallas-interpret", "ref"}

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    def test_profile_records_tile_source(self, monkeypatch, dtype):
        """Under REPRO_PROFILE=1 the forward and dx calls record, at trace
        time, where their tiles came from, tallied over calls."""
        from repro.kernels import profile
        monkeypatch.setenv("REPRO_PROFILE", "1")
        profile.reset()
        x = _rand(18, (8, 256), dtype)
        qt = quant.quantize_blockwise(_rand(19, (256, 512), scale=0.1),
                                      bits=8, symmetric=True)

        def f(x):
            return jnp.sum(ops.quantized_dense(
                x, qt, dtype=dtype, backend="pallas-interpret"))

        jax.make_jaxpr(jax.grad(f))(x)
        jax.make_jaxpr(f)(x)
        snap = profile.snapshot()
        profile.reset()
        assert snap["quantized_dense/fwd"]["meta_calls"] == {
            "backend=pallas-interpret": 2, "tiles=default": 2}
        assert snap["quantized_dense/dx"]["meta"]["tiles"] == "default"
        assert snap["quantized_dense/dx"]["meta_calls"]["tiles=default"] == 1

    def test_dense_fallback_toggle(self, monkeypatch):
        """QUANTIZED_DENSE=False restores the materialize+einsum path and
        produces the same numbers (the dequant reference)."""
        x = _rand(16, (4, 128))
        w = _rand(17, (128, 256), scale=0.1)
        qt = quant.quantize_blockwise(w, bits=8, symmetric=True)
        fast = layers.dense(x, qt, jnp.float32)
        monkeypatch.setattr(layers, "QUANTIZED_DENSE", False)
        slow = layers.dense(x, qt, jnp.float32)
        assert _maxerr(fast, slow) < 1e-5


def _quantize_params(bundle):
    from repro.config import QGaLoreConfig
    params = bundle.init_params(jax.random.PRNGKey(0))
    return train_step.prepare_params(params, QGaLoreConfig(rank=8,
                                                           min_dim=16))


class TestModelIntegration:
    def test_fused_equals_simple_on_quantized_params(self):
        """Both grad paths consume INT8 natively and must agree."""
        bundle = model_zoo.build_arch("llama-60m", smoke=True,
                                      dtype=jnp.float32)
        qparams = _quantize_params(bundle)
        batch = make_batch(bundle)
        (l1, _), g1 = jax.jit(lambda p, b: stack.simple_value_and_grad(
            bundle, p, b))(qparams, batch)
        (l2, _), g2 = jax.jit(lambda p, b: stack.fused_value_and_grad(
            bundle, p, b, {}))(qparams, batch)
        assert abs(float(l1) - float(l2)) < 1e-4 * max(abs(float(l1)), 1.0)
        flat1 = jax.tree_util.tree_flatten_with_path(g1)[0]
        flat2 = {jax.tree_util.keystr(p): l for p, l in
                 jax.tree_util.tree_flatten_with_path(g2)[0]}
        for path, leaf in flat1:
            key = jax.tree_util.keystr(path)
            err = _maxerr(flat2[key], leaf)
            assert err < 5e-3, f"{key}: {err}"

    def test_quantized_grads_match_dequant_reference(self):
        """Grads through quantized_dense == grads of the materialize
        fallback w.r.t. the same virtual weights."""
        bundle = model_zoo.build_arch("llama-60m", smoke=True,
                                      dtype=jnp.float32)
        qparams = _quantize_params(bundle)
        batch = make_batch(bundle)
        (_, _), g_fast = jax.jit(lambda p, b: stack.simple_value_and_grad(
            bundle, p, b))(qparams, batch)
        try:
            layers.QUANTIZED_DENSE = False
            (_, _), g_ref = jax.jit(lambda p, b: stack.simple_value_and_grad(
                bundle, p, b))(qparams, batch)
        finally:
            layers.QUANTIZED_DENSE = True
        flat_ref = {jax.tree_util.keystr(p): l for p, l in
                    jax.tree_util.tree_flatten_with_path(g_ref)[0]}
        for path, leaf in jax.tree_util.tree_flatten_with_path(g_fast)[0]:
            key = jax.tree_util.keystr(path)
            err = _maxerr(leaf, flat_ref[key])
            assert err < 5e-3, f"{key}: {err}"

    @pytest.mark.parametrize("arch", ["zamba2-2.7b", "xlstm-125m",
                                      "qwen3-moe-30b-a3b",
                                      "seamless-m4t-medium",
                                      "deepseek-v3-671b"])
    def test_quantized_families_train_and_serve(self, arch):
        """Every arch family must consume INT8 params natively: stacked
        per-layer vectors (conv_b, dt_bias, A_log, D, gate_bias, norms)
        arrive quantized too — regression for raw-leaf consumption after
        the per-layer tree_dequantize was removed."""
        bundle = model_zoo.build_arch(arch, smoke=True, dtype=jnp.float32)
        qparams = _quantize_params(bundle)
        batch = make_batch(bundle)
        (loss, _), grads = jax.jit(lambda p, b: stack.fused_value_and_grad(
            bundle, p, b, {}))(qparams, batch)
        assert np.isfinite(float(loss))
        for leaf in jax.tree_util.tree_leaves(grads):
            assert np.isfinite(np.asarray(leaf)).all()

        tokens = batch["tokens"]
        prompt = max(tokens.shape[1] // 2, 2)
        b0 = dict(batch)
        b0["tokens"] = tokens[:, :prompt]
        if "labels" in b0:
            b0["labels"] = b0["labels"][:, :prompt]
        prefill = jax.jit(engine.build_prefill(
            bundle, max_len=tokens.shape[1] + 2))
        decode = jax.jit(engine.build_decode(bundle))
        logits, state = prefill(qparams, b0)
        assert np.isfinite(np.asarray(logits)).all()
        logits, _ = decode(qparams, state, tokens[:, prompt: prompt + 1])
        assert np.isfinite(np.asarray(logits)).all()

    @pytest.mark.parametrize("arch", ["llama-60m", "gemma-7b"])
    def test_serve_quantized_logits_parity(self, arch):
        """Prefill + teacher-forced decode on INT8 params reproduces the
        full-forward logits (same quantized params, no per-token dequant);
        gemma-7b covers the tied-embedding head + quantized embed lookup."""
        bundle = model_zoo.build_arch(arch, smoke=True, dtype=jnp.float32)
        qparams = _quantize_params(bundle)
        from repro.config import ShapeCell
        cell = ShapeCell("t", seq_len=12, global_batch=2, kind="train")
        batch = make_batch(bundle, cell)
        tokens = batch["tokens"]
        S = tokens.shape[1]
        prompt = 6

        def full_last_logits(upto):
            b = dict(batch)
            b["tokens"] = tokens[:, :upto]
            if "labels" in b:
                b["labels"] = b["labels"][:, :upto]
            carry, ctx = bundle.embed(qparams, b)
            carry = base.run_segments(bundle, qparams, carry, ctx)
            return bundle.head_logits(qparams, carry)[:, -1, :]

        b0 = dict(batch)
        b0["tokens"] = tokens[:, :prompt]
        if "labels" in b0:
            b0["labels"] = b0["labels"][:, :prompt]
        prefill = jax.jit(engine.build_prefill(bundle, max_len=S + 2))
        decode = jax.jit(engine.build_decode(bundle))
        logits, state = prefill(qparams, b0)
        assert _maxerr(logits[:, -1, :], full_last_logits(prompt)) < 2e-3
        for t in range(prompt, S):
            logits, state = decode(qparams, state, tokens[:, t: t + 1])
            err = _maxerr(logits[:, -1, :], full_last_logits(t + 1))
            assert err < 5e-3, f"{arch} step {t}: {err}"
