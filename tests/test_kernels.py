"""Per-kernel allclose vs the ref.py oracles: shape/dtype sweeps +
hypothesis property tests (interpret mode on CPU)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core import quant
from repro.kernels import ops, ref
from repro.kernels.blockwise_quant import blockwise_quant
from repro.kernels.flash_attention import flash_attention
from repro.kernels.int4_matmul import int4_matmul
from repro.kernels.int8_matmul import int8_matmul, int8_matmul_t
from repro.kernels.sr_requant import sr_requant


def _rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(jax.random.PRNGKey(key), shape) * scale) \
        .astype(dtype)


class TestInt8Matmul:
    @pytest.mark.parametrize("M,K,N", [(128, 512, 256), (256, 1024, 512),
                                       (128, 256, 768)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, M, K, N, dtype):
        x = _rand(0, (M, K), dtype)
        w = _rand(1, (K, N))
        qt = quant.quantize_blockwise(w, bits=8, symmetric=True)
        got = int8_matmul(x.astype(jnp.float32), qt.q, qt.scale,
                          block=qt.block, interpret=True)
        want = ref.int8_matmul_ref(x, qt.q, qt.scale, qt.block)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2)

    def test_ops_wrapper_matches_dense(self):
        x = _rand(2, (3, 7, 256))
        w = _rand(3, (256, 512))
        qt = quant.quantize_blockwise(w, bits=8, symmetric=True)
        got = ops.int8_matmul(x, qt, interpret=True)
        want = x.reshape(-1, 256) @ quant.dequantize(qt, jnp.float32)
        np.testing.assert_allclose(
            np.asarray(got).reshape(-1, 512), np.asarray(want),
            rtol=5e-2, atol=5e-2)


class TestInt8TrainingTiles:
    """Both INT8 kernels at the training step's tile shapes: ``bn`` 1024
    (four scale groups per tile), ``bk`` 1024 and 2048, under bf16 and f32
    activations. Weights at 1/sqrt(K), so outputs are O(1) as in a
    model."""

    M, K, N = 64, 2048, 2048

    def _operands(self, op, dtype):
        w = _rand(31, (self.K, self.N), scale=self.K ** -0.5)
        qt = quant.quantize_blockwise(w, bits=8, symmetric=True)
        rows = self.K if op == "fwd" else self.N
        return _rand(30, (self.M, rows), dtype), qt

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("bk", [1024, 2048])
    @pytest.mark.parametrize("op", ["fwd", "dx"])
    def test_matches_ref(self, op, bk, dtype):
        a, qt = self._operands(op, dtype)
        kern, oracle = ((int8_matmul, ref.int8_matmul_ref) if op == "fwd"
                        else (int8_matmul_t, ref.int8_matmul_t_ref))
        got = kern(a, qt.q, qt.scale, block=qt.block, bm=self.M, bn=1024,
                   bk=bk, interpret=True)
        assert got.dtype == dtype
        want = oracle(a, qt.q, qt.scale, qt.block)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2)

    def test_dx_bf16_products_exact(self):
        """Products of bf16 ``g`` and INT8 codes are exact in f32, so
        ``int8_matmul_t`` meets the f32 oracle at rtol 1e-4 up to the
        output's one rounding to bf16: each output is the bf16 rounding of
        a value within 1e-4 of the oracle's."""
        g, qt = self._operands("dx", jnp.bfloat16)
        got = int8_matmul_t(g, qt.q, qt.scale, block=qt.block, bm=self.M,
                            bn=1024, bk=1024, interpret=True)
        want = np.asarray(ref.int8_matmul_t_ref(g, qt.q, qt.scale,
                                                qt.block))
        tol = 1e-4 * np.abs(want) + 1e-4 * np.abs(want).max()
        lo = jnp.asarray(want - tol).astype(jnp.bfloat16)
        hi = jnp.asarray(want + tol).astype(jnp.bfloat16)
        assert bool(jnp.all((got >= lo) & (got <= hi)))


class TestInt4Matmul:
    @pytest.mark.parametrize("M,K,R", [(128, 512, 128), (256, 1024, 64)])
    def test_matches_ref(self, M, K, R):
        g = _rand(4, (M, K))
        P = _rand(5, (K, R), scale=0.1)
        qt = quant.quantize_blockwise(P, bits=4, block=min(128, R),
                                      symmetric=False)
        got = int4_matmul(g, qt.q, qt.scale, qt.zero, block=qt.block,
                          interpret=True)
        want = ref.int4_matmul_ref(g, qt.q, qt.scale, qt.zero, qt.block)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-2, atol=2e-2)

    def test_projection_close_to_fp(self):
        """INT4-projected gradient ≈ FP projection (paper Fig. 3 claim)."""
        g = _rand(6, (256, 512))
        P = jnp.linalg.qr(_rand(7, (512, 128)))[0]
        qt = quant.quantize_blockwise(P, bits=4, block=128, symmetric=False)
        got = ops.int4_project(g, qt, interpret=True)
        want = g @ P
        cos = float(jnp.sum(got * want) /
                    (jnp.linalg.norm(got) * jnp.linalg.norm(want)))
        assert cos > 0.99


class TestSRRequant:
    def test_matches_ref_given_same_randoms(self):
        R, C = 128, 512
        w = _rand(8, (R, C))
        qt = quant.quantize_blockwise(w, bits=8, symmetric=True)
        upd = _rand(9, (R, C), scale=0.01)
        u01 = jax.random.uniform(jax.random.PRNGKey(10), (R, C))
        qn, sn = sr_requant(qt.q, qt.scale, upd, u01, block=256,
                            interpret=True)
        qr, sr_ = ref.sr_requant_ref(qt.q, qt.scale, upd, u01, 256)
        np.testing.assert_array_equal(np.asarray(qn), np.asarray(qr))
        np.testing.assert_allclose(np.asarray(sn), np.asarray(sr_),
                                   rtol=1e-6)

    def test_unbiased_expectation(self):
        """E[deq(SR(W + u))] == deq(W) + u across many keys."""
        R, C = 8, 256
        w = _rand(11, (R, C))
        qt = quant.quantize_blockwise(w, bits=8, symmetric=True)
        upd = jnp.full((R, C), 1e-4)
        outs = []
        for i in range(64):
            new = ops.sr_requant_update(qt, upd, jax.random.PRNGKey(i),
                                        interpret=True)
            outs.append(np.asarray(quant.dequantize(new, jnp.float32)))
        mean = np.mean(outs, axis=0)
        target = np.asarray(quant.dequantize(qt, jnp.float32)) + 1e-4
        scale_typ = float(np.asarray(qt.scale).mean())
        assert np.abs(mean - target).mean() < 0.3 * scale_typ


class TestBlockwiseQuant:
    @pytest.mark.parametrize("R,C", [(128, 512), (64, 256), (256, 1024)])
    def test_matches_ref(self, R, C):
        x = _rand(12, (R, C), scale=3.0)
        q, s = blockwise_quant(x, interpret=True)
        qr, sr_ = ref.blockwise_quant_ref(x, 256)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
        np.testing.assert_allclose(np.asarray(s), np.asarray(sr_),
                                   rtol=1e-6)

    @given(scale=st.floats(0.01, 100.0))
    @settings(max_examples=8, deadline=None)
    def test_roundtrip_bounded(self, scale):
        x = _rand(13, (32, 256), scale=scale)
        q, s = blockwise_quant(x, interpret=True)
        back = np.asarray(q, np.float32).reshape(32, 1, 256) \
            * np.asarray(s)[..., None]
        err = np.abs(back.reshape(32, 256) - np.asarray(x))
        assert err.max() <= np.asarray(s).max() * 0.5 + 1e-6


class TestFlashAttention:
    @pytest.mark.parametrize("S", [128, 512])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_ref(self, S, causal):
        B, H, d = 2, 3, 64
        q = _rand(14, (B, S, H, d))
        k = _rand(15, (B, S, H, d))
        v = _rand(16, (B, S, H, d))
        got = flash_attention(q, k, v, causal=causal, bq=128, bkv=128,
                              interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)

    def test_mla_style_dv_differs(self):
        B, S, H, d, dv = 1, 128, 2, 48, 32
        q = _rand(17, (B, S, H, d))
        k = _rand(18, (B, S, H, d))
        v = _rand(19, (B, S, H, dv))
        got = flash_attention(q, k, v, causal=True, bq=64, bkv=64,
                              interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)


class TestFusedEpilogueServingShapes:
    """The scale-in-epilogue kernels at the shapes serving actually hits:
    non-tile-multiple M (1-row decode, ragged 9-row), prime K, and an N
    that is not a quant-block multiple (llama-60m d_ff=1376 → padded
    column tail + partially-real last scale group). Parity is against the
    plain dequantize-then-matmul on BOTH the ref oracle backend and the
    Pallas interpreter."""

    SHAPES = [(1, 512, 1376), (9, 67, 160), (256, 256, 1376)]

    @pytest.mark.parametrize("backend", ["ref", "pallas-interpret"])
    @pytest.mark.parametrize("M,K,N", SHAPES)
    def test_forward_matches_dequant(self, backend, M, K, N):
        x = _rand(20, (M, K))
        w = _rand(21, (K, N), scale=0.5)
        qt = quant.quantize_blockwise(w, bits=8, symmetric=True)
        got = ops.quantized_dense(x, qt, dtype=jnp.float32,
                                  backend=backend)
        want = x @ quant.dequantize(qt, jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-2, atol=2e-2)

    @pytest.mark.parametrize("backend", ["ref", "pallas-interpret"])
    def test_dx_grad_plain_qtensor(self, backend):
        """dL/dx through the no-shadow custom VJP (plain-QTensor serving
        weights) streams the INT8 blocks through the transposed kernel
        and must match autodiff of the dequant einsum."""
        M, K, N = 9, 256, 1376
        x = _rand(22, (M, K))
        w = _rand(23, (K, N), scale=0.5)
        qt = quant.quantize_blockwise(w, bits=8, symmetric=True)

        def f_q(a):
            out = ops.quantized_dense(a, qt, dtype=jnp.float32,
                                      backend=backend)
            return jnp.sum(out * out)

        wd = quant.dequantize(qt, jnp.float32)

        def f_d(a):
            out = a @ wd
            return jnp.sum(out * out)

        gq = jax.grad(f_q)(x)
        gd = jax.grad(f_d)(x)
        scale = max(float(jnp.abs(gd).max()), 1.0)
        np.testing.assert_allclose(np.asarray(gq) / scale,
                                   np.asarray(gd) / scale,
                                   rtol=2e-2, atol=2e-2)

    @pytest.mark.parametrize("backend", ["ref", "pallas-interpret"])
    def test_qtensor_matches_qvirtual_bitwise(self, backend):
        """Serving (plain QTensor, no-shadow core) and training
        (QVirtual, shadow core) must produce bit-identical forwards —
        both route through the same _i8_call."""
        M, K, N = 9, 128, 352
        x = _rand(24, (M, K))
        w = _rand(25, (K, N), scale=0.5)
        qt = quant.quantize_blockwise(w, bits=8, symmetric=True)
        out_q = ops.quantized_dense(x, qt, dtype=jnp.float32,
                                    backend=backend)
        out_v = ops.quantized_dense(x, quant.virtualize(qt),
                                    dtype=jnp.float32, backend=backend)
        assert np.array_equal(np.asarray(out_q), np.asarray(out_v))

    @pytest.mark.parametrize("backend", ["ref", "pallas-interpret"])
    def test_transposed_head_matches_dequant(self, backend):
        """quantized_dense_t (tied-embedding head) at a ragged M and a
        vocab that is not a quant-block multiple."""
        M, V, D = 9, 160, 96
        x = _rand(26, (M, D))
        w = _rand(27, (V, D), scale=0.5)
        qt = quant.quantize_blockwise(w, bits=8, symmetric=True)
        got = ops.quantized_dense_t(x, qt, dtype=jnp.float32,
                                    backend=backend)
        want = x @ quant.dequantize(qt, jnp.float32).T
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-2, atol=2e-2)
