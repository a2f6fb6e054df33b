"""Fused Q-GaLore update kernel: parity vs the unfused three-op path,
backend dispatch, and leaf-batching equivalence."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import QGaLoreConfig, replace
from repro.core import projector, qgalore, quant
from repro.kernels import dispatch, ops

B1, B2, EPS = 0.9, 0.999, 1e-8


def _setup(m, n, r, side, key=0, w_scale=0.02):
    k = jax.random.PRNGKey(key)
    W = jax.random.normal(k, (m, n)) * w_scale
    qt = quant.quantize_blockwise(W, bits=8, symmetric=True)
    d = n if side == "right" else m
    P = jnp.linalg.qr(jax.random.normal(jax.random.fold_in(k, 1), (d, r)))[0]
    qp = projector.quantize_projection(P, 4, 256)
    low_shape = (m, r) if side == "right" else (r, n)
    low = jax.random.normal(jax.random.fold_in(k, 2), low_shape)
    m32 = jax.random.normal(jax.random.fold_in(k, 3), low_shape) * 0.1
    v32 = jnp.abs(jax.random.normal(jax.random.fold_in(k, 4), low_shape)) \
        * 0.01
    return qt, qp, low, m32, v32


def _unfused(qt, qp, low, m32, v32, count, lr, gscale, side, key):
    """The three-op reference composition (Adam → back-project → SR)."""
    m_new = B1 * m32 + (1 - B1) * low
    v_new = B2 * v32 + (1 - B2) * low * low
    c = jnp.float32(count)
    dirn = (m_new / (1 - B1 ** c)) / (
        jnp.sqrt(v_new / (1 - B2 ** c)) + EPS)
    Pd = projector.maybe_dequantize(qp, jnp.float32)
    upd = gscale * projector.project_back(dirn, Pd, side)
    new_qt = quant.requantize_sr(qt, -lr * upd, key)
    return new_qt, m_new, v_new


class TestFusedKernelParity:
    @pytest.mark.parametrize("backend", ["ref", "pallas-interpret"])
    @pytest.mark.parametrize("m,n,r,side", [
        (512, 256, 32, "right"),
        (256, 512, 32, "left"),
        (300, 200, 24, "right"),    # non-multiple-of-block rows/cols
        (200, 300, 24, "left"),
    ])
    def test_matches_unfused_within_one_quantum(self, m, n, r, side,
                                                backend):
        qt, qp, low, m32, v32 = _setup(m, n, r, side)
        count, lr, gscale = 3, 1e-2, 0.25
        key = jax.random.PRNGKey(42)
        want, m_ref, v_ref = _unfused(qt, qp, low, m32, v32, count, lr,
                                      gscale, side, key)
        got, m_got, v_got = ops.fused_qgalore_update(
            qt, low, m32, v32, qp, jnp.float32(count), lr, key, side=side,
            gscale=gscale, backend=backend)
        # same SR randoms -> identical up to fp reassociation flipping a
        # value on a floor boundary, i.e. at most one INT8 quantum
        dq_w = np.asarray(quant.dequantize(want, jnp.float32))
        dq_g = np.asarray(quant.dequantize(got, jnp.float32))
        quantum = float(np.asarray(want.scale).max())
        assert float(np.abs(dq_w - dq_g).max()) <= quantum + 1e-6
        # and nearly all codes agree exactly
        frac = (np.asarray(got.q) == np.asarray(want.q))[:, :n].mean()
        assert frac > 0.999
        np.testing.assert_allclose(np.asarray(m_got), np.asarray(m_ref),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(v_got), np.asarray(v_ref),
                                   rtol=1e-5, atol=1e-7)

    def test_mean_error_across_seeds(self):
        """Acceptance: mean deq error vs unfused stays within SR noise
        across >= 3 seeds."""
        qt, qp, low, m32, v32 = _setup(256, 128, 16, "right")
        count, lr, gscale = 2, 5e-3, 0.25
        errs = []
        for seed in range(4):
            key = jax.random.PRNGKey(seed)
            want, _, _ = _unfused(qt, qp, low, m32, v32, count, lr, gscale,
                                  "right", key)
            got, _, _ = ops.fused_qgalore_update(
                qt, low, m32, v32, qp, jnp.float32(count), lr, key,
                side="right", gscale=gscale, backend="ref")
            dq_w = quant.dequantize(want, jnp.float32)
            dq_g = quant.dequantize(got, jnp.float32)
            errs.append(float(jnp.abs(dq_w - dq_g).mean()))
        quantum = float(np.asarray(qt.scale).mean())
        assert np.mean(errs) < 0.05 * quantum

    def test_int4_zero_point_edges(self):
        """Constant / all-zero projection blocks hit the zero-point and
        eps-clamped-scale edge cases of the INT4 dequant."""
        m, n, r = 128, 256, 16
        qt, _, low, m32, v32 = _setup(m, n, r, "right")
        for P in (jnp.zeros((n, r)),                      # scale -> eps
                  jnp.full((n, r), 0.37),                 # zero-range block
                  jnp.concatenate([jnp.zeros((n, r // 2)),
                                   jnp.ones((n, r // 2))], axis=1)):
            qp = projector.quantize_projection(P, 4, 256)
            key = jax.random.PRNGKey(0)
            want, _, _ = _unfused(qt, qp, low, m32, v32, 1, 1e-2, 0.25,
                                  "right", key)
            for backend in ("ref", "pallas-interpret"):
                got, _, _ = ops.fused_qgalore_update(
                    qt, low, m32, v32, qp, jnp.float32(1), 1e-2, key,
                    side="right", gscale=0.25, backend=backend)
                dq_w = np.asarray(quant.dequantize(want, jnp.float32))
                dq_g = np.asarray(quant.dequantize(got, jnp.float32))
                quantum = float(np.asarray(want.scale).max())
                assert float(np.abs(dq_w - dq_g).max()) <= quantum + 1e-6
                assert np.isfinite(dq_g).all()

    def test_weight_decay(self):
        qt, qp, low, m32, v32 = _setup(256, 128, 16, "right")
        key = jax.random.PRNGKey(7)
        wd, lr, gscale = 0.1, 1e-2, 0.25
        m_new = B1 * m32 + (1 - B1) * low
        v_new = B2 * v32 + (1 - B2) * low * low
        dirn = (m_new / (1 - B1)) / (jnp.sqrt(v_new / (1 - B2)) + EPS)
        Pd = projector.maybe_dequantize(qp, jnp.float32)
        upd = gscale * projector.project_back(dirn, Pd, "right") \
            + wd * quant.dequantize(qt, jnp.float32)
        want = quant.requantize_sr(qt, -lr * upd, key)
        got, _, _ = ops.fused_qgalore_update(
            qt, low, m32, v32, qp, jnp.float32(1), lr, key, side="right",
            gscale=gscale, weight_decay=wd, backend="ref")
        dq_w = np.asarray(quant.dequantize(want, jnp.float32))
        dq_g = np.asarray(quant.dequantize(got, jnp.float32))
        quantum = float(np.asarray(want.scale).max())
        assert float(np.abs(dq_w - dq_g).max()) <= quantum + 1e-6


class TestDispatch:
    def test_registry_has_all_backends(self):
        for op in ("int8_matmul", "int4_matmul", "sr_requant",
                   "blockwise_quant", "flash_attention",
                   "fused_qgalore_update"):
            assert set(dispatch.available_backends(op)) == {
                "pallas-tpu", "pallas-interpret", "ref"}

    def test_default_backend_env_override(self, monkeypatch):
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas-interpret")
        assert dispatch.default_backend("anything") == "pallas-interpret"
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", "bogus")
        with pytest.raises(ValueError):
            dispatch.default_backend()

    def test_platform_default_off_tpu(self, monkeypatch):
        monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
        monkeypatch.delenv("REPRO_PALLAS_COMPILED", raising=False)
        want = "pallas-tpu" if dispatch.platform() == "tpu" else "ref"
        assert dispatch.default_backend("fused_qgalore_update") == want

    def test_fallback_chain(self):
        """There is no fallback chain: a backend the op lacks raises
        instead of quietly running the interpreter or the ref oracle."""
        dispatch.register("_test_only_op", "ref")(lambda: "ref")
        name, fn = dispatch.resolve("_test_only_op", "ref")
        assert name == "ref" and fn() == "ref"
        for want in ("pallas-tpu", "pallas-interpret"):
            with pytest.raises(KeyError, match=want):
                dispatch.resolve("_test_only_op", want)

    def test_tuned_blocks_bucketing(self):
        b = dispatch.tuned_blocks("fused_qgalore_update", (1000, 900),
                                  backend="pallas-tpu")
        assert b == {"bm": 256, "bn": 1024}     # bucketed to (1024, 1024)
        d = dispatch.tuned_blocks("fused_qgalore_update", (64, 64),
                                  backend="pallas-tpu")
        assert d == {"bm": 256, "bn": 512}      # per-op defaults

    def test_ops_interpret_flag_still_works(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (64, 256))
        qt = quant.quantize_blockwise(
            jax.random.normal(jax.random.PRNGKey(1), (256, 512)),
            bits=8, symmetric=True)
        got_i = ops.int8_matmul(x, qt, interpret=True)
        got_r = ops.int8_matmul(x, qt, backend="ref")
        np.testing.assert_allclose(np.asarray(got_i), np.asarray(got_r),
                                   rtol=2e-2, atol=2e-2)


class TestTileFitting:
    """Tuned tiles must divide the (padded) problem dims — the Pallas
    grids floor-divide and would silently drop the remainder."""

    def test_fit_block(self):
        assert dispatch.fit_block(384, 256) == 192
        assert dispatch.fit_block(768, 512, 256) == 256
        assert dispatch.fit_block(192, 128) == 96
        assert dispatch.fit_block(512, 512) == 512
        assert dispatch.fit_block(5461, 512, 128) == 5461
        assert dispatch.fit_block(3200, 256, 128) == 128
        assert dispatch.fit_block(256, 1024) == 256
        # awkward dims fall back to one tile, not a grid of 1-wide tiles
        assert dispatch.fit_block(197, 128) == 197
        # ... but a healthy large divisor is still preferred
        assert dispatch.fit_block(394, 256) == 197

    def test_sr_requant_width_not_multiple_of_default_tile(self):
        # C=768: a multiple of the quant block (256) but not of the
        # default bc tile (512) — previously cols 512..767 were never
        # written on the Pallas backends.
        w = jax.random.normal(jax.random.PRNGKey(0), (128, 768)) * 0.02
        qt = quant.quantize_blockwise(w, bits=8, symmetric=True)
        upd = jax.random.normal(jax.random.PRNGKey(1), (128, 768)) * 1e-3
        key = jax.random.PRNGKey(2)
        got = ops.sr_requant_update(qt, upd, key, interpret=True)
        want = ops.sr_requant_update(qt, upd, key, backend="ref")
        np.testing.assert_array_equal(np.asarray(got.q),
                                      np.asarray(want.q))
        np.testing.assert_allclose(np.asarray(got.scale),
                                   np.asarray(want.scale), rtol=1e-6)

    def test_int8_matmul_rows_not_multiple_of_tuned_tile(self):
        # M=384 pads to 384 (multiple of 128) but not of a 256 row tile.
        x = jax.random.normal(jax.random.PRNGKey(3), (384, 256))
        qt = quant.quantize_blockwise(
            jax.random.normal(jax.random.PRNGKey(4), (256, 768)),
            bits=8, symmetric=True)
        got = ops.int8_matmul(x, qt, interpret=True)
        want = ops.int8_matmul(x, qt, backend="ref")
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-2, atol=2e-2)

    def test_flash_attention_seq_not_multiple_of_default_tile(self):
        # S=192 worked pre-dispatch (kernel default bq=min(256,S)); the
        # 128 table default must be fitted down, not crash.
        B, S, H, d = 1, 192, 2, 32
        q = jax.random.normal(jax.random.PRNGKey(5), (B, S, H, d))
        k = jax.random.normal(jax.random.PRNGKey(6), (B, S, H, d))
        v = jax.random.normal(jax.random.PRNGKey(7), (B, S, H, d))
        got = ops.flash_attention(q, k, v, causal=True, interpret=True)
        want = ops.flash_attention(q, k, v, causal=True, backend="ref")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)


class TestOptimizerIntegration:
    def _params(self):
        k = jax.random.PRNGKey(3)
        params = {
            "stack": jax.random.normal(k, (2, 128, 96)) * 0.02,
            "a": jax.random.normal(jax.random.fold_in(k, 1),
                                   (128, 96)) * 0.02,
            "b": jax.random.normal(jax.random.fold_in(k, 2),
                                   (128, 96)) * 0.02,
            "c": jax.random.normal(jax.random.fold_in(k, 3),
                                   (96, 160)) * 0.02,
        }
        return quant.tree_quantize(params, bits=8, symmetric=True,
                                   predicate=lambda p, l: l.ndim >= 2)

    def _run(self, cfg):
        params = self._params()
        specs = qgalore.leaf_specs(params, cfg)
        state = qgalore.init(params, cfg)
        grads = quant.tree_dequantize(params, jnp.float32)
        step = jax.jit(functools.partial(
            qgalore.apply_updates, cfg=cfg, specs=specs, refresh=False))
        new_params, new_state, _ = step(params, grads, state, lr=1e-2,
                                        rng=jax.random.PRNGKey(11))
        return quant.tree_dequantize(new_params, jnp.float32), new_state

    def test_batching_is_numerically_transparent(self):
        """Grouped-scan execution == per-leaf loop, exactly (same RNG
        folding per original leaf index)."""
        base = QGaLoreConfig(rank=16, min_dim=64, fused_update=False)
        got, _ = self._run(replace(base, batch_leaves=True))
        want, _ = self._run(replace(base, batch_leaves=False))
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                       np.asarray(b)),
            got, want)

    def test_fused_matches_unfused_optimizer_step(self):
        base = QGaLoreConfig(rank=16, min_dim=64, adam_bits=32)
        got, gs = self._run(replace(base, fused_update=True))
        want, ws = self._run(replace(base, fused_update=False))
        flat_g = jax.tree_util.tree_leaves(got)
        flat_w = jax.tree_util.tree_leaves(want)
        for a, b in zip(flat_g, flat_w):
            # same SR draws -> differ by at most one INT8 quantum
            q = float(jnp.abs(jnp.asarray(b)).max()) / 127.0 + 1e-6
            assert float(jnp.abs(a - b).max()) <= q

    def test_fused_with_8bit_moments_descends(self):
        cfg = QGaLoreConfig(rank=16, min_dim=64, adam_bits=8,
                            fused_update=True)
        before = quant.tree_dequantize(self._params(), jnp.float32)
        after, state = self._run(cfg)
        assert int(state.count) == 1
        norm_b = sum(float(jnp.sum(x * x))
                     for x in jax.tree_util.tree_leaves(before))
        norm_a = sum(float(jnp.sum(x * x))
                     for x in jax.tree_util.tree_leaves(after))
        # grads == params, lr>0 -> squared norm must shrink
        assert norm_a < norm_b
        for leaf in jax.tree_util.tree_leaves(after):
            assert np.isfinite(np.asarray(leaf)).all()
