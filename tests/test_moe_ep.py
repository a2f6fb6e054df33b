"""Expert-parallel MoE (manual all_to_all inside shard_map) must match the
plain GSPMD-auto MoE exactly (drop-free regime) — run on a real 8-device
mesh in a subprocess."""
import os

from test_distributed import run_py


def test_moe_ep_matches_plain():
    out = run_py("""
        import functools
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.config import MoEConfig, ModelConfig
        from repro.models import moe as moe_lib

        cfg = ModelConfig(
            name="tiny-moe", family="moe", d_model=32, num_heads=4,
            num_kv_heads=4, vocab_size=128,
            moe=MoEConfig(num_experts=8, top_k=2, expert_ff=16,
                          num_shared_experts=1))
        params = moe_lib.moe_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 4, 32)) * 0.5

        ref, aux_ref = moe_lib.moe_apply(params, x, cfg, dtype=jnp.float32)

        mesh = make_mesh((4, 2), ("data", "model"))

        # expert weights sharded on E over data; router/shared replicated
        got, aux_got = jax.jit(functools.partial(
            moe_lib.moe_ep_sharded, cfg=cfg, mesh=mesh, ep_axis="data",
            dtype=jnp.float32))(params, x)
        err = np.abs(np.asarray(got) - np.asarray(ref)).max()
        scale = max(np.abs(np.asarray(ref)).max(), 1e-3)
        assert err / scale < 2e-3, err / scale
        # aux: per-shard density estimates differ from global (local top-1
        # histograms) — just require same order of magnitude
        assert np.isfinite(float(aux_got))
        print("OK moe_ep", err / scale)
    """)
    assert "OK moe_ep" in out
