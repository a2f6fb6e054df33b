"""``repro.launch.cache`` places JAX's persistent compilation cache in
``$JAX_COMPILATION_CACHE_DIR`` when it is set and in
``<checkout>/.jax_cache`` otherwise. Each case runs in a fresh process:
the cache is process-wide JAX configuration."""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str, env: dict) -> str:
    env = dict(env, PYTHONPATH=os.path.join(ROOT, "src"), JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout


def test_cache_lives_in_env_dir(tmp_path):
    cache = tmp_path / "cache"
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.launch.cache import enable_compile_cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        print(enable_compile_cache())
        print(jax.config.jax_compilation_cache_dir)
        f = jax.jit(lambda x: jnp.sin(x) @ x.T)
        f(jnp.ones((64, 64))).block_until_ready()
    """, dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache)))
    assert out.split() == [str(cache), str(cache)]
    assert any(name.endswith("-cache") for name in os.listdir(cache))


def test_cache_defaults_to_checkout_dir():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    out = _run("""
        import jax
        from repro.launch.cache import enable_compile_cache
        print(enable_compile_cache())
        print(jax.config.jax_compilation_cache_dir)
    """, env)
    want = os.path.join(ROOT, ".jax_cache")
    assert out.split() == [want, want]
