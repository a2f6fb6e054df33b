"""The one generator of training traffic: batches from a traffic file's
parameters and the run's seed.

A training mix is ``batch`` rows of ``seq`` positions, of which the first
``prefix`` are image-prefix embeddings (the vision tower's output,
N(0, prefix_std^2)) and the rest token ids, uniform over the vocabulary.
Every step's batch is a fresh draw: rows never repeat within a run, and
the same seed gives the same batches.
"""
from __future__ import annotations


def text_len(traffic: dict) -> int:
    return traffic["seq"] - traffic.get("prefix", 0)


def batch_fn(traffic: dict, vocab: int, d_model: int):
    """A jitted ``(key, step) -> batch`` for this mix."""
    import jax
    import jax.numpy as jnp

    B, S, P = traffic["batch"], text_len(traffic), traffic.get("prefix", 0)

    @jax.jit
    def make(key, step):
        k = jax.random.fold_in(key, step)
        kt, kp = jax.random.split(k)
        tokens = jax.random.randint(kt, (B, S), 0, vocab, jnp.int32)
        out = {"tokens": tokens, "labels": tokens}
        if P:
            out["patch_embeds"] = (
                jax.random.normal(kp, (B, P, d_model), jnp.float32)
                * traffic["prefix_std"]).astype(jnp.bfloat16)
        return out

    return make

