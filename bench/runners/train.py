"""Training cells: Q-GaLore steps of the program's ``Trainer`` on one chip.

Set-up builds one ``Trainer`` on weights the benchmark makes from the seed,
feeds it the benchmark's batches, and drives it through its first steps
(step 0 refreshes every projection; steps 1 and 2 are plain) on the path
``Trainer.run`` takes. What the check needs of those steps is copied to
the host. The window then drives the same object through plain steps for
``--seconds``. After the window the program's state is freed and the
plain reference (``bench/reference/<name>.py``) runs the first steps
again from the same weights and batches.

The program has no public per-step entry yet, so the benchmark calls
``Trainer._run_one``, the body of ``Trainer.run``'s loop, and reads each
step's loss as ``Trainer.run`` does. It replaces the trainer module's
batch source with its own generator (``bench/traffic_gen.py``), and its
learning-rate schedule with the traffic's: where the traffic names a
``learning_rate_step0``, step 0 runs at that rate.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import os
import sys
import time
import types
from typing import Dict, Optional

import numpy as np

from bench import seeds, spans, traffic_gen
from bench.compare import STEPS as CHECK_STEPS

# the main-path kernel ops, as the program's trace-time profile names them
MAIN_PATH_OPS = ("quantized_dense/fwd", "quantized_dense/dx",
                 "fused_qgalore_update")


def half(batch: dict) -> dict:
    """Half of a batch: half the rows or, of one row, the first half of
    its text (causal attention leaves those positions as they were)."""
    rows = batch["tokens"].shape[0]
    if rows > 1:
        return {k: v[: rows // 2] for k, v in batch.items()}
    text = batch["tokens"].shape[1] // 2
    return {k: (v[:, :text] if k in ("tokens", "labels") else v)
            for k, v in batch.items()}


def _memory(when: str) -> None:
    """Device memory in use and its peak so far, on stderr."""
    import jax
    st = jax.devices()[0].memory_stats() or {}
    if st:
        print(f"[memory] {when}: in use {st.get('bytes_in_use')}, peak "
              f"{st.get('peak_bytes_in_use')}", file=sys.stderr, flush=True)


def _last_key(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def _is_q(x) -> bool:
    return hasattr(x, "q") and hasattr(x, "scale") and hasattr(x, "orig_last")


def _deq_host(q, scale, block: int, orig_last: int) -> np.ndarray:
    """Symmetric block codes and scales on the host, in float32."""
    c = q.astype(np.float32)
    cb = c.reshape(*c.shape[:-1], -1, block) * scale[..., None]
    return cb.reshape(c.shape)[..., :orig_last]


def _norms(x: np.ndarray, per_layer: bool) -> np.ndarray:
    x = x.astype(np.float64)
    if per_layer:
        return np.sqrt(np.sum(x * x, axis=tuple(range(1, x.ndim))))
    return np.atleast_1d(np.sqrt(np.sum(x * x)))


class TrainCell:
    """One run of a training cell: ``setup()``, ``window()``, ``free()``,
    then the two sides of the check (``program_readings``,
    ``reference_readings``)."""

    def __init__(self, config: dict, traffic: dict, seed: int, *,
                 clock=None, expect_backend: str = "pallas-tpu",
                 fault: Optional[str] = None):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.model = config["model"]
        self.clock = clock
        self.expect_backend = expect_backend
        self.fault = fault
        self.ref_mod = importlib.import_module(
            f"bench.reference.{config['reference']}")
        self.spans = spans.Spans()
        self.trainer = None
        self.losses = []
        self.moments = []            # each check step's first moments
        self.step_args = {}          # each step program's abstract args
        self.w_after = {}
        self.backends = {}
        self.step = 0
        self.wkey = seeds.key(seed, seeds.WEIGHTS)
        self.tkey = seeds.key(seed, seeds.TRAFFIC)
        self.make_batch = traffic_gen.batch_fn(
            traffic, self.model["vocab_size"], self.model["d_model"])

    # ------------------------------------------------------------------
    def _lrs(self) -> list:
        """The learning rate of each check step: ``learning_rate_step0``
        at step 0, when the traffic names one, ``learning_rate`` after."""
        tr = self.traffic["train"]
        first = tr.get("learning_rate_step0", tr["learning_rate"])
        return [first] + [tr["learning_rate"]] * (CHECK_STEPS - 1)

    def tokens_per_step(self) -> int:
        return self.traffic["batch"] * self.traffic["seq"]

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from repro.config import ModelConfig, QGaLoreConfig, TrainConfig
        from repro.core import optimizers
        from repro.kernels import profile
        from repro.models import model_zoo
        from repro.train import trainer as trainer_mod

        m, t = self.model, self.traffic
        init = jax.jit(lambda k: self.ref_mod.nest(self.ref_mod.init_weights(
            m, self.config["initializer_range"], k)))
        weights = init(self.wkey)

        bundle = model_zoo.build(ModelConfig(**m), dtype=jnp.bfloat16)
        bundle = dataclasses.replace(bundle, init_params=lambda _k: weights)
        qcfg = QGaLoreConfig(**t["qgalore"])
        tr = t["train"]
        tcfg = TrainConfig(
            seed=seeds.small(self.seed), global_batch=t["batch"],
            seq_len=traffic_gen.text_len(t), steps=10**9,
            learning_rate=tr["learning_rate"], warmup_steps=0,
            lr_schedule="constant", grad_clip=tr["grad_clip"], log_every=0)

        def batch_for_bundle(_bundle, _cell, step, _seed=0):
            with self.spans("bench.batch"):
                return self.make_batch(self.tkey, step)

        trainer_mod.batch_for_bundle = batch_for_bundle
        first_lr = self._lrs()[0]
        trainer_mod.optimizers = types.SimpleNamespace(
            lr_at=lambda step, cfg: first_lr if step == 0
            else optimizers.lr_at(step, cfg))
        os.environ["REPRO_PROFILE"] = "1"     # trace-time backend record
        profile.reset()
        trainer = trainer_mod.Trainer(bundle, tcfg, qcfg,
                                      param_dtype=jnp.bfloat16)
        del weights, bundle
        masks = trainer.controller.masks_for_step

        def spanned_masks(step):
            with self.spans("bench.controller"):
                return masks(step)

        trainer.controller.masks_for_step = spanned_masks
        self._record(trainer)
        self._plant(trainer)
        self.trainer = trainer
        self.beta1 = qcfg.beta1
        _memory("program initialised")
        for step in range(CHECK_STEPS):
            self.losses.append(self.run_step())
            if step < 2:
                self._read_moments()    # for the gradients of steps 0, 1
            if step == 0:
                _memory("after the refresh step")
        self._read_weights()
        self.backends = {op: profile.snapshot().get(op, {}).get(
            "meta", {}).get("backend") for op in MAIN_PATH_OPS}
        os.environ["REPRO_PROFILE"] = "0"

    def _record(self, trainer) -> None:
        """Keep the abstract arguments of each step program's first call,
        from which ``step_program_bytes`` lowers it again."""
        import jax

        def abstract(x):
            if isinstance(x, jax.Array):
                return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                            sharding=x.sharding)
            return x

        def recording(name, fn):
            def call(*args):
                if name not in self.step_args:
                    self.step_args[name] = (
                        fn, jax.tree_util.tree_map(abstract, args))
                return fn(*args)
            return call

        trainer._step_normal = recording("plain", trainer._step_normal)
        trainer._step_refresh = recording("refresh", trainer._step_refresh)

    def step_program_bytes(self) -> int:
        """The device memory the larger step program needs by the
        compiler's account: arguments, outputs not aliased to them, and
        temporaries. Lowered again from the abstract arguments, so the
        compiled programs come from the cache."""
        best = 0
        for fn, args in self.step_args.values():
            mem = fn.lower(*args).compile().memory_analysis()
            if mem is None:
                continue
            best = max(best, mem.argument_size_in_bytes
                       + mem.output_size_in_bytes
                       - mem.alias_size_in_bytes
                       + mem.temp_size_in_bytes)
        return best

    def _plant(self, trainer) -> None:
        """Break the timed path on purpose (the harness's own tests): in
        both step programs, or with the suffix ``.plain`` in the plain
        program alone, the one the window times."""
        if self.fault is None:
            return
        fault, _, only = self.fault.partition(".")
        if fault == "unchanged":
            def wrap(fn):
                return lambda st, *a: (st,) + tuple(fn(st, *a)[1:])
        elif fault == "half_batch":
            def wrap(fn):
                return lambda st, batch, *a: fn(st, half(batch), *a)
        else:
            raise ValueError(f"unknown fault {self.fault!r}")
        if only not in ("", "plain"):
            raise ValueError(f"unknown fault {self.fault!r}")
        trainer._step_normal = wrap(trainer._step_normal)
        if not only:
            trainer._step_refresh = wrap(trainer._step_refresh)

    def run_step(self) -> float:
        """One step on ``Trainer.run``'s path: dispatch, then read the loss,
        which waits for the step to finish on the device."""
        with self.spans("bench.dispatch"):
            metrics = self.trainer._run_one(self.step)
        with self.spans("bench.wait"):
            loss = float(metrics["loss"])
        self.step += 1
        return loss

    def _read_moments(self) -> None:
        """Copy each weight's first Adam moment, as stored after the step
        just run, to the host."""
        import jax
        inner = self.trainer.state.opt.inner
        flat = jax.tree_util.tree_flatten_with_path(
            inner, is_leaf=lambda x: hasattr(x, "m") and hasattr(x, "v"))[0]
        moments = {}
        for path, st in flat:
            m = st.m
            moments[_last_key(path)] = (
                _deq_host(np.asarray(m.q), np.asarray(m.scale), m.block,
                          m.orig_last)
                if _is_q(m) else np.asarray(m, np.float32))
        self.moments.append(moments)

    def _grads(self, ref) -> dict:
        """Per weight, the norm of the gradient its optimizer received at
        step 0 (the refresh program) and, for the weights without a
        projection, at step 1 (the plain program), from the stored
        moments: m0 = (1 - b1) g0 and m1 = b1 m0 + (1 - b1) g1."""
        b1 = self.beta1
        out = {"grad": {}, "grad1": {}}
        for name, m0 in self.moments[0].items():
            per_layer = name in self.ref_mod.LAYER_KEYS
            out["grad"][name] = _norms(m0 / (1.0 - b1), per_layer)
            if ref.side(name) is None:
                m1 = self.moments[1][name]
                out["grad1"][name] = _norms((m1 - b1 * m0) / (1.0 - b1),
                                            per_layer)
        return out

    def _read_weights(self) -> None:
        """Copy the weights after the check steps to the host."""
        import jax
        flat = jax.tree_util.tree_flatten_with_path(
            self.trainer.state.params, is_leaf=_is_q)[0]
        for path, leaf in flat:
            name = _last_key(path)
            if _is_q(leaf):
                self.w_after[name] = (np.asarray(leaf.q),
                                      np.asarray(leaf.scale), leaf.block,
                                      leaf.orig_last)
            else:
                self.w_after[name] = (np.asarray(leaf, np.float32),)

    # ------------------------------------------------------------------
    def window(self, seconds: float) -> dict:
        """Plain steps until ``seconds`` have passed; the window ends when
        the last step's loss has been read."""
        c0 = self.clock.count if self.clock else 0
        n, bad = 0, 0
        self.spans.clear()
        t0 = time.monotonic()
        with self.spans("bench.window"):
            while True:
                loss = self.run_step()
                n += 1
                bad += not np.isfinite(loss)
                if time.monotonic() - t0 >= seconds:
                    break
        t1 = time.monotonic()
        return {"steps": n, "failed": bad, "seconds": t1 - t0,
                "tokens": n * self.tokens_per_step(),
                "compiles": (self.clock.count - c0) if self.clock else 0}

    def end_to_end(self, win: dict, setup_s: float, peak) -> dict:
        return {"train_tokens_per_s": win["tokens"] / win["seconds"],
                "peak_hbm_gib": None if peak is None else peak / 2**30,
                "setup_s": setup_s}

    def opt_state_bytes(self) -> int:
        import jax
        return sum(int(x.nbytes) for x in
                   jax.tree_util.tree_leaves(self.trainer.state.opt))

    # ------------------------------------------------------------------
    def free(self) -> None:
        """Drop the program's state and its loaded programs, whose device
        memory the reference needs."""
        import jax
        self.trainer = None
        gc.collect()
        jax.clear_caches()

    def program_readings(self, ref) -> dict:
        """The program's side of the comparison. ``ref`` supplies the
        weights at step 0, made again from the seed."""
        import jax.numpy as jnp
        W = {}
        for name, after in self.w_after.items():
            if len(after) == 4 and after[2] != self.ref_mod.BLOCK:
                raise ValueError(f"{name}: INT8 block {after[2]}")
            parts = after[:2] if len(after) == 4 else after
            if name in self.ref_mod.LAYER_KEYS:
                W[name] = [tuple(jnp.asarray(a[i]) for a in parts)
                           for i in range(parts[0].shape[0])]
            else:
                W[name] = [tuple(jnp.asarray(a) for a in parts)]
        return {"losses": list(self.losses), **self._grads(ref),
                "change": ref.change(W, ref.initial(self.wkey))}

    def reference(self, precision: str = "f32"):
        q = dict(self.traffic["qgalore"])
        q["grad_clip"] = self.traffic["train"]["grad_clip"]
        return self.ref_mod.Reference(self.model, q,
                                      self.config["initializer_range"],
                                      precision)

    def reference_readings(self, ref, halved: bool = False,
                           stream: int = seeds.REFERENCE,
                           still: bool = False) -> dict:
        """A reference over the check steps' batches. ``halved``: over half
        of each; ``still``: at learning rate 0, which leaves the weights
        as they were. Both plant a fault, for the check's calibration."""
        batches = [self.make_batch(self.tkey, s) for s in range(CHECK_STEPS)]
        if halved:
            batches = [half(b) for b in batches]
        lrs = [0.0] * CHECK_STEPS if still else self._lrs()
        return ref.run(self.wkey, batches, lrs,
                       seeds.key(self.seed, stream), CHECK_STEPS)

    def guards(self, window: dict) -> Dict[str, dict]:
        """Numbers beside the comparison: compiles inside the window, and
        main-path kernel ops that did not resolve to the expected
        backend."""
        off = [op for op, b in self.backends.items()
               if b != self.expect_backend]
        return {"window_compiles": {"value": window["compiles"], "limit": 0},
                "ops_off_backend": {"value": len(off), "limit": 0,
                                    "at": ",".join(off)}}


def build(config: dict, traffic: dict, seed: int, **kw) -> TrainCell:
    return TrainCell(config, traffic, seed, **kw)

