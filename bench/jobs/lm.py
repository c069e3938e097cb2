"""The LM job: projected training steps through ``repro.train.loop.train``.

The configuration's ``arch`` is the program's ``ArchConfig`` and its
``projection`` the balls the program holds its weights in. The weights
come from ``bench/weights.py`` (one jitted call from the seed) through the
model's ``init``; the rows come from ``bench/data.py`` through the
program's ``LMBatcher``.

Set-up makes one short ``train`` call (compile or cache load, then steps
whose time sets the window's step count), then starts the measured call:
its first ``check_steps`` steps (three, or ``every_k`` if more, so that
every ball's projection fires once) are read for the check and warm the
loop; the window runs from the end of those steps to the end of the
last, timed by ``train``'s ``on_step`` hook. With a profiler, a further
``trace_steps`` steps run traced after the window.

The check reads the training state from ``train``'s own frame inside
``on_step`` (``params`` and ``opt_state`` after the step that just ran);
nothing in the loop is changed.
"""
from __future__ import annotations

import math
import re
import sys
import time

import jax
import jax.numpy as jnp

from bench import data, plain, weights


class TokenSource:
    """The program's batch source interface over ``bench.data.token_rows``."""

    def __init__(self, seed, vocab, zipf_a):
        self.seed, self.vocab, self.zipf_a = seed, vocab, zipf_a

    def batch(self, step, batch, seq, rows=None):
        out = data.token_rows(self.seed, step, batch, seq, self.vocab,
                              self.zipf_a)
        lo, hi = rows or (0, batch)
        return out[lo:hi]


class Job:
    rate_metric = "lm_train_tokens_per_s"

    def __init__(self, cfg: dict, traffic: dict, seed: int, log):
        from repro.core import ProjectionSpec
        from repro.models.transformer import ArchConfig
        self.cfg, self.traffic, self.seed, self.log = cfg, traffic, seed, log
        arch = dict(cfg["arch"], pattern=tuple(cfg["arch"]["pattern"]))
        specs = tuple(ProjectionSpec(**p) for p in cfg["projection"])
        self.arch = ArchConfig(**arch, projection_specs=specs)
        self.check_steps = max([3] + [p["every_k"] for p in cfg["projection"]])
        self.tokens_per_step = traffic["batch"] * traffic["seq"]
        self.readings = {}

    # ---- the program ----------------------------------------------------
    def _train(self, steps, on_step):
        from repro.train.loop import TrainConfig, train
        tcfg = TrainConfig(steps=steps, log_every=10 ** 9, ckpt_every=10 ** 9,
                           lr=self.traffic["lr"], warmup=self.traffic["warmup"])
        return train(self.model, self.batcher, tcfg, resume=False,
                     on_step=on_step)

    def setup(self):
        from repro.data.pipeline import LMBatcher
        from repro.models.zoo import Model, build
        model = build(self.arch)
        self.shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        seed = self.seed

        class SeededModel(Model):
            """The program's model, its weights drawn by the benchmark."""

            def init(self, key, dtype=jnp.float32):
                return weights.init(self_shapes, seed, dtype)
        self_shapes = self.shapes
        self.model = SeededModel(cfg=model.cfg, layout=model.layout)
        self.batcher = LMBatcher(
            source=TokenSource(seed, self.arch.vocab, self.traffic["zipf_a"]),
            batch=self.traffic["batch"], seq=self.traffic["seq"])
        dts, t0 = [], time.perf_counter()
        self._train(3, lambda step, loss, dt: dts.append(dt))
        self.step_s = sum(dts[1:]) / len(dts[1:])
        self.log(f"[lm] calibration call {time.perf_counter() - t0:.3f} s, "
                 f"steps {dts}, step {self.step_s:.4f} s")

    def window(self, seconds: float, span, profile=None) -> dict:
        # whole every_k cycles, as many as come nearest to ``seconds``, so
        # every window holds the same share of projecting steps
        k = self.check_steps
        n_window = k * max(1, round(seconds / self.step_s / k))
        last = self.check_steps + n_window - 1
        trace_steps = self.traffic["trace_steps"] if profile else 0
        b1 = 0.9
        t, dts = {}, []

        def on_step(step, loss, dt):
            if self.check_steps <= step <= last:
                dts.append(dt)
            if step < self.check_steps:
                state = sys._getframe(1).f_locals
                self._read(step, state["params"], state["opt_state"], b1)
            if step == self.check_steps - 1:
                t["start"] = time.perf_counter()
            elif step == last:
                t["end"] = time.perf_counter()
                if profile:
                    profile.start()
            elif profile and step == last + trace_steps:
                profile.end_window()

        res = self._train(last + 1 + trace_steps, on_step)
        losses = res["losses"]
        self.readings["loss"] = losses[:self.check_steps]
        self.result = res
        win = losses[self.check_steps:last + 1]
        failed = sum(not math.isfinite(v) for v in win)
        elapsed = t["end"] - t["start"]
        slow = max(range(len(dts)), key=dts.__getitem__)
        self.log(f"[lm] window steps: median {sorted(dts)[len(dts) // 2]:.4f}"
                 f" s, slowest {dts[slow]:.4f} s (window step {slow}), sum "
                 f"{sum(dts):.3f} s of {elapsed:.3f} s")
        return {"attempted": n_window, "failed": failed, "units": n_window,
                "elapsed_s": elapsed, "t_start": t["start"],
                "rate": n_window * self.tokens_per_step / elapsed}

    def _read(self, step, params, opt_state, b1):
        """The check's readings of the program's state after ``step``."""
        if step == 0:
            self.readings["grad"] = {
                k: v / (1 - b1) for k, v in plain.norms(opt_state.mu).items()}
        if step == 2:
            p0 = weights.init(self.shapes, self.seed)
            self.readings["change"] = plain.norms(jax.tree_util.tree_map(
                lambda a, b: a - b, params, p0))
            del p0
        if step == self.check_steps - 1:
            flat = plain.norms(params)
            self.readings["proj"] = {
                k: v for k, v in flat.items()
                if any(re.search(s["pattern"], k)
                       for s in self.cfg["projection"])}

    def layer_calls(self):
        """Separately jitted single layers on the window's final state."""
        from repro.launch.steps import projection_engine_for
        from repro.optim import AdamConfig
        res = self.result
        params, opt = res["params"], res["opt_state"]
        batch = jax.tree_util.tree_map(jnp.asarray, self.batcher.get(0))
        model, acfg = self.model, AdamConfig(lr=self.traffic["lr"])
        engine = projection_engine_for(model.cfg, None, True)
        lr = jnp.asarray(self.traffic["lr"], jnp.float32)

        def bench_fwd_bwd_lm(p, b):
            return jax.value_and_grad(model.loss, has_aux=True)(p, b)

        def update(g, o, p, s):
            return engine.projected_update(g, o, p, acfg, lr=lr, state=s)

        def bench_update_lm(g, o, p, s):
            return update(g, o, p, s)

        def bench_proj_fire_lm(g, o, p, s):
            # the same program with one more output: the persistent cache
            # keys programs without their names, and two identical ones
            # would run under the first one's name in the trace
            return update(g, o, p, s), jnp.zeros((), jnp.int8)

        fwd_bwd = jax.jit(bench_fwd_bwd_lm)
        k = self.check_steps
        quiet = opt._replace(count=jnp.asarray(k, opt.count.dtype))
        fire = opt._replace(count=jnp.asarray(2 * k - 1, opt.count.dtype))
        state = res["proj_state"]
        grads = []

        def update_args(o):
            if not grads:       # made once, after fwd_bwd's own calls
                grads.append(fwd_bwd(params, batch)[1])
            return grads[0], o, params, state

        return {
            "fwd_bwd": (fwd_bwd, (params, batch)),
            "update": (jax.jit(bench_update_lm), lambda: update_args(quiet)),
            "proj_fire": (jax.jit(bench_proj_fire_lm),
                          lambda: update_args(fire)),
        }

    def release(self):
        self.result = None

    # ---- the check ----------------------------------------------------------
    def program_readings(self) -> dict:
        return dict(self.readings)

    def reference_readings(self, reference, dtype=jnp.float32) -> dict:
        ref_shapes = jax.tree_util.tree_map(
            lambda s: (s.shape, s.dtype), reference.layout(self.cfg))
        mine = jax.tree_util.tree_map(lambda s: (s.shape, s.dtype),
                                      self.shapes)
        if ref_shapes != mine:
            raise ValueError("the reference reads other parameters than the "
                             "program has")
        return reference.readings(self.cfg, self.traffic, self.seed,
                                  self.check_steps, dtype)
