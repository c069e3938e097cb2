"""The SAE job: back-to-back ``repro.sae.train.train_sae`` fits.

Each fit is the paper's Algorithm 3 at the configuration's sizes: descent
1 with the projection at every step, the support mask, the rewind and
descent 2. Fit k of a run is seeded from (run seed, k). The window counts
the training samples of the fits it completed over their wall time.

The check reads the first three steps of the window's first fit: the job
wraps the step that ``train_sae`` builds (``repro.sae.train._make_step``)
for that fit and keeps what the step was given and returned, then hands
the fit on unchanged.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np
import jax
import jax.numpy as jnp

from bench import data, plain

CHECK_STEPS = 3


def fit_seed(seed: int, k: int) -> int:
    return (seed * 1_000_003 + k) % 2_147_483_647


class Job:
    rate_metric = "sae_train_samples_per_s"

    def __init__(self, cfg: dict, traffic: dict, seed: int, log):
        from repro.core import ProjectionSpec
        from repro.sae import SAEConfig
        self.cfg, self.traffic, self.seed, self.log = cfg, traffic, seed, log
        self.model_cfg = SAEConfig(
            n_features=cfg["n_features"], n_hidden=cfg["n_hidden"],
            n_classes=cfg["n_classes"], lam=cfg["lam"],
            huber_delta=cfg["huber_delta"])
        self.spec = ProjectionSpec(pattern=traffic["pattern"],
                                   norm=traffic["norm"],
                                   radius=traffic["radius"],
                                   axis=traffic["axis"])
        self.fits = 0
        self.recorded = []

    # ---- the program ----------------------------------------------------
    def _tcfg(self, k: int):
        from repro.sae import SAETrainConfig
        c = self.cfg
        return SAETrainConfig(epochs=c["epochs"], batch_size=c["batch_size"],
                              lr=c["lr"], seed=fit_seed(self.seed, k),
                              double_descent=c["double_descent"],
                              projection=self.spec)

    def _fit(self):
        from repro.sae import train_sae
        res = train_sae(self.X_train, self.y_train, self.X_test, self.y_test,
                        self.model_cfg, self._tcfg(self.fits))
        self.fits += 1
        return res

    @contextlib.contextmanager
    def _recording(self):
        """Keep the first CHECK_STEPS calls of the step train_sae builds."""
        import repro.sae.train as program
        make_step = program._make_step

        def recording_make_step(*args, **kwargs):
            step, engine = make_step(*args, **kwargs)

            def step_and_keep(*inputs):
                out = step(*inputs)
                if len(self.recorded) < CHECK_STEPS:
                    self.recorded.append((inputs, out))
                return out
            return step_and_keep, engine

        program._make_step = recording_make_step
        try:
            yield
        finally:
            program._make_step = make_step

    @property
    def samples_per_fit(self) -> int:
        descents = 2 if self.cfg["double_descent"] else 1
        return len(self.X_train) * self.cfg["epochs"] * descents

    # ---- the harness's phases ---------------------------------------------
    def setup(self):
        """Data from the seed, then one fit that compiles (or loads) both
        step shapes; it is not timed and not checked."""
        t0 = time.perf_counter()
        self.X_train, self.y_train, self.X_test, self.y_test = \
            data.sae_table(self.cfg, self.seed)
        t1 = time.perf_counter()
        self.warm = self._fit()
        self.log(f"[sae] set-up: data {t1 - t0:.3f} s, warm fit "
                 f"{time.perf_counter() - t1:.3f} s")

    def window(self, seconds: float, span, profile=None) -> dict:
        """Whole fits until ``seconds`` have passed; the first is recorded
        for the check. With a profiler, one more fit runs traced after."""
        self.checked_fit = self.fits
        done, failed, t0 = 0, 0, time.perf_counter()
        fit_s = []
        while True:
            rec = self._recording() if done == 0 else contextlib.nullcontext()
            with rec, span("bench/fit"):
                res = self._fit()
            fit_s.append(time.perf_counter() - t0 - sum(fit_s))
            losses = [v for _, h in res.history for v in h]
            failed += not np.all(np.isfinite(losses))
            done += 1
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        self.log("[sae] window fits (s): "
                 + " ".join(f"{x:.3f}" for x in fit_s))
        traced = 0
        if profile:
            profile.start()
            with span("bench/fit"):
                self._fit()
            profile.end_window()
            traced = 1
        return {"attempted": done, "failed": int(failed), "units": done,
                "traced_units": traced, "elapsed_s": elapsed, "t_start": t0,
                "rate": done * self.samples_per_fit / elapsed}

    def layer_calls(self):
        """Separately jitted calls of single layers on the window's params,
        grads and state: {span name: (jitted fn, args)}."""
        from repro.core import ProjectionEngine
        from repro.optim import AdamConfig
        from repro.sae.model import sae_loss
        (params, opt_state, proj_state, x, y, mask), _ = self.recorded[1]
        cfg = self.model_cfg
        acfg = AdamConfig(lr=self.cfg["lr"])
        engine = ProjectionEngine((self.spec,), solver="fused")

        def bench_fwd_bwd_sae(p, x, y):
            return jax.value_and_grad(lambda q: sae_loss(q, x, y, cfg),
                                      has_aux=True)(p)

        def bench_proj_update_sae(g, o, p, s, mask):
            return engine.projected_update(g, o, p, acfg, mask=mask, state=s)

        fwd_bwd = jax.jit(bench_fwd_bwd_sae)
        _, grads = fwd_bwd(params, x, y)
        return {
            "fwd_bwd": (fwd_bwd, (params, x, y)),
            "proj_update": (jax.jit(bench_proj_update_sae),
                            (grads, opt_state, params, proj_state, mask)),
        }

    def release(self):
        """Drop what set-up left on the device; the records stay."""
        self.warm = None

    # ---- the check ----------------------------------------------------------
    def program_readings(self) -> dict:
        """The check's readings from the recorded steps (bench/check.py)."""
        from repro.optim import AdamConfig
        b1 = AdamConfig().b1
        (p0, *_), _ = self.recorded[0]
        _, (_, opt1, *_) = self.recorded[0]
        _, (p3, *_) = self.recorded[CHECK_STEPS - 1]
        grad = {k: v / (1 - b1) for k, v in plain.norms(opt1.mu).items()}
        change = jax.tree_util.tree_map(lambda a, b: a - b, p3, p0)
        return {"loss": [float(out[3]) for _, out in self.recorded],
                "grad": grad, "change": plain.norms(change),
                "proj": {self.spec.pattern:
                         plain.norms(p3)[self.spec.pattern]}}

    def reference_readings(self, reference, dtype=jnp.float32) -> dict:
        return reference.readings(self.cfg, self.traffic,
                                  fit_seed(self.seed, self.checked_fit),
                                  self.X_train,
                                  self.y_train, CHECK_STEPS, dtype)
