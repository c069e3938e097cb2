"""Faults planted under the timed path, for the check's own tests and for
reading the check's upper limits on the chip (``bench/calibrate.py``).

Each is a context manager that breaks one thing in the program while it
is open and restores it after:

  state_unchanged  the training step returns the state it was given (the
                   loss is still computed);
  half_batch       the loss is the mean over the first half of the batch.

One chip has no exchange between chips to leave out, and a training cell
produces no token or answer to alter, so those faults do not apply here.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(owner, name, value):
    old = getattr(owner, name)
    setattr(owner, name, value)
    try:
        yield
    finally:
        setattr(owner, name, old)


@contextlib.contextmanager
def state_unchanged(job: str):
    import jax
    if job == "sae":
        import repro.sae.train as program
        make_step = program._make_step

        def broken(cfg, tcfg, acfg):
            step, engine = make_step(cfg, tcfg, acfg)

            @jax.jit
            def still(params, opt_state, proj_state, x, y, mask):
                out = step(params, opt_state, proj_state, x, y, mask)
                return (params, opt_state, proj_state) + tuple(out[3:])
            return still, engine
        with _patched(program, "_make_step", broken):
            yield
    elif job == "lm":
        import repro.train.loop as program

        def broken(model, acfg, tcfg, mesh=None, rules=None, engine=None):
            @jax.jit
            def still(params, opt_state, proj_state, batch, lr):
                return params, opt_state, proj_state, model.loss(
                    params, batch)[0]
            return still
        with _patched(program, "build_accum_step", broken):
            yield
    else:
        raise KeyError(job)


@contextlib.contextmanager
def half_batch(job: str):
    if job == "sae":
        import repro.sae.train as program
        loss = program.sae_loss

        def half(params, x, y, cfg):
            n = x.shape[0] // 2
            return loss(params, x[:n], y[:n], cfg)
        with _patched(program, "sae_loss", half):
            yield
    elif job == "lm":
        import jax
        from repro.models.zoo import Model
        loss = Model.loss

        def half(self, params, batch):
            n = batch["tokens"].shape[0] // 2
            return loss(self, params,
                        jax.tree_util.tree_map(lambda a: a[:n], batch))
        with _patched(Model, "loss", half):
            yield
    else:
        raise KeyError(job)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch}
