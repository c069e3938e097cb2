"""Training runner: the production loop with every fault-tolerance feature
wired in (checkpoint/restart, straggler watchdog, deterministic data,
projection constraints, microbatch gradient accumulation).

Runs unchanged on 1 CPU device (examples) and on the production meshes
(launch/train.py) — the mesh/rules are injected, not assumed.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models.zoo import Model
from ..optim import AdamConfig, adam_init
from ..core import ProjectionEngine, sparsity_report
from ..checkpoint import AsyncCheckpointer, latest_step, restore_tree
from ..dist.sharding import axis_rules
from ..dist.watchdog import StepWatchdog
from ..data.pipeline import LMBatcher
from ..launch.steps import (batch_shardings, opt_shardings, param_shardings,
                            projection_engine_for)


@dataclasses.dataclass
class TrainConfig:
    steps: int = 100
    log_every: int = 10
    ckpt_every: int = 50
    ckpt_dir: Optional[str] = None
    keep_ckpts: int = 3
    microbatches: int = 1          # gradient accumulation
    lr: float = 3e-4
    warmup: int = 20
    with_projection: bool = True
    seed: int = 0


def build_accum_step(model: Model, acfg: AdamConfig, tcfg: TrainConfig,
                     mesh=None, rules=None, engine: ProjectionEngine = None):
    """jit'd train step with optional microbatch accumulation via lax.scan.
    The update half is the shared ``ProjectionEngine.projected_update`` step
    core (Adam + packed warm-started projection + every_k gate); the
    default engine is ``launch.steps.projection_engine_for`` this mesh."""
    if engine is None:
        engine = projection_engine_for(model.cfg, mesh, tcfg.with_projection)

    def loss_fn(params, batch):
        return model.loss(params, batch)

    def step(params, opt_state, proj_state, batch, lr):
        with axis_rules(mesh, rules):
            if tcfg.microbatches > 1:
                def micro(carry, mb):
                    (g_acc, l_acc) = carry
                    (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                        params, mb)
                    g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                    return (g_acc, l_acc + l), None

                mbs = jax.tree_util.tree_map(
                    lambda x: x.reshape((tcfg.microbatches,
                                         x.shape[0] // tcfg.microbatches)
                                        + x.shape[1:]), batch)
                g0 = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                (grads, loss), _ = jax.lax.scan(micro, (g0, 0.0), mbs)
                grads = jax.tree_util.tree_map(
                    lambda g: g / tcfg.microbatches, grads)
                loss = loss / tcfg.microbatches
            else:
                (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, batch)
            params, opt_state, proj_state = engine.projected_update(
                grads, opt_state, params, acfg, lr=lr, state=proj_state)
        return params, opt_state, proj_state, loss

    return jax.jit(step, donate_argnums=(0, 1, 2))


def lr_at(tcfg: TrainConfig, step: int) -> float:
    warm = min(1.0, (step + 1) / max(tcfg.warmup, 1))
    return tcfg.lr * warm


def train(model: Model, batcher: LMBatcher, tcfg: TrainConfig,
          mesh=None, rules=None, resume: bool = True,
          on_step: Optional[Callable[[int, float, float], None]] = None
          ) -> Dict[str, Any]:
    """Run the loop; auto-resumes from the latest checkpoint if present.

    With a ``mesh`` (and its ``rules``) the params, optimizer state and
    batches are laid out on it by ``launch.steps``' shardings and the
    projection runs on the engine ``projection_engine_for`` picks for it;
    without one everything runs on the default device."""
    acfg = AdamConfig(lr=tcfg.lr)
    params = model.init(jax.random.PRNGKey(tcfg.seed))
    opt_state = adam_init(params, acfg)
    start_step = 0

    engine = projection_engine_for(model.cfg, mesh, tcfg.with_projection)
    proj_state = engine.init_state(params)

    ckpt = None
    if tcfg.ckpt_dir:
        ckpt = AsyncCheckpointer(tcfg.ckpt_dir, keep=tcfg.keep_ckpts)
        if resume and latest_step(tcfg.ckpt_dir) is not None:
            # the projection theta state rides in the checkpoint so a resume
            # stays warm-started; pre-engine checkpoints lack it — fall back
            # to a cold Newton start rather than refusing the restore
            try:
                state = {"params": params, "opt": opt_state,
                         "proj": proj_state}
                state, start_step = restore_tree(state, tcfg.ckpt_dir)
                proj_state = state["proj"]
            except KeyError:
                state = {"params": params, "opt": opt_state}
                state, start_step = restore_tree(state, tcfg.ckpt_dir)
                print("[train] checkpoint has no projection state; "
                      "cold-starting Newton")
            params, opt_state = state["params"], state["opt"]
            print(f"[train] resumed from step {start_step}")

    place_batch = lambda b: b
    if mesh is not None:
        p_sh = param_shardings(model, mesh, rules)
        params = jax.device_put(params, p_sh)
        opt_state = jax.device_put(opt_state, opt_shardings(p_sh, mesh))
        proj_state = jax.device_put(proj_state, NamedSharding(mesh, P()))
        place_batch = lambda b: jax.device_put(
            b, batch_shardings(b, mesh, rules))

    step_fn = build_accum_step(model, acfg, tcfg, mesh, rules, engine=engine)
    watchdog = StepWatchdog(on_straggler=lambda s, dt, ew: print(
        f"[watchdog] straggler step {s}: {dt:.3f}s vs EWMA {ew:.3f}s"))

    losses = []
    step_metrics = []   # per-step watchdog snapshots (dist/watchdog.py)
    for step in range(start_step, tcfg.steps):
        batch = place_batch(
            jax.tree_util.tree_map(jnp.asarray, batcher.get(step)))
        watchdog.start()
        params, opt_state, proj_state, loss = step_fn(
            params, opt_state, proj_state, batch, lr_at(tcfg, step))
        loss_f = float(loss)
        dt = watchdog.stop(step)
        step_metrics.append(watchdog.metrics())
        losses.append(loss_f)
        if on_step:
            on_step(step, loss_f, dt)
        if step % tcfg.log_every == 0:
            print(f"[train] step {step:5d} loss {loss_f:.4f} "
                  f"({dt*1e3:.0f} ms)", flush=True)
        if ckpt and (step + 1) % tcfg.ckpt_every == 0:
            ckpt.save({"params": params, "opt": opt_state,
                       "proj": proj_state}, step + 1)
    if ckpt:
        ckpt.save({"params": params, "opt": opt_state, "proj": proj_state},
                  tcfg.steps)
        ckpt.wait()

    report = {}
    if model.cfg.projection_specs:
        report = sparsity_report(params, model.cfg.projection_specs)
    return {"params": params, "opt_state": opt_state, "losses": losses,
            "proj_state": proj_state, "sparsity": report,
            "straggler_events": watchdog.events,
            "step_metrics": step_metrics,
            "watchdog": watchdog.metrics()}
