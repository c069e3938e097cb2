"""Projected training of the supervised autoencoder — the paper's Algorithm 3.

Double descent (Frankle-Carbin style, as adapted by the paper):
  descent 1: projected Adam (projection applied after every update);
  mask:      M0 = surviving column support of the constrained weight;
  rewind:    weights back to their initial values, masked by M0;
  descent 2: retrain with gradients masked by M0 (zero columns stay frozen),
             projection kept active.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from .. import obs
from ..core import (ProjectionEngine, ProjectionSpec, column_masks,
                    family_for_norm, newton_evals, sparsity_report)
from ..optim import AdamConfig, adam_init
from .model import SAEConfig, sae_init, sae_loss, accuracy

__all__ = ["SAETrainConfig", "train_sae", "SAEResult"]


@dataclasses.dataclass(frozen=True)
class SAETrainConfig:
    epochs: int = 30
    batch_size: int = 128
    lr: float = 1e-3
    seed: int = 0
    double_descent: bool = True
    projection: Optional[ProjectionSpec] = None   # None => unconstrained baseline


@dataclasses.dataclass
class SAEResult:
    params: dict
    test_accuracy: float
    column_sparsity: float     # % of feature columns of enc1/w fully zero
    selected: np.ndarray       # indices of surviving features
    history: list
    # serving-eval path: per-epoch surviving-column fraction of the
    # constrained leaves (J/m — what compact_sae would keep at that epoch),
    # mirrored by history entries; compaction_ratio is the final value
    compaction_history: list = dataclasses.field(default_factory=list)
    compaction_ratio: float = 1.0


def _make_step(cfg: SAEConfig, tcfg: SAETrainConfig, acfg: AdamConfig):
    specs = (tcfg.projection,) if tcfg.projection else ()
    # the shared projected-update step core: Adam (grads masked), packed
    # warm-started projection, then the mask freeze (Algorithm 3); "fused"
    # runs the two-HBM-pass megakernel where the constraint family streams
    # its statistics and falls back to the identical Newton path elsewhere
    engine = ProjectionEngine(specs, solver="fused")

    @jax.jit
    def sae_step(params, opt_state, proj_state, x, y, mask):
        """One projected step; the last output is the update's Eq.-(19)
        evaluation count (``core.newton_evals``)."""
        obs.count("sae/step_traces")        # the body runs once per trace
        with obs.scope("fwd_bwd"):
            (loss, aux), grads = jax.value_and_grad(
                lambda p: sae_loss(p, x, y, cfg), has_aux=True)(params)
        params, opt_state, proj_state, stats = engine.projected_update(
            grads, opt_state, params, acfg, mask=mask, state=proj_state,
            with_stats=True)
        return (params, opt_state, proj_state, loss, aux,
                newton_evals(stats))

    return sae_step, engine


def _compaction_ratio(params, specs) -> float:
    """Mean surviving-column fraction J/m of the constrained leaves — the
    width ``compact_sae`` would serve at (1.0 when nothing is constrained)."""
    rep = sparsity_report(params, specs)
    if not rep:
        return 1.0
    return float(np.mean([1.0 - v / 100.0 for v in rep.values()]))


@functools.partial(jax.jit, static_argnames="batch_size")
def _epoch_batches(X, y, perm, batch_size):
    """An epoch's batches ``(X[perm[s:s + b]], y[perm[s:s + b]])`` in order,
    the last one ragged, made on the device by one program. The data comes
    in as arguments, never closed over, so the program holds no data and
    traces once per process for each shape of ``X``, ``y`` and batch size."""
    return tuple((X[perm[s:s + batch_size]], y[perm[s:s + batch_size]])
                 for s in range(0, perm.shape[0], batch_size))


def _run_descent(params, step_fn, engine, X, y, tcfg, mask, rng, specs=()):
    acfg = AdamConfig(lr=tcfg.lr)
    opt_state = adam_init(params, acfg)
    proj_state = engine.init_state(params)
    n = X.shape[0]
    history, compaction = [], []
    k = 0
    for epoch in range(tcfg.epochs):
        perm = rng.permutation(n)
        with obs.span("sae/batch"):
            batches = _epoch_batches(X, y, perm, batch_size=tcfg.batch_size)
        obs.count("sae/batch_programs")
        evals = []
        for xb, yb in batches:
            with obs.span("sae/step", step=k):
                params, opt_state, proj_state, loss, aux, ev = step_fn(
                    params, opt_state, proj_state, xb, yb, mask)
            evals.append(ev)
            k += 1
        with obs.span("sae/epoch_end"):
            # the epoch's one sync: its last loss and every step's count
            loss, evals = jax.device_get((loss, evals))
            history.append(float(loss))
            compaction.append(_compaction_ratio(params, specs))
        if engine.specs:
            obs.count("proj/updates", len(evals))
            obs.count("proj/newton_evals", int(np.sum(evals)))
    return params, history, compaction


def train_sae(X_train: np.ndarray, y_train: np.ndarray,
              X_test: np.ndarray, y_test: np.ndarray,
              cfg: SAEConfig, tcfg: SAETrainConfig) -> SAEResult:
    """One fit of Algorithm 3, under the host span ``sae/fit``."""
    obs.count("sae/fits")
    with obs.span("sae/fit"):
        return _fit(X_train, y_train, X_test, y_test, cfg, tcfg)


def _fit(X_train, y_train, X_test, y_test, cfg, tcfg) -> SAEResult:
    key = jax.random.PRNGKey(tcfg.seed)
    rng = np.random.default_rng(tcfg.seed)
    X_train = jnp.asarray(X_train)
    y_train_j = jnp.asarray(y_train)

    params0 = sae_init(key, cfg)
    ones_mask = jax.tree_util.tree_map(jnp.ones_like, params0)
    acfg = AdamConfig(lr=tcfg.lr)

    # masked variant (Eq. 20 / torch-pruning semantics): descent 1 uses the
    # TRUE projection to find the support; descent 2 keeps only the frozen
    # mask — magnitudes unbounded ("maximum value of the columns is not
    # bounded"). Applying the unclipped masked projection every step instead
    # makes theta run away and over-prunes (support collapses; see
    # EXPERIMENTS.md §Paper-validation).
    fam = (family_for_norm(tcfg.projection.norm)
           if tcfg.projection is not None else None)
    masked_mode = fam is not None and fam.name == "l1inf_masked"
    if masked_mode:
        import dataclasses as _dc
        tcfg1 = _dc.replace(tcfg, projection=_dc.replace(
            tcfg.projection, norm="l1inf"))
    else:
        tcfg1 = tcfg
    step_fn, step_engine = _make_step(cfg, tcfg1, acfg)

    eval_specs = (tcfg1.projection,) if tcfg1.projection else ()

    # ---- descent 1: projected training --------------------------------
    params, hist1, comp1 = _run_descent(params0, step_fn, step_engine,
                                        X_train, y_train_j, tcfg, ones_mask,
                                        rng, specs=eval_specs)
    history = [("descent1", hist1)]
    compaction_history = [("descent1", comp1)]

    # ---- double descent: mask, rewind, retrain -------------------------
    if tcfg.projection and tcfg.double_descent:
        with obs.span("sae/rewind"):
            specs = (tcfg1.projection,)
            masks = column_masks(params, specs)
            rewound = jax.tree_util.tree_map(lambda p0, m: p0 * m, params0,
                                             masks)
            if masked_mode:  # retrain mask-only, no clipping
                import dataclasses as _dc
                step_fn, step_engine = _make_step(
                    cfg, _dc.replace(tcfg, projection=None), acfg)
        params, hist2, comp2 = _run_descent(rewound, step_fn, step_engine,
                                            X_train, y_train_j, tcfg, masks,
                                            rng, specs=eval_specs)
        history.append(("descent2", hist2))
        compaction_history.append(("descent2", comp2))

    with obs.span("sae/eval"):
        test_acc = float(accuracy(params, jnp.asarray(X_test),
                                  jnp.asarray(y_test)))
        w1 = np.asarray(params["enc1"]["w"])
        live = np.any(w1 != 0, axis=1)
        colsp = 100.0 * (1.0 - live.mean())
        return SAEResult(params=params, test_accuracy=test_acc,
                         column_sparsity=float(colsp),
                         selected=np.nonzero(live)[0], history=history,
                         compaction_history=compaction_history,
                         compaction_ratio=_compaction_ratio(params,
                                                            eval_specs))
