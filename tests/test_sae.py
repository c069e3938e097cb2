"""SAE framework: model, data generators, Algorithm 3 end-to-end on a
scaled-down version of the paper's synthetic setting."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.sae.train as sae_train
from repro.core import ProjectionSpec, column_masks
from repro.optim import AdamConfig, adam_init
from repro.sae import (SAEConfig, SAETrainConfig, sae_init, sae_apply,
                       sae_loss, make_classification, make_lung_surrogate,
                       train_test_split, train_sae)


def test_make_classification_signal():
    X, y, inf_idx = make_classification(n_samples=300, n_features=200,
                                        n_informative=16, seed=1)
    assert X.shape == (300, 200) and y.shape == (300,)
    assert len(inf_idx) == 16
    # informative features separate the classes; noise features don't
    d_inf = np.abs(X[y == 0][:, inf_idx].mean(0) - X[y == 1][:, inf_idx].mean(0))
    noise_idx = np.setdiff1d(np.arange(200), inf_idx)
    d_noise = np.abs(X[y == 0][:, noise_idx].mean(0) - X[y == 1][:, noise_idx].mean(0))
    assert d_inf.mean() > 3 * d_noise.mean()


def test_lung_surrogate_stats():
    X, y, inf_idx = make_lung_surrogate(seed=0)
    assert X.shape == (1005, 2944)
    assert (y == 1).sum() == 469 and (y == 0).sum() == 536
    assert np.all(X > 0)  # intensities; caller log-transforms


def test_sae_shapes_and_grads():
    cfg = SAEConfig(n_features=50, n_hidden=8, n_classes=3)
    params = sae_init(jax.random.PRNGKey(0), cfg)
    x = jnp.ones((4, 50))
    z, xhat = sae_apply(params, x)
    assert z.shape == (4, 3) and xhat.shape == (4, 50)
    (loss, aux), grads = jax.value_and_grad(
        lambda p: sae_loss(p, x, jnp.array([0, 1, 2, 0]), cfg), has_aux=True)(params)
    assert np.isfinite(float(loss))
    assert all(np.all(np.isfinite(np.asarray(g)))
               for g in jax.tree_util.tree_leaves(grads))


@pytest.mark.parametrize("norm", ["l1inf", "l1inf_masked", "bilevel"])
def test_algorithm3_end_to_end(norm):
    """Scaled-down paper setting: projection selects (mostly) the informative
    features and beats chance by a wide margin. ``bilevel`` exercises the
    registry end-to-end through ``sae/train.py``'s unchanged signature (the
    bi-level operator is a drop-in structured-sparsity projection)."""
    X, y, inf_idx = make_classification(n_samples=400, n_features=300,
                                        n_informative=12, class_sep=1.5,
                                        seed=3)
    mu, sd = X.mean(0), X.std(0) + 1e-6
    X = (X - mu) / sd
    Xtr, ytr, Xte, yte = train_test_split(X, y, 0.25, seed=0)
    spec = ProjectionSpec(pattern=r"enc1/w", norm=norm, radius=0.35, axis=1)
    res = train_sae(Xtr, ytr, Xte, yte,
                    SAEConfig(n_features=300, n_hidden=32, n_classes=2),
                    SAETrainConfig(epochs=25, lr=2e-3, projection=spec,
                                   seed=0))
    assert res.test_accuracy > 0.75, res.test_accuracy
    assert res.column_sparsity > 50.0, res.column_sparsity
    # clipped l1,inf recovers a solid fraction of the informative features;
    # the masked variant only claims accuracy parity (paper §6 Overall), so
    # support recall is asserted for the true projection only.
    if norm == "l1inf" and len(res.selected):
        hits = np.intersect1d(res.selected, inf_idx).size
        assert hits / len(inf_idx) > 0.3, (res.selected, inf_idx)


def test_baseline_no_projection_runs():
    X, y, _ = make_classification(n_samples=200, n_features=64,
                                  n_informative=8, class_sep=1.5, seed=5)
    X = (X - X.mean(0)) / (X.std(0) + 1e-6)
    Xtr, ytr, Xte, yte = train_test_split(X, y, 0.25, seed=1)
    res = train_sae(Xtr, ytr, Xte, yte,
                    SAEConfig(n_features=64, n_hidden=16, n_classes=2),
                    SAETrainConfig(epochs=25, lr=2e-3, projection=None, seed=0))
    assert res.column_sparsity == 0.0
    assert res.test_accuracy > 0.6


def _eager_fit_losses(X, y, cfg, tcfg):
    """Every step's loss of Algorithm 3 with the batches gathered eagerly,
    ``X[perm[s:s + b]]`` at each step: the loop the fit's one-program-per-
    epoch batches replaced, kept here as the reference."""
    acfg = AdamConfig(lr=tcfg.lr)
    step, engine = sae_train._make_step(cfg, tcfg, acfg)
    rng = np.random.default_rng(tcfg.seed)
    params0 = sae_init(jax.random.PRNGKey(tcfg.seed), cfg)
    Xj, yj = jnp.asarray(X), jnp.asarray(y)

    def descent(params, mask):
        opt_state, proj_state = adam_init(params, acfg), engine.init_state(
            params)
        losses = []
        for _ in range(tcfg.epochs):
            perm = rng.permutation(len(X))
            for s in range(0, len(X), tcfg.batch_size):
                idx = perm[s:s + tcfg.batch_size]
                params, opt_state, proj_state, loss, *_ = step(
                    params, opt_state, proj_state, Xj[idx], yj[idx], mask)
                losses.append(float(loss))
        return params, losses

    params, losses1 = descent(
        params0, jax.tree_util.tree_map(jnp.ones_like, params0))
    masks = column_masks(params, (tcfg.projection,))
    _, losses2 = descent(
        jax.tree_util.tree_map(lambda p, m: p * m, params0, masks), masks)
    return losses1 + losses2


def test_fit_batches_are_the_eager_gather_of_each_permutation(monkeypatch):
    """n = 100 in batches of 32: 32, 32, 32 and a ragged 4, two epochs, both
    descents. The step is wrapped through ``_make_step`` as the benchmark's
    check wraps it, and must see exactly the rows the permutations of
    ``default_rng(seed)`` name, and give the losses of the eager loop."""
    rng = np.random.default_rng(7)
    X = rng.standard_normal((100, 24)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.int32)
    cfg = SAEConfig(n_features=24, n_hidden=8, n_classes=2)
    tcfg = SAETrainConfig(epochs=2, batch_size=32, seed=5, projection=
                          ProjectionSpec(pattern="enc1/w", norm="l1inf",
                                         radius=0.5, axis=1))
    seen = []
    make_step = sae_train._make_step

    def recording_make_step(*args):
        step, engine = make_step(*args)

        def step_and_keep(*inputs):
            out = step(*inputs)
            seen.append((inputs[3], inputs[4], float(out[3])))
            return out
        return step_and_keep, engine

    monkeypatch.setattr(sae_train, "_make_step", recording_make_step)
    res = train_sae(X, y, X, y, cfg, tcfg)
    monkeypatch.undo()

    perms = np.random.default_rng(tcfg.seed)
    want = [perm[s:s + 32] for perm in (perms.permutation(100)
                                        for _ in range(2 * 2))
            for s in range(0, 100, 32)]
    assert len(seen) == len(want) == 2 * 2 * 4
    for (xb, yb, _), idx in zip(seen, want):
        assert xb.shape == (len(idx), 24) and yb.shape == (len(idx),)
        np.testing.assert_array_equal(np.asarray(xb), X[idx])
        np.testing.assert_array_equal(np.asarray(yb), y[idx])

    losses = [loss for *_, loss in seen]
    assert losses == _eager_fit_losses(X, y, cfg, tcfg)
    assert [v for _, h in res.history for v in h] == losses[3::4]
