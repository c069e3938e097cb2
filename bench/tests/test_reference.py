"""The plain references against the program at small sizes on the CPU."""
import json
import pathlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from bench import data, plain, weights
from bench.run import load_module

from .conftest import TINY_LM, TINY_SAE

BENCH = pathlib.Path(__file__).resolve().parents[1]


def reference(name):
    return load_module(BENCH / "reference" / f"{name}.py", f"ref_{name}")


@pytest.fixture(scope="module")
def sae_cfg():
    cfg = json.loads((BENCH / "configs" / "sae-table1.json").read_text())
    return {**cfg, **TINY_SAE}


def test_sae_init_and_loss_match_the_program(sae_cfg):
    from repro.sae import SAEConfig, sae_init, sae_loss
    ref = reference("sae-table1")
    X, y, _, _ = data.sae_table(sae_cfg, 5)
    mcfg = SAEConfig(n_features=sae_cfg["n_features"],
                     n_hidden=sae_cfg["n_hidden"])
    prog_p = sae_init(jax.random.PRNGKey(123), mcfg)
    ref_p = ref.init(123, sae_cfg)
    for a, b in zip(jax.tree_util.tree_leaves(prog_p),
                    jax.tree_util.tree_leaves(ref_p)):
        np.testing.assert_array_equal(a, b)
    x, yy = jnp.asarray(X[:32]), jnp.asarray(y[:32])
    lp, gp = jax.value_and_grad(lambda p: sae_loss(p, x, yy, mcfg)[0])(prog_p)
    lr, gr = jax.value_and_grad(ref.loss)(ref_p, x, yy, sae_cfg)
    np.testing.assert_allclose(lp, lr, rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(gp), jax.tree_util.tree_leaves(gr)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("axis", [0, 1])
def test_l1inf_projection_matches_the_programs_reference(axis):
    from repro.core import get_family
    w = jax.random.normal(jax.random.PRNGKey(axis), (40, 24))
    radius = 0.2 * float(plain.l1inf_norm(w, axis))
    got = plain.project_l1inf(w, radius, axis)
    want = get_family("l1inf").reference(w, radius, axis=axis)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(plain.l1inf_norm(got, axis)) <= radius * (1 + 1e-5)
    inside = plain.project_l1inf(w, 2 * float(plain.l1inf_norm(w, axis)), axis)
    np.testing.assert_array_equal(inside, w)


def test_adam_step_matches_the_programs_adam():
    from repro.optim import AdamConfig, adam_init, adam_update
    p = {"a": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones(4)}
    g = {"a": jnp.full((2, 3), 0.7), "b": jnp.linspace(-2, 2, 4)}
    cfg = AdamConfig(lr=1e-2)
    want, st = adam_update(g, adam_init(p, cfg), p, cfg)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, p)
    got, m, v, gc = plain.adam_step(p, g, zeros, zeros, 1, 1e-2)
    for k in p:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(m[k], st.mu[k], rtol=1e-6)


def test_mamba2_reference_matches_the_program_model():
    from repro.core import ProjectionSpec
    from repro.models.transformer import ArchConfig
    from repro.models.zoo import build
    ref = reference("mamba2-370m")
    cfg = dict(TINY_LM)
    arch = dict(cfg["arch"], pattern=tuple(cfg["arch"]["pattern"]))
    model = build(ArchConfig(**arch, projection_specs=tuple(
        ProjectionSpec(**p) for p in cfg["projection"])))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    mine = jax.tree_util.tree_map(lambda s: (s.shape, s.dtype), shapes)
    theirs = jax.tree_util.tree_map(lambda s: (s.shape, s.dtype),
                                    ref.layout(cfg))
    assert mine == theirs
    params = weights.init(shapes, 77)
    tok = jnp.asarray(data.token_rows(77, 0, 2, 128, arch["vocab"], 1.4))
    batch = {"tokens": tok[:, :-1], "labels": tok[:, 1:]}
    lp, gp = jax.value_and_grad(lambda p: model.loss(p, batch)[0])(params)
    lr, gr = jax.value_and_grad(ref.loss)(params, tok[:, :-1], tok[:, 1:], cfg)
    np.testing.assert_allclose(lp, lr, rtol=1e-5)
    for k, a in plain.leaf_paths(gp).items():
        b = plain.leaf_paths(gr)[k]
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * scale, k
