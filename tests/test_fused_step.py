"""The fused optimizer+projection megakernel (kernels/fused_step, §11).

Covers: Pallas-interpret vs jnp-reference equality of both passes (odd
shapes, transpose, stacked leaves, masks, bf16 params with fp32 moments),
fused-vs-unfused ``projected_update`` equality across constraint families
(bilevel takes the megakernel; plain/weighted fall back bit-exactly),
warm-start theta threading through the fused solve, ``every_k`` gating
falling back to the unfused path, and the per-plan engine counters
distinguishing fused from fallback solves.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (ProjectionEngine, ProjectionSpec, engine_counters,
                        engine_counters_reset)
from repro.core.constraints import build_packed_plans
from repro.kernels.fused_step import (fused_adam_clip_apply,
                                      fused_adam_colstats)
from repro.optim import AdamConfig, adam_init, adam_update


def _tol(a, b, tol=2e-6):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=tol, rtol=tol)


def _leaf_set(seed, shape, dtype=jnp.float32):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 5)
    g, m, v, p, mk = [jax.random.normal(k, shape, jnp.float32) for k in ks]
    v = jnp.abs(v)
    mask = (mk > -0.5).astype(jnp.float32)
    return (g.astype(dtype), m, v, p.astype(dtype), mask)


# ---------------------------------------------------------------------------
# kernel vs reference (Pallas interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(48, 200), (33, 130), (3, 17, 96)])
@pytest.mark.parametrize("transpose", [False, True])
def test_pallas_matches_ref_both_passes(shape, transpose):
    g, m, v, p, mask = _leaf_set(0, shape)
    cfg = AdamConfig(lr=1e-2, weight_decay=0.01)
    kw = dict(cfg=cfg, lr_t=jnp.float32(1e-2), b1c=jnp.float32(0.3),
              b2c=jnp.float32(0.05), mask=mask, transpose=transpose)
    r = fused_adam_colstats(g, m, v, p, scale=jnp.float32(0.9),
                            impl="ref", **kw)
    q = fused_adam_colstats(g, m, v, p, scale=jnp.float32(0.9),
                            impl="pallas", interpret=True, **kw)
    for a, b in zip(r, q):
        assert a.shape == b.shape
        _tol(a, b, 2e-6)
    lead, mcols = r[2].shape
    mu = jnp.abs(jax.random.normal(jax.random.PRNGKey(9), (lead, mcols)))
    xr = fused_adam_clip_apply(r[0], r[1], p, mu, impl="ref", **kw)
    xq = fused_adam_clip_apply(r[0], r[1], p, mu, impl="pallas",
                               interpret=True, **kw)
    # interpret mode compiles the kernel body as one fused XLA computation,
    # so FMA contraction can wobble the last ulp vs the eager reference
    _tol(xr, xq, 1e-6)


def test_pallas_matches_ref_bf16_params_fp32_moments():
    g, m, v, p, _ = _leaf_set(1, (32, 160), dtype=jnp.bfloat16)
    cfg = AdamConfig(lr=1e-2, moment_dtype=jnp.float32)
    kw = dict(cfg=cfg, lr_t=jnp.float32(1e-2), b1c=jnp.float32(0.3),
              b2c=jnp.float32(0.05))
    r = fused_adam_colstats(g, m, v, p, impl="ref", **kw)
    q = fused_adam_colstats(g, m, v, p, impl="pallas", interpret=True, **kw)
    assert r[0].dtype == jnp.float32          # moments stay fp32
    for a, b in zip(r, q):
        _tol(a, b, 1e-6)
    mu = jnp.full(r[2].shape, 0.5, jnp.float32)
    xr = fused_adam_clip_apply(r[0], r[1], p, mu, impl="ref", **kw)
    xq = fused_adam_clip_apply(r[0], r[1], p, mu, impl="pallas",
                               interpret=True, **kw)
    assert xr.dtype == jnp.bfloat16           # params written in their dtype
    _tol(np.asarray(xr, np.float32), np.asarray(xq, np.float32), 1e-2)


def test_colstats_describe_the_rounded_update():
    """The statistics are taken on u AFTER rounding through the param dtype
    (the matrix pass 2 actually clips), not on the fp32 intermediate."""
    g, m, v, p, _ = _leaf_set(2, (16, 128), dtype=jnp.bfloat16)
    cfg = AdamConfig(lr=1e-2)
    kw = dict(cfg=cfg, lr_t=jnp.float32(1e-2), b1c=jnp.float32(0.3),
              b2c=jnp.float32(0.05))
    m_st, v_st, colsum, colmax = fused_adam_colstats(g, m, v, p,
                                                     impl="ref", **kw)
    # identity clip: pass 2 reproduces u itself — its stats must equal the
    # pass-1 statistics exactly
    mu = jnp.full(colsum.shape, 1e30, jnp.float32)
    u = fused_adam_clip_apply(m_st, v_st, p, mu, impl="ref", **kw)
    a = jnp.abs(u[None].astype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(jnp.max(a, axis=1)),
                                  np.asarray(colmax))
    _tol(jnp.sum(a, axis=1), colsum, 1e-4)


# ---------------------------------------------------------------------------
# fused projected_update vs the unfused engine
# ---------------------------------------------------------------------------

def _tree(seed=0):
    key = jax.random.PRNGKey(seed)
    return {
        "enc1": {"w": jax.random.normal(jax.random.fold_in(key, 0),
                                        (24, 50)),
                 "b": jnp.zeros((50,))},
        "blocks": {"w": jax.random.normal(jax.random.fold_in(key, 1),
                                          (3, 16, 40))},
    }


def _run(engine, specs, acfg, steps=4, seed=0, mask=None):
    params = _tree(seed)
    grads = jax.tree_util.tree_map(
        lambda p: jax.random.normal(jax.random.PRNGKey(7), p.shape), params)
    opt = adam_init(params, acfg)
    state = engine.init_state(params)
    step = jax.jit(lambda g, o, p, s: engine.projected_update(
        g, o, p, acfg, mask=mask, state=s, with_stats=True))
    for _ in range(steps):
        params, opt, state, stats = step(grads, opt, params, state)
    return params, opt, state, stats


def _assert_same_run(specs, acfg, mask=None, tol=2e-6, seed=0):
    pn, on, sn, stn = _run(ProjectionEngine(specs), specs, acfg,
                           seed=seed, mask=mask)
    pf, of, sf, stf = _run(ProjectionEngine(specs, solver="fused"), specs,
                           acfg, seed=seed, mask=mask)
    for a, b in zip(jax.tree_util.tree_leaves(pn),
                    jax.tree_util.tree_leaves(pf)):
        _tol(a, b, tol)
    for a, b in zip(jax.tree_util.tree_leaves(on.mu),
                    jax.tree_util.tree_leaves(of.mu)):
        _tol(a, b, tol)
    assert set(sn) == set(sf)
    for k in sn:
        _tol(sn[k], sf[k], tol)
    return stn, stf


BILEVEL = (ProjectionSpec(pattern=r"enc1/w", norm="bilevel", radius=4.0),
           ProjectionSpec(pattern=r"blocks/w", norm="bilevel", radius=2.0,
                          axis=1))


def test_fused_equals_newton_bilevel():
    acfg = AdamConfig(lr=1e-2, weight_decay=0.01, clip_norm=1.0)
    engine_counters_reset()
    _assert_same_run(BILEVEL, acfg)
    counts = engine_counters()
    assert counts["bilevel_packed/k1/fused"] > 0
    assert counts["bilevel_packed/k1/newton"] > 0   # the unfused twin's runs
    engine_counters_reset()


def test_fused_equals_newton_with_mask():
    mask = jax.tree_util.tree_map(jnp.ones_like, _tree())
    mask["enc1"]["w"] = mask["enc1"]["w"].at[:, :12].set(0.0)
    acfg = AdamConfig(lr=1e-2, weight_decay=0.05)
    _assert_same_run(BILEVEL, acfg, mask=mask)
    # and the freeze really holds on the fused path
    pf, _, _, _ = _run(ProjectionEngine(BILEVEL, solver="fused"), BILEVEL,
                       acfg, mask=mask)
    np.testing.assert_array_equal(np.asarray(pf["enc1"]["w"][:, :12]), 0.0)


@pytest.mark.parametrize("norm,extra", [
    ("l1inf", {}),
    ("l1inf_weighted", {"weights": tuple(np.linspace(0.5, 2.0, 50))}),
])
def test_fused_falls_back_for_unfusable_families(norm, extra):
    """Plain/weighted need per-column sorted prefix sums — no streaming
    hook, so solver='fused' must replay the unfused path bit-exactly."""
    specs = (ProjectionSpec(pattern=r"enc1/w", norm=norm, radius=4.0,
                            **extra),)
    acfg = AdamConfig(lr=1e-2)
    engine_counters_reset()
    _assert_same_run(specs, acfg, tol=0.0)      # same code path: bit-equal
    counts = engine_counters()
    assert not any(k.endswith("/fused") for k in counts), counts
    engine_counters_reset()


def test_fused_every_k_gating_falls_back():
    """A gated bilevel plan (every_k > 1) cannot fuse (pass 1 must not move
    the params on skipped steps); it solves through the unfused path while
    a k=1 plan in the same spec list still takes the megakernel."""
    specs = (ProjectionSpec(pattern=r"enc1/w", norm="bilevel", radius=4.0),
             ProjectionSpec(pattern=r"blocks/w", norm="bilevel", radius=2.0,
                            axis=1, every_k=3))
    acfg = AdamConfig(lr=1e-2)
    engine_counters_reset()
    stn, stf = _assert_same_run(specs, acfg)
    counts = engine_counters()
    assert counts["bilevel_packed/k1/fused"] > 0
    assert counts["bilevel_packed/k3/newton"] > 0
    assert "bilevel_packed/k3/fused" not in counts
    engine_counters_reset()


def test_fused_warm_start_threads_theta():
    acfg = AdamConfig(lr=1e-3)
    engine = ProjectionEngine(BILEVEL, solver="fused")
    params = _tree(3)
    grads = jax.tree_util.tree_map(
        lambda p: jax.random.normal(jax.random.PRNGKey(5), p.shape) * 0.01,
        params)
    opt = adam_init(params, acfg)
    state = engine.init_state(params)
    step = jax.jit(lambda g, o, p, s: engine.projected_update(
        g, o, p, acfg, state=s, with_stats=True))
    iters = []
    for _ in range(6):
        params, opt, state, stats = step(grads, opt, params, state)
        iters.append(int(stats["bilevel_packed/k1"]))
    assert max(iters[2:]) <= 2, iters           # steady state: bootstrap only
    assert all(float(v.min()) >= 0 for v in state.values())


def test_fused_plan_detection_is_static():
    """Plan qualification happens at trace time on shapes alone."""
    params = _tree(0)
    plans, per_leaf = build_packed_plans(params, BILEVEL)
    assert len(plans) == 1 and not per_leaf
    plan = plans[0]
    sids = plan.virtual_seg_ids()
    assert sids.shape == (plan.virtual_num_cols(),)
    assert sids.shape[0] == 50 + 3 * 16          # no lane padding
    assert sids.max() == plan.num_segments - 1
    # entry order matches the concatenated statistics layout
    spans = np.concatenate([
        np.repeat(np.arange(e.lead) + e.seg_start, e.m)
        for e in plan.entries])
    np.testing.assert_array_equal(sids, spans)
    w = plan.virtual_col_weights()
    np.testing.assert_array_equal(w, np.ones_like(w))


def test_fused_bf16_params_fp32_moments_end_to_end():
    params = jax.tree_util.tree_map(
        lambda p: p.astype(jnp.bfloat16), _tree(4))
    grads = jax.tree_util.tree_map(
        lambda p: jax.random.normal(jax.random.PRNGKey(8), p.shape,
                                    jnp.float32).astype(jnp.bfloat16),
        params)
    acfg = AdamConfig(lr=1e-2, moment_dtype=jnp.float32)
    outs = {}
    for solver in ("newton", "fused"):
        engine = ProjectionEngine(BILEVEL, solver=solver)
        opt = adam_init(params, acfg)
        state = engine.init_state(params)
        p = params
        for _ in range(3):
            p, opt, state = jax.jit(
                lambda g, o, pp, s: engine.projected_update(
                    g, o, pp, acfg, state=s))(grads, opt, p, state)
        outs[solver] = (p, opt)
    for a, b in zip(jax.tree_util.tree_leaves(outs["newton"][0]),
                    jax.tree_util.tree_leaves(outs["fused"][0])):
        assert a.dtype == b.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    for a, b in zip(jax.tree_util.tree_leaves(outs["newton"][1].mu),
                    jax.tree_util.tree_leaves(outs["fused"][1].mu)):
        assert a.dtype == jnp.float32
        _tol(a, b)


# ---------------------------------------------------------------------------
# the l1,2 family through the megakernel (PR 10: stat="sq", mode="scale")
# ---------------------------------------------------------------------------

L12 = (ProjectionSpec(pattern=r"enc1/w", norm="l12", radius=4.0),
       ProjectionSpec(pattern=r"blocks/w", norm="l12", radius=2.0, axis=1))


def test_fused_equals_newton_l12():
    """l1,2 qualifies for the two-pass megakernel (from_colstats streams
    column energies); the fused step must match the packed Newton to fp
    reduction order, counted under its own fused key."""
    acfg = AdamConfig(lr=1e-2, weight_decay=0.01, clip_norm=1.0)
    engine_counters_reset()
    _assert_same_run(L12, acfg, tol=1e-5)
    counts = engine_counters()
    assert counts["l12_packed/k1/fused"] > 0
    assert counts["l12_packed/k1/newton"] > 0   # the unfused twin's runs
    engine_counters_reset()


def test_fused_l12_bf16_params_fp32_moments():
    params = jax.tree_util.tree_map(
        lambda p: p.astype(jnp.bfloat16), _tree(6))
    grads = jax.tree_util.tree_map(
        lambda p: jax.random.normal(jax.random.PRNGKey(8), p.shape,
                                    jnp.float32).astype(jnp.bfloat16),
        params)
    acfg = AdamConfig(lr=1e-2, moment_dtype=jnp.float32)
    outs = {}
    for solver in ("newton", "fused"):
        engine = ProjectionEngine(L12, solver=solver)
        opt = adam_init(params, acfg)
        state = engine.init_state(params)
        p = params
        for _ in range(3):
            p, opt, state = jax.jit(
                lambda g, o, pp, s: engine.projected_update(
                    g, o, pp, acfg, state=s))(grads, opt, p, state)
        outs[solver] = p
    for a, b in zip(jax.tree_util.tree_leaves(outs["newton"]),
                    jax.tree_util.tree_leaves(outs["fused"])):
        assert a.dtype == b.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_fused_l12_warm_start_survives_solver_switch():
    """Theta threads under ONE plan key whichever solver runs — switching
    newton -> fused mid-run keeps the warm start: steady-state solves stay
    in the bootstrap pair of Eq.-(19) evaluations."""
    acfg = AdamConfig(lr=1e-3)
    params = _tree(7)
    grads = jax.tree_util.tree_map(
        lambda p: jax.random.normal(jax.random.PRNGKey(5), p.shape) * 0.01,
        params)
    opt = adam_init(params, acfg)
    en = ProjectionEngine(L12)
    ef = ProjectionEngine(L12, solver="fused")
    state = en.init_state(params)
    step_n = jax.jit(lambda g, o, p, s: en.projected_update(
        g, o, p, acfg, state=s, with_stats=True))
    step_f = jax.jit(lambda g, o, p, s: ef.projected_update(
        g, o, p, acfg, state=s, with_stats=True))
    for _ in range(4):
        params, opt, state, stats = step_n(grads, opt, params, state)
    iters = []
    for _ in range(4):
        params, opt, state, stats = step_f(grads, opt, params, state)
        iters.append(int(stats["l12_packed/k1"]))
    assert max(iters[1:]) <= 2, iters
    assert all(float(v.min()) >= 0 for v in state.values())


def test_fused_no_specs_passthrough():
    engine = ProjectionEngine((), solver="fused")
    params = _tree(5)
    grads = jax.tree_util.tree_map(lambda p: 0.01 * jnp.ones_like(p), params)
    acfg = AdamConfig(lr=1e-2)
    opt = adam_init(params, acfg)
    p1, o1, s1 = engine.projected_update(grads, opt, params, acfg, state={})
    p2, o2 = adam_update(grads, opt, params, acfg)
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert s1 == {}


def test_backend_resolution_never_interprets_on_tpu(monkeypatch):
    """One helper picks kernel vs jnp twin and interpret mode: on a TPU
    backend the defaults are the compiled kernel; elsewhere the jnp twin /
    the interpreter; an explicit argument always wins."""
    from repro.kernels.backend import resolve_impl
    assert resolve_impl("auto") == ("ref", True)
    assert resolve_impl("pallas") == ("pallas", True)
    assert resolve_impl("pallas", False) == ("pallas", False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_impl("auto") == ("pallas", False)
    assert resolve_impl("pallas") == ("pallas", False)
    assert resolve_impl("ref") == ("ref", False)
    assert resolve_impl("pallas", True) == ("pallas", True)
    with pytest.raises(ValueError):
        resolve_impl("triton")
