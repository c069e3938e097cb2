"""Model-generic compact serving (serve/compact.py, serve/refresh.py).

Covers the PR-6 contract (DESIGN.md §10): exact forward/decode parity for
MLP hidden-unit compaction and MoE expert compaction, scatter-back
exactness for residual-output (w2) compaction, the BatchServer ragged
prompt regression, hot refresh + live re-compaction with zero retraces,
and re-compaction monotonicity (support never grows; unchanged support is
the identity). Also the satellite-1 shared test: sae's ``compact_leaf``
IS ``core.compact_columns``.
"""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_reduced
from repro.core.constraints import ProjectionSpec
from repro.core.l1inf import compact_columns
from repro.models.zoo import build, make_batch
from repro.models.transformer import forward, init_cache, decode_step
from repro.models.layers import scatter_residual
from repro.serve import (compact_model, refresh_model, recompact_model,
                         support_selection)
from repro.train.serve import BatchServer, ServeConfig


def _kill_columns(leaf, frac, axis, seed=0):
    """Zero a random fraction of columns — simulated projected training."""
    rng = np.random.default_rng(seed)
    arr = np.array(leaf)
    dead = rng.choice(arr.shape[axis], int(arr.shape[axis] * frac),
                      replace=False)
    idx = [slice(None)] * arr.ndim
    idx[axis] = dead
    arr[tuple(idx)] = 0.0
    return jnp.asarray(arr)


def _mlp_setup(w2_spec=True):
    """Reduced gemma (pure MLP) with sparsified w1 (+ optionally w2)."""
    cfg = dataclasses.replace(get_reduced("gemma_7b"), n_layers=2)
    specs = cfg.projection_specs
    if w2_spec:
        specs = specs + (ProjectionSpec(pattern="blocks/.*/mlp/w2$",
                                        norm="l1inf", radius=64.0, axis=0,
                                        every_k=10),)
    cfg = dataclasses.replace(cfg, projection_specs=specs)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    mlp = params["blocks"]["p0_global"]["mlp"]
    mlp["w1"] = _kill_columns(mlp["w1"], 0.75, axis=2, seed=0)
    if w2_spec:
        mlp["w2"] = _kill_columns(mlp["w2"], 0.50, axis=2, seed=1)
    return cfg, model, params


def test_mlp_compact_forward_and_decode_exact():
    """Hidden-unit (w1/w3/w2-rows) + residual-output (w2-cols, scatter-back)
    compaction both reproduce the dense model bit-exactly: dead columns are
    structural zeros, so the gathered GEMMs sum the same nonzero terms."""
    cfg, model, params = _mlp_setup()
    cm = compact_model(params, cfg.projection_specs)
    assert cm.compaction_ratios() == {
        "blocks/p0_global/mlp/w1": 0.25, "blocks/p0_global/mlp/w2": 0.5}
    # coupled gathers: w3 cols and w2 rows follow w1; w2 cols are primary
    mlp = cm.params["blocks"]["p0_global"]["mlp"]
    assert mlp["w1"].shape == (2, 64, 32)
    assert mlp["w3"].shape == (2, 64, 32)
    assert mlp["w2"].shape == (2, 32, 32)
    assert mlp["w2_sel"].shape == (2, 32)

    batch = make_batch(cfg, 2, 16, kind="train")
    dense, _ = forward(params, batch, cfg)
    compact, _ = forward(cm.params, batch, cfg)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(compact))

    cache_d = init_cache(cfg, 2, 16, jnp.float32)
    cache_c = init_cache(cfg, 2, 16, jnp.float32)
    t = jnp.asarray([[3], [5]], jnp.int32)
    for pos in range(4):
        od, cache_d = decode_step(params, cache_d, t, jnp.asarray(pos), cfg)
        oc, cache_c = decode_step(cm.params, cache_c, t, jnp.asarray(pos),
                                  cfg)
    np.testing.assert_array_equal(np.asarray(od), np.asarray(oc))


def test_moe_expert_compact_exact():
    """MoE expert w1/w3/w2 compaction over the stacked expert dim (union
    support across experts) reproduces the dense forward bit-exactly."""
    cfg = get_reduced("mixtral_8x7b")
    specs = cfg.projection_specs + (ProjectionSpec(
        pattern="blocks/.*/moe/w2$", norm="l1inf", radius=64.0, axis=0,
        every_k=10),)
    cfg = dataclasses.replace(cfg, projection_specs=specs)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(1))
    moe = params["blocks"]["p0_local"]["moe"]
    # w1: (cycles, E, d, ff) — kill ff columns; w2: (..., ff, d) — kill d cols
    moe["w1"] = _kill_columns(moe["w1"], 0.75, axis=3, seed=2)
    moe["w2"] = _kill_columns(moe["w2"], 0.50, axis=3, seed=3)
    cm = compact_model(params, cfg.projection_specs)
    assert cm.params["blocks"]["p0_local"]["moe"]["w1"].shape[-1] == 32
    assert cm.params["blocks"]["p0_local"]["moe"]["w2"].shape[-1] == 32

    batch = make_batch(cfg, 2, 16, kind="train")
    dense, _ = forward(params, batch, cfg)
    compact, _ = forward(cm.params, batch, cfg)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(compact))


def test_scatter_residual_matches_dense_gemm():
    """scatter_residual(h @ w2[:, sel], sel, d) == h @ w2 when the killed
    columns are exact zeros — the residual-stream exactness argument."""
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.normal(size=(3, 16)).astype(np.float32))
    w2 = np.asarray(rng.normal(size=(16, 24)).astype(np.float32))
    w2[:, ::3] = 0.0
    sel = np.flatnonzero(np.any(w2 != 0, axis=0)).astype(np.int32)
    dense = h @ jnp.asarray(w2)
    compact = np.asarray(scatter_residual(h @ jnp.asarray(w2[:, sel]),
                                          jnp.asarray(sel), 24))
    dead = np.setdiff1d(np.arange(24), sel)
    np.testing.assert_array_equal(compact[:, dead], 0.0)
    np.testing.assert_array_equal(np.asarray(dense)[:, dead], 0.0)
    # a 24-wide and a 16-wide GEMM may accumulate each dot product in a
    # different order, so the surviving columns agree to rounding only
    np.testing.assert_allclose(compact[:, sel], np.asarray(dense)[:, sel],
                               rtol=1e-6)


def test_unmatched_spec_leaf_is_skipped_dense():
    """A spec-matched leaf no CompactRule covers (ssm/wx) is left dense and
    reported, not silently mis-compacted."""
    cfg = get_reduced("mamba2_370m")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cm = compact_model(params, cfg.projection_specs)
    assert any("ssm/wx" in p for p in cm.skipped)
    assert not cm.sels        # nothing compacted, params unchanged
    a = jax.tree_util.tree_leaves(params)
    b = jax.tree_util.tree_leaves(cm.params)
    assert all(x.shape == y.shape for x, y in zip(a, b))


def test_wrong_axis_spec_refused():
    """A spec pruning an axis its rule has no exactness argument for raises
    instead of serving wrong results."""
    cfg = dataclasses.replace(get_reduced("gemma_7b"), n_layers=2)
    bad = (ProjectionSpec(pattern="blocks/.*/mlp/w1$", norm="l1inf",
                          radius=64.0, axis=1, every_k=10),)
    cfg2 = dataclasses.replace(cfg, projection_specs=bad)
    model = build(cfg2)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="exactness"):
        compact_model(params, cfg2.projection_specs)


def test_compact_leaf_is_compact_columns():
    """Satellite 1: sae's compact_leaf is a shim over the ONE core gather
    primitive — identical results on the same LeafSupport."""
    from repro.sae.serve import compact_leaf
    rng = np.random.default_rng(5)
    w = np.asarray(rng.normal(size=(40, 8)).astype(np.float32))
    w[rng.choice(40, 30, replace=False), :] = 0.0
    params = {"enc1": {"w": jnp.asarray(w)}}
    spec = ProjectionSpec(pattern="enc1/w$", norm="l1inf", radius=1.0,
                          axis=1)
    sup = support_selection(params, (spec,))["enc1/w"]
    a = compact_leaf(params["enc1"]["w"], sup)
    b = compact_columns(params["enc1"]["w"], sup.sel, axis=sup.col_axis)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert a.shape == (sup.n_selected, 8)


# --------------------------- BatchServer ------------------------------------


def test_ragged_prompts_match_per_prompt_outputs():
    """Regression (satellite 2): a ragged batch must produce the SAME
    output per row as serving each prompt alone — short rows used to re-feed
    left-aligned pad tokens into their cache."""
    cfg = dataclasses.replace(get_reduced("gemma_7b"), n_layers=2)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    server = BatchServer(model, batch_slots=3, scfg=ServeConfig(max_seq=32))
    server.load(params)
    ragged = server.generate([[1, 2, 3], [4, 5], [7]], max_new=6)
    for i, prompt in enumerate([[1, 2, 3], [4, 5], [7]]):
        alone = server.generate([prompt], max_new=6)
        assert ragged[i] == alone[0], f"row {i} diverges from solo serving"


def test_batch_server_compact_matches_dense():
    """load_compact serves the compacted checkpoint through the generic
    layer and reproduces the dense server's outputs exactly."""
    cfg, model, params = _mlp_setup()
    dense = BatchServer(model, batch_slots=2, scfg=ServeConfig(max_seq=32))
    dense.load(params)
    compact = BatchServer(model, batch_slots=2, scfg=ServeConfig(max_seq=32))
    compact.load_compact(params=params)
    assert compact.compact is not None
    prompts = [[1, 2, 3], [4, 5]]
    assert dense.generate(prompts, max_new=6) == \
        compact.generate(prompts, max_new=6)


def test_hot_refresh_and_recompact_never_retrace():
    """Satellite 3 + tentpole: hot refresh and live re-compaction keep all
    shapes frozen, so the jit'd decode step traces exactly once across
    load -> refresh -> recompact."""
    cfg, model, params = _mlp_setup()
    server = BatchServer(model, batch_slots=2, scfg=ServeConfig(max_seq=32))
    server.load_compact(params=params)
    prompts = [[1, 2, 3], [4, 5]]
    out0 = server.generate(prompts, max_new=4)
    assert server.n_traces == 1

    # hot refresh: new values, same support
    params2 = jax.tree_util.tree_map(lambda a: a * 1.5, params)
    server.refresh(params2)
    server.generate(prompts, max_new=4)
    assert server.n_traces == 1

    # live re-compaction: kill one more live column, support shrinks
    w1_path = "blocks/p0_global/mlp/w1"
    victim = int(server.compact.sels[w1_path][0])
    mlp2 = params2["blocks"]["p0_global"]["mlp"]
    arr = np.array(mlp2["w1"])
    arr[:, :, victim] = 0.0
    mlp2["w1"] = jnp.asarray(arr)
    live_before = server.compact.live[w1_path]
    server.recompact(params2)
    assert server.compact.live[w1_path] == live_before - 1
    assert server.compact.slot_width(w1_path) == live_before  # slot frozen
    out2 = server.generate(prompts, max_new=4)
    assert server.n_traces == 1, "re-compaction must not retrace"

    # recompacted serving still matches the dense model
    dense = BatchServer(model, batch_slots=2, scfg=ServeConfig(max_seq=32))
    dense.load(params2)
    assert out2 == dense.generate(prompts, max_new=4)
    assert out0 is not None


def test_recompact_monotonicity():
    """Satellite 3: support growth across checkpoints raises (frozen-mask
    contract), and recompacting an unchanged support is the identity."""
    cfg, model, params = _mlp_setup(w2_spec=False)
    cm = compact_model(params, cfg.projection_specs)
    w1_path = "blocks/p0_global/mlp/w1"

    # identity: same checkpoint -> same sel array, same compact leaves
    cm_id = recompact_model(cm, params)
    np.testing.assert_array_equal(cm_id.sels[w1_path], cm.sels[w1_path])
    for a, b in zip(jax.tree_util.tree_leaves(cm.params),
                    jax.tree_util.tree_leaves(cm_id.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # growth: revive a dead column -> ValueError, both recompact and refresh
    grown = jax.tree_util.tree_map(lambda a: a, params)
    mlp = grown["blocks"]["p0_global"]["mlp"]
    arr = np.array(mlp["w1"])
    dead_col = next(j for j in range(arr.shape[2])
                    if j not in set(cm.sels[w1_path].tolist()))
    arr[:, :, dead_col] = 1.0
    mlp["w1"] = jnp.asarray(arr)
    with pytest.raises(ValueError, match="monotonicity"):
        recompact_model(cm, grown)
    with pytest.raises(ValueError, match="slot set"):
        refresh_model(cm, grown)
