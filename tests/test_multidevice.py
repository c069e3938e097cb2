"""Multi-device GSPMD semantics, run in a subprocess with 8 host devices
(the main test process keeps the default 1-device config).

Verifies: (a) the sharded train step matches the single-device step
numerically, (b) the dry-run machinery (lower+compile+roofline parse) works
end-to-end on a small mesh, (c) the sharded packed projection keeps FSDP
shards resident (zero all-gathers in its HLO; theta equals the gathered
solve) and turning projection on adds no full-weight all-gather to the
production train cell.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run_subprocess(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=1200)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


def test_sharded_train_step_matches_single_device():
    out = _run_subprocess("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_reduced
        from repro.models.zoo import build, make_batch
        from repro.launch.steps import (build_train_step, param_shardings,
                                        batch_shardings, opt_shardings,
                                        projection_engine_for)
        from repro.dist.sharding import default_rules
        from repro.optim import AdamConfig, adam_init

        cfg = get_reduced("gemma_7b")
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = make_batch(cfg, 4, 32, kind="train")
        acfg = AdamConfig(lr=1e-3)
        opt = adam_init(params, acfg)

        # single device reference (solver: newton)
        engine_ref = projection_engine_for(cfg, None)
        proj0 = engine_ref.init_state(params)
        step_ref = build_train_step(model, None, None, acfg,
                                    with_projection=True)
        loss_ref, _, p_ref, _, _ = jax.jit(step_ref)(params, opt, proj0,
                                                     batch)

        # 2x4 mesh (solver: sharded — shard_map segmented Newton)
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        rules = default_rules()
        rules.update(dict(cfg.rules_overrides))
        p_sh = param_shardings(model, mesh, rules)
        params_s = jax.device_put(params, p_sh)
        opt_s = jax.device_put(opt, opt_shardings(p_sh, mesh))
        proj_s = jax.device_put(proj0, jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P()), proj0))
        batch_s = jax.device_put(batch, batch_shardings(
            jax.tree_util.tree_map(lambda x: x, batch), mesh, rules))
        step = build_train_step(model, mesh, rules, acfg,
                                with_projection=True)
        with mesh:
            loss_s, _, p_s, _, th_s = jax.jit(step)(params_s, opt_s, proj_s,
                                                    batch_s)

        print("LOSS", float(loss_ref), float(loss_s))
        assert abs(float(loss_ref) - float(loss_s)) < 2e-2, (
            float(loss_ref), float(loss_s))
        d = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32))))
                for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                                jax.tree_util.tree_leaves(p_s)))
        print("MAXDIFF", d)
        assert d < 5e-2, d
        print("OK")
    """)
    assert "OK" in out


def test_dryrun_machinery_small_mesh():
    out = _run_subprocess("""
        import jax
        from repro.configs import get_reduced
        from repro.models.zoo import build
        from repro.launch.steps import lower_cell
        from repro.roofline.analysis import parse_collectives
        import repro.models.zoo as zoo

        # shrink one shape cell so it lowers fast on 8 devices
        zoo.SHAPES["train_4k"] = dict(seq=64, batch=8, kind="train")
        zoo.SHAPES["decode_32k"] = dict(seq=64, batch=8, kind="decode")

        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        for arch in ("gemma_7b", "mixtral_8x7b", "mamba2_370m"):
            cfg = get_reduced(arch)
            model = build(cfg)
            for shape in ("train_4k", "decode_32k"):
                cell = lower_cell(model, shape, mesh, False)
                compiled = cell.compile()
                ma = compiled.memory_analysis()
                cost = compiled.cost_analysis()
                cost = cost[0] if isinstance(cost, (list, tuple)) else cost
                stats = parse_collectives(compiled.as_text())
                assert cost.get("flops", 0) > 0, (arch, shape)
                print(arch, shape, "collectives:", stats.counts)
        print("OK")
    """)
    assert "OK" in out
    # sharded cells must actually communicate
    assert "all-reduce" in out or "all-gather" in out


def test_sharded_projection_keeps_shards_resident():
    """The sharded packed projection of FSDP-sharded leaves must contain NO
    all-gather in its lowered HLO (the reshard to the canonical column
    layout is an all-to-all), and its theta / outputs must equal the
    gathered single-buffer solve."""
    out = _run_subprocess("""
        import re
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import (ProjectionSpec, ProjectionEngine,
                                init_projection_state)

        rng = np.random.default_rng(0)
        params = {
            # FSDP style: rows (the max axis) sharded over "data"
            "blocks": {"w1": jnp.asarray(rng.normal(size=(4, 64, 256)),
                                         jnp.float32)},
            "enc": {"w": jnp.asarray(rng.normal(size=(128, 512)),
                                     jnp.float32)},
        }
        specs = (ProjectionSpec(pattern=r"w1$", norm="l1inf", radius=16.0),
                 ProjectionSpec(pattern=r"enc/w", norm="l1inf", radius=8.0))
        mesh = jax.make_mesh((8,), ("data",))
        sh = {
            "blocks": {"w1": NamedSharding(mesh, P(None, "data", None))},
            "enc": {"w": NamedSharding(mesh, P("data", None))},
        }
        params_s = jax.device_put(params, sh)
        state0 = init_projection_state(params, specs)

        eng = ProjectionEngine(specs, solver="sharded", mesh=mesh)
        fn = jax.jit(lambda p, s: eng.apply(p, state=s))
        with mesh:
            lowered = fn.lower(params_s, state0)
            hlo = lowered.compile().as_text()
        ags = [l for l in hlo.splitlines() if re.search(r"all-gather", l)]
        assert not ags, "projection HLO contains all-gather:\\n" + \
            "\\n".join(ags[:5])
        assert "all-to-all" in hlo  # the reshard really is an all-to-all

        with mesh:
            out_s, st_s = fn(params_s, state0)
        ref_eng = ProjectionEngine(specs)  # gathered single-buffer solve
        out_r, st_r = ref_eng.apply(params, state=state0)
        for a, b in zip(jax.tree_util.tree_leaves(out_r),
                        jax.tree_util.tree_leaves(out_s)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5)
        k = list(st_r)[0]
        np.testing.assert_allclose(np.asarray(st_r[k]), np.asarray(st_s[k]),
                                   rtol=1e-6, atol=1e-6)
        print("THETA", np.asarray(st_s[k])[:3])
        print("OK")
    """)
    assert "OK" in out


def test_sharded_mixed_families_zero_allgather_and_match_gathered():
    """Family-registry acceptance: a mixed-family spec list (plain +
    weighted + bilevel) solved by the SHARDED engine keeps zero all-gathers
    in its HLO and matches the gathered per-family solves (theta included),
    with the weighted family's per-column weights sliced rank-locally."""
    out = _run_subprocess("""
        import re
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import (ProjectionSpec, ProjectionEngine,
                                init_projection_state)

        rng = np.random.default_rng(0)
        params = {
            "blocks": {"w1": jnp.asarray(rng.normal(size=(4, 64, 256)),
                                         jnp.float32)},
            "enc": {"w": jnp.asarray(rng.normal(size=(128, 512)),
                                     jnp.float32)},
            "dec": {"w": jnp.asarray(rng.normal(size=(64, 256)),
                                     jnp.float32)},
        }
        specs = (
            ProjectionSpec(pattern=r"w1$", norm="bilevel", radius=16.0),
            ProjectionSpec(pattern=r"enc/w", norm="l1inf", radius=8.0),
            ProjectionSpec(pattern=r"dec/w", norm="l1inf_weighted",
                           radius=8.0,
                           weights=tuple(1.0 + 0.01 * i
                                         for i in range(256))),
        )
        mesh = jax.make_mesh((8,), ("data",))
        sh = {
            "blocks": {"w1": NamedSharding(mesh, P(None, "data", None))},
            "enc": {"w": NamedSharding(mesh, P("data", None))},
            "dec": {"w": NamedSharding(mesh, P(None, "data"))},
        }
        params_s = jax.device_put(params, sh)
        state0 = init_projection_state(params, specs)

        eng = ProjectionEngine(specs, solver="sharded", mesh=mesh)
        fn = jax.jit(lambda p, s: eng.apply(p, state=s))
        with mesh:
            hlo = fn.lower(params_s, state0).compile().as_text()
        ags = [l for l in hlo.splitlines() if re.search(r"all-gather", l)]
        assert not ags, "projection HLO contains all-gather:\\n" + \
            "\\n".join(ags[:5])

        with mesh:
            out_s, st_s = fn(params_s, state0)
        ref = ProjectionEngine(specs)       # gathered per-family solves
        out_r, st_r = ref.apply(params, state=state0)
        for a, b in zip(jax.tree_util.tree_leaves(out_r),
                        jax.tree_util.tree_leaves(out_s)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5)
        assert set(st_s) == {"bilevel_packed/k1", "l1inf_packed/k1",
                             "l1inf_weighted_packed/k1"}, sorted(st_s)
        for k in st_r:
            np.testing.assert_allclose(np.asarray(st_r[k]),
                                       np.asarray(st_s[k]),
                                       rtol=1e-6, atol=1e-6)
        print("OK")
    """)
    assert "OK" in out


def test_sharded_l12_and_hoyer_families():
    """PR 10 families on a mesh: the l1,2 sharded solve keeps zero
    all-gathers with its Newton while body doing exactly ONE stacked
    f32[2, G] psum per evaluation, and its outputs/theta equal the gathered
    solve; the fused_sharded l1,2 step (stat="sq" pass 1, scale-mode pass 2)
    matches the gathered solver="fused" step; hoyer — per-leaf only —
    solves sharded-vs-gathered equal with no all-gather (columns are
    independent, so a column-sharded leaf never moves)."""
    out = _run_subprocess(_WHILE_HELPER + textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import (ProjectionSpec, ProjectionEngine,
                                init_projection_state)
        from repro.optim.adam import AdamConfig, adam_init

        rng = np.random.default_rng(0)
        params = {
            "blocks": {"w1": jnp.asarray(rng.normal(size=(4, 64, 256)),
                                         jnp.float32)},
            "enc": {"w": jnp.asarray(rng.normal(size=(128, 512)),
                                     jnp.float32)},
        }
        specs = (ProjectionSpec(pattern=r"w1$", norm="l12", radius=16.0),
                 ProjectionSpec(pattern=r"enc/w", norm="l12", radius=8.0))
        mesh = jax.make_mesh((8,), ("data",))
        sh = {
            "blocks": {"w1": NamedSharding(mesh, P(None, "data", None))},
            "enc": {"w": NamedSharding(mesh, P("data", None))},
        }
        params_s = jax.device_put(params, sh)
        state0 = init_projection_state(params, specs)

        # --- sharded packed Newton on column energies: zero all-gathers,
        # one stacked f32[2, G] psum per Eq.-(19) evaluation
        eng = ProjectionEngine(specs, solver="sharded", mesh=mesh)
        fn = jax.jit(lambda p, s: eng.apply(p, state=s))
        with mesh:
            hlo = fn.lower(params_s, state0).compile().as_text()
        ags = [l for l in hlo.splitlines() if re.search("all-gather", l)]
        assert not ags, "\\n".join(ags[:5])
        comm = {k: v for k, v in while_body_allreduces(hlo).items() if v}
        assert len(comm) == 1, comm   # only the Newton loop communicates
        (shapes,) = comm.values()
        G = 4 + 1                     # 4 stacked w1 segments + enc
        assert shapes == [f"f32[2,{G}]"], comm

        with mesh:
            out_s, st_s = fn(params_s, state0)
        ref = ProjectionEngine(specs)       # gathered packed Newton
        out_r, st_r = ref.apply(params, state=state0)
        for a, b in zip(jax.tree_util.tree_leaves(out_r),
                        jax.tree_util.tree_leaves(out_s)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5)
        assert set(st_s) == {"l12_packed/k1"}
        np.testing.assert_allclose(np.asarray(st_r["l12_packed/k1"]),
                                   np.asarray(st_s["l12_packed/k1"]),
                                   rtol=1e-6, atol=1e-6)

        # --- fused_sharded: the two-pass megakernel with column energies
        # (pass 1 stat="sq") and the scale-mode write (pass 2), rank-local
        grads = jax.tree_util.tree_map(
            lambda p: 0.01 * jnp.asarray(rng.normal(size=p.shape),
                                         jnp.float32), params)
        grads_s = jax.device_put(grads, sh)
        acfg = AdamConfig(lr=1e-3)
        opt = adam_init(params, acfg)
        ref_eng = ProjectionEngine(specs, solver="fused")
        shd_eng = ProjectionEngine(specs, solver="fused_sharded", mesh=mesh)
        ref_step = jax.jit(lambda g, o, p, s: ref_eng.projected_update(
            g, o, p, acfg, state=s))
        shd_step = jax.jit(lambda g, o, p, s: shd_eng.projected_update(
            g, o, p, acfg, state=s))
        with mesh:
            hlo_f = shd_step.lower(grads_s, opt, params_s,
                                   state0).compile().as_text()
        ags = [l for l in hlo_f.splitlines() if re.search("all-gather", l)]
        assert not ags, "\\n".join(ags[:5])
        comm = {k: v for k, v in while_body_allreduces(hlo_f).items() if v}
        assert len(comm) == 1, comm
        (shapes,) = comm.values()
        assert shapes == [f"f32[2,{G}]"], comm
        p_r, o_r, s_r = ref_step(grads, opt, params, state0)
        with mesh:
            p_s, o_s, s_s = shd_step(grads_s, opt, params_s, state0)
        d = max(float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(jax.tree_util.tree_leaves(p_r),
                                jax.tree_util.tree_leaves(p_s)))
        td = float(jnp.max(jnp.abs(s_r["l12_packed/k1"]
                                   - s_s["l12_packed/k1"])))
        print("fused_sharded l12 param maxdiff", d, "theta maxdiff", td)
        assert d <= 1e-5 and td <= 1e-5, (d, td)

        # --- hoyer rides per-leaf under every solver: a column-sharded
        # leaf solves rank-local (columns independent), no all-gather
        hp = {"hoy": {"w": jnp.asarray(rng.normal(size=(64, 128)),
                                       jnp.float32)}}
        hspecs = (ProjectionSpec(pattern=r"hoy/w", norm="hoyer",
                                 radius=0.75),)
        hsh = {"hoy": {"w": NamedSharding(mesh, P(None, "data"))}}
        hp_s = jax.device_put(hp, hsh)
        heng = ProjectionEngine(hspecs, solver="sharded", mesh=mesh)
        hfn = jax.jit(lambda p: heng.apply(p)[0])
        with mesh:
            hlo_h = hfn.lower(hp_s).compile().as_text()
            out_h = hfn(hp_s)
        ags = [l for l in hlo_h.splitlines() if re.search("all-gather", l)]
        assert not ags, "\\n".join(ags[:5])
        out_hr = ProjectionEngine(hspecs).apply(hp)[0]
        np.testing.assert_allclose(np.asarray(out_hr["hoy"]["w"]),
                                   np.asarray(out_h["hoy"]["w"]),
                                   atol=1e-6, rtol=1e-6)
        from repro.core import hoyer_sparseness
        assert float(jnp.min(hoyer_sparseness(out_h["hoy"]["w"]))) \\
            >= 0.75 - 1e-4
        print("OK")
    """))
    assert "OK" in out


def test_train_cell_projection_adds_no_full_weight_allgather():
    """lower_cell train HLO on an FSDP mesh: turning the projection ON must
    not add any all-gather at full-weight size (the sharded engine moves
    shards with all-to-all and statistics with psum)."""
    out = _run_subprocess("""
        import re
        import numpy as np, jax
        from repro.configs import get_reduced
        from repro.models.zoo import build
        from repro.launch.steps import lower_cell
        import repro.models.zoo as zoo

        zoo.SHAPES["train_4k"] = dict(seq=64, batch=8, kind="train")
        cfg = get_reduced("gemma_7b")
        model = build(cfg)
        mesh = jax.make_mesh((8, 1), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)

        def ag_sizes(hlo):
            "multiset of all-gather result element counts"
            sizes = []
            for line in hlo.splitlines():
                m = re.search(r"= \\S*?(f32|bf16|f16|s32|u32)"
                              r"\\[([0-9,]*)\\][^ ]* all-gather", line)
                if m:
                    dims = [int(d) for d in m.group(2).split(",") if d]
                    sizes.append(int(np.prod(dims)) if dims else 1)
            return sizes

        hlo_off = lower_cell(model, "train_4k", mesh, False,
                             with_projection=False).compile().as_text()
        hlo_on = lower_cell(model, "train_4k", mesh, False,
                            with_projection=True).compile().as_text()
        # full size of the projected leaf (stacked mlp w1)
        from repro.core.constraints import leaf_path_str
        flat = jax.tree_util.tree_flatten_with_path(
            model.abstract_params())[0]
        w1 = [l for p, l in flat
              if re.search(r"mlp/w1$", leaf_path_str(p))][0]
        full = int(np.prod(w1.shape))
        big_off = sorted(s for s in ag_sizes(hlo_off) if s >= full)
        big_on = sorted(s for s in ag_sizes(hlo_on) if s >= full)
        print("big all-gathers off/on:", big_off, big_on)
        assert len(big_on) <= len(big_off), (big_off, big_on)
        print("OK")
    """)
    assert "OK" in out


# HLO introspection shared by the fused_sharded tests: map every while-loop
# body computation to the shapes of the all-reduces it contains. The
# projection's Newton loop is the only while body allowed to communicate,
# and it must do so exactly once per evaluation — one stacked
# (2, num_segments) f32 psum (DESIGN.md §12).
_WHILE_HELPER = r'''
import re

def while_body_allreduces(hlo):
    "{while-body computation name: [all-reduce result shapes]}"
    bodies = set(n.lstrip("%") for n in re.findall(
        r"while\(.*?\), condition=[^,]+, body=([%\w\.\-]+)", hlo))
    out = {}
    for comp in re.split(r"\n(?=%?[\w\.\-]+ \(|ENTRY )", hlo):
        lines = comp.splitlines()
        if not lines:
            continue
        name = lines[0].split(" ")[0].lstrip("%")
        if name in bodies:
            out[name] = [s.split("{")[0] for s in
                         re.findall(r"= (\S+) all-reduce", comp)]
    return out
'''


def test_fused_sharded_cell_one_psum_per_eval_and_matches_fused():
    """The tentpole contract: the fused_sharded train cell's HLO contains
    zero all-gathers and its Newton while body exactly ONE all-reduce,
    shaped f32[2, num_segments] (the stacked Eq.-(19) numerator/denominator
    psum); params match the gathered solver="fused" step to <= 1e-5; theta
    warm starts thread across a fused -> fused_sharded solver switch."""
    out = _run_subprocess(_WHILE_HELPER + textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import ProjectionSpec, ProjectionEngine
        from repro.optim.adam import AdamConfig, adam_init

        rng = np.random.default_rng(0)
        params = {
            "enc1": {"w": jnp.asarray(rng.normal(size=(64, 256)),
                                      jnp.float32)},
            "blocks": {"w": jnp.asarray(rng.normal(size=(3, 64, 256)),
                                        jnp.float32)},
        }
        grads = jax.tree_util.tree_map(
            lambda p: 0.01 * jnp.asarray(rng.normal(size=p.shape),
                                         jnp.float32), params)
        norm = float(jnp.abs(params["enc1"]["w"]).max(axis=0).sum())
        specs = (ProjectionSpec(pattern=r"enc1/w", norm="bilevel",
                                radius=0.1 * norm),
                 ProjectionSpec(pattern=r"blocks/w", norm="bilevel",
                                radius=0.05 * norm, axis=1))
        acfg = AdamConfig(lr=1e-3)

        mesh = jax.make_mesh((8,), ("data",))
        sh = {
            "enc1": {"w": NamedSharding(mesh, P("data", None))},   # FSDP
            "blocks": {"w": NamedSharding(mesh, P(None, None, "data"))},
        }
        params_s = jax.device_put(params, sh)
        grads_s = jax.device_put(grads, sh)

        ref_eng = ProjectionEngine(specs, solver="fused")
        shd_eng = ProjectionEngine(specs, solver="fused_sharded", mesh=mesh)
        opt = adam_init(params, acfg)
        state0 = ref_eng.init_state(params)
        ref_step = jax.jit(lambda g, o, p, s: ref_eng.projected_update(
            g, o, p, acfg, state=s, with_stats=True))
        shd_step = jax.jit(lambda g, o, p, s: shd_eng.projected_update(
            g, o, p, acfg, state=s, with_stats=True))

        # --- HLO: zero all-gathers; ONE f32[2,G] psum in the Newton body
        with mesh:
            hlo = shd_step.lower(grads_s, opt, params_s,
                                 state0).compile().as_text()
        ags = [l for l in hlo.splitlines() if re.search("all-gather", l)]
        assert not ags, "\\n".join(ags[:5])
        comm = {k: v for k, v in while_body_allreduces(hlo).items() if v}
        assert len(comm) == 1, comm   # only the Newton loop communicates
        (shapes,) = comm.values()
        G = 1 + 3                     # enc1 segment + 3 stacked blocks
        assert shapes == [f"f32[2,{G}]"], comm

        # --- step 1 (cold): params + theta match the gathered fused solve
        p_r, o_r, s_r, st_r = ref_step(grads, opt, params, state0)
        with mesh:
            p_s, o_s, s_s, st_s = shd_step(grads_s, opt, params_s, state0)
        d = max(float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(jax.tree_util.tree_leaves(p_r),
                                jax.tree_util.tree_leaves(p_s)))
        k = list(s_r)[0]
        td = float(jnp.max(jnp.abs(s_r[k] - s_s[k])))
        print("step1 param maxdiff", d, "theta maxdiff", td)
        assert d <= 1e-5 and td <= 1e-5, (d, td)
        iters_cold = int(st_s[k])

        # --- step 2: WARM-started across the solver switch — hand the
        # gathered fused solver's theta to the sharded engine and vice
        # versa; both must agree and take no more evals than the cold start
        with mesh:
            p_x, o_x, s_x, st_x = shd_step(grads_s, o_r, p_r, s_r)
        p_r2, o_r2, s_r2, st_r2 = ref_step(grads, o_r, p_r, s_r)
        d2 = max(float(jnp.max(jnp.abs(a - b)))
                 for a, b in zip(jax.tree_util.tree_leaves(p_r2),
                                 jax.tree_util.tree_leaves(p_x)))
        td2 = float(jnp.max(jnp.abs(s_r2[k] - s_x[k])))
        print("switch param maxdiff", d2, "theta maxdiff", td2,
              "iters cold/warm", iters_cold, int(st_x[k]))
        assert d2 <= 1e-5 and td2 <= 1e-5, (d2, td2)
        assert int(st_x[k]) <= iters_cold, (int(st_x[k]), iters_cold)
        print("OK")
    """))
    assert "OK" in out


def test_projection_engine_for_solver_selection_and_fallback():
    """Launch policy regression: projection_engine_for picks solver="fused"
    with no mesh / a 1-device mesh and solver="fused_sharded" on every
    >1-device mesh shape; plans the megakernel cannot take (plain l1inf —
    sorted prefix sums) fall back to the shard_map Newton bit-identically
    to solver="sharded"."""
    out = _run_subprocess("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_reduced
        from repro.launch.steps import projection_engine_for
        from repro.core import ProjectionSpec, ProjectionEngine
        from repro.optim.adam import AdamConfig, adam_init

        cfg = get_reduced("gemma_7b")
        assert projection_engine_for(cfg, None).solver == "fused"
        m1 = jax.make_mesh((1,), ("data",))
        assert projection_engine_for(cfg, m1).solver == "fused"
        for shape, names in (((8,), ("data",)),
                             ((4, 2), ("data", "model"))):
            m = jax.make_mesh(shape, names)
            eng = projection_engine_for(cfg, m)
            assert eng.solver == "fused_sharded", (shape, eng.solver)
            assert eng.mesh is m

        # fallback bit-identity: plain l1inf never qualifies for the fused
        # family hook, so under solver="fused_sharded" it must replay the
        # solver="sharded" path exactly (same ops, same fp order)
        rng = np.random.default_rng(1)
        params = {"enc": {"w": jnp.asarray(rng.normal(size=(64, 256)),
                                           jnp.float32)}}
        grads = {"enc": {"w": 0.01 * jnp.asarray(
            rng.normal(size=(64, 256)), jnp.float32)}}
        specs = (ProjectionSpec(pattern=r"enc/w", norm="l1inf",
                                radius=8.0),)
        mesh = jax.make_mesh((8,), ("data",))
        sh = {"enc": {"w": NamedSharding(mesh, P("data", None))}}
        params_s = jax.device_put(params, sh)
        grads_s = jax.device_put(grads, sh)
        acfg = AdamConfig(lr=1e-3)
        opt = adam_init(params, acfg)

        outs = {}
        for solver in ("fused_sharded", "sharded"):
            eng = ProjectionEngine(specs, solver=solver, mesh=mesh)
            state0 = eng.init_state(params)
            step = jax.jit(lambda g, o, p, s, e=eng: e.projected_update(
                g, o, p, acfg, state=s))
            with mesh:
                outs[solver] = step(grads_s, opt, params_s, state0)
        for a, b in zip(jax.tree_util.tree_leaves(outs["fused_sharded"]),
                        jax.tree_util.tree_leaves(outs["sharded"])):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (
                "fallback diverged from solver='sharded'")
        print("OK")
    """)
    assert "OK" in out


def test_compressed_grad_reduce_composes_with_fused_sharded():
    """dist/compression composition: per-rank DP gradient partials reduced
    by compressed_psum inside a shard_map feed the fused_sharded
    projected_update through its grad_reduce hook in ONE jitted step. The
    projection's one-psum-per-Newton-evaluation contract must be unchanged
    by the compression mode, and the mode="none" step must match the
    gathered fused solve on the summed gradient."""
    out = _run_subprocess(_WHILE_HELPER + textwrap.dedent("""
        import functools
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from jax import shard_map
        from repro.core import ProjectionSpec, ProjectionEngine
        from repro.dist.compression import compressed_psum
        from repro.optim.adam import AdamConfig, adam_init

        D = 8
        rng = np.random.default_rng(0)
        params = {"enc": {"w": jnp.asarray(rng.normal(size=(64, 256)),
                                           jnp.float32)}}
        specs = (ProjectionSpec(pattern=r"enc/w", norm="bilevel",
                                radius=20.0),)
        # per-rank gradient partials, stacked on a leading DP dim
        gstack = {"enc": {"w": 0.01 * jnp.asarray(
            rng.normal(size=(D, 64, 256)), jnp.float32)}}
        acfg = AdamConfig(lr=1e-3)
        mesh = jax.make_mesh((8,), ("data",))
        params_s = jax.device_put(
            params, {"enc": {"w": NamedSharding(mesh, P(None, "data"))}})
        gstack_s = jax.device_put(
            gstack,
            {"enc": {"w": NamedSharding(mesh, P("data", None, None))}})

        eng = ProjectionEngine(specs, solver="fused_sharded", mesh=mesh)
        opt = adam_init(params, acfg)
        state0 = eng.init_state(params)

        def make_step(mode):
            def reduce_fn(gs):
                def body(g):
                    r = compressed_psum(g, "data", mode=mode)
                    return jax.tree_util.tree_map(lambda x: x[0], r)
                return shard_map(
                    body, mesh=mesh,
                    in_specs=(P("data", None, None),), out_specs=P(),
                    check_vma=False)(gs)

            def step(gs, o, p, s):
                return eng.projected_update(gs, o, p, acfg, state=s,
                                            grad_reduce=reduce_fn)
            return jax.jit(step)

        hlos = {}
        for mode in ("none", "int8"):
            with mesh:
                hlos[mode] = make_step(mode).lower(
                    gstack_s, opt, params_s, state0).compile().as_text()
            comm = {k: v for k, v in while_body_allreduces(
                hlos[mode]).items() if v}
            assert len(comm) == 1, (mode, comm)
            (shapes,) = comm.values()
            assert shapes == ["f32[2,1]"], (mode, comm)
        # the uncompressed composition also keeps the zero-gather contract
        # (int8's shared-scale payload exchange is an all_gather by design,
        # outside the projection)
        assert "all-gather" not in hlos["none"]

        # mode="none" == plain psum: bit-for-bit the summed gradient, so
        # the composed step must match the gathered fused solve on it
        with mesh:
            p_c, o_c, s_c = make_step("none")(gstack_s, opt, params_s,
                                              state0)
        gsum = jax.tree_util.tree_map(lambda x: x.sum(0), gstack)
        ref = ProjectionEngine(specs, solver="fused")
        p_r, o_r, s_r = jax.jit(
            lambda g, o, p, s: ref.projected_update(g, o, p, acfg,
                                                    state=s))(
            gsum, opt, params, state0)
        d = max(float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(jax.tree_util.tree_leaves(p_r),
                                jax.tree_util.tree_leaves(p_c)))
        k = list(s_r)[0]
        td = float(jnp.max(jnp.abs(s_r[k] - s_c[k])))
        print("composed param maxdiff", d, "theta maxdiff", td)
        assert d <= 1e-5 and td <= 1e-5, (d, td)

        # int8 mode runs end to end and stays a sane approximation
        with mesh:
            p_q, _, _ = make_step("int8")(gstack_s, opt, params_s, state0)
        dq = max(float(jnp.max(jnp.abs(a - b)))
                 for a, b in zip(jax.tree_util.tree_leaves(p_r),
                                 jax.tree_util.tree_leaves(p_q)))
        print("int8 param maxdiff", dq)
        assert dq < 1e-2, dq
        print("OK")
    """))
    assert "OK" in out


def test_sharded_serve_step_matches_dense():
    """The shard_map'd compact serving step (sae/serve.make_serve_step with
    a mesh): batch laid out over the data axis by dist.sharding rules,
    params replicated, output equal to the dense single-device apply."""
    out = _run_subprocess("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import ProjectionSpec, apply_constraints
        from repro.sae import SAEConfig, sae_init, sae_apply, compact_sae
        from repro.sae.serve import make_serve_step

        cfg = SAEConfig(n_features=512, n_hidden=32, n_classes=2)
        params = sae_init(jax.random.PRNGKey(0), cfg)
        spec = ProjectionSpec(pattern=r"enc1/w", norm="l1inf", radius=0.2,
                              axis=1)
        params = apply_constraints(params, (spec,))
        compact = compact_sae(params, (spec,))
        assert 0 < compact.n_selected < 512

        mesh = jax.make_mesh((8,), ("data",))
        x = jnp.asarray(np.random.default_rng(0).normal(size=(64, 512)),
                        jnp.float32)
        step = make_serve_step(compact, mesh=mesh)
        z_c, xh_c = step(compact.params, x)
        z_d, xh_d = sae_apply(params, x)
        np.testing.assert_allclose(np.asarray(z_c), np.asarray(z_d),
                                   rtol=0, atol=1e-5)
        np.testing.assert_allclose(np.asarray(xh_c),
                                   np.asarray(xh_d)[:, compact.sel],
                                   rtol=0, atol=1e-5)

        # serving is embarrassingly row-parallel: the compiled step must
        # contain no cross-rank collectives at all
        import re
        hlo = step.lower(compact.params, x).compile().as_text()
        for op in ("all-gather", "all-reduce", "all-to-all",
                   "collective-permute"):
            assert not re.search(op, hlo), op
        print("OK")
    """)
    assert "OK" in out


def test_sharded_zoo_serve_matches_single_device():
    """BatchServer with a mesh: the shard_map'd compact decode step lays
    the batch over the data axis (params replicated), produces the same
    tokens as single-device serving, and — rows being independent —
    compiles to an HLO with zero cross-rank collectives."""
    out = _run_subprocess("""
        import dataclasses, re
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import get_reduced
        from repro.models.zoo import build
        from repro.models.transformer import init_cache
        from repro.core.constraints import ProjectionSpec
        from repro.train.serve import BatchServer, ServeConfig

        cfg = dataclasses.replace(get_reduced("gemma_7b"), n_layers=2)
        cfg = dataclasses.replace(cfg, projection_specs=cfg.projection_specs
            + (ProjectionSpec(pattern="blocks/.*/mlp/w2$", norm="l1inf",
                              radius=64.0, axis=0, every_k=10),))
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        mlp = params["blocks"]["p0_global"]["mlp"]
        for name, frac in (("w1", 0.75), ("w2", 0.5)):
            arr = np.array(mlp[name])
            dead = rng.choice(arr.shape[2], int(arr.shape[2]*frac),
                              replace=False)
            arr[:, :, dead] = 0.0
            mlp[name] = jnp.asarray(arr)

        prompts = [[1, 2, 3], [4, 5], [7], [8, 9]]
        ref = BatchServer(model, batch_slots=8, scfg=ServeConfig(max_seq=32))
        ref.load_compact(params=params)
        want = ref.generate(prompts, max_new=6)

        mesh = jax.make_mesh((8,), ("data",))
        srv = BatchServer(model, batch_slots=8, scfg=ServeConfig(max_seq=32),
                          mesh=mesh)
        srv.load_compact(params=params)
        got = srv.generate(prompts, max_new=6)
        assert got == want, (got, want)

        hlo = srv.engine.step_hlo()
        for op in ("all-gather", "all-reduce", "all-to-all",
                   "collective-permute"):
            assert not re.search(op, hlo), op
        assert "input_output_alias" in hlo   # donated cache + slot state
        print("OK")
    """)
    assert "OK" in out


def test_launch_train_lays_state_over_every_device():
    """repro.launch.train builds its mesh from every visible device: the
    params and optimizer state are laid out over all 8, and the losses
    match the same run on one device."""
    out = _run_subprocess("""
        import numpy as np, jax
        from repro.configs import get_reduced
        from repro.data.pipeline import LMBatcher, SyntheticLM
        from repro.launch import train as launch_train
        from repro.models.zoo import build
        from repro.train.loop import TrainConfig, train

        argv = ["--arch", "gemma_7b", "--reduced", "--steps", "3",
                "--batch", "8", "--seq", "16", "--resume", "none"]
        out8 = launch_train.main(argv)
        devs = {d for leaf in jax.tree_util.tree_leaves(out8["params"])
                for d in leaf.sharding.device_set}
        assert len(devs) == 8, devs
        cfg = get_reduced("gemma_7b")
        out1 = train(build(cfg), LMBatcher(SyntheticLM(cfg.vocab), 8, 16),
                     TrainConfig(steps=3, lr=3e-4), resume=False)
        print("losses", out8["losses"], out1["losses"])
        np.testing.assert_allclose(out8["losses"], out1["losses"],
                                   rtol=1e-5)
        print("OK")
    """)
    assert "OK" in out
