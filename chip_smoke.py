#!/usr/bin/env python3
"""Smoke test of the main path on a TPU, through the entry points a user calls.

    python chip_smoke.py               # one chip
    python chip_smoke.py --chips 4     # four chips: the sharded fused step only

One chip runs, in one process and in order:

  device   what JAX sees;
  sae      the paper's Algorithm 3 at the paper's size (Table 1's synthetic
           set, d=10000, 96 hidden units): ``train_sae`` under the l1,inf
           ball (the packed Newton) and under the bilevel ball (the fused
           Pallas step), then compact serving of the l1,inf model against
           the dense forward, then the compiled Pallas l1,inf engine against
           the Newton solver on the trained weight;
  lm       mamba2-370m at its published widths through ``repro.launch.train``
           for 10 steps, so its every_k=10 l1,inf projection fires once, then
           2 more steps with ``ssm/wx`` under the bilevel ball (the fused
           kernels on the scan-stacked leaf).

``--chips 4`` runs only the path that exists across chips: projected SAE
steps under ``solver="fused_sharded"`` on column-sharded weights over a
4-device mesh, compared with ``solver="fused"`` on one device.

Any failed check raises, and the script exits non-zero. Off a TPU the same
phases run as a rehearsal (mamba2-370m at its reduced CPU config) and the
script exits non-zero. Only a TPU run whose phases all pass ends with the
line ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import re
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.launch.cache import enable_compile_cache  # noqa: E402

SAE_FEATURES, SAE_HIDDEN, SAE_SAMPLES, SAE_BATCH = 10_000, 96, 1000, 128
SAE_RADIUS = 0.1            # Table 1's C for the l1,inf ball
SERVE_ROWS, SERVE_BATCHES = 1024, 3
SERVE_TOL = 1e-4            # compact vs dense logits, the serving bound
PROJ_TOL = 1e-5             # Pallas vs Newton, fused_sharded vs fused
BALL_SLACK = 1e-5           # relative slack on ||W||_{1,inf} <= C
LM_ARCH, LM_STEPS, LM_EXTRA_STEPS, LM_BATCH, LM_SEQ = (
    "mamba2_370m", 10, 2, 8, 64)


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)
    log(f"  ok: {what}")


def _sae_data(seed: int):
    from repro.sae import make_classification, train_test_split

    X, y, _ = make_classification(n_samples=SAE_SAMPLES,
                                  n_features=SAE_FEATURES, n_informative=64,
                                  class_sep=0.8, seed=seed)
    mu, sd = X.mean(0), X.std(0) + 1e-6
    X = ((X - mu) / sd).astype(np.float32)
    return X, y, train_test_split(X, y, 0.2, seed=seed)


def _spec(norm: str, radius: float = SAE_RADIUS):
    from repro.core import ProjectionSpec
    return ProjectionSpec(pattern=r"enc1/w", norm=norm, radius=radius, axis=1)


def _in_ball(W, C, norm="l1inf", axis=1) -> tuple:
    from repro.core import get_family
    val = float(get_family(norm).norm_fn(W, axis=axis))
    return val <= C * (1 + BALL_SLACK), val


def _step_hlo(step, *args) -> str:
    return step.lower(*args).compile().as_text()


def check_kernels(hlo: str, what: str, on_tpu: bool) -> None:
    """The compiled program holds Mosaic kernels, not an interpreter or a
    jnp twin. Off a TPU the kernels are not compiled, so this is skipped."""
    what = f"{what}: the compiled program holds the Mosaic kernels"
    if on_tpu:
        check("tpu_custom_call" in hlo, what)
    else:
        log(f"  skipped off TPU: {what}")


# ---------------------------------------------------------------------------
# one chip: the paper's SAE
# ---------------------------------------------------------------------------

def sae_phase(seed: int, on_tpu: bool) -> None:
    from repro.core import (ProjectionEngine, engine_counters,
                            engine_counters_reset, get_family)
    from repro.optim import AdamConfig, adam_init
    from repro.sae import (SAEConfig, SAETrainConfig, compact_sae,
                           make_serve_step, sae_apply, sae_init, train_sae)
    from repro.sae.train import _make_step

    X, _, (Xtr, ytr, Xte, yte) = _sae_data(seed)
    cfg = SAEConfig(n_features=SAE_FEATURES, n_hidden=SAE_HIDDEN)
    results = {}
    for norm in ("l1inf", "bilevel"):
        spec = _spec(norm)
        tcfg = SAETrainConfig(epochs=3, batch_size=SAE_BATCH, lr=2e-3,
                              projection=spec, seed=seed)
        log(f"[sae] train_sae d={SAE_FEATURES} h={SAE_HIDDEN} {norm} "
            f"C={SAE_RADIUS}")
        engine_counters_reset()
        t0 = time.perf_counter()
        res = train_sae(Xtr, ytr, Xte, yte, cfg, tcfg)
        counters = engine_counters()
        log(f"  info: {time.perf_counter() - t0:.1f}s incl. compiles, "
            f"acc {res.test_accuracy:.3f}, column sparsity "
            f"{res.column_sparsity:.1f}%, {len(res.selected)} features kept, "
            f"losses {res.history}, counters {counters}")
        losses = [v for _, h in res.history for v in h]
        check(bool(np.all(np.isfinite(losses))), f"{norm}: losses finite")
        check(len(res.selected) > 0, f"{norm}: some features survive")
        W = res.params["enc1"]["w"]
        inside, val = _in_ball(W, SAE_RADIUS, norm)
        check(inside, f"{norm}: ||enc1/w||_1,inf = {val:.6g} <= "
                      f"{SAE_RADIUS}(1+{BALL_SLACK})")
        ref = get_family(norm).reference(W, SAE_RADIUS, axis=1)
        d = float(jnp.max(jnp.abs(ref - W)))
        check(d <= PROJ_TOL, f"{norm}: reference projection of enc1/w moves "
                             f"it by {d:.3g} <= {PROJ_TOL}")
        if norm == "bilevel":
            check(any(k.endswith("/fused") for k in counters),
                  f"bilevel: a /fused launch in {counters}")
            step, engine = _make_step(cfg, tcfg, AdamConfig(lr=tcfg.lr))
            p0 = sae_init(jax.random.PRNGKey(seed), cfg)
            hlo = _step_hlo(step, p0, adam_init(p0, AdamConfig()),
                            engine.init_state(p0),
                            jnp.asarray(Xtr[:SAE_BATCH]),
                            jnp.asarray(ytr[:SAE_BATCH]),
                            jax.tree_util.tree_map(jnp.ones_like, p0))
            check_kernels(hlo, "bilevel train step", on_tpu)
        results[norm] = res

    res = results["l1inf"]
    log(f"[sae] compact serving, {SERVE_BATCHES} batches of {SERVE_ROWS}")
    compact = compact_sae(res.params, (_spec("l1inf"),))
    serve = make_serve_step(compact)
    rng = np.random.default_rng(seed)
    worst = 0.0
    for _ in range(SERVE_BATCHES):
        x = jnp.asarray(X[rng.integers(0, len(X), SERVE_ROWS)])
        z, _ = serve(compact.params, x)
        z_dense, _ = sae_apply(res.params, x)
        check(z.shape == (SERVE_ROWS, cfg.n_classes)
              and bool(jnp.all(jnp.isfinite(z))),
              f"serve: finite logits of shape {z.shape}")
        worst = max(worst, float(jnp.max(jnp.abs(z - z_dense))))
    check(worst <= SERVE_TOL, f"serve: compact vs dense logits {worst:.3g} "
                              f"<= {SERVE_TOL} (J={compact.sel.shape[0]})")

    # project the trained weight onto a ball half its norm, so it moves
    W = res.params["enc1"]["w"]
    C_half = 0.5 * float(get_family("l1inf").norm_fn(W, axis=1))
    log(f"[sae] Pallas l1,inf engine vs Newton, C={C_half:.6g}")
    out = {}
    for solver in ("pallas", "newton"):
        eng = ProjectionEngine((_spec("l1inf", C_half),), solver=solver)
        fn = jax.jit(lambda p, eng=eng: eng.apply(p)[0]["enc1"]["w"])
        if solver == "pallas":
            check_kernels(_step_hlo(fn, res.params), "pallas projection",
                          on_tpu)
        out[solver] = fn(res.params)
    moved = float(jnp.max(jnp.abs(out["newton"] - W)))
    d = float(jnp.max(jnp.abs(out["pallas"] - out["newton"])))
    check(moved > 0, f"pallas: the projection moves the weight ({moved:.3g})")
    check(d <= PROJ_TOL, f"pallas vs newton: {d:.3g} <= {PROJ_TOL}")


# ---------------------------------------------------------------------------
# one chip: an LM trainer at published widths
# ---------------------------------------------------------------------------

def _wx_slices_in_ball(params, spec, norm, proj_state) -> tuple:
    """Whether every slice of the leaves ``spec`` matches lies in its ball,
    the largest slice norm, the number of slices and the slack allowed.

    The f32 Newton stops where its Eq.-(19) sums say sum_j mu_j = C. Those
    sums carry the level theta cut from each of the m columns, so their
    rounding can leave the ball by about eps * theta * m: on a wide slice
    cut hard (mamba2's 2048 columns) that is above C * BALL_SLACK."""
    from repro.core import get_family
    from repro.core.constraints import leaf_path_str

    fam = get_family(norm)
    theta = max(float(jnp.max(t)) for t in
                jax.tree_util.tree_leaves(proj_state))
    worst, n, slack = 0.0, 0, spec.radius * BALL_SLACK
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        if not re.search(spec.pattern, leaf_path_str(path)):
            continue
        m = leaf.shape[-1 if spec.axis in (0, -2) else -2]
        slack = max(slack, float(np.finfo(np.float32).eps) * theta * m)
        for W in leaf.reshape((-1,) + leaf.shape[-2:]):
            worst = max(worst, float(fam.norm_fn(W, axis=spec.axis)))
            n += 1
    return n > 0 and worst <= spec.radius + slack, worst, n, slack


def lm_phase(on_tpu: bool) -> None:
    from repro.configs import get_config, get_reduced
    from repro.core import engine_counters, engine_counters_reset
    from repro.data.pipeline import LMBatcher, SyntheticLM
    from repro.dist.sharding import default_rules
    from repro.launch import train as launch_train
    from repro.launch.mesh import make_local_mesh
    from repro.launch.steps import projection_engine_for
    from repro.models.zoo import build
    from repro.optim import AdamConfig
    from repro.train.loop import TrainConfig, build_accum_step, lr_at

    argv = ["--arch", LM_ARCH, "--steps", str(LM_STEPS), "--batch",
            str(LM_BATCH), "--seq", str(LM_SEQ), "--resume", "none"]
    if not on_tpu:
        argv.append("--reduced")
    cfg = get_config(LM_ARCH) if on_tpu else get_reduced(LM_ARCH)
    (spec,) = cfg.projection_specs
    log(f"[lm] repro.launch.train {' '.join(argv)}")
    out = launch_train.main(argv)
    losses = out["losses"]
    times = [m["step_time_s"] for m in out["step_metrics"]]
    log(f"  info: losses {losses}; first step {times[0]:.3f}s (compile "
        f"included), later median {float(np.median(times[1:])):.4f}s")
    check(len(losses) == LM_STEPS and bool(np.all(np.isfinite(losses))),
          f"lm: {LM_STEPS} finite losses")
    inside, worst, n, slack = _wx_slices_in_ball(
        out["params"], spec, "l1inf", out["proj_state"])
    check(inside, f"lm: all {n} ssm/wx slices in the l1,inf ball after step "
                  f"{LM_STEPS} (max {worst!r} <= {spec.radius} + {slack:.3g})")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"  info: peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 'not reported')}")

    # the same model, ssm/wx under the bilevel ball at every step: the
    # fused kernels on the scan-stacked leaf
    spec2 = dataclasses.replace(spec, norm="bilevel", every_k=1)
    model = build(dataclasses.replace(cfg, projection_specs=(spec2,)))
    mesh, rules = make_local_mesh(data=len(jax.devices())), default_rules()
    tcfg = TrainConfig(steps=LM_STEPS + LM_EXTRA_STEPS)
    engine = projection_engine_for(model.cfg, mesh)
    engine_counters_reset()
    step = build_accum_step(model, AdamConfig(lr=tcfg.lr), tcfg, mesh, rules,
                            engine=engine)
    params, opt = out["params"], out["opt_state"]
    proj = engine.init_state(params)
    del out
    batcher = LMBatcher(SyntheticLM(cfg.vocab), LM_BATCH, LM_SEQ)
    batch = lambda s: jax.tree_util.tree_map(jnp.asarray, batcher.get(s))
    compiled = step.lower(params, opt, proj, batch(LM_STEPS),
                          lr_at(tcfg, LM_STEPS)).compile()
    counters = engine_counters()
    check(any(k.endswith("/fused") for k in counters),
          f"lm bilevel: a /fused launch in {counters}")
    check_kernels(compiled.as_text(), "lm bilevel step", on_tpu)
    losses2 = []
    for s in range(LM_STEPS, LM_STEPS + LM_EXTRA_STEPS):
        params, opt, proj, loss = compiled(params, opt, proj, batch(s),
                                           lr_at(tcfg, s))
        losses2.append(float(loss))
    log(f"  info: bilevel losses {losses2}")
    check(bool(np.all(np.isfinite(losses2))), "lm bilevel: losses finite")
    inside, worst, n, slack = _wx_slices_in_ball(params, spec2, "bilevel",
                                                 proj)
    check(inside, f"lm bilevel: all {n} ssm/wx slices in the ball "
                  f"(max {worst!r} <= {spec2.radius} + {slack:.3g})")


# ---------------------------------------------------------------------------
# four chips: the sharded fused step against the one-device fused step
# ---------------------------------------------------------------------------

def while_body_allreduces(hlo: str) -> dict:
    """{while-body computation: [result shape of each all-reduce it starts]}.

    Counts ``all-reduce`` and ``all-reduce-start`` (the TPU compiler's
    async form), never the matching ``-done``."""
    bodies = set(n.lstrip("%") for n in re.findall(
        r"while\(.*?\), condition=[^,]+, body=([%\w\.\-]+)", hlo))
    out = {}
    for comp in re.split(r"\n(?=%?[\w\.\-]+ \(|ENTRY )", hlo):
        lines = comp.splitlines()
        if lines and lines[0].split(" ")[0].lstrip("%") in bodies:
            out[lines[0].split(" ")[0].lstrip("%")] = [
                s.split("{")[0] for s in re.findall(
                    r"= \(?(\S+?)\)? all-reduce(?:-start)?\(", comp)]
    return out


def sharded_phase(seed: int, n_dev: int, steps: int = 3) -> None:
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import ProjectionEngine
    from repro.launch.mesh import make_local_mesh
    from repro.optim import AdamConfig, AdamState, adam_init
    from repro.sae import SAEConfig, sae_init, sae_loss

    check(len(jax.devices()) == n_dev, f"{n_dev} devices visible")
    mesh = make_local_mesh(data=n_dev)
    X, _, (Xtr, ytr, _, _) = _sae_data(seed)
    cfg = SAEConfig(n_features=SAE_FEATURES, n_hidden=SAE_HIDDEN)
    acfg = AdamConfig(lr=2e-3)
    p0 = sae_init(jax.random.PRNGKey(seed), cfg)
    # the feature dim of enc1/w (its projected columns) over the whole mesh
    rep = NamedSharding(mesh, P())
    col = jax.tree_util.tree_map(lambda _: rep, p0)
    col["enc1"]["w"] = NamedSharding(mesh, P("data", None))
    batches = [(jnp.asarray(Xtr[i * SAE_BATCH:(i + 1) * SAE_BATCH]),
                jnp.asarray(ytr[i * SAE_BATCH:(i + 1) * SAE_BATCH]))
               for i in range(steps)]

    # both solvers take the same gradients, those of the one-device model:
    # Adam's early steps amplify the rounding of a differently partitioned
    # forward pass, which is not what this phase compares
    grad_fn = jax.jit(lambda p, x, y: jax.grad(
        lambda q: sae_loss(q, x, y, cfg)[0])(p))

    def make(engine):
        return jax.jit(lambda p, o, s, g: engine.projected_update(
            g, o, p, acfg, state=s))

    for norm in ("bilevel", "l1inf"):
        specs = (_spec(norm),)
        ref_eng = ProjectionEngine(specs, solver="fused")
        shd_eng = ProjectionEngine(specs, solver="fused_sharded", mesh=mesh)
        ref_step, shd_step = make(ref_eng), make(shd_eng)
        state0 = ref_eng.init_state(p0)
        one = jax.devices()[0]
        ref = (jax.device_put(p0, one), jax.device_put(
            adam_init(p0, acfg), one), jax.device_put(state0, one))
        shd = jax.device_put((p0, adam_init(p0, acfg), state0),
                             (col, AdamState(rep, col, col), rep))
        g0 = grad_fn(p0, *batches[0])
        with mesh:
            hlo = _step_hlo(shd_step, *shd, jax.device_put(g0, col))
        ags = [l.strip() for l in hlo.splitlines() if "all-gather" in l]
        check(not ags, f"{norm}: no all-gather in the sharded step "
                       f"{ags[:3]}")
        G = 1
        comm = {k: v for k, v in while_body_allreduces(hlo).items() if v}
        check(list(comm.values()) == [[f"f32[2,{G}]"]],
              f"{norm}: one f32[2,{G}] all-reduce in the Newton while body, "
              f"and no other collective in a loop ({comm})")
        for x, y in batches:
            g = grad_fn(ref[0], x, y)
            ref = ref_step(*ref, g)
            with mesh:
                shd = shd_step(*shd, jax.device_put(g, col))
        ref, shd = jax.device_get((ref, shd))
        dp = max(float(np.max(np.abs(a - b))) for a, b in zip(
            jax.tree_util.tree_leaves(ref[0]),
            jax.tree_util.tree_leaves(shd[0])))
        dt = max(float(np.max(np.abs(ref[2][k] - shd[2][k])))
                 for k in ref[2])
        log(f"  info: {norm} after {steps} steps: params max diff {dp!r}, "
            f"theta max diff {dt!r}, theta {shd[2]}")
        check(dp <= PROJ_TOL and dt <= PROJ_TOL,
              f"{norm}: fused_sharded vs fused params {dp:.3g}, theta "
              f"{dt:.3g} <= {PROJ_TOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    log(f"[cache] {enable_compile_cache()}")

    devices = jax.devices()
    dev = devices[0]
    on_tpu = dev.platform == "tpu"
    log(f"[device] {devices}")
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase(args.seed, 4)
    else:
        sae_phase(args.seed, on_tpu)
        lm_phase(on_tpu)
    log(f"[done] all phases passed in {time.perf_counter() - t0:.1f}s")
    if not on_tpu:
        log(f"[fail] platform {dev.platform!r} is not a TPU: a rehearsal, "
            f"not a chip result")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
