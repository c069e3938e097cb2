import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# The two lines above MUST run before jax is imported (device count locks at
# first init) — this module is its own entry point; ``proj_bench`` runs it in
# a subprocess so the parent's 1-device config stays untouched.
#
# Fused-sharded vs unfused-sharded projected step on a host-device mesh
# (``BENCH_dist_fused.json``): column-sharded weights updated+projected by
#   * solver="sharded"        — Adam update, then pack (all-to-all reshard +
#     physical transposes into the lane-padded buffer), shard_map Newton,
#     unpack, and
#   * solver="fused_sharded"  — the PR-7 two-HBM-pass megakernel rank-local
#     inside shard_map: no packed buffer exists, the only cross-rank traffic
#     is ONE stacked (2, num_segments) f32 psum per Newton evaluation
#     (DESIGN.md §12).
# Both sides take the SAME column-sharded inputs (the canonical layout), so
# the A/B isolates the fused dataflow, not a resharding artifact. Timing is
# interleaved A/B (medians) to cancel machine drift.
# ``scripts/check.sh --bench-smoke`` gates fused_sharded <= 0.85x unfused
# wall time and params exact to <= 1e-5; CI uploads the JSON artifact.
import argparse
import json
import re
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmarks.run import bench_meta
from repro.core import ProjectionEngine, ProjectionSpec
from repro.optim.adam import AdamConfig, adam_init


def _time_pair(fn_a, fn_b, reps: int):
    """Interleaved A/B medians (us): alternating reps cancel thermal and
    scheduler drift that back-to-back loops fold into one side."""
    fn_a()
    fn_b()
    ta, tb = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn_a()
        ta.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        fn_b()
        tb.append(time.perf_counter() - t0)
    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    return med(ta) * 1e6, med(tb) * 1e6


def _collective_counts(hlo: str) -> dict:
    return {op: len(re.findall(op, hlo))
            for op in ("all-gather", "all-to-all", "all-reduce",
                       "collective-permute")}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="BENCH_dist_fused.json")
    args = ap.parse_args()

    n_dev = len(jax.devices())
    mesh = jax.make_mesh((n_dev,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    reps = 10 if args.quick else 30
    # enc: 2-D, columns = last axis; blocks: stacked with axis=1 (transpose
    # entries — where the unfused pack pays physical transposes per step)
    if args.quick:
        (n_e, m_e), (lead, r_b, c_b) = (256, 1024), (4, 256, 512)
    else:
        (n_e, m_e), (lead, r_b, c_b) = (512, 4096), (6, 512, 2048)

    rng = np.random.default_rng(11)
    params = {
        "enc": {"w": jnp.asarray(rng.normal(size=(n_e, m_e)), jnp.float32)},
        "blocks": {"w": jnp.asarray(rng.normal(size=(lead, r_b, c_b)),
                                    jnp.float32)},
    }
    grads = jax.tree_util.tree_map(
        lambda p: 0.01 * jnp.asarray(
            rng.normal(size=p.shape), jnp.float32), params)
    norm = float(jnp.abs(params["enc"]["w"]).max(axis=0).sum())
    specs = (ProjectionSpec(pattern=r"enc/w", norm="bilevel",
                            radius=0.1 * norm),
             ProjectionSpec(pattern=r"blocks/w", norm="bilevel",
                            radius=0.05 * norm, axis=1))
    acfg = AdamConfig(lr=1e-3)

    # canonical column layout for BOTH sides: the constrained axis sharded
    sh = {"enc": {"w": NamedSharding(mesh, P(None, "data"))},
          "blocks": {"w": NamedSharding(mesh, P(None, "data", None))}}
    params_s = jax.device_put(params, sh)
    grads_s = jax.device_put(grads, sh)
    opt = adam_init(params, acfg)

    shd_eng = ProjectionEngine(specs, solver="sharded", mesh=mesh)
    fus_eng = ProjectionEngine(specs, solver="fused_sharded", mesh=mesh)
    state0 = shd_eng.init_state(params)
    shd_fn = jax.jit(lambda g, o, p, s: shd_eng.projected_update(
        g, o, p, acfg, state=s))
    fus_fn = jax.jit(lambda g, o, p, s: fus_eng.projected_update(
        g, o, p, acfg, state=s))

    with mesh:
        hlo_shd = shd_fn.lower(grads_s, opt, params_s,
                               state0).compile().as_text()
        hlo_fus = fus_fn.lower(grads_s, opt, params_s,
                               state0).compile().as_text()
        p_shd, o_shd, s_shd = shd_fn(grads_s, opt, params_s, state0)
        p_fus, o_fus, s_fus = fus_fn(grads_s, opt, params_s, state0)
        jax.block_until_ready((s_shd, s_fus))
        # steady state: warm theta, step-2 moments
        shd_us, fus_us = _time_pair(
            lambda: jax.block_until_ready(
                shd_fn(grads_s, o_shd, p_shd, s_shd)),
            lambda: jax.block_until_ready(
                fus_fn(grads_s, o_fus, p_fus, s_fus)),
            reps)

    max_diff = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree_util.tree_leaves(p_shd),
                        jax.tree_util.tree_leaves(p_fus)))
    k0 = list(s_shd)[0]
    theta_diff = float(jnp.max(jnp.abs(s_shd[k0] - s_fus[k0])))
    G = 1 + lead  # enc segment + one per stacked blocks slice

    payload = {
        "meta": bench_meta(mesh, quick=bool(args.quick),
                           enc_shape=[n_e, m_e],
                           blocks_shape=[lead, r_b, c_b]),
        "sharded_us": shd_us,
        "fused_sharded_us": fus_us,
        "ratio_fused_vs_sharded": fus_us / shd_us,
        "max_abs_diff": max_diff,
        "theta_max_abs_diff": theta_diff,
        "collectives": {"sharded": _collective_counts(hlo_shd),
                        "fused_sharded": _collective_counts(hlo_fus)},
        "num_segments": G,
        # the stacked (2, G) f32 Eq.-(19) psum — all the projection moves
        "newton_psum_bytes_per_eval": 2 * 4 * G,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(json.dumps(payload, indent=2))


if __name__ == "__main__":
    main()
