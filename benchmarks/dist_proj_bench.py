import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# The two lines above MUST run before jax is imported (device count locks at
# first init) — this module is its own entry point; ``proj_bench`` runs it in
# a subprocess so the parent's 1-device config stays untouched.
#
# Sharded-vs-replicated packed projection on a host-device mesh
# (``BENCH_dist_proj.json``): FSDP-sharded weight matrices projected by
#   * the replicated engine (the pack all-gathers every shard, every rank
#     runs the full segmented Newton), and
#   * the sharded engine (shards stay resident; an all-to-all moves columns,
#     one (num_segments,) psum crosses the link per Newton evaluation).
# ``scripts/check.sh --bench-smoke`` gates sharded <= 1.15x replicated and
# exactness; CI uploads the JSON artifact.
import argparse
import json
import re
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmarks.run import bench_meta
from repro.core import ProjectionEngine, ProjectionSpec, init_projection_state


def _time_call(fn, reps: int) -> float:
    fn()  # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps * 1e6


def _collective_counts(hlo: str) -> dict:
    return {op: len(re.findall(op, hlo))
            for op in ("all-gather", "all-to-all", "all-reduce",
                       "collective-permute")}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="BENCH_dist_proj.json")
    args = ap.parse_args()

    n_dev = len(jax.devices())
    mesh = jax.make_mesh((n_dev,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    reps = 10 if args.quick else 30
    k_mats, n, m = (4, 256, 1024) if args.quick else (6, 512, 4096)

    rng = np.random.default_rng(11)
    scale = np.exp(rng.normal(size=(1, m)))
    params = {f"w{i}": jnp.asarray(
        rng.uniform(0, 1, size=(n, m)) * scale, jnp.float32)
        for i in range(k_mats)}
    radius = float(0.1 * np.abs(np.asarray(params["w0"])).max(axis=0).sum())
    specs = (ProjectionSpec(pattern=r"w\d", norm="l1inf", radius=radius),)

    # FSDP layout: rows (the max axis) sharded — the worst case for the
    # replicated pack (a full all-gather per leaf per step)
    shardings = {k: NamedSharding(mesh, P("data", None)) for k in params}
    params_s = jax.device_put(params, shardings)
    state0 = init_projection_state(params, specs)

    rep_eng = ProjectionEngine(specs)                       # gathers
    shd_eng = ProjectionEngine(specs, solver="sharded", mesh=mesh)
    rep_fn = jax.jit(lambda p, s: rep_eng.apply(p, state=s),
                     in_shardings=(shardings, None))
    shd_fn = jax.jit(lambda p, s: shd_eng.apply(p, state=s),
                     in_shardings=(shardings, None))

    with mesh:
        hlo_rep = rep_fn.lower(params_s, state0).compile().as_text()
        hlo_shd = shd_fn.lower(params_s, state0).compile().as_text()
        out_r, state1 = rep_fn(params_s, state0)
        out_s, state1_s = shd_fn(params_s, state0)
        jax.block_until_ready((state1, state1_s))
        rep_us = _time_call(
            lambda: jax.block_until_ready(rep_fn(params_s, state1)), reps)
        shd_us = _time_call(
            lambda: jax.block_until_ready(shd_fn(params_s, state1_s)), reps)

    max_diff = max(float(jnp.max(jnp.abs(out_r[k] - out_s[k])))
                   for k in params)
    k0 = list(state1)[0]
    theta_diff = float(jnp.max(jnp.abs(state1[k0] - state1_s[k0])))

    payload = {
        "meta": bench_meta(mesh, quick=bool(args.quick),
                           matrices=k_mats, shape=[n, m]),
        "replicated_us": rep_us,
        "sharded_us": shd_us,
        "ratio_sharded_vs_replicated": shd_us / rep_us,
        "max_abs_diff": max_diff,
        "theta_max_abs_diff": theta_diff,
        "collectives": {"replicated": _collective_counts(hlo_rep),
                        "sharded": _collective_counts(hlo_shd)},
        "psum_bytes_per_newton_eval": 4 * k_mats,   # one f32 per segment
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(json.dumps(payload, indent=2))


if __name__ == "__main__":
    main()
