"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Default scale completes on a
single CPU core in ~20-30 min; ``--full`` uses the paper's exact sizes;
``--only PREFIX`` filters benches; ``--quick`` trims to a smoke pass.

The ``proj_engine`` bench additionally writes machine-readable
``BENCH_proj.json`` (sparsity-adaptive engine trajectory: warm-start Newton
counts, J-proportional work counter, packed-batch vs per-matrix) — CI
uploads it as an artifact and ``scripts/check.sh --bench-smoke`` gates on
it.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

_HOST_MESH_BENCHES = ("proj_dist", "dist_fused")


def bench_meta(mesh=None, **extra) -> dict:
    """The shared environment block every ``BENCH_*.json`` emitter stamps
    into its ``meta``: backend, device count/kind, and (when the bench ran
    on one) the mesh topology — so an artifact pulled off CI says what
    hardware produced its numbers without consulting the build log.
    Bench-specific keys (quick flags, shapes) ride along via ``extra``.
    """
    import jax

    meta = {
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "device_kind": jax.devices()[0].device_kind,
        "mesh_shape": (list(mesh.devices.shape)
                       if mesh is not None else None),
        "mesh_axes": (list(mesh.axis_names)
                      if mesh is not None else None),
    }
    meta.update(extra)
    return meta


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-exact sizes (slow on 1 CPU core)")
    ap.add_argument("--quick", action="store_true",
                    help="minimal smoke pass")
    ap.add_argument("--only", default=None)
    args, _ = ap.parse_known_args()

    import jax
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    from . import (fleet_serve_bench, fused_step_bench, proj_bench,
                   sae_bench, serve_bench, zoo_serve_bench)

    benches = []
    if args.quick:
        benches = [
            ("fig1", lambda: proj_bench.fig1_radius_sweep(
                n=200, m=200, radii=(0.01, 1.0))),
            ("jaxvar", lambda: proj_bench.jax_variants(n=128, m=128)),
            ("proj_engine", lambda: proj_bench.engine_report(quick=True)),
            ("proj_families", lambda: proj_bench.families_report(quick=True)),
            ("proj_dist", lambda: proj_bench.dist_engine_report(quick=True)),
            ("dist_fused",
             lambda: proj_bench.dist_fused_report(quick=True)),
            ("fused_step",
             lambda: fused_step_bench.fused_step_report(quick=True)),
            ("serve", lambda: serve_bench.serve_report(quick=True)),
            ("zoo_serve",
             lambda: zoo_serve_bench.zoo_serve_report(quick=True)),
            ("fleet_serve",
             lambda: fleet_serve_bench.fleet_serve_report(quick=True)),
        ]
    else:
        benches = [
            ("fig1", lambda: proj_bench.fig1_radius_sweep()),
            ("fig2", proj_bench.fig2_shape_sweep),
            ("fig3", proj_bench.fig3_size_growth),
            ("jaxvar", proj_bench.jax_variants),
            ("proj_engine", lambda: proj_bench.engine_report(quick=False)),
            ("proj_families",
             lambda: proj_bench.families_report(quick=False)),
            ("proj_dist", lambda: proj_bench.dist_engine_report(quick=False)),
            ("dist_fused",
             lambda: proj_bench.dist_fused_report(quick=False)),
            ("fused_step",
             lambda: fused_step_bench.fused_step_report(quick=False)),
            ("serve", lambda: serve_bench.serve_report(quick=False)),
            ("zoo_serve",
             lambda: zoo_serve_bench.zoo_serve_report(quick=False)),
            ("fleet_serve",
             lambda: fleet_serve_bench.fleet_serve_report(quick=False)),
            ("table1", lambda: sae_bench.table1_synthetic(full=args.full)),
            ("table2", sae_bench.table2_lung),
            ("fig5-8", sae_bench.fig_radius_curves),
        ]
    if args.only:
        benches = [(n, f) for n, f in benches if n.startswith(args.only)]

    print("name,us_per_call,derived")
    failed = []
    for bname, fn in benches:
        if bname in _HOST_MESH_BENCHES and jax.default_backend() == "tpu":
            # this process holds the chip, and these phases are CPU
            # host-mesh rehearsals run in child processes; the four-chip
            # path is chip_smoke.py --chips 4
            print(f"# {bname} skipped: CPU host-mesh rehearsal, not run on "
                  f"a TPU backend", file=sys.stderr)
            continue
        t0 = time.time()
        try:
            rows = fn()
        except Exception:
            traceback.print_exc()
            print(f"{bname}/ERROR,0,failed", flush=True)
            failed.append(bname)
            continue
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}", flush=True)
        print(f"# {bname} wall {time.time()-t0:.1f}s", file=sys.stderr)
    if failed:
        sys.exit(f"benchmark phases failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
