#!/usr/bin/env python3
"""Readings that set a cell's limits (``bench/limits/<cell>.json``).

    python3 bench/calibrate.py --workload <cell> --seeds 12 --controls 3 \\
        --faults 3 [--first-seed N]

In one process on the chip, at the cell's own size: for each seed the
program's check numbers from a short window (the lower readings); on the
first ``--controls`` seeds the control, the plain reference computed in
bfloat16 in the program's place; on ``--faults`` seeds the program with
each planted fault that needs a run (``bench/faults.py``). One JSON line
per reading, then the largest program reading and the smallest control
and fault reading of every number.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

WINDOW_S = 0.5


def _program(cell, seed, log):
    """(the program's readings, the reference's) of one short run."""
    from bench.run import _span
    job = cell.job_module.Job(cell.cfg, cell.traffic, seed, log)
    job.setup()
    job.window(WINDOW_S, _span)
    prog = job.program_readings()
    job.release()
    return job, prog


def readings(workload, seeds, controls, faults, root=ROOT, log=print,
             chip_check=None):
    """Yield {"kind", "seed", "numbers"} for each reading."""
    import jax.numpy as jnp
    from bench import check
    from bench.faults import FAULTS
    from bench.run import Cell, require_chip
    from repro.launch.cache import enable_compile_cache
    import jax

    cell = Cell(workload, root)
    (chip_check or require_chip)(cell.chips)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for i, seed in enumerate(seeds):
        job, prog = _program(cell, seed, log)
        ref = job.reference_readings(cell.reference)
        yield {"kind": "program", "seed": seed,
               "numbers": check.gaps(prog, ref)}
        if i < controls:
            ctrl = job.reference_readings(cell.reference, jnp.bfloat16)
            yield {"kind": "control", "seed": seed,
                   "numbers": check.gaps(ctrl, ref)}
        if i < faults:
            for name in ("half_batch",):
                with FAULTS[name](cell.cfg["job"]):
                    _, fprog = _program(cell, seed, log)
                yield {"kind": f"fault:{name}", "seed": seed,
                       "numbers": check.gaps(fprog, ref)}


def summary(rows):
    from bench.check import NUMBERS
    out = {}
    for kind in sorted({r["kind"] for r in rows}):
        pick = max if kind == "program" else min
        out[kind] = {n: pick(r["numbers"][n] for r in rows if r["kind"] == kind)
                     for n in NUMBERS}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    args = ap.parse_args(argv)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    log = lambda m: print(m, file=sys.stderr, flush=True)
    rows = []
    for row in readings(args.workload, seeds, args.controls, args.faults,
                        log=log):
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"summary": summary(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
