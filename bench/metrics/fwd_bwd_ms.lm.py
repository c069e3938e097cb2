"""Forward and backward of the LM loss at the cell's batch.

Median device time of one execution of the program ``jit_bench_fwd_bwd_lm``, which the
harness jits and calls on the window's own arrays, from the device trace."""

MODULE = "jit_bench_fwd_bwd_lm"


def read(ctx):
    runs = (ctx.get("trace") or {}).get("modules", {}).get(MODULE)
    if not runs:
        return None
    runs = sorted(runs)
    return 1e3 * runs[len(runs) // 2]
