"""One projected update (Adam, l1,inf projection, mask) on the window's params, grads and state.

Median device time of one execution of the program ``jit_bench_proj_update_sae``, which the
harness jits and calls on the window's own arrays, from the device trace."""

MODULE = "jit_bench_proj_update_sae"


def read(ctx):
    runs = (ctx.get("trace") or {}).get("modules", {}).get(MODULE)
    if not runs:
        return None
    runs = sorted(runs)
    return 1e3 * runs[len(runs) // 2]
