"""Roofline share of the projected update: the least time its bytes take
at the chip's HBM rate (read param, grad, m, v; write param, m, v for
every leaf; ``bench/work``) over its median device time."""

MODULE = "jit_bench_proj_update_sae"


def read(ctx):
    runs = (ctx.get("trace") or {}).get("modules", {}).get(MODULE)
    if not runs:
        return None
    t = sorted(runs)[len(runs) // 2]
    least = ctx["work"].update_bytes(ctx["cfg"], ctx["traffic"]) / \
        ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * least / t
