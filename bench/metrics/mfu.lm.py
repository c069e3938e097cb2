"""Model FLOP utilization of the whole step: fwd + bwd FLOPs per unit
(``bench/work``, recompute not counted) times the window's rate, over the
chip's bf16 peak."""


def read(ctx):
    rate = ctx.get("rate")
    if not rate:
        return None
    flops = ctx["work"].train_flops_per_unit(ctx["cfg"], ctx["traffic"])
    return 100.0 * flops * rate / ctx["peak"]["flops_bf16"]

