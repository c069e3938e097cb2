"""jaxpr traces per ``train_sae`` fit over the untraced window, from
``jax.monitoring``'s ``/jax/core/compile/jaxpr_trace_duration`` events."""


def read(ctx):
    c = ctx.get("counters", {})
    if not c.get("units"):
        return None
    return c["jaxpr_traces"] / c["units"]
