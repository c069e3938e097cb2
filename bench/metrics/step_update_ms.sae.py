"""The projected update (Adam, l1,inf projection, mask) inside the training
step.

Median over the executions of ``jit_sae_step``, the step the program's own
loop runs, of the device time of its ops under the named scope
``proj/update`` (``bench/program_trace.py``'s ``scopes``, from the device
trace)."""

MODULE, SCOPE = "jit_sae_step", "proj/update"


def read(ctx):
    runs = (ctx.get("trace") or {}).get("scopes", {}).get(MODULE)
    if not runs:
        return None
    times = sorted(r.get(SCOPE, 0.0) for r in runs)
    return 1e3 * times[len(times) // 2]
