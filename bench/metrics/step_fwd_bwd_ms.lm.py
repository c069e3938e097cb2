"""Forward and backward of the LM loss inside the training step.

Median over the executions of ``jit_lm_train_step``, the step the program's
own loop runs, of the device time of its ops under the named scope
``fwd_bwd`` (``bench/program_trace.py``'s ``scopes``, from the device
trace)."""

MODULE, SCOPE = "jit_lm_train_step", "fwd_bwd"


def read(ctx):
    runs = (ctx.get("trace") or {}).get("scopes", {}).get(MODULE)
    if not runs:
        return None
    times = sorted(r.get(SCOPE, 0.0) for r in runs)
    return 1e3 * times[len(times) // 2]
