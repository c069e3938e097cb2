"""Share of the traced window in which the chip is idle while the host is
inside the program span ``repro/sae/step`` and no span nested in it: the
call of the jitted step (dispatch; trace and cache load at a new batch
shape).

``bench/program_trace.py``'s ``idle_by_span``, from the device trace and the
host spans on its clock."""

SPAN = "sae/step"


def read(ctx):
    t = ctx.get("trace") or {}
    idle = t.get("idle_by_span")
    if idle is None or not t.get("window_s"):
        return None
    return 100.0 * idle.get(SPAN, 0.0) / t["window_s"]
