"""Share of the traced window in which the chip is idle while the host is
inside the program span ``repro/sae/epoch_end`` and no span nested in it:
the epoch's sync (its loss and evaluation counts) and the compaction ratio.

``bench/program_trace.py``'s ``idle_by_span``, from the device trace and the
host spans on its clock."""

SPAN = "sae/epoch_end"


def read(ctx):
    t = ctx.get("trace") or {}
    idle = t.get("idle_by_span")
    if idle is None or not t.get("window_s"):
        return None
    return 100.0 * idle.get(SPAN, 0.0) / t["window_s"]
