"""Eq.-(19) Newton evaluations per projected update: the program's
counters ``proj/newton_evals`` over ``proj/updates`` (``repro.obs``), read
by the training loop from each step's own output at its sync. Over every
update the process ran: set-up, window and traced stretch."""


def read(ctx):
    try:
        from repro.obs import counters
    except ImportError:         # a program without the counters
        return None
    c = counters()
    if not c.get("proj/updates"):
        return None
    return c.get("proj/newton_evals", 0) / c["proj/updates"]
