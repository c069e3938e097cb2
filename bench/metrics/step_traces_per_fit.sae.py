"""Traces of the SAE training step per ``train_sae`` fit: the program's
counters ``sae/step_traces`` (incremented in the step's Python body, so
once per trace) over ``sae/fits`` (``repro.obs``). Over every fit the
process ran: set-up, window and traced fit."""


def read(ctx):
    try:
        from repro.obs import counters
    except ImportError:         # a program without the counters
        return None
    c = counters()
    if not c.get("sae/fits"):
        return None
    return c.get("sae/step_traces", 0) / c["sae/fits"]
