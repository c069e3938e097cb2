"""What the program tells a profiler and a reader: spans, scopes, counters.

  span(name, step=None)  a host span ``repro/<name>`` on the profiler's
                         clock (``jax.profiler.TraceAnnotation``); with
                         ``step`` a ``StepTraceAnnotation`` whose
                         ``step_num`` is the step's number
  scope(name)            a ``jax.named_scope``: the name rides in the
                         ``op_name`` metadata of every operation traced
                         under it, and so in a device trace's ``tf_op``
  count / counters / counters_reset
                         the process's counter registry

There is no switch. Without a running profiler a span costs a flag check
and a scope exists only in the compiled program's metadata.

Spans (host loops): ``sae/fit``, ``sae/batch`` (once per epoch, around the
one program that makes the epoch's batches), ``sae/step``,
``sae/epoch_end``, ``sae/rewind``, ``sae/eval`` (``sae/train.py``);
``train/batch``, ``train/step``, ``train/sync`` (``train/loop.py``).
Scopes (jitted steps): ``fwd_bwd``, ``proj/update``, ``proj/newton``,
``ssd/chunk_scan``. Counters: ``sae/fits``, ``sae/step_traces`` (once per
trace of the SAE step), ``sae/batch_programs`` (once per epoch's batch
program dispatched), ``proj/updates`` and ``proj/newton_evals`` (read at
the loops' syncs), and the projection engine's routing counts, keyed
``"<plan key>/<solver>"`` or ``"per_leaf"``, incremented once per solver
call traced or run eagerly.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax

__all__ = ["span", "scope", "count", "counters", "counters_reset",
           "engine_count", "engine_counters", "engine_counters_reset"]

PREFIX = "repro/"


def span(name: str, step: Optional[int] = None):
    """A host span ``repro/<name>``; ``step`` makes it a step span.

    >>> with span("sae/step", step=3): ...
    """
    if step is None:
        return jax.profiler.TraceAnnotation(PREFIX + name)
    return jax.profiler.StepTraceAnnotation(PREFIX + name, step_num=step)


def scope(name: str):
    """A named scope for the operations traced inside it.

    >>> with scope("fwd_bwd"): ...
    """
    return jax.named_scope(name)


# One registry for the process. Snapshot it before a measured region and
# diff after, or reset it: counts never leave the process.
_COUNTERS: Dict[str, int] = {}


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name``.

    >>> count("proj/newton_evals", 3)
    """
    _COUNTERS[name] = _COUNTERS.get(name, 0) + int(n)


def counters() -> Dict[str, int]:
    """A copy of every counter: ``{name: int}``."""
    return dict(_COUNTERS)


def counters_reset() -> None:
    """Zero every counter."""
    _COUNTERS.clear()


# The projection engine's names for the same registry: the engine counts
# one solver call, ``"<plan key>/<solver>"`` (e.g.
# ``"l1inf_packed/k1/newton"``) or ``"per_leaf"``, while tracing or running
# eagerly, so a jitted steady state adds nothing; tests use that to prove
# one launch per step.
engine_count = count
engine_counters = counters
engine_counters_reset = counters_reset
