"""The one place that picks how a Pallas kernel runs.

Every Pallas entry point in ``repro.kernels`` resolves its ``impl`` /
``interpret`` arguments here, so the choice cannot drift between kernels,
and the engine (``core.engine``) leaves both at their defaults. On a TPU
backend the defaults always mean the compiled Mosaic kernel: neither the
interpreter nor a jnp reference is ever chosen implicitly there. Off TPU
the defaults pick what can run at all (the interpreter) or what XLA runs
best (the jnp reference). An explicit argument always wins, which is how
the CPU tests diff interpret-mode kernels against their references.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

__all__ = ["resolve_impl"]


def resolve_impl(impl: str = "pallas",
                 interpret: Optional[bool] = None) -> Tuple[str, bool]:
    """Resolve a kernel's ``(impl, interpret)`` pair for this backend.

    ``impl``: ``"pallas"`` (the kernel), ``"ref"`` (its jnp twin) or
    ``"auto"`` (the kernel on TPU, the twin elsewhere). ``interpret``:
    None runs the kernel compiled on TPU and interpreted elsewhere.

    >>> resolve_impl("auto")          # on a TPU backend
    ('pallas', False)
    """
    on_tpu = jax.default_backend() == "tpu"
    if impl == "auto":
        impl = "pallas" if on_tpu else "ref"
    if impl not in ("pallas", "ref"):
        raise ValueError(f"unknown impl {impl!r} (auto | pallas | ref)")
    return impl, (not on_tpu) if interpret is None else bool(interpret)
