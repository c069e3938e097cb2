"""Dispatch wrappers for the fused Adam+projection passes (DESIGN.md §11).

One projected train step over a constrained leaf = ``fused_adam_colstats``
(pass 1: moments out, per-column |u| statistics out, u never written) +
the O(num_segments) segmented Newton on those statistics (the engine's
job, ``core.engine``) + ``fused_adam_clip_apply`` (pass 2: recompute u
from the stored moments, clip, write). Two HBM passes per leaf, against
the >= 4 of the unfused adam-write/pack/solve/clip pipeline.

Both wrappers take the leaf in its OWN layout (any rank >= 2; leading dims
are stacked matrices) — virtual packing: no packed buffer, no concatenate
copy, the caller only threads per-leaf slices of the flat statistics
vector. ``impl`` picks the backend: ``"pallas"`` (the TPU kernels of
``kernel.py``; interpret mode off-TPU), ``"ref"`` (the jnp twins of
``ref.py`` — what XLA fuses best on CPU/GPU), or ``"auto"`` (pallas on
TPU, ref elsewhere); ``kernels.backend.resolve_impl`` makes that choice.
The two implementations are tile-for-tile identical; tests diff them in
interpret mode.

The step scalars (lr_t, b1c, b2c) come from ``optim.adam.adam_scalars``
and ``scale`` from ``optim.adam.clip_scale`` so the fused and unfused
paths share one definition of the update math.
"""
from __future__ import annotations

import jax.numpy as jnp

from . import ref
from . import kernel as _k
from ..backend import resolve_impl

__all__ = ["fused_adam_colstats", "fused_adam_clip_apply"]

_SUB = 16     # sublane padding multiple (bf16-safe; f32 needs only 8)
_LANE = 128   # lane padding multiple


def _view3(x):
    return x.reshape((-1,) + x.shape[-2:]) if x.ndim > 2 else x[None]


def _pad3(x, Rp, Cp):
    L, R, C = x.shape
    if R != Rp or C != Cp:
        x = jnp.pad(x, ((0, 0), (0, Rp - R), (0, Cp - C)))
    return x


def _padded_dims(shape):
    R, C = shape[-2:]
    return -(-R // _SUB) * _SUB, -(-C // _LANE) * _LANE


def _scalars(scale, lr_t, b1c, b2c):
    one = jnp.ones((), jnp.float32)
    return jnp.stack([
        one if scale is None else jnp.asarray(scale, jnp.float32),
        jnp.asarray(lr_t, jnp.float32) * one,
        jnp.asarray(b1c, jnp.float32),
        jnp.asarray(b2c, jnp.float32)])


def fused_adam_colstats(g, m, v, p, *, cfg, lr_t, b1c, b2c,
                        scale=None, mask=None, transpose: bool = False,
                        stat: str = "abs",
                        impl: str = "auto", interpret=None):
    """Pass 1 of the fused step: Adam moments + Newton column statistics.

    ``g``/``m``/``v``/``p``: gradient, first/second moment, and param leaf
    (rank >= 2, leading dims stacked; moments in ``cfg.moment_dtype``).
    ``cfg``: AdamConfig; ``lr_t``/``b1c``/``b2c``: the traced step scalars
    (``optim.adam.adam_scalars``); ``scale``: optional global-norm clip
    multiplier (``optim.adam.clip_scale``); ``mask``: optional {0,1} leaf
    (Algorithm-3 freeze — zeroes grads AND the whole step); ``transpose``:
    True when the spec's max axis is the trailing dim (canonical columns
    are then the second-to-last dim); ``stat``: what the colsum slot
    accumulates — ``"abs"`` (sum |u|) or ``"sq"`` (sum u^2, the l1,2
    family's column energies; the family's ``colstats_stat`` attribute
    picks this). Returns ``(m_new, v_new, colsum, colmax)`` — moments with
    the leaf's shape/``moment_dtype``, statistics f32 (lead, m) of the
    updated-but-never-written values |u|.

    >>> mn, vn, cs, cm = fused_adam_colstats(g, m, v, p, cfg=acfg,
    ...     lr_t=1e-3, b1c=b1c, b2c=b2c, transpose=True)
    """
    if stat not in ("abs", "sq"):
        raise ValueError(f"unknown stat {stat!r} (abs | sq)")
    impl, interpret = resolve_impl(impl, interpret)
    if impl == "ref":
        return ref.adam_colstats_ref(g, m, v, p, cfg=cfg, lr_t=lr_t,
                                     b1c=b1c, b2c=b2c, scale=scale,
                                     mask=mask, transpose=transpose,
                                     stat=stat)
    shape = p.shape
    R, C = shape[-2:]
    Rp, Cp = _padded_dims(shape)
    pad = lambda x: _pad3(_view3(x), Rp, Cp)
    mk = None if mask is None else pad(mask)
    m_new, v_new, colsum, colmax = _k.adam_colstats(
        _scalars(scale, lr_t, b1c, b2c), pad(g), pad(m), pad(v), pad(p), mk,
        moment_dtype=cfg.moment_dtype, b1=cfg.b1, b2=cfg.b2, eps=cfg.eps,
        wd=cfg.weight_decay, transpose=transpose, stat=stat,
        interpret=interpret)
    mcols = R if transpose else C
    flat = lambda s: s.reshape(s.shape[0], -1)[:, :mcols]
    return (m_new[:, :R, :C].reshape(shape),
            v_new[:, :R, :C].reshape(shape),
            flat(colsum), flat(colmax))


def fused_adam_clip_apply(m, v, p, mu, *, cfg, lr_t, b1c, b2c,
                          mask=None, transpose: bool = False,
                          mode: str = "clip",
                          impl: str = "auto", interpret=None):
    """Pass 2 of the fused step: recompute the update, clip, write params.

    ``m``/``v``: the moments pass 1 just wrote (recomputing u from them is
    what keeps the two passes bit-consistent — see ``ref.py``); ``p``: the
    ORIGINAL (pre-step) params; ``mu``: (lead, m) f32 per-column clip level
    with the engine's gating folded in (1e30-class sentinel = segment
    inside the ball -> identity; 0 = dead column). ``mode``: ``"clip"``
    writes sign(u) * min(|u|, mu); ``"scale"`` writes u * mu with mu a
    per-column multiplier (the l1,2 family's ``fused_mode``; identity
    sentinel 1.0, dead column 0.0). Other args as in
    ``fused_adam_colstats``. Returns the projected params (leaf shape and
    dtype) — the only param write of the whole step.

    >>> p_new = fused_adam_clip_apply(mn, vn, p, mu, cfg=acfg,
    ...     lr_t=1e-3, b1c=b1c, b2c=b2c)
    """
    if mode not in ("clip", "scale"):
        raise ValueError(f"unknown mode {mode!r} (clip | scale)")
    impl, interpret = resolve_impl(impl, interpret)
    if impl == "ref":
        return ref.adam_clip_apply_ref(m, v, p, mu, cfg=cfg, lr_t=lr_t,
                                       b1c=b1c, b2c=b2c, mask=mask,
                                       transpose=transpose, mode=mode)
    shape = p.shape
    R, C = shape[-2:]
    Rp, Cp = _padded_dims(shape)
    pad = lambda x: _pad3(_view3(x), Rp, Cp)
    mk = None if mask is None else pad(mask)
    mcols_p = Rp if transpose else Cp
    mu2 = jnp.asarray(mu, jnp.float32)
    if mu2.shape[1] != mcols_p:
        mu2 = jnp.pad(mu2, ((0, 0), (0, mcols_p - mu2.shape[1])))
    mu3 = mu2.reshape(_k._stat_shape(mu2.shape[0], mcols_p, transpose))
    x = _k.adam_clip_apply(
        _scalars(None, lr_t, b1c, b2c), pad(m), pad(v), pad(p), mu3, mk,
        b1=cfg.b1, b2=cfg.b2, eps=cfg.eps, wd=cfg.weight_decay,
        transpose=transpose, mode=mode, interpret=interpret)
    return x[:, :R, :C].reshape(shape)
