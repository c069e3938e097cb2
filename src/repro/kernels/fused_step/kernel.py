"""Pallas TPU kernels: the fused Adam+projection train step (DESIGN.md §11).

Two kernels, two HBM passes over each constrained leaf — the whole
projected train step's weight traffic:

  * ``adam_colstats``  (pass 1): reads one (grad, mu, nu, param) tile set,
    computes the Adam update IN-REGISTER, writes the new moments, and
    accumulates the per-column (sum |u|, max |u|) statistics of the updated
    values u — which are never written to HBM. The O(num_segments) Newton
    solve runs on those statistics between the passes (host of the launch:
    ``core.engine``).
  * ``adam_clip_apply`` (pass 2): recomputes u from the just-written
    moments (register recompute is free — HBM is the bottleneck, and
    stashing u would BE a third pass) and writes sign(u) * min(|u|, mu_j)
    directly: the clipped parameter.

Both kernels keep the two ``ref.py`` invariants (moment-consistent
recompute, param-dtype rounding before statistics); the update formula
mirrors ``optim.adam.adam_leaf_update``. Leaves keep their own layout —
the grid runs over the (lead, rows, cols) view of each leaf ("virtual
packing"); there is no packed buffer and no concatenate copy.

Grid: (lead, col_tiles, reduce_tiles) with the reduce dim innermost
(sequential on TPU) so the stats accumulate across row tiles exactly like
``kernels/l1inf/kernel.py::colstats``. The ``transpose`` static flips the
tile orientation for specs whose max axis is the trailing dim. Traced
step scalars [clip_scale, lr_t, b1c, b2c] ride in one prefetched (4,)
vector; compile-time constants (betas, eps, weight decay) close over the
kernel body.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _adam_u(sc_ref, g, m, v, p, mk, mo_ref, vo_ref, *, b1, b2, eps, wd,
            with_moment_update):
    """Shared in-register update: returns u (param dtype); optionally
    updates + stores the moments (pass 1) or steps on them as-is (pass 2).
    """
    lr_t, b1c, b2c = sc_ref[1], sc_ref[2], sc_ref[3]
    if with_moment_update:
        g = (g * sc_ref[0]).astype(g.dtype)
        if mk is not None:
            g = g * mk.astype(g.dtype)
        g32 = g.astype(jnp.float32)
        m_st = (b1 * m.astype(jnp.float32)
                + (1 - b1) * g32).astype(mo_ref.dtype)
        v_st = (b2 * v.astype(jnp.float32)
                + (1 - b2) * g32 * g32).astype(vo_ref.dtype)
        mo_ref[0] = m_st
        vo_ref[0] = v_st
    else:
        m_st, v_st = m, v
    mhat = m_st.astype(jnp.float32) / b1c
    vhat = v_st.astype(jnp.float32) / b2c
    step = lr_t * mhat / (jnp.sqrt(vhat) + eps)
    if wd:
        step = step + lr_t * wd * p.astype(jnp.float32)
    if mk is not None:
        step = step * mk.astype(jnp.float32)
    return (p.astype(jnp.float32) - step).astype(p.dtype)


def _adam_colstats_kernel(sc_ref, g_ref, m_ref, v_ref, p_ref, *rest,
                          b1, b2, eps, wd, has_mask, transpose, stat):
    if has_mask:
        mk_ref, mo_ref, vo_ref, sum_ref, max_ref = rest
        mk = mk_ref[0]
    else:
        mo_ref, vo_ref, sum_ref, max_ref = rest
        mk = None
    i = pl.program_id(2)   # reduce-tile index (innermost, sequential)
    u = _adam_u(sc_ref, g_ref[0], m_ref[0], v_ref[0], p_ref[0], mk,
                mo_ref, vo_ref, b1=b1, b2=b2, eps=eps, wd=wd,
                with_moment_update=True)
    a = jnp.abs(u.astype(jnp.float32))
    red = 1 if transpose else 0
    psum = jnp.sum(a * a if stat == "sq" else a, axis=red, keepdims=True)
    pmax = jnp.max(a, axis=red, keepdims=True)

    @pl.when(i == 0)
    def _init():
        sum_ref[0] = psum
        max_ref[0] = pmax

    @pl.when(i > 0)
    def _acc():
        sum_ref[0] = sum_ref[0] + psum
        max_ref[0] = jnp.maximum(max_ref[0], pmax)


def _adam_clip_apply_kernel(sc_ref, m_ref, v_ref, p_ref, mu_ref, *rest,
                            b1, b2, eps, wd, has_mask, transpose, mode):
    if has_mask:
        mk_ref, x_ref = rest
        mk = mk_ref[0]
    else:
        (x_ref,) = rest
        mk = None
    u = _adam_u(sc_ref, None, m_ref[0], v_ref[0], p_ref[0], mk,
                None, None, b1=b1, b2=b2, eps=eps, wd=wd,
                with_moment_update=False)
    uf = u.astype(jnp.float32)
    mu_b = mu_ref[0]                        # (bm, 1) if transpose else (1, bm)
    if mode == "scale":
        x = uf * mu_b
    else:
        x = jnp.sign(uf) * jnp.minimum(jnp.abs(uf), mu_b)
    if mk is not None:
        x = x * mk.astype(jnp.float32)
    x_ref[0] = x.astype(x_ref.dtype)


def _tiles(Rp: int, Cp: int, transpose: bool):
    """(bm, bn, grid tail): col tile, reduce tile, (col_tiles, red_tiles).

    Lane dim (the trailing Cp) tiles in 128s, sublane (Rp) in 16s (safe for
    f32 and bf16); the reduce tile is capped so a 4-buffer f32 tile set
    stays within ~2 MiB of VMEM.
    """
    def pick(dim, lo, cap):
        b = min(dim, cap)
        while b > lo and dim % b:
            b -= lo
        return b

    if transpose:                    # cols = rows dim, reduce = lane dim
        bm = pick(Rp, 16, 128)
        bn = pick(Cp, 128, 512)
    else:                            # cols = lane dim, reduce = rows dim
        bm = pick(Cp, 128, 128)
        bn = pick(Rp, 16, 512)
    cols = Rp if transpose else Cp
    red = Cp if transpose else Rp
    return bm, bn, (cols // bm, red // bn)


def _data_spec(bm, bn, transpose):
    if transpose:
        return pl.BlockSpec((1, bm, bn), lambda l, j, i, sc: (l, j, i))
    return pl.BlockSpec((1, bn, bm), lambda l, j, i, sc: (l, i, j))


def _stat_spec(bm, transpose):
    """Per-column statistics and clip levels ride as (L, 1, m) — or
    (L, m, 1) when the columns are the sublane dim — so that a block's last
    two dims are (1, bm) / (bm, 1): Mosaic tiles those for any lead count
    L, where an (L, m) array with (1, bm) blocks compiles only for L = 1."""
    if transpose:
        return pl.BlockSpec((1, bm, 1), lambda l, j, i, sc: (l, j, 0))
    return pl.BlockSpec((1, 1, bm), lambda l, j, i, sc: (l, 0, j))


def _stat_shape(L, mcols, transpose):
    return (L, mcols, 1) if transpose else (L, 1, mcols)


def adam_colstats(sc, g, m, v, p, mask=None, *, moment_dtype,
                  b1, b2, eps, wd, transpose: bool, stat: str = "abs",
                  interpret: bool = False):
    """Pass-1 launch on padded (L, Rp, Cp) views (see module docstring).

    ``sc``: (4,) f32 traced scalars [clip_scale, lr_t, b1c, b2c]. Returns
    (m_new, v_new (L, Rp, Cp) in ``moment_dtype``, colsum, colmax f32 in
    the ``_stat_shape`` layout: (L, mcols, 1) if ``transpose`` else
    (L, 1, mcols)). ``stat``: "abs" accumulates sum |u| into colsum, "sq" sum u^2
    (l1,2 column energies). Rp must be a multiple of 16 and Cp of 128
    (``ops.py`` pads).
    """
    L, Rp, Cp = p.shape
    bm, bn, tail = _tiles(Rp, Cp, transpose)
    grid = (L,) + tail
    mcols = Rp if transpose else Cp
    kern = functools.partial(_adam_colstats_kernel, b1=b1, b2=b2, eps=eps,
                             wd=wd, has_mask=mask is not None,
                             transpose=transpose, stat=stat)
    data = lambda: _data_spec(bm, bn, transpose)
    in_specs = [data(), data(), data(), data()]
    args = [g, m, v, p]
    if mask is not None:
        in_specs.append(data())
        args.append(mask)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=[data(), data(), _stat_spec(bm, transpose),
                   _stat_spec(bm, transpose)],
    )
    m_new, v_new, colsum, colmax = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((L, Rp, Cp), moment_dtype),
                   jax.ShapeDtypeStruct((L, Rp, Cp), moment_dtype),
                   jax.ShapeDtypeStruct(_stat_shape(L, mcols, transpose),
                                        jnp.float32),
                   jax.ShapeDtypeStruct(_stat_shape(L, mcols, transpose),
                                        jnp.float32)],
        interpret=interpret,
    )(sc, *args)
    return m_new, v_new, colsum, colmax


def adam_clip_apply(sc, m, v, p, mu, mask=None, *,
                    b1, b2, eps, wd, transpose: bool, mode: str = "clip",
                    interpret: bool = False):
    """Pass-2 launch: clipped params (L, Rp, Cp) in p's dtype.

    ``mu``: f32 per-column clip level in the ``_stat_shape`` layout
    (sentinel-folded by the
    engine: 1e30 = identity, 0 = dead column). ``mode``: "clip" writes
    sign(u) * min(|u|, mu), "scale" writes u * mu (per-column multiplier,
    identity sentinel 1.0). Same padding contract as ``adam_colstats``.
    """
    L, Rp, Cp = p.shape
    bm, bn, tail = _tiles(Rp, Cp, transpose)
    grid = (L,) + tail
    kern = functools.partial(_adam_clip_apply_kernel, b1=b1, b2=b2, eps=eps,
                             wd=wd, has_mask=mask is not None,
                             transpose=transpose, mode=mode)
    data = lambda: _data_spec(bm, bn, transpose)
    in_specs = [data(), data(), data(), _stat_spec(bm, transpose)]
    args = [m, v, p, mu]
    if mask is not None:
        in_specs.append(data())
        args.append(mask)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=data(),
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((L, Rp, Cp), p.dtype),
        interpret=interpret,
    )(sc, *args)
