"""Jitted wrappers: the sparsity-adaptive l1,inf projection engine built on
the Pallas kernels.

Engine shape (DESIGN.md §3):

  * outer monotone Newton on theta, warm-startable via ``theta0=`` (any
    value >= 0; an overshooting stale guess is repaired by the first
    unclamped Eq.-(19) step);
  * **active-column shrinking** — after the first full ``mu_solve`` pass the
    surviving columns are compacted into the leading slots of a packed
    buffer, ordered by descending death margin (a column dies exactly when
    its segment's theta passes its l1 norm, so deaths peel off the END of
    the prefix), and every subsequent Newton step solves only the exact
    still-alive prefix of ``ceil(J / block_m)`` column blocks — the bound
    re-tightens each iteration as theta rises (J-proportional work; blocks
    past the prefix skip via an in-kernel predicate). ``mu`` is carried
    through the loop, so the old post-loop extra ``mu_solve`` pass is gone,
    and the water levels are scattered back through the inverse permutation
    right before ``clip_apply`` (a permutation scatter is exact — see
    DESIGN.md §3);
  * **packed multi-ball** (``project_l1inf_pallas_segmented``) — one packed
    (n, M) buffer with a per-column segment id projects a whole group of
    matrices, each onto its own radius, with ONE kernel launch per Newton
    step (theta becomes a per-segment vector, Eq. (19) a segment-sum).

A ``work_cols`` counter (columns swept per ``mu_solve`` launch, accumulated
through the loop carry) makes the J-proportional claim measurable in
interpret mode; ``return_stats=True`` exposes it together with the Newton
evaluation count.

Column blocks are always one 128-lane tile; fast memory is bounded by
tiling rows (``_padded``). ``interpret`` is resolved by
``kernels.backend.resolve_impl``: compiled on TPU, the interpreter
elsewhere, unless the caller says otherwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.l1inf import _PAD_THETA, active_compaction
from ..backend import resolve_impl
from .kernel import colstats, mu_solve, clip_apply

_LANE = 128          # column block: one full lane tile, whatever n is
_CHUNK_ROWS = 4096   # mu_solve's VMEM row chunk: 4096 x 128 f32 = 2 MiB


def _padded(Y: jnp.ndarray):
    """Zero-pad Y to (n_pad, m_pad) and pick ``mu_solve``'s row chunk.

    Columns pad to the 128-lane block. Rows pad to 8; a column taller than
    ``_CHUNK_ROWS`` pads to a multiple of 512 instead and is cut into the
    largest chunk of 512-row pieces that divides it, so the other kernels'
    row tiles stay 512. Zero rows and columns change no column statistic
    and no water level. Returns (Ypad, chunk rows).
    """
    n, m = Y.shape
    n_pad = -(-n // 8) * 8
    chunk = n_pad
    if n_pad > _CHUNK_ROWS:
        n_pad = -(-n // 512) * 512
        k = n_pad // 512
        chunk = 512 * max(d for d in range(1, _CHUNK_ROWS // 512 + 1)
                          if k % d == 0)
    m_pad = -(-m // _LANE) * _LANE
    if (n_pad, m_pad) != (n, m):
        Y = jnp.pad(Y, ((0, n_pad - n), (0, m_pad - m)))
    return Y, chunk


def _pick_block_n(n_pad: int, cap: int = 512) -> int:
    """Largest divisor of n_pad that is <= cap and a multiple of 8.

    Shared by the colstats and clip_apply launch sites. n_pad is always a
    multiple of 8 (callers pad), so 8 is a guaranteed fallback — but unlike
    the old ``512-or-8`` rule this never collapses e.g. n_pad=520 to an
    8-row grid (a ~64x grid blowup); 520 -> 104.
    """
    if n_pad % 8:
        raise ValueError(f"n_pad must be a multiple of 8, got {n_pad}")
    best = 8
    for bn in range(8, min(cap, n_pad) + 1, 8):
        if n_pad % bn == 0:
            best = bn
    return best


def _engine(Ypad, seg_ids, C_seg, num_segments, theta0, *, chunk, n_bisect,
            n_polish, max_newton, interpret, shrink):
    """Shared sparsity-adaptive Newton engine over a padded (n_pad, m_pad)
    buffer whose columns map to `num_segments` independent balls (plus the
    dummy padding segment `num_segments`).

    Returns (mu_full, theta_seg, norm_seg, colsum, stats) where mu_full and
    colsum are in the ORIGINAL column order (mu already scattered back
    through the compaction permutation) and stats carries the Newton/work
    counters.

    NOTE: the outer-Newton structure (bootstrap, monotone ascent, carried
    mu, cap-exit re-eval) is the Pallas twin of core/l1inf.py's
    _newton_solve / project_l1inf_segmented — keep structural fixes in
    sync.
    """
    n_pad, m_pad = Ypad.shape
    G = int(num_segments)
    bm = _LANE
    nblocks = m_pad // bm
    Aabs = jnp.abs(Ypad.astype(jnp.float32))
    seg_ids = jnp.asarray(seg_ids, jnp.int32)
    C_seg = jnp.asarray(C_seg, jnp.float32)
    tiny = jnp.float32(1e-30)

    bn = _pick_block_n(n_pad)
    colsum, colmax = colstats(Aabs, block_m=bm, block_n=bn,
                              interpret=interpret)
    valid = seg_ids < G
    sum_all = functools.partial(jax.ops.segment_sum, segment_ids=seg_ids,
                                num_segments=G + 1)
    norm_seg = sum_all(jnp.where(valid, colmax, 0.0))[:G]
    m_seg = sum_all(valid.astype(jnp.float32))[:G]

    Csafe = jnp.where(C_seg > 0, C_seg, jnp.ones_like(C_seg))
    cold = jnp.maximum((norm_seg - Csafe) / jnp.maximum(m_seg, 1.0), 0.0)
    if theta0 is None:
        start = cold
    else:
        start = jnp.maximum(
            jnp.maximum(jnp.asarray(theta0, jnp.float32), 0.0), cold)

    def theta_cols(th_seg, sids):
        ext = jnp.concatenate(
            [th_seg, jnp.full((1,), _PAD_THETA, jnp.float32)])
        return ext[jnp.minimum(sids, G)]

    def eval_step(th_seg, A, sids, nact_blocks):
        """One mu_solve launch + segmented Eq.-(19) update at th_seg."""
        mu, k, S, act = mu_solve(A, theta_cols(th_seg, sids), block_m=bm,
                                 block_rows=chunk,
                                 n_bisect=n_bisect, n_polish=n_polish,
                                 interpret=interpret,
                                 nact_blocks=nact_blocks)
        act = jnp.logical_and(act, sids < G)
        seg_sum = functools.partial(jax.ops.segment_sum, segment_ids=sids,
                                    num_segments=G + 1)
        Aa = seg_sum(jnp.where(act, S / k, 0.0))[:G]
        Ba = seg_sum(jnp.where(act, 1.0 / k, 0.0))[:G]
        new = (Aa - Csafe) / jnp.maximum(Ba, tiny)
        return new, mu

    # --- pass 1: full sweep (establishes a point <= theta* per segment).
    # Clamp the repair to the COLD bound, not 0: cold <= theta* always, and
    # cold > 0 for any segment outside its ball, which keeps theta away
    # from the degenerate theta=0 water level (mu = colmax, empty active
    # set) where the kernel's Eq.-(19) payloads carry no slope information.
    t1 = jnp.maximum(eval_step(start, Aabs, seg_ids, nblocks)[0], cold)

    # --- active-column shrinking: theta is monotone non-decreasing from t1,
    # so any column with colsum <= theta_cols(t1) is dead forever. Compact
    # the survivors into the leading blocks, ordered by DESCENDING death
    # margin (colsum - theta at t1): column j dies exactly when its
    # segment's theta passes colsum_j, so deaths peel off the END of the
    # packed prefix and the still-alive set stays (near-)contiguous. The
    # loop re-tightens the prefix bound every iteration from the exact
    # last-alive index — J-proportional work that keeps shrinking as
    # columns die, not just once.
    if shrink:
        act1 = jnp.logical_and(colsum > theta_cols(t1, seg_ids), valid)
        perm, J = active_compaction(act1, key=theta_cols(t1, seg_ids) - colsum)
        Ap = jnp.take(Aabs, perm, axis=1)
        sids_p = jnp.take(seg_ids, perm)
        colsum_p = jnp.take(colsum, perm)
        iota = jnp.arange(m_pad, dtype=jnp.int32)

        def nact_of(th_seg):
            alive = jnp.logical_and(colsum_p > theta_cols(th_seg, sids_p),
                                    sids_p < G)
            last = jnp.max(jnp.where(alive, iota, -1))
            return ((last + 1) + bm - 1) // bm
    else:
        act1 = valid
        perm = jnp.arange(m_pad, dtype=jnp.int32)
        J = jnp.asarray(m_pad, jnp.int32)
        Ap, sids_p = Aabs, seg_ids

        def nact_of(th_seg):
            return jnp.asarray(nblocks, jnp.int32)

    # --- pass 2 + monotone loop on the packed prefix, mu carried ----------
    nact1 = nact_of(t1)
    t2, mu1 = eval_step(t1, Ap, sids_p, nact1)
    t2 = jnp.maximum(t2, t1)
    work0 = jnp.asarray(nblocks * bm, jnp.int32) + nact1 * bm

    def cond(carry):
        i, th, prev, _, _ = carry
        return jnp.logical_and(i < max_newton, jnp.any(th > prev))

    def body(carry):
        i, th, _, _, work = carry
        nact = nact_of(th)
        new, mu = eval_step(th, Ap, sids_p, nact)
        return (i + 1, jnp.maximum(new, th), th, mu, work + nact * bm)

    iters, theta, prev, mu_p, work = jax.lax.while_loop(
        cond, body, (jnp.asarray(2, jnp.int32), t2, t1, mu1, work0))
    # max_iter-cap exit: the carried mu lags the final theta by one iterate
    # for the still-moving segments; re-evaluate to keep (theta, mu)
    # consistent (free when converged).
    mu_p = jax.lax.cond(
        jnp.any(theta > prev),
        lambda: eval_step(theta, Ap, sids_p, nact_of(theta))[1],
        lambda: mu_p)

    # scatter back: perm is a bijection, so this is exact (DESIGN.md §3)
    mu_full = jnp.zeros((m_pad,), jnp.float32).at[perm].set(mu_p)
    stats = {
        "newton_iters": iters,
        "num_active": J,
        "active_cols_per_step": nact_of(theta) * bm,
        "work_cols": work,
        "full_cols": jnp.asarray(m_pad, jnp.int32),
    }
    return mu_full, theta, norm_seg, colsum, stats


@functools.partial(jax.jit, static_argnames=("n_bisect", "n_polish",
                                             "max_newton", "interpret",
                                             "shrink", "return_stats"))
def project_l1inf_pallas(Y: jnp.ndarray, C, *, theta0=None,
                         n_bisect: int = 26, n_polish: int = 8,
                         max_newton: int = 32, interpret=None,
                         shrink: bool = True, return_stats: bool = False):
    """Exact projection of Y (n, m; max over axis 0) onto the l1,inf ball.

    Sort-free sparsity-adaptive engine: outer monotone Newton on theta
    (Eq. 19, warm-startable via ``theta0``), inner fused VMEM bisection +
    polish per column, active-column shrinking after the first pass.
    ``interpret``: see ``kernels.backend.resolve_impl``. With
    ``return_stats=True`` returns (X, stats) where stats carries the
    Newton-evaluation count and the ``work_cols`` counter (columns swept
    across all mu_solve launches).
    """
    if Y.ndim != 2:
        raise ValueError("expected 2-D input")
    n, m = Y.shape
    C = jnp.asarray(C, jnp.float32)
    interpret = resolve_impl("pallas", interpret)[1]

    Ypad, chunk = _padded(Y)
    n_pad, m_pad = Ypad.shape
    seg_ids = jnp.where(jnp.arange(m_pad) < m, 0, 1).astype(jnp.int32)
    th0 = None if theta0 is None else jnp.reshape(
        jnp.asarray(theta0, jnp.float32), (1,))

    mu_full, theta, norm_seg, colsum, stats = _engine(
        Ypad, seg_ids, jnp.reshape(C, (1,)), 1, th0, chunk=chunk,
        n_bisect=n_bisect, n_polish=n_polish, max_newton=max_newton,
        interpret=interpret, shrink=shrink)

    bn = _pick_block_n(n_pad)
    Xpad = clip_apply(Ypad, mu_full.astype(Ypad.dtype), block_m=_LANE,
                      block_n=bn, interpret=interpret)
    X = Xpad[:n, :m]
    inside = norm_seg[0] <= C
    X = jnp.where(inside, Y, X)
    X = jnp.where(C > 0, X, jnp.zeros_like(X)).astype(Y.dtype)
    if not return_stats:
        return X
    stats = dict(stats)
    stats["theta"] = jnp.where(C > 0,
                               jnp.where(inside, 0.0, theta[0]),
                               jnp.max(colsum, initial=0.0))
    return X, stats


@functools.partial(jax.jit, static_argnames=("num_segments", "n_bisect",
                                             "n_polish", "max_newton",
                                             "interpret", "shrink",
                                             "return_stats"))
def project_l1inf_pallas_segmented(Y: jnp.ndarray, seg_ids: jnp.ndarray,
                                   C_seg, *, num_segments: int, theta0=None,
                                   n_bisect: int = 26,
                                   n_polish: int = 8, max_newton: int = 32,
                                   interpret=None,
                                   shrink: bool = True,
                                   return_stats: bool = False):
    """Packed multi-ball projection: one engine run, one kernel launch per
    Newton step, for EVERY segment of a packed (n, M) buffer.

    seg_ids (M,) int32 maps column -> ball in [0, num_segments); the value
    ``num_segments`` marks lane-padding columns (dummy segment, returned
    unchanged). C_seg (num_segments,) is the per-ball radius; theta0
    (num_segments,) warm-starts all balls. Returns (X, theta_seg) or
    (X, theta_seg, stats) with ``return_stats=True``.
    """
    if Y.ndim != 2:
        raise ValueError("expected a packed 2-D buffer")
    n, m = Y.shape
    G = int(num_segments)
    C_seg = jnp.asarray(C_seg, jnp.float32)
    interpret = resolve_impl("pallas", interpret)[1]

    Ypad, chunk = _padded(Y)
    n_pad, m_pad = Ypad.shape
    sids = jnp.full((m_pad,), G, jnp.int32).at[:m].set(
        jnp.asarray(seg_ids, jnp.int32))
    th0 = None if theta0 is None else jnp.asarray(theta0, jnp.float32)

    mu_full, theta, norm_seg, colsum, stats = _engine(
        Ypad, sids, C_seg, G, th0, chunk=chunk, n_bisect=n_bisect,
        n_polish=n_polish, max_newton=max_newton, interpret=interpret,
        shrink=shrink)

    bn = _pick_block_n(n_pad)
    Xpad = clip_apply(Ypad, mu_full.astype(Ypad.dtype), block_m=_LANE,
                      block_n=bn, interpret=interpret)

    inside_seg = norm_seg <= C_seg
    zero_seg = C_seg <= 0
    ext_in = jnp.concatenate([inside_seg, jnp.array([True])])
    ext_zero = jnp.concatenate([zero_seg, jnp.array([False])])
    inside_col = ext_in[jnp.minimum(sids, G)]
    zero_col = ext_zero[jnp.minimum(sids, G)]
    Xpad = jnp.where(inside_col[None, :], Ypad, Xpad)
    Xpad = jnp.where(zero_col[None, :], 0.0, Xpad).astype(Y.dtype)
    X = Xpad[:n, :m]

    seg_max = jax.ops.segment_max(
        jnp.where(sids < G, colsum, 0.0), sids, num_segments=G + 1)[:G]
    theta_out = jnp.where(zero_seg, seg_max,
                          jnp.where(inside_seg, 0.0, theta))
    if not return_stats:
        return X, theta_out
    return X, theta_out, stats


@functools.partial(jax.jit, static_argnames=("num_segments", "max_newton",
                                             "interpret", "return_stats"))
def project_bilevel_pallas_segmented(Y: jnp.ndarray, seg_ids: jnp.ndarray,
                                     C_seg, *, num_segments: int, theta0=None,
                                     max_newton: int = 32,
                                     interpret=None,
                                     return_stats: bool = False):
    """Packed multi-ball BI-LEVEL projection (arXiv:2407.16293) on the fused
    kernels: same contract as ``project_l1inf_pallas_segmented``.

    The bi-level operator's Eq.-(19) statistics are pinned at k = 1 (only
    the column maximum carries removal mass — see ``core.bilevel``), so the
    whole Newton iteration state is the (M,) column-max vector produced by
    ONE ``colstats`` sweep. The plain engine's per-iteration ``mu_solve``
    launches and the active-column compaction machinery are structurally
    unnecessary here: after the single stats sweep no per-row work remains,
    each Newton step is an O(M) segment-sum on data already resident, and
    the only other kernel launch is the final ``clip_apply`` — exactly two
    full-buffer HBM passes however many segments or iterations, the
    linear-time claim of the bi-level paper made concrete.

    Returns (X, theta_seg) or (X, theta_seg, stats) with
    ``return_stats=True`` (stats: ``newton_iters`` and the two-sweep
    ``work_cols`` accounting comparable to the plain engine's counter).
    """
    if Y.ndim != 2:
        raise ValueError("expected a packed 2-D buffer")
    n, m = Y.shape
    G = int(num_segments)
    C_seg = jnp.asarray(C_seg, jnp.float32)
    interpret = resolve_impl("pallas", interpret)[1]

    Ypad, _ = _padded(Y)
    n_pad, m_pad = Ypad.shape
    sids = jnp.full((m_pad,), G, jnp.int32).at[:m].set(
        jnp.asarray(seg_ids, jnp.int32))
    valid = sids < G
    bn = _pick_block_n(n_pad)
    _, u = colstats(jnp.abs(Ypad.astype(jnp.float32)), block_m=_LANE,
                    block_n=bn, interpret=interpret)

    sum_seg = functools.partial(jax.ops.segment_sum, segment_ids=sids,
                                num_segments=G + 1)
    norm_seg = sum_seg(jnp.where(valid, u, 0.0))[:G]
    m_seg = sum_seg(valid.astype(jnp.float32))[:G]
    Csafe = jnp.where(C_seg > 0, C_seg, jnp.ones_like(C_seg))
    cold = jnp.maximum((norm_seg - Csafe) / jnp.maximum(m_seg, 1.0), 0.0)
    if theta0 is None:
        start = cold
    else:
        start = jnp.maximum(
            jnp.maximum(jnp.asarray(theta0, jnp.float32), 0.0), cold)

    def theta_cols(th_seg):
        ext = jnp.concatenate(
            [th_seg, jnp.full((1,), _PAD_THETA, jnp.float32)])
        return ext[jnp.minimum(sids, G)]

    # the outer-Newton twin of core/bilevel.py::_bilevel_impl (k = 1 stats;
    # active convention: a column exactly at the threshold stays in the
    # tangent) — keep structural fixes in sync with it and with _engine
    def eval_step(th_seg):
        th_col = theta_cols(th_seg)
        active = jnp.logical_and(jnp.logical_not(u < th_col), valid)
        Aa = sum_seg(jnp.where(active, u, 0.0))[:G]
        Ba = sum_seg(active.astype(jnp.float32))[:G]
        new = (Aa - Csafe) / jnp.maximum(Ba, jnp.float32(1e-30))
        mu = jnp.where(active, jnp.maximum(u - th_col, 0.0), 0.0)
        return new, mu

    t1 = jnp.maximum(eval_step(start)[0], cold)
    t2, mu1 = eval_step(t1)
    t2 = jnp.maximum(t2, t1)

    def cond(carry):
        i, th, prev, _ = carry
        return jnp.logical_and(i < max_newton, jnp.any(th > prev))

    def body(carry):
        i, th, _, _ = carry
        new, mu = eval_step(th)
        return (i + 1, jnp.maximum(new, th), th, mu)

    iters, theta, prev, mu = jax.lax.while_loop(
        cond, body, (jnp.asarray(2, jnp.int32), t2, t1, mu1))
    mu = jax.lax.cond(jnp.any(theta > prev),
                      lambda: eval_step(theta)[1],
                      lambda: mu)

    Xpad = clip_apply(Ypad, mu.astype(Ypad.dtype), block_m=_LANE,
                      block_n=bn, interpret=interpret)
    inside_seg = norm_seg <= C_seg
    zero_seg = C_seg <= 0
    ext_in = jnp.concatenate([inside_seg, jnp.array([True])])
    ext_zero = jnp.concatenate([zero_seg, jnp.array([False])])
    inside_col = ext_in[jnp.minimum(sids, G)]
    zero_col = ext_zero[jnp.minimum(sids, G)]
    Xpad = jnp.where(inside_col[None, :], Ypad, Xpad)
    Xpad = jnp.where(zero_col[None, :], 0.0, Xpad).astype(Y.dtype)
    X = Xpad[:n, :m]

    # a bilevel column dies as soon as theta passes its MAXIMUM (not its l1
    # norm): the C <= 0 threshold is the per-segment max of u
    seg_max = jax.ops.segment_max(
        jnp.where(valid, u, 0.0), sids, num_segments=G + 1)[:G]
    theta_out = jnp.where(zero_seg, seg_max,
                          jnp.where(inside_seg, 0.0, theta))
    if not return_stats:
        return X, theta_out
    stats = {
        "newton_iters": iters,
        "work_cols": jnp.asarray(2 * m_pad, jnp.int32),   # colstats + clip
        "full_cols": jnp.asarray(m_pad, jnp.int32),
    }
    return X, theta_out, stats
