"""Pallas TPU kernels for the l1,inf projection hot path.

TPU-native adaptation of the paper's near-linear projection (DESIGN.md §2):
instead of heaps (sequential) or per-column sorts (log n HBM passes under
XLA), the water-level solve is FUSED in VMEM — each grid program copies an
(n x bm) tile of |Y| in once and runs the entire per-column bisection +
Michelot-polish iteration on-chip. One HBM pass per outer Newton step on
theta (columns taller than one VMEM chunk re-stream their rows once per
sweep), and with the sparsity-adaptive engine in ``ops.py`` the pass only
covers the compacted active-column prefix (J-proportional work; DESIGN.md
§3).

Kernels:
  * colstats:   per-column (sum, max) of |Y|, row-tiled accumulation
  * mu_solve:   per-column water level mu_j(theta) + exact (k_j, S_kj)
                payloads for the outer Eq.-(19) Newton update. theta may be
                a scalar (one ball) or a per-column vector (packed
                multi-ball buffers, one theta per segment). An SMEM-style
                active-block count lets grid programs beyond the compacted
                active prefix skip the solve entirely.
  * clip_apply: X = sign(Y) * min(|Y|, mu_j), fully tiled, memory-bound

Every kernel keeps the lane block at 128 columns and bounds fast memory by
tiling rows; all are validated against ``ref.py`` in interpret mode and
compiled for TPU v5e by ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_BIG = -1e30


# -----------------------------------------------------------------------------
# colstats
# -----------------------------------------------------------------------------

def _colstats_kernel(y_ref, sum_ref, max_ref):
    i = pl.program_id(1)  # row-tile index (innermost, sequential on TPU)
    y = jnp.abs(y_ref[...].astype(jnp.float32))
    psum = jnp.sum(y, axis=0, keepdims=True)
    pmax = jnp.max(y, axis=0, keepdims=True)

    @pl.when(i == 0)
    def _init():
        sum_ref[...] = psum
        max_ref[...] = pmax

    @pl.when(i > 0)
    def _acc():
        sum_ref[...] = sum_ref[...] + psum
        max_ref[...] = jnp.maximum(max_ref[...], pmax)


def colstats(Y: jnp.ndarray, *, block_m: int = 128, block_n: int = 512,
             interpret: bool = False):
    """Per-column (sum, max) of |Y|. Y is (n, m) with n % block_n == 0 and
    m % block_m == 0 (callers pad)."""
    n, m = Y.shape
    grid = (m // block_m, n // block_n)
    out = pl.pallas_call(
        _colstats_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_n, block_m), lambda j, i: (i, j))],
        out_specs=[pl.BlockSpec((1, block_m), lambda j, i: (0, j)),
                   pl.BlockSpec((1, block_m), lambda j, i: (0, j))],
        out_shape=[jax.ShapeDtypeStruct((1, m), jnp.float32),
                   jax.ShapeDtypeStruct((1, m), jnp.float32)],
        interpret=interpret,
    )(Y)
    return out[0][0], out[1][0]


# -----------------------------------------------------------------------------
# mu_solve: fused per-column water-level solve at a given theta
# -----------------------------------------------------------------------------

def _mu_solve_kernel(nact_ref, theta_ref, y_hbm, mu_ref, k_ref, s_ref,
                     act_ref, buf, *, n_bisect: int, n_polish: int,
                     n_chunks: int):
    j = pl.program_id(0)
    chunk, bm = buf.shape

    def load(c):
        row0 = 0 if n_chunks == 1 else pl.multiple_of(c * chunk, chunk)
        rows = pl.ds(row0, chunk)
        cols = pl.ds(pl.multiple_of(j * bm, bm), bm)
        pltpu.sync_copy(y_hbm.at[rows, cols], buf)

    def sweep(fn, acc):
        """Fold ``fn(|y| chunk, acc)`` over the column block's row chunks.
        One chunk stays resident in VMEM for every sweep; more chunks
        stream from HBM once per sweep."""
        if n_chunks == 1:
            return fn(jnp.abs(buf[...].astype(jnp.float32)), acc)

        def body(c, acc):
            load(c)
            return fn(jnp.abs(buf[...].astype(jnp.float32)), acc)

        return jax.lax.fori_loop(0, n_chunks, body, acc)

    @pl.when(j < nact_ref[0])
    def _solve():
        if n_chunks == 1:
            load(0)
        theta = theta_ref[0, :]                          # (1,) or (bm,)
        zeros = jnp.zeros((bm,), jnp.float32)
        colsum, colmax = sweep(
            lambda y, a: (a[0] + jnp.sum(y, axis=0),
                          jnp.maximum(a[1], jnp.max(y, axis=0))),
            (zeros, zeros))
        active = colsum > theta

        def kS(y, a, *, mu):
            gt = y > mu[None, :]
            return (a[0] + jnp.sum(gt.astype(jnp.float32), axis=0),
                    a[1] + jnp.sum(jnp.where(gt, y, 0.0), axis=0))

        # --- bisection: shrink [lo, hi] around mu*; removed(mu) decreasing --
        def bis(_, lohi):
            lo, hi = lohi
            mid = 0.5 * (lo + hi)
            removed = sweep(
                lambda y, a: a + jnp.sum(jnp.maximum(y - mid[None, :], 0.0),
                                         axis=0), zeros)
            ge = removed >= theta
            return jnp.where(ge, mid, lo), jnp.where(ge, hi, mid)

        lo, hi = jax.lax.fori_loop(0, n_bisect, bis, (zeros, colmax))

        # --- Michelot polish from below (monotone, finitely convergent) -----
        def mich(_, mu):
            k, S = sweep(functools.partial(kS, mu=mu), (zeros, zeros))
            return jnp.maximum((S - theta) / jnp.maximum(k, 1.0), mu)

        mu = jax.lax.fori_loop(0, n_polish, mich, lo)
        mu = jnp.maximum(mu, 0.0)

        # exact payloads at the solved level
        k, S = sweep(functools.partial(kS, mu=mu), (zeros, zeros))
        k = jnp.maximum(k, 1.0)

        mu_ref[...] = jnp.where(active, mu, 0.0)[None, :]
        k_ref[...] = jnp.where(active, k, 1.0)[None, :]
        s_ref[...] = jnp.where(active, S, 0.0)[None, :]
        act_ref[...] = active.astype(jnp.float32)[None, :]

    @pl.when(j >= nact_ref[0])
    def _skip():
        # Block lies past the compacted active prefix: every column is
        # dominated, payloads are the inactive defaults. No solve runs and
        # nothing is copied in from HBM.
        mu_ref[...] = jnp.zeros(mu_ref.shape, mu_ref.dtype)
        k_ref[...] = jnp.ones(k_ref.shape, k_ref.dtype)
        s_ref[...] = jnp.zeros(s_ref.shape, s_ref.dtype)
        act_ref[...] = jnp.zeros(act_ref.shape, act_ref.dtype)


def mu_solve(Yabs: jnp.ndarray, theta: jnp.ndarray, *, block_m: int = 128,
             block_rows: int = 0, n_bisect: int = 26, n_polish: int = 8,
             interpret: bool = False, nact_blocks=None):
    """Water level per column at removed mass theta. Yabs is (n, m) with
    m % block_m == 0 and n % block_rows == 0 (0 = one chunk of all n rows).

    Yabs stays in HBM; each grid program copies its (block_rows, block_m)
    row chunks into one VMEM buffer, so fast memory is bounded by the chunk
    and not by n. A column block that fits one chunk is copied once and
    every bisection/polish sweep runs on the resident copy; taller blocks
    re-stream their chunks once per sweep.

    theta: scalar (one ball) or (m,) vector (per-column, for packed
    multi-segment buffers). nact_blocks: optional traced count of leading
    column blocks that still contain active columns — grid programs at or
    beyond it skip the solve, emit inactive payloads and copy nothing, so
    both compute and HBM traffic stay J-proportional (the shrinking
    engine's inner pass). None means all blocks solve.
    """
    n, m = Yabs.shape
    rows = block_rows or n
    if n % rows or m % block_m:
        raise ValueError(f"({n}, {m}) is not tiled by ({rows}, {block_m})")
    nblocks = m // block_m
    theta = jnp.asarray(theta, jnp.float32)

    def gated(j, nact):
        return jnp.where(j < nact[0], j, 0)

    if theta.ndim == 0:
        theta = jnp.reshape(theta, (1, 1))
        theta_spec = pl.BlockSpec((1, 1), lambda j, nact: (0, 0))
    else:
        theta = jnp.reshape(theta, (1, m))
        theta_spec = pl.BlockSpec((1, block_m),
                                  lambda j, nact: (0, gated(j, nact)))
    if nact_blocks is None:
        nact_blocks = nblocks
    nact = jnp.reshape(jnp.asarray(nact_blocks, jnp.int32), (1,))
    kern = functools.partial(_mu_solve_kernel, n_bisect=n_bisect,
                             n_polish=n_polish, n_chunks=n // rows)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nblocks,),
        in_specs=[theta_spec, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec((1, block_m), lambda j, nact: (0, j))] * 4,
        scratch_shapes=[pltpu.VMEM((rows, block_m), Yabs.dtype)],
    )
    outs = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((1, m), jnp.float32)] * 4,
        interpret=interpret,
    )(nact, theta, Yabs)
    mu, k, S, act = (o[0] for o in outs)
    return mu, k, S, act > 0.5


# -----------------------------------------------------------------------------
# clip_apply
# -----------------------------------------------------------------------------

def _clip_apply_kernel(y_ref, mu_ref, x_ref):
    y = y_ref[...]
    mu = mu_ref[...].astype(y.dtype)         # (1, bm)
    a = jnp.abs(y)
    x_ref[...] = jnp.sign(y) * jnp.minimum(a, mu)


def clip_apply(Y: jnp.ndarray, mu: jnp.ndarray, *, block_m: int = 128,
               block_n: int = 512, interpret: bool = False) -> jnp.ndarray:
    """X = sign(Y) * min(|Y|, mu_j). Fused elementwise, memory-bound."""
    n, m = Y.shape
    grid = (m // block_m, n // block_n)
    return pl.pallas_call(
        _clip_apply_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_n, block_m), lambda j, i: (i, j)),
                  pl.BlockSpec((1, block_m), lambda j, i: (0, j))],
        out_specs=pl.BlockSpec((block_n, block_m), lambda j, i: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), Y.dtype),
        interpret=interpret,
    )(Y, mu.reshape(1, m))
