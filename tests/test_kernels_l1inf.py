"""Pallas l1,inf kernels vs the pure-jnp oracle (interpret mode, CPU).

Shape/dtype sweeps per kernel + full-projection equivalence against both the
ref oracle and the faithful heap algorithm, plus the sparsity-adaptive
engine features: active-column shrinking, warm start, the packed segmented
path, and adversarial shapes (non-multiples of the tile dims, n=1, m=1,
tie-heavy inputs, inside-ball, bf16).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels.l1inf import ref
from repro.kernels.l1inf.kernel import colstats, mu_solve, clip_apply
from repro.kernels.l1inf.ops import (project_l1inf_pallas,
                                     project_l1inf_pallas_segmented,
                                     _pick_block_n)
from repro.core import (project_l1inf_heap, project_l1inf_newton,
                        project_l1inf_sorted)


@pytest.mark.parametrize("shape", [(8, 128), (512, 128), (1024, 256), (64, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_colstats(shape, dtype):
    rng = np.random.default_rng(0)
    Y = jnp.asarray(rng.normal(size=shape), dtype)
    bn = shape[0] if shape[0] <= 512 else 512
    s, mx = colstats(Y, block_m=128, block_n=bn, interpret=True)
    s_ref, mx_ref = ref.colstats_ref(Y)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5)
    np.testing.assert_allclose(np.asarray(mx), np.asarray(mx_ref), rtol=1e-6)


@pytest.mark.parametrize("shape", [(16, 128), (256, 128), (777, 128), (96, 256)])
@pytest.mark.parametrize("theta_frac", [0.01, 0.3, 0.9])
def test_mu_solve(shape, theta_frac):
    rng = np.random.default_rng(1)
    Y = jnp.asarray(rng.uniform(0, 1, size=shape), jnp.float32)
    colsum = jnp.sum(Y, axis=0)
    theta = jnp.asarray(theta_frac * float(jnp.median(colsum)), jnp.float32)
    mu, k, S, act = mu_solve(Y, theta, block_m=128, interpret=True)
    mu_r, k_r, S_r, act_r = ref.mu_solve_ref(Y, theta)
    np.testing.assert_array_equal(np.asarray(act), np.asarray(act_r))
    np.testing.assert_allclose(np.asarray(mu), np.asarray(mu_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(k), np.asarray(k_r))
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_r), rtol=1e-5, atol=1e-5)
    # defining property: removed mass == theta on active columns
    removed = np.sum(np.maximum(np.asarray(Y) - np.asarray(mu)[None, :], 0), axis=0)
    np.testing.assert_allclose(removed[np.asarray(act)], float(theta), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_clip_apply(dtype):
    rng = np.random.default_rng(2)
    Y = jnp.asarray(rng.normal(size=(256, 128)), dtype)
    mu = jnp.asarray(np.abs(rng.normal(size=128)), jnp.float32)
    X = clip_apply(Y, mu, block_m=128, block_n=256, interpret=True)
    X_ref = ref.clip_apply_ref(Y, mu)
    np.testing.assert_allclose(np.asarray(X, np.float32), np.asarray(X_ref, np.float32), atol=1e-6)


@pytest.mark.parametrize("shape", [(7, 5), (100, 100), (33, 257), (1000, 64), (2, 1000)])
@pytest.mark.parametrize("Cfrac", [0.02, 0.25, 0.8, 1.3])
def test_full_projection_vs_heap(shape, Cfrac):
    rng = np.random.default_rng(hash(shape) % 2**31)
    Y = rng.normal(size=shape)
    norm = np.abs(Y).max(axis=0).sum()
    C = float(Cfrac * norm)
    X = np.asarray(project_l1inf_pallas(jnp.asarray(Y, jnp.float32), C, interpret=True))
    Xh = project_l1inf_heap(Y, C)
    scale = max(np.abs(Y).max(), 1.0)
    np.testing.assert_allclose(X, Xh, atol=3e-4 * scale, rtol=3e-3)
    assert np.abs(X).max(axis=0).sum() <= C * (1 + 1e-3) + 1e-6


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_full_projection_dtypes(dtype):
    rng = np.random.default_rng(5)
    Y = jnp.asarray(rng.normal(size=(96, 200)), dtype)
    C = 10.0
    X = project_l1inf_pallas(Y, C, interpret=True)
    assert X.dtype == dtype
    Xn = project_l1inf_newton(jnp.asarray(Y, jnp.float32), C)
    tol = 3e-2 if dtype == jnp.bfloat16 else 5e-4
    np.testing.assert_allclose(np.asarray(X, np.float32), np.asarray(Xn),
                               atol=tol, rtol=tol)


def test_inside_ball_identity():
    rng = np.random.default_rng(6)
    Y = jnp.asarray(rng.normal(size=(32, 48)) * 0.01, jnp.float32)
    C = 1e6
    X = project_l1inf_pallas(Y, C, interpret=True)
    np.testing.assert_array_equal(np.asarray(X), np.asarray(Y))


def test_pick_block_n():
    # largest divisor <= 512 that is a multiple of 8 — never the old
    # silent 8-row fallback for awkward n_pad
    assert _pick_block_n(512) == 512
    assert _pick_block_n(8) == 8
    assert _pick_block_n(1024) == 512
    assert _pick_block_n(520) == 104     # old rule collapsed this to 8
    assert _pick_block_n(136) == 136
    assert _pick_block_n(8 * 127) == 8   # prime sublane count: 8 is correct
    for n_pad in range(8, 2048, 8):
        bn = _pick_block_n(n_pad)
        assert n_pad % bn == 0 and bn % 8 == 0 and bn <= 512
    with pytest.raises(ValueError):
        _pick_block_n(12)


# ----------------------------- adversarial shapes ---------------------------

@pytest.mark.parametrize("shape", [
    (1, 300),        # n=1: every column is its own max
    (50, 1),         # m=1: simplex-style water filling
    (1, 1),
    (130, 257),      # non-multiples of 8 / 128
    (520, 130),      # n_pad=520 exercises the block_n divisor fallback
    (9, 129),        # one past the tile boundary in both dims
])
@pytest.mark.parametrize("Cfrac", [0.05, 0.6])
def test_pallas_adversarial_shapes(shape, Cfrac):
    rng = np.random.default_rng(hash(shape) % 2**31)
    Y = rng.normal(size=shape)
    C = float(Cfrac * np.abs(Y).max(axis=0).sum())
    if C <= 0:
        return
    X = np.asarray(project_l1inf_pallas(jnp.asarray(Y, jnp.float32), C,
                                        interpret=True))
    Xh = project_l1inf_heap(Y, C)
    Xs = np.asarray(project_l1inf_sorted(jnp.asarray(Y, jnp.float32), C))
    scale = max(np.abs(Y).max(), 1.0)
    np.testing.assert_allclose(X, Xh, atol=3e-4 * scale, rtol=3e-3)
    np.testing.assert_allclose(X, Xs, atol=3e-4 * scale, rtol=3e-3)
    assert np.abs(X).max(axis=0).sum() <= C * (1 + 1e-3) + 1e-6


def test_pallas_tie_heavy():
    """Many equal |Y| values straddling mu (degenerate breakpoints)."""
    rng = np.random.default_rng(11)
    Y = rng.choice([0.0, 1.0, -1.0, 2.0, 2.0], size=(40, 96))
    norm = np.abs(Y).max(axis=0).sum()
    for Cfrac in (0.1, 0.45, 0.9):
        C = float(Cfrac * norm)
        X = np.asarray(project_l1inf_pallas(jnp.asarray(Y, jnp.float32), C,
                                            interpret=True))
        Xh = project_l1inf_heap(Y, C)
        np.testing.assert_allclose(X, Xh, atol=5e-4, rtol=3e-3)


def test_pallas_inside_ball_and_bf16_adversarial():
    rng = np.random.default_rng(12)
    # inside-ball on a non-tile-aligned shape: exact identity
    Y = jnp.asarray(rng.normal(size=(33, 77)) * 0.01, jnp.float32)
    X = project_l1inf_pallas(Y, 1e5, interpret=True)
    np.testing.assert_array_equal(np.asarray(X), np.asarray(Y))
    # bf16 on a ragged shape, vs the f32 newton reference
    Yb = jnp.asarray(rng.normal(size=(37, 131)), jnp.bfloat16)
    C = 8.0
    Xb = project_l1inf_pallas(Yb, C, interpret=True)
    assert Xb.dtype == jnp.bfloat16
    Xn = project_l1inf_newton(jnp.asarray(Yb, jnp.float32), C)
    np.testing.assert_allclose(np.asarray(Xb, np.float32), np.asarray(Xn),
                               atol=3e-2, rtol=3e-2)
    Xhb = project_l1inf_heap(np.asarray(Yb, np.float32), C)
    np.testing.assert_allclose(np.asarray(Xb, np.float32), Xhb,
                               atol=3e-2, rtol=3e-2)


# ----------------------- sparsity-adaptive engine ---------------------------

def test_shrink_matches_no_shrink():
    """Active-column shrinking is a layout optimization: identical results
    with the engine's compaction on or off, up to the fp accumulation-order
    wobble of the permuted Eq.-(19) reductions."""
    rng = np.random.default_rng(13)
    scale = np.exp(rng.normal(size=(1, 300)))
    Y = jnp.asarray(rng.normal(size=(60, 300)) * scale, jnp.float32)
    for Cfrac in (0.02, 0.3):
        C = float(Cfrac * np.abs(np.asarray(Y)).max(axis=0).sum())
        X1 = np.asarray(project_l1inf_pallas(Y, C, interpret=True,
                                             shrink=True))
        X0 = np.asarray(project_l1inf_pallas(Y, C, interpret=True,
                                             shrink=False))
        tol = 1e-6 * float(np.abs(np.asarray(Y)).max())
        np.testing.assert_allclose(X1, X0, atol=tol)
        # and both agree with the heap oracle
        Xh = project_l1inf_heap(np.asarray(Y, np.float64), C)
        np.testing.assert_allclose(X1, Xh, atol=3e-4 * scale.max(), rtol=3e-3)


def test_work_counter_j_proportional():
    """The per-step work counter must shrink with column sparsity."""
    rng = np.random.default_rng(14)
    scale = np.exp(rng.normal(size=(1, 512)))
    Y = jnp.asarray(rng.uniform(0, 1, size=(40, 512)) * scale, jnp.float32)
    norm = float(np.abs(np.asarray(Y)).max(axis=0).sum())
    X, st = project_l1inf_pallas(Y, 0.01 * norm, interpret=True,
                                 return_stats=True)
    _, st0 = project_l1inf_pallas(Y, 0.01 * norm, interpret=True,
                                  shrink=False, return_stats=True)
    colsp = float((np.abs(np.asarray(X)).max(axis=0) <= 1e-12).mean())
    assert colsp > 0.5                       # high-sparsity regime
    # strictly less work than the non-shrinking engine, and the final
    # Newton step touches only the surviving prefix
    assert int(st["work_cols"]) < int(st0["work_cols"])
    assert int(st["active_cols_per_step"]) < int(st["full_cols"])


def test_pallas_warm_start():
    rng = np.random.default_rng(15)
    Y = jnp.asarray(rng.normal(size=(48, 200)), jnp.float32)
    C = float(0.2 * np.abs(np.asarray(Y)).max(axis=0).sum())
    X, st = project_l1inf_pallas(Y, C, interpret=True, return_stats=True)
    Xw, stw = project_l1inf_pallas(Y, C, theta0=st["theta"], interpret=True,
                                   return_stats=True)
    np.testing.assert_allclose(np.asarray(Xw), np.asarray(X), atol=1e-6)
    # exact restart: the bootstrap pair (+ at most one fp-wobble step from
    # the bisection-approximate payloads), well below a cold solve
    assert int(stw["newton_iters"]) <= 3
    assert int(stw["newton_iters"]) < int(st["newton_iters"])
    # overshooting warm start is repaired, result unchanged
    Xo = project_l1inf_pallas(Y, C, theta0=st["theta"] * 7.0, interpret=True)
    np.testing.assert_allclose(np.asarray(Xo), np.asarray(X), atol=1e-5)


def test_pallas_segmented_vs_per_matrix():
    rng = np.random.default_rng(16)
    sizes = [(40, 50), (64, 130), (24, 33)]
    n_max = max(n for n, _ in sizes)
    cols, sids, Cs, mats = [], [], [], []
    for g, (n, m) in enumerate(sizes):
        Yg = rng.normal(size=(n, m)) * rng.choice([0.3, 1.0, 4.0])
        pad = np.zeros((n_max, m), np.float32)
        pad[:n] = Yg
        cols.append(pad)
        sids += [g] * m
        Cs.append(float(0.2 * np.abs(Yg).max(axis=0).sum()))
        mats.append(Yg)
    Yp = jnp.asarray(np.concatenate(cols, axis=1))
    sids = jnp.asarray(np.array(sids, np.int32))
    X, theta = project_l1inf_pallas_segmented(
        Yp, sids, jnp.asarray(np.array(Cs, np.float32)), num_segments=3,
        interpret=True)
    Xref = ref.project_l1inf_segmented_ref(np.asarray(Yp), np.asarray(sids),
                                           np.array(Cs, np.float32), 3)
    np.testing.assert_allclose(np.asarray(X), Xref, atol=3e-4, rtol=3e-3)
    # segment thetas match the scalar engine's
    for g, (n, m) in enumerate(sizes):
        Xh = project_l1inf_heap(mats[g], Cs[g])
        cols_g = np.asarray(sids) == g
        np.testing.assert_allclose(np.asarray(X)[:n, cols_g], Xh,
                                   atol=3e-4, rtol=3e-3)


def test_mu_solve_vector_theta_and_nact():
    rng = np.random.default_rng(17)
    Y = jnp.asarray(rng.uniform(0, 1, size=(64, 256)), jnp.float32)
    colsum = jnp.sum(Y, axis=0)
    th_scalar = jnp.asarray(0.3 * float(jnp.median(colsum)), jnp.float32)
    mu_s, k_s, S_s, a_s = mu_solve(Y, th_scalar, block_m=128, interpret=True)
    # vector theta equal everywhere == scalar theta
    th_vec = jnp.full((256,), th_scalar, jnp.float32)
    mu_v, k_v, S_v, a_v = mu_solve(Y, th_vec, block_m=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(mu_s), np.asarray(mu_v))
    np.testing.assert_array_equal(np.asarray(k_s), np.asarray(k_v))
    # nact_blocks=1: second block (cols 128+) emits inactive defaults
    mu_1, k_1, S_1, a_1 = mu_solve(Y, th_scalar, block_m=128, interpret=True,
                                   nact_blocks=jnp.asarray(1, jnp.int32))
    np.testing.assert_array_equal(np.asarray(mu_1)[:128],
                                  np.asarray(mu_s)[:128])
    assert not np.asarray(a_1)[128:].any()
    assert (np.asarray(mu_1)[128:] == 0).all()
    assert (np.asarray(k_1)[128:] == 1).all()


def test_ref_oracle_matches_heap():
    rng = np.random.default_rng(7)
    Y = rng.uniform(-1, 1, size=(60, 80))
    for Cfrac in (0.05, 0.5):
        C = float(Cfrac * np.abs(Y).max(axis=0).sum())
        Xr = np.asarray(ref.project_l1inf_ref(jnp.asarray(Y, jnp.float32), C))
        Xh = project_l1inf_heap(Y, C)
        np.testing.assert_allclose(Xr, Xh, atol=1e-4, rtol=1e-3)


def test_mu_solve_row_chunks_match_resident_column():
    """Columns cut into row chunks stream them once per sweep and reach the
    water levels of a column held whole, up to fp summation order."""
    rng = np.random.default_rng(11)
    Y = jnp.asarray(np.abs(rng.normal(size=(256, 256))), jnp.float32)
    theta = jnp.asarray(rng.uniform(0.5, 4.0, size=256), jnp.float32)
    whole = mu_solve(Y, theta, interpret=True)
    for rows in (64, 128):
        chunked = mu_solve(Y, theta, block_rows=rows, interpret=True)
        for a, b in zip(whole, chunked):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n", [1, 96, 520, 4096, 4100, 8192, 10000])
def test_padded_rows_tile_into_vmem_chunks(n):
    """The lane block is always 128 columns; rows pad so that mu_solve's
    chunk divides them and stays within the VMEM budget."""
    from repro.kernels.l1inf.ops import _CHUNK_ROWS, _padded
    Ypad, chunk = _padded(jnp.zeros((n, 96), jnp.float32))
    n_pad, m_pad = Ypad.shape
    assert m_pad == 128 and n_pad >= n and n_pad % chunk == 0
    assert chunk <= _CHUNK_ROWS and chunk % 8 == 0
    if n_pad > _CHUNK_ROWS:
        assert n_pad % 512 == 0 and chunk % 512 == 0
    else:
        assert chunk == n_pad == -(-n // 8) * 8


def test_engine_streams_tall_columns(monkeypatch):
    """Past the chunk cap the engine streams each column's rows from HBM;
    the projection still matches the Newton solver."""
    from repro.kernels.l1inf import ops
    monkeypatch.setattr(ops, "_CHUNK_ROWS", 1024)
    rng = np.random.default_rng(12)
    Y = jnp.asarray(rng.normal(size=(1100, 200)), jnp.float32)
    C = 0.2 * float(jnp.sum(jnp.max(jnp.abs(Y), axis=0)))
    X = project_l1inf_pallas(Y, C, interpret=True)
    np.testing.assert_allclose(np.asarray(X),
                               np.asarray(project_l1inf_newton(Y, C)),
                               atol=1e-5)
