"""SSD Pallas kernel vs the naive-recurrence oracle and the model's chunked
scan; plus full-sequence vs step-by-step decode equivalence of the SSM."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels.ssd import ssd_fwd, ssd_attention, ref


def _mk(BH, S, P, N, BG, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(BH, S, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.05, 0.6, size=(BH, S)), jnp.float32)
    a = jnp.asarray(-rng.uniform(0.5, 2.0, size=(BH,)), jnp.float32)
    d = jnp.asarray(rng.normal(size=(BH,)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(BG, S, N)) * 0.5, jnp.float32)
    C = jnp.asarray(rng.normal(size=(BG, S, N)) * 0.5, jnp.float32)
    return x, dt, a, d, B, C


@pytest.mark.parametrize("S,chunk", [(64, 16), (128, 32), (96, 32)])
@pytest.mark.parametrize("groups", [1, 4])
def test_ssd_kernel_vs_naive_recurrence(S, chunk, groups):
    BG, P, N = 2, 8, 16
    BH = BG * groups
    x, dt, a, d, B, C = _mk(BH, S, P, N, BG)
    y, state = ssd_fwd(x, dt, a, d, B, C, chunk=chunk, groups=groups,
                       interpret=True)
    y_ref, state_ref = ref.ssd_ref(x, dt, a, d, B, C, groups=groups)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(state), np.asarray(state_ref),
                               atol=2e-4, rtol=2e-4)


def test_ssd_kernel_model_shape_wrapper():
    Bb, S, H, P, N = 2, 64, 4, 8, 16
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(Bb, S, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.05, 0.5, size=(Bb, S, H)), jnp.float32)
    A_log = jnp.asarray(rng.uniform(-1, 0.5, size=(H,)), jnp.float32)
    D = jnp.asarray(rng.normal(size=(H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(Bb, S, N)) * 0.5, jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(Bb, S, N)) * 0.5, jnp.float32)
    y = ssd_attention(x, dt, A_log, D, Bm, Cm, chunk=16, interpret=True)
    assert y.shape == (Bb, S, H, P)
    # oracle through the flat layout
    xf = x.transpose(0, 2, 1, 3).reshape(Bb * H, S, P)
    dtf = dt.transpose(0, 2, 1).reshape(Bb * H, S)
    a = jnp.tile(-jnp.exp(A_log), Bb)
    dflat = jnp.tile(D, Bb)
    y_ref, _ = ref.ssd_ref(xf, dtf, a, dflat, Bm, Cm, groups=H)
    y_ref = y_ref.reshape(Bb, H, S, P).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=2e-4, rtol=2e-4)


def test_model_ssd_full_vs_decode_steps():
    """models/ssm.py: the chunked full-sequence scan must equal running the
    recurrent decode step token by token (same params, same cache math)."""
    from repro.models import ssm as SS
    from repro.models.param import materialize

    d, d_inner, n_state, headdim = 32, 64, 8, 8
    lay = SS.ssm_layout(d, d_inner, n_state, headdim)
    params = materialize(jax.random.PRNGKey(0), lay, jnp.float32)
    rng = np.random.default_rng(0)
    S = 24
    u = jnp.asarray(rng.normal(size=(2, S, d)) * 0.5, jnp.float32)

    y_full = SS.ssd_apply(params, u, headdim=headdim, chunk=8)

    cache = SS.ssm_init_cache(2, d_inner, n_state, headdim, jnp.float32)
    ys = []
    for t in range(S):
        y_t, cache = SS.ssd_decode(params, u[:, t:t + 1], cache,
                                   headdim=headdim)
        ys.append(y_t)
    y_steps = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_full), np.asarray(y_steps),
                               atol=3e-4, rtol=3e-3)


def test_model_ssd_gradients_finite_at_long_chunks():
    """mamba2-370m's 64-token chunks: above the diagonal the decay exponent
    cum_i - cum_j grows past exp's f32 range. Masking after the exp left an
    inf whose zero cotangent made every SSM gradient NaN at the published
    widths; masked before the exp, the gradients stay finite."""
    from repro.models import ssm as SS
    from repro.models.param import materialize

    d, d_inner, n_state, headdim = 32, 64, 8, 8
    params = materialize(jax.random.PRNGKey(1),
                         SS.ssm_layout(d, d_inner, n_state, headdim),
                         jnp.float32)
    params["dt_bias"] = jnp.full_like(params["dt_bias"], 3.0)   # dt ~ 3
    u = jnp.asarray(np.random.default_rng(1).normal(size=(2, 64, d)),
                    jnp.float32)
    grads = jax.grad(lambda p: jnp.sum(
        SS.ssd_apply(p, u, headdim=headdim, chunk=64) ** 2))(params)
    for name, g in grads.items():
        assert np.all(np.isfinite(np.asarray(g))), name
