"""Plain reference pieces shared by the configurations' references.

Written from the published descriptions, in ``jax.numpy``, with nothing
taken from the program: Adam with global-norm clipping (Kingma & Ba 2015;
the clip as in Pascanu et al. 2013) and the exact Euclidean projection
onto an l1,inf ball (Quattoni et al. 2009, the paper's Eq. (5)). The
precision is the caller's: under ``jax.default_matmul_precision("highest")``
in float32 for the reference; for its control, parameters, moments and
matmuls in bfloat16, with scalars and the sums of each update and
projection in float32, as a mixed-precision program keeps them.
"""
from __future__ import annotations

from typing import Dict

import numpy as np
import jax
import jax.numpy as jnp


def leaf_paths(tree) -> Dict[str, jax.Array]:
    """{"enc1/w": leaf, ...} for a nested dict of arrays."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(k, "key", k)) for k in path): leaf
            for path, leaf in flat}


def adam_step(params, grads, m, v, t, lr, b1=0.9, b2=0.999, eps=1e-8,
              clip_norm=1.0):
    """One Adam step with global-norm clipping at optimizer count ``t``
    (1-based). Returns (params, m, v, the clipped grads).

    Parameters, moments and gradients are stored in the parameters' dtype;
    the optimizer's scalars (b1, b2, eps, t, lr, the bias corrections and
    the clip scale) and the arithmetic of each update are float32, as in
    any mixed-precision Adam: in bfloat16, 0.999 ** t would round to 1."""
    f32 = jnp.float32
    dt = jax.tree_util.tree_leaves(params)[0].dtype
    b1, b2, eps = f32(b1), f32(b2), f32(eps)
    t, lr = jnp.asarray(t, f32), jnp.asarray(lr, f32)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(f32)))
                         for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(f32(1), clip_norm / jnp.maximum(gnorm, f32(1e-12)))
    grads = jax.tree_util.tree_map(lambda g: (g * scale).astype(dt), grads)
    m = jax.tree_util.tree_map(
        lambda m, g: (b1 * m + (1 - b1) * g).astype(dt), m, grads)
    v = jax.tree_util.tree_map(
        lambda v, g: (b2 * v + (1 - b2) * jnp.square(g.astype(f32))
                      ).astype(dt), v, grads)
    c1 = 1 - b1 ** t
    c2 = 1 - b2 ** t
    params = jax.tree_util.tree_map(
        lambda p, m, v: (p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps)
                         ).astype(dt), params, m, v)
    return params, m, v, grads


def l1inf_norm(w, axis):
    """sum over groups of the largest |entry|; ``axis`` is the max axis."""
    return jnp.sum(jnp.max(jnp.abs(w), axis=axis).astype(jnp.float32))


def project_l1inf(w, radius, axis, iters: int = 60):
    """Euclidean projection of ``w`` onto {sum_g max_i |w_gi| <= radius},
    the max taken along ``axis`` (2-D ``w``).

    Each group g is clipped at a level mu_g with sum_i (|w_gi| - mu_g)_+ =
    theta, where theta makes sum_g mu_g = radius (mu_g = 0 for groups of
    l1 norm <= theta). For one group, mu(theta) = max_k (S_k - theta) / k
    over its sorted prefix sums S_k. theta is found by bisection, in
    float32 whatever the dtype of ``w``; the result has that dtype."""
    a = jnp.abs(jnp.moveaxis(w, axis, -1)).astype(jnp.float32)
    radius = jnp.asarray(radius, jnp.float32)
    s = -jnp.sort(-a, axis=-1)
    csum = jnp.cumsum(s, axis=-1)
    k = jnp.arange(1, a.shape[-1] + 1, dtype=a.dtype)

    def mus(theta):
        return jnp.maximum(jnp.max((csum - theta) / k, axis=-1), 0)

    def body(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) / 2
        over = jnp.sum(mus(mid)) > radius
        return jnp.where(over, mid, lo), jnp.where(over, hi, mid)

    lo, hi = jax.lax.fori_loop(0, iters, body,
                               (jnp.zeros((), a.dtype), jnp.max(csum[:, -1])))
    mu = mus(hi)
    clipped = jnp.sign(w) * jnp.minimum(
        jnp.abs(w).astype(jnp.float32), jnp.expand_dims(mu, axis))
    inside = l1inf_norm(w, axis) <= radius
    return jnp.where(inside, w, clipped.astype(w.dtype))


def norms(tree) -> Dict[str, float]:
    """Frobenius norm of every leaf, in float32, as Python floats."""
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))))
            for k, v in leaf_paths(tree).items()}


def median(values) -> float:
    return float(np.median(np.asarray(list(values), np.float64)))
