"""Weights of a language model, made on the device from the seed.

One jitted call fills a tree of shapes, leaf by leaf by name, so the
program and the plain reference start from the same weights without one
taking them from the other. Each leaf draws from its own key, folded from
the seed and the leaf's path. The kinds follow Mamba-2's published
initialisation (arXiv:2405.21060, the state-spaces reference code):

  embed/table          normal, std 0.02
  .../A_log            log of uniform(1, 16)
  .../dt_bias          softplus^-1 of dt, dt log-uniform in [1e-3, 1e-1]
  .../D, norm scales   ones
  .../conv_*           uniform(-1/2, 1/2): 1/sqrt(kernel width 4)
  other matrices       normal, std 1/sqrt(fan in), fan in = the second-to-
                       last dimension (a leading dimension stacks layers)
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def _leaf(key, path: str, shape, dtype):
    name = path.rsplit("/", 1)[-1]
    if path == "embed/table":
        return jax.random.normal(key, shape) * 0.02
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, minval=1.0, maxval=16.0))
    if name == "dt_bias":
        lo, hi = jnp.log(1e-3), jnp.log(1e-1)
        dt = jnp.exp(jax.random.uniform(key, shape, minval=lo, maxval=hi))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name in ("D", "scale", "norm"):
        return jnp.ones(shape)
    if name.startswith("conv_"):
        return jax.random.uniform(key, shape, minval=-0.5, maxval=0.5)
    if len(shape) >= 2:
        return jax.random.normal(key, shape) / jnp.sqrt(shape[-2])
    raise ValueError(f"no initialisation for leaf {path} {shape}")


def init(shapes, seed: int, dtype=jnp.float32):
    """A tree like ``shapes`` (of ShapeDtypeStructs) filled from ``seed``,
    in one jitted call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]

    @jax.jit
    def make(key):
        leaves = [_leaf(jax.random.fold_in(key, zlib.crc32(p.encode())
                                           & 0x7FFFFFFF), p, s.shape, dtype
                        ).astype(dtype)
                  for p, (_, s) in zip(paths, flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return make(jax.random.PRNGKey(seed))
