"""Cross-pod data parallelism with compressed gradient reduction.

Demonstrates the dist/compression primitives in an explicit shard_map DP
step: within-pod math stays exact; the cross-pod gradient combine uses the
int8 shared-scale psum (4x DCI traffic cut) or EF top-k. Runs on host
devices standing in for pods:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python examples/compressed_dp.py
"""
import os

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.dist.compression import compressed_psum

PODS = 4
D = 256

mesh = jax.make_mesh((PODS,), ("pod",),
                     axis_types=(jax.sharding.AxisType.Auto,))
rng = np.random.default_rng(0)
W = jnp.asarray(rng.normal(size=(D, D)) * 0.1, jnp.float32)
X = jnp.asarray(rng.normal(size=(PODS * 8, D)), jnp.float32)
Y = jnp.asarray(rng.normal(size=(PODS * 8, D)), jnp.float32)


def local_grad(W, x, y):
    def loss(W):
        return jnp.mean((x @ W - y) ** 2)
    return jax.grad(loss)(W)


def dp_step(mode):
    def body(x, y):
        g = local_grad(W, x, y)                       # per-pod gradient
        g = compressed_psum({"g": g}, "pod", mode=mode)["g"] / PODS
        return g

    fn = shard_map(body, mesh=mesh, in_specs=(P("pod"), P("pod")),
                   out_specs=P(), check_vma=False)
    with mesh:
        return jax.jit(fn)(X, Y)


g_exact = dp_step("none")
g_int8 = dp_step("int8")
err = float(jnp.max(jnp.abs(g_exact - g_int8)))
rel = err / float(jnp.max(jnp.abs(g_exact)))
print(f"exact-vs-int8 grad max err: {err:.3e} (rel {rel:.3%}) — "
      f"4x cross-pod traffic cut")
assert rel < 0.02
print("compressed cross-pod DP OK")
