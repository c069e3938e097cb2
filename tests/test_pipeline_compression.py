"""Pipeline-parallel stage handoff + compressed cross-pod psum, validated on
host-device meshes in subprocesses."""
import os
import subprocess
import sys
import textwrap

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


def test_pipeline_matches_sequential():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.dist.pipeline import build_pipeline_fn

        N_STAGES, N_MICRO, D = 4, 8, 16
        mesh = jax.make_mesh((4,), ("pod",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        rng = np.random.default_rng(0)
        Ws = jnp.asarray(rng.normal(size=(N_STAGES, D, D)) * 0.3, jnp.float32)
        x = jnp.asarray(rng.normal(size=(N_MICRO, 2, D)), jnp.float32)

        stage = lambda W, h: jnp.tanh(h @ W)
        pipe = build_pipeline_fn(stage, N_STAGES, N_MICRO, mesh, "pod")
        with mesh:
            y = jax.jit(pipe)(Ws, x)

        # sequential reference
        ref = x
        for s in range(N_STAGES):
            ref = jnp.tanh(ref @ Ws[s])
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   atol=1e-5)
        print("OK")
    """)
    assert "OK" in out


def test_compressed_psum_int8():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.dist.compression import compressed_psum

        mesh = jax.make_mesh((4,), ("pod",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        rng = np.random.default_rng(1)
        g = jnp.asarray(rng.normal(size=(4, 256)), jnp.float32)

        def body(gs):
            return compressed_psum({"g": gs[0]}, "pod", mode="int8")["g"]

        fn = shard_map(body, mesh=mesh, in_specs=(P("pod"),),
                       out_specs=P(), check_vma=False)
        with mesh:
            total_c = fn(g)
        total = np.asarray(g).sum(0)
        err = np.abs(np.asarray(total_c) - total).max()
        scale = np.abs(np.asarray(g)).max() / 127
        assert err <= 4 * scale + 1e-5, (err, scale)
        print("OK")
    """)
    assert "OK" in out
