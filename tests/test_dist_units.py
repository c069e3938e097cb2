"""Direct single-device unit tests for the repro.dist substrate.

The subprocess tests (test_multidevice / test_pipeline_compression) validate
the collective semantics on forced multi-device meshes; these cover the
module-level contracts fast and in-process: quantization error bounds, top-k
exactness, error-feedback telescoping, the watchdog EWMA trigger (with an
injected clock — no sleeps), and the sharding-rule plumbing.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P

from repro.dist.compression import (compressed_psum, ef_step, int8_dequantize,
                                    int8_quantize, topk_compress,
                                    topk_decompress)
from repro.dist.pipeline import build_pipeline_fn
from repro.dist.sharding import (axis_rules, current_rules, default_rules,
                                 logical_spec, shard)
from repro.dist.watchdog import StepWatchdog


def _auto_mesh(axis):
    # jax.make_mesh defaults to Explicit axes, which with_sharding_constraint
    # refuses; the repo's meshes (launch/mesh.py) are Auto
    return jax.make_mesh((1,), (axis,), axis_types=(AxisType.Auto,))


# ---------------------------------------------------------------------------
# int8 quantization
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(7,), (64,), (16, 16), (3, 5, 2)])
@pytest.mark.parametrize("scale", [1e-3, 1.0, 1e4])
def test_int8_roundtrip_bound(shape, scale):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)
    q, s = int8_quantize(x)
    xr = int8_dequantize(q, s)
    assert q.dtype == jnp.int8
    assert xr.shape == x.shape
    # symmetric quantization: elementwise error <= scale/2
    np.testing.assert_allclose(np.asarray(xr), np.asarray(x),
                               atol=float(s) * 0.5 + 1e-12)
    # extremes map to +-127 exactly (no clipping loss at the shared scale)
    amax = float(jnp.max(jnp.abs(x)))
    assert int(jnp.max(jnp.abs(q))) == (127 if amax > 0 else 0)


def test_int8_zero_input_no_nan():
    q, s = int8_quantize(jnp.zeros((8,), jnp.float32))
    xr = int8_dequantize(q, s)
    assert np.all(np.isfinite(np.asarray(xr)))
    np.testing.assert_array_equal(np.asarray(xr), np.zeros(8))


def test_int8_shared_scale_matches_explicit():
    x = jnp.asarray([-3.0, 0.5, 2.0], jnp.float32)
    q1, s1 = int8_quantize(x)
    q2, s2 = int8_quantize(x, jnp.max(jnp.abs(x)) / 127.0)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    assert float(s1) == pytest.approx(float(s2))


# ---------------------------------------------------------------------------
# top-k
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,k_frac", [(64, 0.25), (100, 0.05), (7, 0.5),
                                      (5, 1.0)])
def test_topk_exactness(n, k_frac):
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    vals, idx = topk_compress(g, k_frac)
    k = max(1, min(n, int(round(n * k_frac))))
    assert vals.shape == (k,) and idx.shape == (k,)
    rec = np.asarray(topk_decompress(vals, idx, g.shape, g.dtype))
    # the kept entries are exactly the k largest |g| and are bit-identical
    gn = np.asarray(g)
    keep = np.argsort(-np.abs(gn))[:k]
    expect = np.zeros_like(gn)
    expect[keep] = gn[keep]
    np.testing.assert_array_equal(rec, expect)


def test_topk_2d_uses_flat_indices():
    g = jnp.asarray([[0.0, 5.0], [-7.0, 1.0]], jnp.float32)
    vals, idx = topk_compress(g, 0.5)
    rec = np.asarray(topk_decompress(vals, idx, g.shape, g.dtype))
    np.testing.assert_array_equal(rec, np.array([[0.0, 5.0], [-7.0, 0.0]]))


# ---------------------------------------------------------------------------
# error feedback
# ---------------------------------------------------------------------------

def test_ef_residual_telescopes():
    """After T rounds, transmitted + residual == sum of raw gradients: EF
    delays gradient mass but never loses it."""
    rng = np.random.default_rng(2)
    T, n = 10, 64
    gs = [jnp.asarray(rng.normal(size=(n,)), jnp.float32) for _ in range(T)]
    err = jnp.zeros((n,), jnp.float32)
    sent = jnp.zeros((n,), jnp.float32)
    for g in gs:
        sparse, err = ef_step(g, err, k_frac=0.125)
        assert int(jnp.sum(sparse != 0)) == 8
        sent = sent + sparse
    total = np.sum(np.asarray(gs), axis=0)
    np.testing.assert_allclose(np.asarray(sent + err), total, atol=1e-4)


def test_ef_step_exact_split():
    g = jnp.asarray([4.0, -1.0, 0.5, 3.0], jnp.float32)
    err0 = jnp.asarray([0.0, 2.5, 0.0, 0.0], jnp.float32)
    sparse, err = ef_step(g, err0, k_frac=0.5)
    # corrected = [4, 1.5, 0.5, 3] -> top-2 = indices 0, 3
    np.testing.assert_array_equal(np.asarray(sparse), [4.0, 0.0, 0.0, 3.0])
    np.testing.assert_allclose(np.asarray(sparse + err),
                               np.asarray(g + err0), atol=1e-7)


# ---------------------------------------------------------------------------
# watchdog (injected clock: deterministic, no sleeps)
# ---------------------------------------------------------------------------

class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _run_steps(w, clock, durations):
    for i, d in enumerate(durations):
        w.start()
        clock.t += d
        w.stop(i)


def test_watchdog_ewma_trigger_and_grace():
    clock = _FakeClock()
    fired = []
    w = StepWatchdog(threshold=2.0, grace_steps=2, alpha=0.5,
                     on_straggler=lambda s, dt, ew: fired.append(s),
                     clock=clock)
    # grace window: a slow step among the first grace_steps must NOT fire
    _run_steps(w, clock, [1.0, 10.0, 1.0, 1.0])
    assert fired == []
    # EWMA is now O(1s); a 3x step fires
    w.start(); clock.t += 50.0; w.stop(99)
    assert fired == [99]
    assert len(w.events) == 1
    step, dt, ewma = w.events[0]
    assert step == 99 and dt == pytest.approx(50.0) and dt > 2.0 * ewma


def test_watchdog_straggler_not_folded_into_ewma():
    clock = _FakeClock()
    w = StepWatchdog(threshold=2.0, grace_steps=0, alpha=0.5, clock=clock)
    _run_steps(w, clock, [1.0, 1.0, 100.0, 1.0, 100.0])
    # both 100s steps fire: the first did not inflate the baseline
    assert [e[0] for e in w.events] == [2, 4]
    assert w.ewma == pytest.approx(1.0)


def test_watchdog_stop_returns_duration_and_requires_start():
    clock = _FakeClock()
    w = StepWatchdog(clock=clock)
    w.start()
    clock.t += 0.25
    assert w.stop(0) == pytest.approx(0.25)
    with pytest.raises(RuntimeError):
        w.stop(1)


def test_watchdog_metrics_snapshot():
    clock = _FakeClock()
    w = StepWatchdog(threshold=2.0, grace_steps=0, alpha=0.5, clock=clock)
    # before any step: sentinel step, zeros everywhere
    m = w.metrics()
    assert m["step"] == -1.0 and m["step_time_s"] == 0.0
    assert m["step_time_ewma_s"] == 0.0 and m["straggler"] == 0.0
    assert m["straggler_events_total"] == 0.0

    _run_steps(w, clock, [1.0, 1.0])
    m = w.metrics()
    assert m["step"] == 1.0
    assert m["step_time_s"] == pytest.approx(1.0)
    assert m["step_time_ewma_s"] == pytest.approx(1.0)
    assert m["straggler"] == 0.0 and m["straggler_events_total"] == 0.0

    # a straggler step flags itself but leaves the EWMA baseline alone
    w.start(); clock.t += 100.0; w.stop(2)
    m = w.metrics()
    assert m["step"] == 2.0 and m["step_time_s"] == pytest.approx(100.0)
    assert m["step_time_ewma_s"] == pytest.approx(1.0)
    assert m["straggler"] == 1.0 and m["straggler_events_total"] == 1.0

    # the next normal step clears the flag; the total is cumulative
    w.start(); clock.t += 1.0; w.stop(3)
    m = w.metrics()
    assert m["straggler"] == 0.0 and m["straggler_events_total"] == 1.0
    # every value is a plain float so the dict drops into a metrics stream
    assert all(isinstance(v, float) for v in m.values())


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def test_default_rules_layout():
    r = default_rules()
    assert r["fsdp"] == "data" and r["mlp"] == "model"
    assert r["batch"] == "data" and r["layers"] is None
    rp = default_rules(multi_pod=True)
    assert rp["batch"] == ("pod", "data")
    assert rp["cache_batch"] == ("pod", "data")
    assert rp["fsdp"] == "data"  # FSDP stays within-pod


def test_logical_spec_and_context():
    rules = default_rules()
    assert logical_spec(("batch", "vocab"), rules) == P("data", "model")
    assert logical_spec(("nope", None), rules) == P(None, None)
    assert current_rules() is None
    mesh = _auto_mesh("data")
    with axis_rules(mesh, rules):
        assert current_rules() == (mesh, rules)
        with axis_rules(None, None):  # nesting: innermost wins
            assert current_rules() == (None, None)
        assert current_rules() == (mesh, rules)
    assert current_rules() is None


def test_shard_noop_outside_context_and_on_none_mesh():
    x = jnp.ones((4, 8))
    assert shard(x, "batch", "embed") is x
    with axis_rules(None, None):
        assert shard(x, "batch", "embed") is x


def test_shard_constrains_and_drops_nondivisible():
    mesh = _auto_mesh("data")
    rules = {"batch": "data", "ghost": "absent_axis"}
    x = jnp.arange(12, dtype=jnp.float32).reshape(3, 4)
    with axis_rules(mesh, rules):
        y = jax.jit(lambda a: shard(a, "batch", "ghost"))(x)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
        with pytest.raises(ValueError):
            shard(x, "batch")  # rank mismatch


# ---------------------------------------------------------------------------
# 1-device pipeline / compressed_psum (in-process smoke; multi-device
# semantics live in the subprocess tests)
# ---------------------------------------------------------------------------

def test_pipeline_single_stage_identity_schedule():
    mesh = _auto_mesh("pp")
    rng = np.random.default_rng(3)
    W = jnp.asarray(rng.normal(size=(1, 6, 6)) * 0.3, jnp.float32)
    x = jnp.asarray(rng.normal(size=(5, 2, 6)), jnp.float32)
    pipe = build_pipeline_fn(lambda w, h: jnp.tanh(h @ w), 1, 5, mesh, "pp")
    with mesh:
        y = jax.jit(pipe)(W, x)
    np.testing.assert_allclose(np.asarray(y), np.tanh(np.asarray(x) @
                                                      np.asarray(W[0])),
                               atol=1e-6)


def test_pipeline_rejects_wrong_mesh():
    mesh = _auto_mesh("pp")
    with pytest.raises(ValueError):
        build_pipeline_fn(lambda w, h: h, 4, 8, mesh, "pp")


@pytest.mark.parametrize("mode", ["none", "int8", "topk"])
def test_compressed_psum_single_device(mode):
    from jax import shard_map
    mesh = _auto_mesh("pod")
    g = jnp.asarray(np.random.default_rng(4).normal(size=(32,)), jnp.float32)

    def body(gs):
        return compressed_psum({"g": gs}, "pod", mode=mode, k_frac=1.0)["g"]

    fn = shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                   check_vma=False)
    with mesh:
        out = np.asarray(fn(g))
    atol = (float(jnp.max(jnp.abs(g))) / 127.0 * 0.51 + 1e-6
            if mode == "int8" else 1e-6)
    np.testing.assert_allclose(out, np.asarray(g), atol=atol)


def test_compressed_psum_unknown_mode():
    with pytest.raises(ValueError):
        compressed_psum({"g": jnp.ones(4)}, "pod", mode="fp4")
