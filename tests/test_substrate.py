"""Substrate tests: checkpointing (atomic/async/crash-resume/elastic),
data pipeline determinism, gradient compression, watchdog, train loop."""
import os
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.checkpoint import (save, restore, restore_tree, latest_step,
                              gc_keep_last, AsyncCheckpointer)
from repro.data.pipeline import SyntheticLM, LMBatcher, host_batch_slice
from repro.dist.compression import (ef_step, int8_quantize, int8_dequantize,
                                    topk_compress, topk_decompress)
from repro.dist.watchdog import StepWatchdog


def _tree():
    return {"a": {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4)},
            "b": jnp.ones((5,), jnp.bfloat16),
            "count": jnp.asarray(7, jnp.int32)}


def test_checkpoint_roundtrip(tmp_path):
    t = _tree()
    save(t, tmp_path, 3)
    save(t, tmp_path, 10)
    assert latest_step(tmp_path) == 10
    flat, step = restore(tmp_path)
    assert step == 10
    restored, step = restore_tree(t, tmp_path)
    for a, b in zip(jax.tree_util.tree_leaves(t),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_integrity_and_gc(tmp_path):
    t = _tree()
    for s in (1, 2, 3, 4):
        save(t, tmp_path, s)
    gc_keep_last(tmp_path, 2)
    assert latest_step(tmp_path) == 4
    flat, _ = restore(tmp_path, 3)  # step 3 kept
    with pytest.raises(FileNotFoundError):
        restore(str(tmp_path) + "-missing")
    # corrupt a leaf -> crc failure
    import pathlib
    p = pathlib.Path(tmp_path) / "step-00000004"
    target = next(p.glob("*.npy"))
    arr = np.load(target)
    arr2 = arr.copy()
    arr2.flat[0] = arr2.flat[0] + 1
    np.save(target, arr2)
    with pytest.raises(IOError):
        restore(tmp_path, 4)


def test_async_checkpointer(tmp_path):
    c = AsyncCheckpointer(tmp_path, keep=2)
    t = _tree()
    for s in (5, 6, 7):
        c.save(t, s)
    c.wait()
    assert latest_step(tmp_path) == 7


def test_crash_resume_bitwise(tmp_path):
    """Train 6 steps; 'crash'; resume from step-3 ckpt; identical final
    params to an uninterrupted run (deterministic data + optimizer)."""
    from repro.configs import get_reduced
    from repro.models.zoo import build
    from repro.train.loop import TrainConfig, train
    from repro.data.pipeline import SyntheticLM, LMBatcher

    cfg = get_reduced("mamba2_370m")
    model = build(cfg)
    batcher = LMBatcher(SyntheticLM(cfg.vocab, seed=1), 2, 16)

    d1 = os.path.join(tmp_path, "a")
    full = train(model, batcher, TrainConfig(
        steps=6, ckpt_dir=d1, ckpt_every=3, log_every=100,
        with_projection=False), resume=False)

    d2 = os.path.join(tmp_path, "b")
    train(model, batcher, TrainConfig(steps=3, ckpt_dir=d2, ckpt_every=3,
                                      log_every=100, with_projection=False),
          resume=False)
    resumed = train(model, batcher, TrainConfig(
        steps=6, ckpt_dir=d2, ckpt_every=3, log_every=100,
        with_projection=False), resume=True)

    for a, b in zip(jax.tree_util.tree_leaves(full["params"]),
                    jax.tree_util.tree_leaves(resumed["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-6)


def test_elastic_restore_different_structure_dtype(tmp_path):
    t = {"w": jnp.ones((4, 4), jnp.float32)}
    save(t, tmp_path, 1)
    template = {"w": jnp.zeros((4, 4), jnp.bfloat16)}  # dtype change OK
    restored, _ = restore_tree(template, tmp_path)
    assert restored["w"].dtype == np.dtype("bfloat16") or \
        str(restored["w"].dtype) == "bfloat16"


def test_data_determinism_and_sharding():
    src = SyntheticLM(vocab=1000, seed=3)
    b1 = src.batch(step=5, batch=8, seq=32)
    b2 = src.batch(step=5, batch=8, seq=32)
    np.testing.assert_array_equal(b1, b2)
    # host slicing covers the global batch exactly
    lo0, hi0 = host_batch_slice(8, 2, 0)
    lo1, hi1 = host_batch_slice(8, 2, 1)
    sh0 = src.batch(step=5, batch=8, seq=32, rows=(lo0, hi0))
    sh1 = src.batch(step=5, batch=8, seq=32, rows=(lo1, hi1))
    np.testing.assert_array_equal(np.concatenate([sh0, sh1]), b1)
    batch = LMBatcher(src, 4, 16).get(0)
    np.testing.assert_array_equal(batch["tokens"][:, 1:],
                                  batch["labels"][:, :-1])


def test_compression_ef_topk():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(64,)), jnp.float32)
    err = jnp.zeros_like(g)
    sparse, err = ef_step(g, err, k_frac=0.25)
    assert int(jnp.sum(sparse != 0)) == 16
    # error feedback: sparse + err == g
    np.testing.assert_allclose(np.asarray(sparse + err), np.asarray(g),
                               atol=1e-6)
    vals, idx = topk_compress(g, 0.25)
    rec = topk_decompress(vals, idx, g.shape, g.dtype)
    np.testing.assert_allclose(np.asarray(rec), np.asarray(sparse), atol=1e-6)


def test_compression_int8():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(128,)) * 3, jnp.float32)
    q, s = int8_quantize(x)
    xr = int8_dequantize(q, s)
    assert q.dtype == jnp.int8
    np.testing.assert_allclose(np.asarray(xr), np.asarray(x),
                               atol=float(s) * 0.51 + 1e-6)


def test_watchdog():
    import time
    events = []
    w = StepWatchdog(threshold=3.0, grace_steps=1,
                     on_straggler=lambda s, dt, ew: events.append(s))
    for i in range(5):
        w.start()
        time.sleep(0.002)
        w.stop(i)
    w.start()
    time.sleep(0.05)  # straggler
    w.stop(5)
    assert events == [5]


def test_compile_cache_dir_is_fixed(monkeypatch):
    """The entry points' compile cache: $JAX_COMPILATION_CACHE_DIR when set
    (nothing set in code), else a fixed directory in the checkout."""
    import pathlib
    from repro.launch.cache import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = enable_compile_cache()
        root = pathlib.Path(__file__).resolve().parent.parent
        assert path == str(root / ".jax_cache") == enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
