"""repro.obs: the counter registry, the spans of the two training loops, the
scopes and the trailing evaluation count of the two jitted steps."""
import dataclasses
import glob

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import obs
from repro.core import ProjectionSpec
from repro.optim import AdamConfig, adam_init
from repro.sae import SAEConfig, SAETrainConfig, train_sae
from repro.sae.model import sae_init
from repro.sae.train import _epoch_batches, _make_step

SPEC = ProjectionSpec(pattern="enc1/w", norm="l1inf", radius=0.5, axis=1)
TRAIN_KEYS = {"params", "opt_state", "losses", "proj_state", "sparsity",
              "straggler_events", "step_metrics", "watchdog"}


@pytest.fixture(autouse=True)
def fresh_counters():
    obs.counters_reset()
    yield
    obs.counters_reset()


def _sae_data(n, d=40, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d)).astype(np.float32)
    return X, (X[:, 0] > 0).astype(np.int32)


# ---- the registry -----------------------------------------------------------

def test_registry_counts_and_resets():
    obs.count("a")
    obs.count("a", 2)
    obs.count("b", 0)
    assert obs.counters() == {"a": 3, "b": 0}
    snap = obs.counters()
    snap["a"] = 99                      # a copy: the registry is untouched
    assert obs.counters()["a"] == 3
    obs.counters_reset()
    assert obs.counters() == {}


def test_engine_counters_are_the_same_registry():
    from repro import core
    from repro.core import constraints
    assert core.engine_counters is obs.engine_counters
    assert constraints.engine_count is obs.engine_count
    obs.engine_count("l1inf_packed/k1/newton")
    obs.engine_count("l1inf_packed/k1/newton")
    obs.count("proj/updates", 4)
    assert core.engine_counters() == {"l1inf_packed/k1/newton": 2,
                                      "proj/updates": 4}
    core.engine_counters_reset()
    assert obs.counters() == {}


def test_engine_counters_count_one_solver_call_per_trace():
    from repro.core import ProjectionEngine
    engine = ProjectionEngine((SPEC,))
    params = {"enc1": {"w": jnp.ones((6, 8))}}
    state = engine.init_state(params)
    f = jax.jit(lambda p, s: engine.apply(p, state=s))
    for _ in range(3):
        f(params, state)
    assert obs.engine_counters() == {"l1inf_packed/k1/newton": 1}


def test_spans_and_scopes_need_no_profiler():
    with obs.span("sae/fit"):
        with obs.span("sae/step", step=7):
            with obs.scope("fwd_bwd"):
                x = jnp.ones(3) * 2
    assert float(x.sum()) == 6.0


# ---- the SAE step and loop --------------------------------------------------

def _sae_step_args(cfg, n_batch=16):
    step, engine = _make_step(cfg, SAETrainConfig(projection=SPEC),
                              AdamConfig())
    p = sae_init(jax.random.PRNGKey(0), cfg)
    X, y = _sae_data(n_batch, cfg.n_features)
    args = (p, adam_init(p, AdamConfig()), engine.init_state(p),
            jnp.asarray(X), jnp.asarray(y),
            jax.tree_util.tree_map(jnp.ones_like, p))
    return step, args


def test_sae_step_keeps_its_outputs_and_adds_the_eval_count():
    cfg = SAEConfig(n_features=40, n_hidden=8, n_classes=2)
    step, args = _sae_step_args(cfg)
    out = step(*args)
    assert len(out) == 6
    params, opt_state, proj_state, loss, aux, evals = out
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(args[0])
    assert int(opt_state.count) == 1 and set(proj_state) == set(args[2])
    assert np.ndim(loss) == 0 and np.isfinite(float(loss))
    assert evals.dtype == jnp.int32 and int(evals) > 0


def test_sae_step_module_and_scopes():
    cfg = SAEConfig(n_features=40, n_hidden=8, n_classes=2)
    step, args = _sae_step_args(cfg)
    text = step.lower(*args).as_text(debug_info=True)
    assert "jit_sae_step" in text
    for scope in ("fwd_bwd", "proj/update", "proj/newton"):
        assert f"/{scope}/" in text, scope


@pytest.mark.parametrize("n_train,shapes", [(96, 1), (100, 2)])
def test_train_sae_counts_traces_updates_and_evals(n_train, shapes):
    X, y = _sae_data(n_train)
    cfg = SAEConfig(n_features=40, n_hidden=8, n_classes=2)
    tcfg = SAETrainConfig(epochs=2, batch_size=32, projection=SPEC)
    train_sae(X, y, X, y, cfg, tcfg)
    c = obs.counters()
    steps = 2 * 2 * -(-n_train // 32)        # two descents of two epochs
    assert c["sae/fits"] == 1
    assert c["sae/step_traces"] == shapes    # one trace per batch shape
    assert c["proj/updates"] == steps
    assert c["proj/newton_evals"] >= steps   # every update evaluates


def test_unprojected_sae_counts_no_updates():
    X, y = _sae_data(64)
    cfg = SAEConfig(n_features=40, n_hidden=8, n_classes=2)
    train_sae(X, y, X, y, cfg, SAETrainConfig(epochs=1, batch_size=32))
    c = obs.counters()
    assert c["sae/fits"] == 1 and c["sae/step_traces"] == 1
    assert "proj/updates" not in c and "proj/newton_evals" not in c


@pytest.mark.parametrize("spec,descents", [(SPEC, 2), (None, 1)])
def test_each_epochs_batches_come_from_one_program_traced_once(spec,
                                                               descents):
    X, y = _sae_data(100)
    cfg = SAEConfig(n_features=40, n_hidden=8, n_classes=2)
    tcfg = SAETrainConfig(epochs=3, batch_size=32, projection=spec)
    train_sae(X, y, X, y, cfg, tcfg)
    assert obs.counters()["sae/batch_programs"] == 3 * descents
    traced = _epoch_batches._cache_size()
    assert traced >= 1
    # the same shapes, other permutations
    train_sae(X, y, X, y, cfg, dataclasses.replace(tcfg, seed=1))
    assert obs.counters()["sae/batch_programs"] == 2 * 3 * descents
    assert _epoch_batches._cache_size() == traced   # no new trace


def _profiled(fn, tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    spans = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro/"):
                    spans.setdefault(ev.name, []).append(dict(ev.stats))
    return spans


def test_a_profiled_fit_holds_one_step_span_per_step(tmp_path):
    X, y = _sae_data(100)
    cfg = SAEConfig(n_features=40, n_hidden=8, n_classes=2)
    tcfg = SAETrainConfig(epochs=2, batch_size=32, projection=SPEC)
    train_sae(X, y, X, y, cfg, tcfg)               # compiled outside
    spans = _profiled(lambda: train_sae(X, y, X, y, cfg, tcfg), tmp_path)
    per_descent = 2 * 4
    steps = spans["repro/sae/step"]
    assert len(steps) == 2 * per_descent
    assert sorted(s["step_num"] for s in steps) == \
        sorted(list(range(per_descent)) * 2)
    assert len(spans["repro/sae/batch"]) == 2 * 2     # one per epoch
    assert len(spans["repro/sae/epoch_end"]) == 2 * 2
    for name in ("repro/sae/fit", "repro/sae/rewind", "repro/sae/eval"):
        assert len(spans[name]) == 1, name


# ---- the LM step and loop ---------------------------------------------------

def _lm_model():
    """The reduced mamba2 with its ball applied at every step, so that
    every update runs the Newton."""
    from repro.configs import get_reduced
    from repro.models.zoo import build
    cfg = get_reduced("mamba2_370m")
    return build(dataclasses.replace(cfg, projection_specs=tuple(
        dataclasses.replace(s, every_k=1) for s in cfg.projection_specs)))


def _lm_batcher(model):
    from repro.data.pipeline import LMBatcher, SyntheticLM
    return LMBatcher(SyntheticLM(model.cfg.vocab, seed=1), 2, 16)


def test_train_keeps_its_keys_and_counts_each_step():
    from repro.train.loop import TrainConfig, train
    model = _lm_model()
    assert model.cfg.projection_specs
    out = train(model, _lm_batcher(model),
                TrainConfig(steps=3, log_every=100, ckpt_every=100),
                resume=False)
    assert set(out) == TRAIN_KEYS
    c = obs.counters()
    assert c["proj/updates"] == 3
    assert c["proj/newton_evals"] >= 3


def test_lm_train_step_module_scopes_and_outputs():
    from repro.launch.steps import projection_engine_for
    from repro.train.loop import TrainConfig, build_accum_step
    model = _lm_model()
    acfg, tcfg = AdamConfig(), TrainConfig()
    engine = projection_engine_for(model.cfg, None, True)
    step = build_accum_step(model, acfg, tcfg, engine=engine)
    params = model.init(jax.random.PRNGKey(0))
    batch = jax.tree_util.tree_map(jnp.asarray, _lm_batcher(model).get(0))
    args = (params, adam_init(params, acfg), engine.init_state(params),
            batch, 1e-3)
    text = step.lower(*args).as_text(debug_info=True)
    assert "jit_lm_train_step" in text
    for scope in ("fwd_bwd", "ssd/chunk_scan", "proj/update", "proj/newton"):
        assert f"/{scope}/" in text, scope
    out = step(*args)
    assert len(out) == 5 and int(out[4]) > 0


def test_a_profiled_train_holds_its_spans(tmp_path):
    from repro.train.loop import TrainConfig, train
    model = _lm_model()
    tcfg = TrainConfig(steps=2, log_every=100, ckpt_every=100)
    spans = _profiled(lambda: train(model, _lm_batcher(model), tcfg,
                                    resume=False), tmp_path)
    assert [s["step_num"] for s in spans["repro/train/step"]] == [0, 1]
    assert len(spans["repro/train/batch"]) == 2
    assert len(spans["repro/train/sync"]) == 2


def test_microbatched_step_keeps_the_trailing_count():
    from repro.train.loop import TrainConfig, build_accum_step
    from repro.launch.steps import projection_engine_for
    model = _lm_model()
    acfg = AdamConfig()
    tcfg = dataclasses.replace(TrainConfig(), microbatches=2)
    engine = projection_engine_for(model.cfg, None, True)
    step = build_accum_step(model, acfg, tcfg, engine=engine)
    params = model.init(jax.random.PRNGKey(0))
    batch = jax.tree_util.tree_map(jnp.asarray, _lm_batcher(model).get(0))
    out = step(params, adam_init(params, acfg), engine.init_state(params),
               batch, 1e-3)
    assert len(out) == 5 and np.isfinite(float(out[3])) and int(out[4]) > 0
