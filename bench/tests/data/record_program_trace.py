"""Records ``program_trace.xplane.pb``, a small trace with the program's
own spans, which the tests reduce.

    JAX_PLATFORMS=cpu PYTHONPATH=src \
        python bench/tests/data/record_program_trace.py

One descent of the SAE's training loop (``repro.sae.train._run_descent``:
72 rows in batches of 32, 32 and 8, two epochs, so six ``repro/sae/step``
spans) inside ``bench/window``, after an untraced descent with the same
jitted step that compiles both batch shapes.
"""
import glob
import pathlib
import shutil
import tempfile

import numpy as np
import jax

from repro.core import ProjectionSpec
from repro.optim import AdamConfig
from repro.sae import SAEConfig, SAETrainConfig
from repro.sae.model import sae_init
from repro.sae.train import _make_step, _run_descent

HERE = pathlib.Path(__file__).resolve().parent


def main():
    rng = np.random.default_rng(0)
    X = rng.standard_normal((72, 24)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.int32)
    cfg = SAEConfig(n_features=24, n_hidden=8, n_classes=2)
    tcfg = SAETrainConfig(epochs=2, batch_size=32, projection=ProjectionSpec(
        pattern="enc1/w", norm="l1inf", radius=0.5, axis=1))
    step, engine = _make_step(cfg, tcfg, AdamConfig(lr=tcfg.lr))
    params = sae_init(jax.random.PRNGKey(0), cfg)
    mask = jax.tree_util.tree_map(jax.numpy.ones_like, params)

    def descent():
        _run_descent(params, step, engine, jax.numpy.asarray(X),
                     jax.numpy.asarray(y), tcfg, mask,
                     np.random.default_rng(0), specs=(tcfg.projection,))

    descent()
    tmp = tempfile.mkdtemp()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0        # no Python call events: small
    options.enable_hlo_proto = False
    jax.profiler.start_trace(tmp, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench/window"):
        descent()
    jax.profiler.stop_trace()
    src = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(src, HERE / "program_trace.xplane.pb")
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
