"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Interpret mode cannot see what the chip's compiler refuses (block shapes
that do not tile, fast memory overruns). These tests compile each kernel of
the projected train step for a described ``v5e:2x2`` topology, which needs
the TPU compiler but no chip, and check that the compiled program holds the
Mosaic kernel (``tpu_custom_call``) rather than an interpreted twin.

Shapes: the SAE's ``enc1/w`` (10000 features x 96 hidden) both ways round,
its packed projection buffer (96, 10112), mamba2-370m's scan-stacked
``ssm/wx`` (48, 1024, 2048) and its packed buffer (1024, 98304), and the
(10000, 96) engine buffer whose 10000-row columns exceed one VMEM tile.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_step import (fused_adam_clip_apply,
                                      fused_adam_colstats)
from repro.kernels.l1inf import (project_bilevel_pallas_segmented,
                                 project_l1inf_pallas_segmented)
from repro.optim import AdamConfig


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("shape,transpose", [
    ((10000, 96), False),
    ((96, 10000), True),
    ((48, 1024, 2048), False),
    ((48, 1024, 2048), True),
])
def test_fused_step_passes_compile(one_chip, shape, transpose):
    acfg = AdamConfig()
    kw = dict(cfg=acfg, lr_t=1e-3, b1c=0.1, b2c=0.001, transpose=transpose,
              impl="pallas", interpret=False)
    leaf = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    lead = 1 if len(shape) == 2 else shape[0]
    mcols = shape[-2] if transpose else shape[-1]
    mu = jax.ShapeDtypeStruct((lead, mcols), jnp.float32, sharding=one_chip)

    pass1 = _compiled_text(
        lambda g, m, v, p: fused_adam_colstats(g, m, v, p, **kw),
        leaf, leaf, leaf, leaf)
    pass2 = _compiled_text(
        lambda m, v, p, mu: fused_adam_clip_apply(m, v, p, mu, **kw),
        leaf, leaf, leaf, mu)
    assert "tpu_custom_call" in pass1
    assert "tpu_custom_call" in pass2


@pytest.mark.parametrize("family", ["l1inf", "bilevel"])
@pytest.mark.parametrize("shape", [(96, 10112), (1024, 98304), (10000, 96)])
def test_segmented_engine_compiles(one_chip, family, shape):
    fn = {"l1inf": project_l1inf_pallas_segmented,
          "bilevel": project_bilevel_pallas_segmented}[family]
    Y = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    sids = jax.ShapeDtypeStruct(shape[1:], jnp.int32, sharding=one_chip)
    C = jax.ShapeDtypeStruct((2,), jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda y, s, c: fn(y, s, c, num_segments=2, interpret=False),
        Y, sids, C)
    assert "tpu_custom_call" in text
