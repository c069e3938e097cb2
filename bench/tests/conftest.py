"""Shared pieces of the benchmark's tests: the checkout on ``sys.path``, a
copy of the benchmark with tiny cells, and a stand-in for the chip."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import trace  # noqa: E402

# the CPU backend's trace: ops run on the client's and Eigen's threads of
# the host plane, beside bookkeeping events
CPU_LAYOUT = trace.DeviceLayout(
    plane_prefix="/host:CPU",
    op_line=lambda name: name.startswith("tf_XLA"),
    module_line=lambda name: False,
    op_event=lambda name: not (name.startswith("ThreadpoolListener")
                               or name.startswith("end:")))

TINY_SAE = {"n_samples": 160, "n_features": 300, "n_informative": 8,
            "n_hidden": 16, "batch_size": 32, "epochs": 2}
TINY_LM = {"arch": {"name": "mamba2-tiny", "family": "ssm", "n_layers": 2,
                    "d_model": 64, "n_heads": 1, "n_kv_heads": 1,
                    "head_dim": 16, "d_ff": 0, "vocab": 500,
                    "pattern": ["ssm"], "ssm_state": 16, "ssm_expand": 2,
                    "ssm_headdim": 16, "ssm_chunk": 16,
                    "tie_embeddings": True, "norm_eps": 1e-5,
                    "ssd_bf16": False},
           "gated_norm_eps": 1e-6,
           "projection": [{"pattern": "blocks/.*/ssm/wx$", "norm": "l1inf",
                           "radius": 4.0, "axis": 0, "every_k": 10}]}
TINY_LM_TRAFFIC = {"seq": 128, "batch": 2, "trace_steps": 2}


def cpu_chip(chips):
    import jax
    from bench.peaks import PEAKS
    return jax.devices()[:chips], PEAKS["TPU v5 lite"]


def tiny_root(tmp_path: pathlib.Path, cells: dict,
              traffic: dict = None) -> pathlib.Path:
    """A copy of the benchmark whose configurations are cut by ``cells``
    ({config name: {key: value}}) and traffic mixes by ``traffic`` (the
    same by mix name), with the limits of the real cells."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        if c["name"] in cells:
            path = root / c["file"]
            cfg = json.loads(path.read_text())
            cfg.update(cells[c["name"]])
            path.write_text(json.dumps(cfg))
    for name, over in (traffic or {}).items():
        path = root / "bench" / "traffic" / f"{name}.json"
        path.write_text(json.dumps({**json.loads(path.read_text()), **over}))
    return root


@pytest.fixture
def tiny(tmp_path):
    return tiny_root(tmp_path, {"sae-table1": TINY_SAE, "mamba2-370m": TINY_LM},
                     {"train-2k": TINY_LM_TRAFFIC})
