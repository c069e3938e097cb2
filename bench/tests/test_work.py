"""FLOP and byte counts (bench/work) against hand counts at small sizes,
and the peak table (bench/peaks.py)."""
import json
import pathlib

import pytest

from bench import peaks
from bench.run import load_module

BENCH = pathlib.Path(__file__).resolve().parents[1]


def work(name):
    return load_module(BENCH / "work" / f"{name}.py", f"work_{name}")


def test_sae_counts_by_hand():
    w = work("sae-table1")
    cfg = {"n_features": 10, "n_hidden": 4, "n_classes": 2}
    # layers 10x4, 4x2, 2x4, 4x10: 40 + 8 + 8 + 40 = 96 weights, 20 biases
    assert w.n_params(cfg) == 116
    # fwd 2*96 = 192; bwd weight grads 192; input grads 192 - 2*40
    assert w.train_flops_per_unit(cfg, {}) == 192 * 3 - 80
    assert w.update_bytes(cfg, {}) == 7 * 4 * 116


def test_mamba2_counts_by_hand():
    w = work("mamba2-370m")
    cfg = {"arch": {"n_layers": 2, "d_model": 8, "ssm_state": 4,
                    "ssm_expand": 2, "ssm_headdim": 4, "ssm_chunk": 8,
                    "vocab": 100}}
    # d=8, di=16, H=4, N=4: wz, wx 2*128, wB, wC 2*32, wdt 32, wo 128
    per_layer = 256 + 64 + 32 + 128
    assert w.matmul_params(cfg) == 2 * per_layer + 100 * 8
    ssd = 8 * 4 / 2 + 8 * 4 * 4 / 2 + 2 * 4 * 4 * 4
    assert w.train_flops_per_unit(cfg, {}) == \
        6 * w.matmul_params(cfg) + 6 * ssd * 2
    # every leaf of the layout: per layer the matmuls, dt_bias/A_log/D
    # (3*4), three convs (4*(16+4+4)), the gate norm (16), the block norm
    # (8); the padded table 128*8; the final norm 8
    leaves = per_layer + 12 + 96 + 16 + 8
    assert w.n_params(cfg) == 2 * leaves + 128 * 8 + 8


def test_mamba2_370m_is_370m():
    w = work("mamba2-370m")
    cfg = json.loads((BENCH / "configs" / "mamba2-370m.json").read_text())
    assert 360e6 < w.n_params(cfg) < 380e6
    assert 2.2e9 < w.train_flops_per_unit(cfg, {}) < 2.5e9


def test_peak_table_has_its_chip_and_refuses_others():
    p = peaks.peak("TPU v5 lite")
    assert p["flops_bf16"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak("TPU v9 imaginary")
