"""The check decides ``correct``: sound runs pass, while the control (the
reference in bfloat16 in the program's place) and each planted fault of
bench/faults.py fail, with the cells' own limits, at a size a test can
hold."""
import jax.numpy as jnp
import pytest

from bench import check, faults, run

from .conftest import cpu_chip

CELLS = ["sae-table1.l1inf-sparse", "mamba2-370m.train-2k"]


def test_gaps_are_gaps_of_norms_at_the_worst_leaf():
    ref = {"loss": [2.0, 1.0], "grad": {"a": 1.0, "b": 1.0, "q": 1e-9},
           "change": {"a": 2.0, "b": 4.0, "q": 5.0}, "proj": {"a": 0.5}}
    prog = {"loss": [2.0, 1.1], "grad": {"a": 1.0, "b": 0.9, "q": 0.0},
            "change": {"a": 1.0, "b": 4.0, "q": 0.0}, "proj": {"a": 0.25}}
    g = check.gaps(prog, ref)
    assert g["loss_gap"] == pytest.approx(0.1)
    assert g["grad_gap"] == pytest.approx(0.1)
    # leaf q's gradient is quiet: its change is left out; a is measured
    # against the median change of the moving leaves, 3.0
    assert g["change_gap"] == pytest.approx(1.0 / 3.0)
    assert g["proj_gap"] == pytest.approx(0.5)
    ok, rows = check.judge(g, dict.fromkeys(check.NUMBERS, 0.2))
    assert not ok and [r["name"] for r in rows] == list(check.NUMBERS)
    ok, _ = check.judge({**g, "loss_gap": float("nan")},
                        dict.fromkeys(check.NUMBERS, 1.0))
    assert not ok


def test_a_nan_reading_is_never_skipped():
    ref = {"loss": [2.0, 1.0], "grad": {"a": 1.0}, "change": {"a": 1.0},
           "proj": {"a": 1.0}}
    prog = {"loss": [2.0, float("nan")], "grad": {"a": 1.0},
            "change": {"a": float("nan")}, "proj": {"a": 1.0}}
    g = check.gaps(prog, ref)
    assert g["loss_gap"] != g["loss_gap"] and g["change_gap"] != g["change_gap"]
    assert g["grad_gap"] == 0.0


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_bfloat16_fails(tiny, cell):
    c = run.Cell(cell, tiny)
    job = c.job_module.Job(c.cfg, c.traffic, 2**31 + 21, print)
    job.setup()
    job.window(0.1, run._span)
    ref = job.reference_readings(c.reference)
    ctrl = job.reference_readings(c.reference, jnp.bfloat16)
    ok, rows = check.judge(check.gaps(ctrl, ref), c.limits)
    assert not ok, rows


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_planted_fault_makes_the_run_incorrect(tiny, cell, fault):
    job = run.Cell(cell, tiny).cfg["job"]
    with faults.FAULTS[fault](job):
        r = run.run(cell, 2**31 + 33, 0.1, False, root=tiny,
                    chip_check=cpu_chip)
    assert r["correct"] is False, r["compared"]
