"""The harness: cells assembled by name, a cell added by files alone, and
no result off a TPU."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from bench import run

from .conftest import CPU_LAYOUT, ROOT, cpu_chip

SAE_CELL = "sae-table1.l1inf-sparse"
LM_CELL = "mamba2-370m.train-2k"


def test_every_cell_is_assembled_from_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = run.Cell(w["name"])
        assert cell.traffic and cell.limits and cell.cfg["job"]
        assert hasattr(cell.reference, "readings")
        assert hasattr(cell.work, "train_flops_per_unit")
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s",
                                                        cell.rate_metric}
        assert cell.per_layer and set(cell.readers) == {
            m["name"] for m in cell.per_layer}


def test_benchmark_json_keeps_the_contract_shape():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", [w])
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()


def test_a_cell_added_as_files_runs_without_edits(tiny):
    """A new traffic mix, its limits and a BENCHMARK.json entry are all a
    new cell needs."""
    traffic = json.loads((tiny / "bench/traffic/l1inf-sparse.json").read_text())
    (tiny / "bench/traffic/l1inf-dense.json").write_text(
        json.dumps({**traffic, "radius": 20.0}))
    shutil.copy(tiny / f"bench/limits/{SAE_CELL}.json",
                tiny / "bench/limits/sae-table1.l1inf-dense.json")
    bench = json.loads((tiny / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "sae-table1.l1inf-dense",
                               "config": "sae-table1",
                               "traffic": "l1inf-dense", "chips": 1,
                               "why": "the dense regime"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if SAE_CELL in m.get("workloads", []):
            m["workloads"].append("sae-table1.l1inf-dense")
    (tiny / "BENCHMARK.json").write_text(json.dumps(bench))
    r = run.run("sae-table1.l1inf-dense", 2**31 + 5, 0.1, False, root=tiny,
                chip_check=cpu_chip)
    assert r["correct"], r["compared"]
    assert set(r["metrics"]) == {"setup_s", "sae_train_samples_per_s"}
    assert list(r)[-1] == "compared"


@pytest.mark.parametrize("cell", [SAE_CELL, LM_CELL])
def test_traced_run_reports_per_layer_metrics(tiny, cell):
    r = run.run(cell, 2**31 + 9, 0.2, True, root=tiny, chip_check=cpu_chip,
                trace_layout=CPU_LAYOUT)
    assert r["correct"], r["compared"]
    bench = json.loads((tiny / "BENCHMARK.json").read_text())
    names = {m["name"] for m in bench["per_layer"] if cell in m["workloads"]}
    # the module timings need the TPU's module line; the rest read here
    assert {"mfu", "device_idle_share"} <= {n.split(".")[0]
                                            for n in r["metrics"]}
    assert set(r["metrics"]) <= names
    assert 0 < r["device"]["busy_s"] <= r["device"]["window_s"]
    assert len(r["breakdown"]["device_ops"]) <= 10


def _cli(args, cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_the_command_refuses_a_cpu():
    p = _cli(["--workload", SAE_CELL, "--seed", "1", "--seconds", "1",
              "--trace", "0"], ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == "" or not p.stdout.strip().splitlines()[-1] \
        .startswith("{")
    assert "platform 'cpu'" in p.stderr


def test_an_unknown_chip_is_refused(monkeypatch):
    import jax

    class Fake:
        platform, device_kind = "tpu", "TPU v9 imaginary"
    monkeypatch.setattr(jax, "devices", lambda *a: [Fake()])
    with pytest.raises(run.NoChip, match="no peak rates"):
        run.require_chip(1)


def test_too_few_chips_are_refused(monkeypatch):
    import jax

    class Fake:
        platform, device_kind = "tpu", "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda *a: [Fake()])
    with pytest.raises(run.NoChip, match="needs 4"):
        run.require_chip(4)


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = _cli(["--workload", SAE_CELL, "--seed", "1", "--seconds", "1",
              "--trace", "0"], tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert not p.stdout.strip()
