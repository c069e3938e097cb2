#!/usr/bin/env python3
"""The on-chip benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration and a traffic mix; everything that belongs to
one of them sits in files of its own, found by name:

  bench/configs/<config>.json      the sizes as run, and the ``job`` that runs them
  bench/traffic/<traffic>.json     the mix's parameters, read by that job
  bench/jobs/<job>.py              drives the program's own entry point
  bench/reference/<config>.py      the plain reference of the check
  bench/work/<config>.py           FLOPs and bytes counted from the shapes
  bench/limits/<cell>.json         the limit of each number the check compares
  bench/metrics/<metric>.py        one per-layer metric, read from the trace

A run: set-up (imports, data and weights from the seed, every shape
warmed, compiled programs from ``.jax_cache`` in the checkout), the window
of ``--seconds``, with ``--trace 1`` a traced window and the jitted layer
calls under the profiler, then the check against the plain reference. The
last line of standard output is the result as JSON. Off a TPU, or on a
chip missing from ``bench/peaks.py``, it prints no result and exits
non-zero.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


class NoChip(RuntimeError):
    """The run is not on a chip the benchmark measures."""


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Cell:
    """One entry of ``workloads`` with its parts, loaded by name from
    ``root``."""

    def __init__(self, name: str, root: pathlib.Path = ROOT):
        bench = json.loads((root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"known: {sorted(cells)}")
        self.name, self.entry = name, cells[name]
        configs = {c["name"]: c for c in bench["configs"]}
        self.config_entry = configs[self.entry["config"]]
        here = root / "bench"
        self.cfg = json.loads((root / self.config_entry["file"]).read_text())
        self.traffic = json.loads(
            (here / "traffic" / f"{self.entry['traffic']}.json").read_text())
        self.limits = json.loads(
            (here / "limits" / f"{name}.json").read_text())
        cfg_name = self.entry["config"]
        self.job_module = load_module(here / "jobs" / f"{self.cfg['job']}.py",
                                      f"bench_job_{self.cfg['job']}")
        self.reference = load_module(here / "reference" / f"{cfg_name}.py",
                                     f"bench_reference_{cfg_name}")
        self.work = load_module(here / "work" / f"{cfg_name}.py",
                                f"bench_work_{cfg_name}")
        self.chips = self.entry["chips"]
        self.rate_metric = self.job_module.Job.rate_metric
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])
                          and m["moves"] in reported]
        self.readers = {m["name"]: load_module(
            here / "metrics" / f"{m['name']}.py", f"bench_metric_{m['name']}")
            for m in self.per_layer}


def require_chip(chips: int):
    """The devices of a measured run: a TPU in the peak table, with at
    least ``chips`` devices. Raises NoChip otherwise."""
    import jax
    from bench.peaks import UnknownDevice, peak
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"platform {dev.platform!r}: the benchmark measures a "
                     "TPU and prints no result elsewhere")
    if len(devices) < chips:
        raise NoChip(f"{len(devices)} devices, the cell needs {chips}")
    try:
        return devices[:chips], peak(dev.device_kind)
    except UnknownDevice as e:
        raise NoChip(str(e)) from None


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def _layer_calls(job, repeats: int = 5) -> None:
    """Each of the job's layer calls, ``repeats`` times under its span. An
    entry's arguments may be a function that makes them, called when the
    entry's turn comes, so that no two entries' arrays need be live."""
    import jax
    for name, (fn, args) in job.layer_calls().items():
        args = args() if callable(args) else args
        jax.block_until_ready(fn(*args))            # compiled before tracing
        for _ in range(repeats):
            with _span(f"bench/{name}"):
                jax.block_until_ready(fn(*args))


class Profile:
    """The profiler session of a traced run. The job calls ``start`` and
    ``end_window`` around the stretch it runs traced after its window; the
    harness then traces the layer calls and stops."""

    def __init__(self, trace_dir: str):
        self.dir, self.on, self.span = trace_dir, False, None

    def start(self):
        import jax
        jax.profiler.start_trace(self.dir)
        self.on = True
        self.span = _span("bench/window")
        self.span.__enter__()

    def end_window(self):
        self.span.__exit__(None, None, None)

    def stop(self):
        import jax
        if self.on:
            jax.profiler.stop_trace()
            self.on = False


def run(workload: str, seed: int, seconds: float, traced: bool,
        root: pathlib.Path = ROOT, chip_check=require_chip,
        trace_layout=None) -> dict:
    """One run of ``workload``; returns the result line as a dict.
    ``chip_check`` and ``trace_layout`` let the tests drive the rest of a
    run on the CPU."""
    import jax
    from bench import check, trace
    from repro.launch.cache import enable_compile_cache

    cell = Cell(workload, root)
    t_imports = time.perf_counter() - T_START
    devices, peak = chip_check(cell.chips)
    t_chip = time.perf_counter() - T_START - t_imports
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    traces = {"n": 0}

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            traces["n"] += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)

    job = cell.job_module.Job(cell.cfg, cell.traffic, seed, log)
    log(f"[bench] set-up: imports {t_imports:.3f} s, chip {t_chip:.3f} s")
    job.setup()
    n0 = traces["n"]
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    profile = Profile(trace_dir) if traced else None
    try:
        win = job.window(seconds, _span, profile)
        setup_s = win["t_start"] - T_START
        n_traces = traces["n"] - n0
        memory_peak = _peak_bytes(devices)
        if traced:
            _layer_calls(job)
            profile.stop()
            reduced = trace.reduce_trace(trace.find_xplane(trace_dir),
                                         layout=trace_layout or trace.TPU)
    finally:
        if traced:
            profile.stop()
            shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"[bench] {workload}: set-up {setup_s:.3f} s; window "
        f"{win['units']} units in {win['elapsed_s']:.3f} s, "
        f"{cell.rate_metric} {win['rate']:.6g}, {n_traces} jaxpr traces")

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": False, "attempted": win["attempted"],
              "failed": win["failed"]}
    if traced:
        ctx = {"rate": win["rate"], "trace": reduced, "cfg": cell.cfg,
               "traffic": cell.traffic, "work": cell.work, "peak": peak,
               "counters": {"jaxpr_traces": n_traces,
                            "units": win["units"] + win.get("traced_units", 0)}}
        metrics = {}
        for m in cell.per_layer:
            value = cell.readers[m["name"]].read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    else:
        values = {"setup_s": setup_s, cell.rate_metric: win["rate"]}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result["metrics"] = metrics
    result["device"] = device

    prog = job.program_readings()
    job.release()
    ref = job.reference_readings(cell.reference)
    numbers = check.gaps(prog, ref)
    ok, rows = check.judge(numbers, cell.limits)
    result["correct"] = bool(ok) and win["failed"] == 0
    result["compared"] = {r["name"]: {"value": r["value"], "limit": r["limit"]}
                          for r in rows}
    for r in rows:
        log(f"[check] {r['name']} {r['value']!r} limit {r['limit']!r} "
            f"{'ok' if r['value'] <= r['limit'] else 'FAILED'}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for p in (ROOT, ROOT / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"[bench] no result: {e}")
        return 3
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
