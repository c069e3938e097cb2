#!/usr/bin/env python3
"""Reduce the program's own spans and scopes in a ``jax.profiler`` trace.

``bench/trace.py`` reduces a trace by the harness's spans (``bench/``) and
the device's lines. This module adds what the program itself writes
(``repro.obs``):

  scopes        for each execution of a module (an ``XLA Modules`` event),
                the device seconds of its ops grouped by named scope
                (``SCOPES``); ops under none go under ``""``. An op's
                scope path is the ``tf_op`` stat of its event metadata
                (the ``op_name`` of the HLO instruction). Nested ops (a
                ``while`` and its body) count once: each instant goes to
                the innermost op running. Scopes nest, so an op under
                ``proj/newton`` counts under ``proj/update`` as well.
  op_s          beside ``scopes``: each execution's device seconds of ops.
  idle_by_span  idle device seconds of the window, split by time over the
                innermost program span (``repro/<name>``) open at each
                instant, keyed ``<name>``; ``""`` where none is open.
                Averaged over the chips, as ``busy_s`` is, so the values
                sum to ``window_s - busy_s``.
  span_counts   how many of each program span start in the window.

``python3 bench/program_trace.py --workload <cell> --seed <n> --seconds
<s>`` makes one traced run of the cell through ``bench/run.py`` with these
reductions added, and prints the per-layer metrics that read them
(``PROGRAM_METRICS``) beside the run's own result, as one JSON line.
"""
from __future__ import annotations

import json
import pathlib
import re
import sys
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

if __package__ in (None, ""):        # run as a script from the checkout
    ROOT = pathlib.Path(__file__).resolve().parents[1]
    for _p in (ROOT, ROOT / "src"):
        if str(_p) not in sys.path:
            sys.path.insert(0, str(_p))

from bench import trace  # noqa: E402

SCOPES = ("fwd_bwd", "proj/update", "proj/newton", "ssd/chunk_scan")
PROGRAM = "repro/"
# the per-layer metrics that read these reductions, by job
PROGRAM_METRICS = {
    "sae": ("step_fwd_bwd_ms.sae", "step_update_ms.sae",
            "newton_evals_per_update.sae", "step_traces_per_fit.sae",
            "idle_batch_share.sae", "idle_step_call_share.sae",
            "idle_epoch_end_share.sae"),
    "lm": ("step_fwd_bwd_ms.lm", "step_ssd_ms.lm", "step_update_ms.lm",
           "step_newton_ms.lm", "newton_evals_per_update.lm"),
}

Interval = Tuple[float, float]
Op = Tuple[float, float, str]          # start, end, op name or scope path


# ---- the op names: a protobuf reader for the trace's event metadata --------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int) -> Iterator[Tuple[int, object]]:
    """(field number, value) of each field of the message in buf[lo:hi]:
    an int for a varint, (start, end) for a length-delimited field."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield field, v
        elif wire == 2:
            n, i = _varint(buf, i)
            yield field, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def op_names(path: str, plane_prefix: str = trace.TPU.plane_prefix
             ) -> Dict[Tuple[int, str], str]:
    """{(program id, op event name): its ``tf_op``} over the planes named
    ``plane_prefix*`` of the ``.xplane.pb`` at ``path``. The stats are kept
    in the plane's event metadata (``XPlane.event_metadata``, field 4; a
    stat's name in ``stat_metadata``, field 5), which ``ProfileData`` does
    not expose. Two programs may hold an op of the same text, so the key
    carries the op's ``program_id``, the number in its module's event name
    (``jit_f(<program id>)``); the event's long and short names both map,
    and the trailing ``:<type>`` of ``tf_op`` is dropped."""
    buf = pathlib.Path(path).read_bytes()
    out: Dict[Tuple[int, str], str] = {}
    for field, plane in _fields(buf, 0, len(buf)):
        if field != 1:                            # XSpace.planes
            continue
        name, metas, stat_names = "", [], {}
        for f, v in _fields(buf, *plane):
            if f == 2:
                name = _text(buf, v)
                if not name.startswith(plane_prefix):
                    break
            elif f == 4:                           # map<int64, XEventMetadata>
                metas += [m for k, m in _fields(buf, *v) if k == 2]
            elif f == 5:                           # map<int64, XStatMetadata>
                for k, m in _fields(buf, *v):
                    if k == 2:
                        got = dict(_fields(buf, *m))
                        if 1 in got and 2 in got:
                            stat_names[got[1]] = _text(buf, got[2])
        else:
            for m in metas:
                names, stats = [], {}
                for f, v in _fields(buf, *m):
                    if f in (2, 4):                # name, display_name
                        names.append(_text(buf, v))
                    elif f == 5:
                        st = dict(_fields(buf, *v))
                        stats[stat_names.get(st.get(1))] = st
                op = stats.get("tf_op", {})
                if 5 in op:                        # str_value
                    op = _text(buf, op[5])
                elif 7 in op:                      # ref_value: a stat name
                    op = stat_names.get(op[7], "")
                else:
                    continue
                op = op.rsplit(":", 1)[0] if ":" in op else op
                pid = stats.get("program_id", {}).get(3, 0)  # uint64_value
                for n in names:
                    if n:
                        out.setdefault((pid, n), op)
    return out


# ---- scopes -----------------------------------------------------------------

_WRAPPER = re.compile(r"^[\w\-]+\(")
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


def _components(path: str) -> List[str]:
    """The components of an op path with transform and call wrappers taken
    off: ``jit(f)/transpose(jvp(proj/update))/dot`` ->
    ``["f", "proj", "update", "dot"]``."""
    out = []
    for c in path.split("/"):
        while _WRAPPER.match(c):
            c = _WRAPPER.sub("", c, count=1)
        out.append(c.rstrip(")"))
    return out


def in_scope(path: str, scope: str) -> bool:
    """Whether ``scope`` (one or more ``/`` components) is a run of path
    components of the op path ``path``."""
    comps, want = _components(path), scope.split("/")
    n = len(want)
    return any(comps[i:i + n] == want for i in range(len(comps) - n + 1))


def self_times(ops: Sequence[Op]) -> List[Tuple[float, str]]:
    """(own time, scope path) of each op of one line, where ops nest: the
    time no op nested inside it covers."""
    out: List[List] = []
    stack: List[Tuple[float, int]] = []           # (end, index in out)
    for s, e, path in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            parent_end, j = stack[-1]
            out[j][0] -= min(e, parent_end) - s
        out.append([e - s, path])
        stack.append((e, len(out) - 1))
    return [(max(t, 0.0), p) for t, p in out]


def scope_times(ops: Sequence[Op], scopes: Sequence[str] = SCOPES
                ) -> Dict[str, float]:
    """Device time of ``ops`` (one execution) by scope; ``""`` for ops
    under none of ``scopes``."""
    out: Dict[str, float] = {}
    for t, path in self_times(ops):
        hit = [sc for sc in scopes if in_scope(path, sc)] or [""]
        for sc in hit:
            out[sc] = out.get(sc, 0.0) + t
    return out


def by_execution(ops: Sequence[Op], modules: Sequence[Tuple[float, float,
                                                             str]]
                 ) -> List[Tuple[str, List[Op]]]:
    """(module event name, its ops) of each execution, in time order: an
    op belongs to the execution whose interval holds its start."""
    out: List[Tuple[str, List[Op]]] = []
    ops = sorted(ops)
    j = 0
    for s, e, name in sorted(modules):
        mine: List[Op] = []
        while j < len(ops) and ops[j][0] < s:
            j += 1
        while j < len(ops) and ops[j][0] < e:
            mine.append(ops[j])
            j += 1
        out.append((name, mine))
    return out


def program_id(module_event: str) -> int:
    """``jit_f(6551415854901150784)`` -> 6551415854901150784 (0 if none)."""
    m = _PROGRAM_ID.search(module_event)
    return int(m.group(1)) if m else 0


# ---- program spans and idle time --------------------------------------------

def innermost(spans: Sequence[Tuple[float, float, str]], lo: float,
              hi: float) -> List[Tuple[float, float, str]]:
    """[lo, hi) cut into (start, end, name) by the innermost span open at
    each instant (spans of one thread nest); ``""`` where none is open."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []           # (end, name)
    t = lo

    def upto(x):
        nonlocal t
        while stack and stack[-1][0] <= x:
            end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end
        if x > t:
            out.append((t, x, stack[-1][1] if stack else ""))
            t = x

    for s, e, name in sorted(spans, key=lambda sp: (sp[0], -sp[1])):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        upto(s)
        stack.append((e, name))
    upto(hi)
    return out


def split_idle(idle: Sequence[Interval],
               parts: Sequence[Tuple[float, float, str]]) -> Dict[str, float]:
    """Time of each part's name that ``idle`` covers; both sorted and
    disjoint."""
    out: Dict[str, float] = {}
    i = 0
    for s, e, name in parts:
        while i < len(idle) and idle[i][1] <= s:
            i += 1
        k = i
        while k < len(idle) and idle[k][0] < e:
            d = min(e, idle[k][1]) - max(s, idle[k][0])
            if d > 0:
                out[name] = out.get(name, 0.0) + d
            k += 1
    return out


def span_counts(spans: Sequence[Tuple[float, float, str]], lo: float,
                hi: float) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for s, _, name in spans:
        if lo <= s < hi:
            out[name] = out.get(name, 0) + 1
    return out


def reduce_program(path: str, window: str = "bench/window",
                   layout: trace.DeviceLayout = trace.TPU,
                   scopes: Sequence[str] = SCOPES) -> dict:
    """``scopes``, ``op_s``, ``idle_by_span`` and ``span_counts`` (module
    docstring) of the ``.xplane.pb`` at ``path``, over the window of
    ``reduce_trace``."""
    import jax

    names = op_names(path, layout.plane_prefix)
    pd = jax.profiler.ProfileData.from_file(path)
    program, wins, planes = [], [], []
    for plane in pd.planes:
        if plane.name == layout.host_plane:
            for line in plane.lines:
                for ev in line.events:
                    end = ev.start_ns + ev.duration_ns
                    if ev.name.startswith(PROGRAM):
                        program.append((ev.start_ns, end,
                                        ev.name[len(PROGRAM):]))
                    elif ev.name == window:
                        wins.append((ev.start_ns, end))
        if plane.name.startswith(layout.plane_prefix):
            planes.append(plane)
    if not wins:
        raise ValueError(f"the trace holds no host span named {window!r}")
    if not planes:
        raise ValueError(f"the trace holds no plane {layout.plane_prefix}*")
    lo, hi = min(s for s, _ in wins), max(e for _, e in wins)

    parts = innermost(program, lo, hi)
    idle: Dict[str, float] = {}
    per_module: Dict[str, List[Dict[str, float]]] = {}
    op_s: Dict[str, List[float]] = {}
    for plane in planes:
        ops: List[Op] = []
        modules = []
        for line in plane.lines:
            if layout.op_line(line.name):
                for ev in line.events:
                    if ev.duration_ns > 0 and layout.op_event(ev.name):
                        ops.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                    ev.name))
            elif layout.module_line(line.name):
                modules += [(ev.start_ns, ev.start_ns + ev.duration_ns,
                             ev.name) for ev in line.events]
        busy = trace.clip(trace.union([(s, e) for s, e, _ in ops]), lo, hi)
        for name, t in split_idle(trace.gaps(busy, lo, hi), parts).items():
            idle[name] = idle.get(name, 0.0) + t
        for module, run in by_execution(ops, modules):
            pid = program_id(module)
            run = [(s, e, names.get((pid, op), "")) for s, e, op in run]
            mod = trace.module_name(module)
            per_module.setdefault(mod, []).append(
                {k: v * 1e-9 for k, v in scope_times(run, scopes).items()})
            op_s.setdefault(mod, []).append(
                1e-9 * sum(t for t, _ in self_times(run)))
    n = len(planes)
    return {"scopes": per_module, "op_s": op_s,
            "idle_by_span": {k: v * 1e-9 / n for k, v in idle.items()},
            "span_counts": span_counts(program, lo, hi)}


# ---- one traced run with these reductions -----------------------------------

def traced_run(workload: str, seed: int, seconds: float, **run_kwargs
               ) -> dict:
    """One traced run of ``workload`` through ``bench.run.run`` with the
    reductions of this module beside ``reduce_trace``'s. Returns
    {"result": the run's result line, "program_metrics": {name: value},
    "reduced": idle_by_span, span_counts and ``summary`` of the scopes}."""
    from bench import run

    got: dict = {}
    reduce_trace = trace.reduce_trace

    def reduce_both(path, *args, **kwargs):
        out = reduce_trace(path, *args, **kwargs)
        got.update(out)
        got.update(reduce_program(
            path, layout=kwargs.get("layout", trace.TPU)))
        return out

    trace.reduce_trace = reduce_both
    try:
        result = run.run(workload, seed, seconds, True, **run_kwargs)
    finally:
        trace.reduce_trace = reduce_trace
    root = pathlib.Path(run_kwargs.get("root", run.ROOT))
    cell = run.Cell(workload, root)
    metrics: Dict[str, Optional[float]] = {}
    for name in PROGRAM_METRICS[cell.cfg["job"]]:
        reader = run.load_module(root / "bench" / "metrics" / f"{name}.py",
                                 f"bench_metric_{name}")
        metrics[name] = reader.read({"trace": got})
    return {"result": result, "program_metrics": metrics,
            "reduced": {"idle_by_span": got.get("idle_by_span"),
                        "span_counts": got.get("span_counts"),
                        "scopes": summary(got.get("scopes", {}),
                                          got.get("op_s", {}))}}


def summary(scopes: Dict[str, List[Dict[str, float]]],
            op_s: Dict[str, List[float]]) -> dict:
    """Per module with scoped ops: its executions, the median device
    seconds of each scope and of its ops, and the median share of an
    execution's op time that no scope holds."""
    def median(vals):
        vals = sorted(vals)
        return vals[len(vals) // 2] if vals else None

    out = {}
    for mod, runs in scopes.items():
        keys = sorted({k for r in runs for k in r if k})
        if not keys:
            continue
        out[mod] = {
            "executions": len(runs),
            "median_s": {k: median([r.get(k, 0.0) for r in runs])
                         for k in keys + [""]},
            "op_s": median(op_s[mod]),
            "unscoped_share": median([r.get("", 0.0) / t for r, t in
                                      zip(runs, op_s[mod]) if t > 0])}
    return out


def main(argv=None) -> int:
    import argparse
    from bench import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        out = traced_run(args.workload, args.seed, args.seconds)
    except run.NoChip as e:
        run.log(f"[program_trace] no result: {e}")
        return 3
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
