"""Reduce a ``jax.profiler`` trace to device busy time, top ops and idle gaps.

A TPU trace has one plane per chip, ``/device:TPU:<n>``, whose ``XLA Ops``
line holds one event per operation that ran on the chip and whose ``XLA
Modules`` line holds one event per executed program (``jit_<name>(<id>)``).
The host's plane, ``/host:CPU``, holds the harness's own spans
(``jax.profiler.TraceAnnotation``) on the line of the thread that opened
them. All of them share one clock.

``reduce_trace`` takes the window from the host span named ``window`` and
returns, for that window:

  busy_s       the union of the op intervals, averaged over the chips;
  window_s     the span's length;
  device_ops   the ops that took most device time, summed by name (an
               HLO instruction shortened by ``short_op``);
  idle_gaps    the longest stretches with no op on the chip, each named by
               the innermost harness span open at its middle;
  modules      device seconds of every execution of each program, by name.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from typing import Callable, Dict, List, Optional, Tuple

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class DeviceLayout:
    """Where a backend's trace keeps the device's work."""
    plane_prefix: str = "/device:TPU:"
    op_line: Callable[[str], bool] = lambda name: name == "XLA Ops"
    module_line: Callable[[str], bool] = lambda name: name == "XLA Modules"
    op_event: Callable[[str], bool] = lambda name: True
    host_plane: str = "/host:CPU"


TPU = DeviceLayout()


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: List[Interval]) -> List[Interval]:
    """Merge overlapping [start, end) intervals; sorted, disjoint."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The stretches of [lo, hi) that no interval of ``busy`` covers."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


_MODULE_ID = re.compile(r"\(\d+\)$")
_HLO_OP = re.compile(r"^(%\S+) = (.*?) ([a-z][\w\-]*)\(")


def short_op(event_name: str, type_chars: int = 48) -> str:
    """A TPU op event is named by its whole HLO instruction; keep its name,
    opcode and the start of its result type:
    ``%sort.7 = (f32[96,10112]..., ...) sort(...)`` ->
    ``%sort.7 sort (f32[96,10112]...``. Other names pass unchanged."""
    m = _HLO_OP.match(event_name)
    if not m:
        return event_name
    name, typ, opcode = m.groups()
    return f"{name} {opcode} {typ[:type_chars]}"


def module_name(event_name: str) -> str:
    """``jit_bench_fwd_bwd(42)`` -> ``jit_bench_fwd_bwd``."""
    return _MODULE_ID.sub("", event_name)


def _label(spans: List[Tuple[float, float, str]], t: float) -> str:
    """The innermost (shortest) host span that holds time ``t``."""
    best: Optional[Tuple[float, str]] = None
    for s, e, name in spans:
        if s <= t < e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "outside the window"


def reduce_trace(path: str, window: str = "bench/window",
                 layout: DeviceLayout = TPU, top: int = 10) -> dict:
    """Reduce the ``.xplane.pb`` at ``path`` (see the module docstring)."""
    import jax  # the reader ships with jax; import late so tests stay light

    pd = jax.profiler.ProfileData.from_file(path)
    spans: List[Tuple[float, float, str]] = []
    device_planes = []
    for plane in pd.planes:
        if plane.name == layout.host_plane:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench/"):
                        spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                      ev.name))
        if plane.name.startswith(layout.plane_prefix):
            device_planes.append(plane)
    wins = [(s, e) for s, e, name in spans if name == window]
    if not wins:
        raise ValueError(f"the trace holds no host span named {window!r}")
    lo, hi = min(s for s, _ in wins), max(e for _, e in wins)
    if not device_planes:
        raise ValueError(f"the trace holds no plane {layout.plane_prefix}*")

    busy_total = 0.0
    op_time: Dict[str, float] = {}
    modules: Dict[str, List[float]] = {}
    all_gaps: List[Interval] = []
    for plane in device_planes:
        ops: List[Interval] = []
        for line in plane.lines:
            if layout.op_line(line.name):
                for ev in line.events:
                    if ev.duration_ns <= 0 or not layout.op_event(ev.name):
                        continue
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    ops.append((s, e))
                    if e > lo and s < hi:
                        op_time[ev.name] = op_time.get(ev.name, 0.0) + (
                            min(e, hi) - max(s, lo)) * 1e-9
            elif layout.module_line(line.name):
                for ev in line.events:
                    modules.setdefault(module_name(ev.name), []).append(
                        ev.duration_ns * 1e-9)
        busy = clip(union(ops), lo, hi)
        busy_total += sum(e - s for s, e in busy)
        all_gaps += gaps(busy, lo, hi)

    n = len(device_planes)
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]
    inner = spans   # the window itself names a gap no inner span holds
    return {
        "busy_s": busy_total * 1e-9 / n,
        "window_s": (hi - lo) * 1e-9,
        "device_ops": [[short_op(k), v / n] for k, v in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_label(inner, (s + e) / 2), (e - s) * 1e-9]
                      for s, e in longest],
        "modules": modules,
    }
