"""bench/trace.py on hand-made intervals and on a trace recorded on the CPU
(data/cpu_trace.xplane.pb, made by data/record_cpu_trace.py)."""
import pathlib

import pytest

from bench import trace

from .conftest import CPU_LAYOUT

TRACE = pathlib.Path(__file__).parent / "data" / "cpu_trace.xplane.pb"


def test_union_merges_overlaps_and_keeps_gaps():
    got = trace.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 11)])
    assert got == [(0, 4), (5, 7), (10, 11)]


def test_gaps_and_clip_cover_the_window_exactly():
    busy = trace.clip(trace.union([(-5, 2), (4, 6), (9, 20)]), 0, 10)
    assert busy == [(0, 2), (4, 6), (9, 10)]
    idle = trace.gaps(busy, 0, 10)
    assert idle == [(2, 4), (6, 9)]
    assert sum(e - s for s, e in busy + idle) == 10


def test_module_name_drops_the_execution_id():
    assert trace.module_name("jit_bench_fwd_bwd_sae(1234)") == \
        "jit_bench_fwd_bwd_sae"
    assert trace.module_name("jit_step") == "jit_step"


def test_recorded_cpu_trace_reduces_to_its_spans():
    r = trace.reduce_trace(str(TRACE), layout=CPU_LAYOUT)
    # three 20 ms sleeps inside a window of about 70 ms
    assert 0.06 <= r["window_s"] <= 0.2
    assert 0 < r["busy_s"] < r["window_s"] - 0.055
    labels = [name for name, _ in r["idle_gaps"]]
    assert labels[:3] == ["bench/host_wait"] * 3
    assert all(0.019 <= s <= 0.03 for _, s in r["idle_gaps"][:3])
    ops = dict(r["device_ops"])
    assert any(name.startswith("dot_general") for name in ops)
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError, match="no host span"):
        trace.reduce_trace(str(TRACE), window="bench/absent",
                           layout=CPU_LAYOUT)


def test_a_trace_without_a_device_plane_is_refused():
    with pytest.raises(ValueError, match="no plane"):
        trace.reduce_trace(str(TRACE))          # the TPU layout


def test_short_op_keeps_name_opcode_and_type_start():
    ev = ("%sort.7 = (f32[96,10112]{1,0:T(8,128)S(1)}, s32[96,10112]{1,0:"
          "T(8,128)}) sort(f32[96,10112]{1,0:T(8,128)S(1)} %negate), "
          "dimensions={0}")
    assert trace.short_op(ev, 16) == "%sort.7 sort (f32[96,10112]{1"
    ev = "%fusion.8 = f32[2]{0:T(128)S(1)} fusion(s32[10112]{0} %c), kind=k"
    assert trace.short_op(ev) == "%fusion.8 fusion f32[2]{0:T(128)S(1)}"
    assert trace.short_op("dot_general.1") == "dot_general.1"
