"""bench/program_trace.py on hand-made events, on a trace recorded on the
CPU with the program's spans (data/program_trace.xplane.pb, made by
data/record_program_trace.py), and the readers of its metrics."""
import pathlib
import sys

import pytest

from bench import program_trace as pt
from bench import run, trace

from .conftest import CPU_LAYOUT, cpu_chip

DATA = pathlib.Path(__file__).parent / "data"
TRACE = DATA / "program_trace.xplane.pb"
METRICS = pathlib.Path(run.__file__).parent / "metrics"


def reader(name):
    return run.load_module(METRICS / f"{name}.py", f"test_metric_{name}")


# ---- scopes -----------------------------------------------------------------

def test_components_drop_transform_wrappers():
    assert pt._components("jit(sae_step)/fwd_bwd/transpose(jvp())/mul") == \
        ["sae_step", "fwd_bwd", "", "mul"]
    assert pt._components("jit(f)/transpose(jvp(proj/update))/dot") == \
        ["f", "proj", "update", "dot"]


@pytest.mark.parametrize("path,scope,hit", [
    ("jit(s)/transpose(jvp(fwd_bwd))/dot_general", "fwd_bwd", True),
    ("jit(s)/fwd_bwd/jvp(jit(log_softmax))/reduce_sum", "fwd_bwd", True),
    ("jit(s)/checkpoint(remat(ssd/chunk_scan))/exp", "ssd/chunk_scan", True),
    ("jit(s)/proj/update/proj/newton/while/body/slice", "proj/newton", True),
    ("jit(s)/proj/update/proj/newton/while/body/slice", "proj/update", True),
    ("jit(s)/proj/updates/add", "proj/update", False),
    ("jit(s)/newton/proj/add", "proj/newton", False),
    ("", "fwd_bwd", False),
])
def test_in_scope_matches_runs_of_components(path, scope, hit):
    assert pt.in_scope(path, scope) is hit


def test_self_times_count_nested_ops_once():
    ops = [(0, 100, "while"), (10, 30, "a"), (40, 50, "b"), (45, 48, "c"),
           (120, 130, "d")]
    got = dict((p, t) for t, p in pt.self_times(ops))
    assert got == {"while": 70, "a": 20, "b": 7, "c": 3, "d": 10}
    assert sum(got.values()) == 110   # the union of the intervals


def test_scope_times_group_by_scope_with_unscoped_ops_under_empty():
    ops = [
        (0, 10, "jit(s)/fwd_bwd/jvp()/dot_general"),
        (10, 25, "jit(s)/transpose(jvp(fwd_bwd))/dot_general"),
        (30, 90, "jit(s)/proj/update/proj/newton/while"),
        (35, 45, "jit(s)/proj/update/proj/newton/while/body/sort"),
        (90, 100, "jit(s)/proj/update/add"),
        (100, 104, ""),                                    # a copy
        (104, 106, "jit(s)/max"),
    ]
    got = pt.scope_times(ops)
    assert got == {"fwd_bwd": 25, "proj/newton": 60, "proj/update": 70,
                   "": 6}


def test_by_execution_assigns_ops_to_the_module_run_holding_them():
    modules = [(100, 150, "jit_sae_step(7)"), (0, 50, "jit_sae_step(7)"),
               (60, 80, "jit_gather(3)")]
    ops = [(1, 5, "a"), (10, 49, "b"), (61, 70, "c"), (100, 140, "d"),
           (55, 58, "outside")]
    assert pt.by_execution(ops, modules) == [
        ("jit_sae_step(7)", [(1, 5, "a"), (10, 49, "b")]),
        ("jit_gather(3)", [(61, 70, "c")]),
        ("jit_sae_step(7)", [(100, 140, "d")])]
    assert pt.program_id("jit_sae_step(6551415854901150784)") == \
        6551415854901150784
    assert pt.program_id("jit_sae_step") == 0


# ---- spans and idle time ----------------------------------------------------

def test_innermost_cuts_the_window_by_the_innermost_open_span():
    spans = [(10, 90, "sae/fit"), (20, 30, "sae/batch"),
             (30, 50, "sae/step"), (70, 80, "sae/epoch_end")]
    assert pt.innermost(spans, 0, 100) == [
        (0, 10, ""), (10, 20, "sae/fit"), (20, 30, "sae/batch"),
        (30, 50, "sae/step"), (50, 70, "sae/fit"), (70, 80, "sae/epoch_end"),
        (80, 90, "sae/fit"), (90, 100, "")]


def test_an_idle_stretch_is_split_across_two_spans_by_time():
    spans = [(10, 90, "sae/fit"), (20, 30, "sae/batch"),
             (30, 50, "sae/step")]
    parts = pt.innermost(spans, 0, 100)
    idle = [(25, 40), (60, 65), (95, 100)]
    got = pt.split_idle(idle, parts)
    assert got == {"sae/batch": 5, "sae/step": 10, "sae/fit": 5, "": 5}
    assert sum(got.values()) == sum(e - s for s, e in idle)


def test_span_counts_count_spans_starting_in_the_window():
    spans = [(5, 20, "sae/step"), (15, 30, "sae/step"), (40, 50, "sae/step"),
             (12, 14, "sae/batch")]
    assert pt.span_counts(spans, 10, 40) == {"sae/step": 1, "sae/batch": 1}


# ---- the op names in the trace's metadata -----------------------------------

def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _plane(name, ops):
    """An XPlane holding the event metadata of ``ops`` ({(program id, long
    name): tf_op}) and one stat besides those two."""
    msg = _field(2, name)
    for sid, sname in ((1, "hlo_category"), (2, "tf_op"), (3, "program_id")):
        msg += _field(5, _field(1, sid) + _field(2, _field(1, sid)
                                                 + _field(2, sname)))
    for i, ((pid, long_name), tf_op) in enumerate(ops.items(), start=1):
        stats = (_field(5, _field(1, 1) + _field(5, "fusion"))
                 + _field(5, _field(1, 3) + _field(3, pid)))
        if tf_op is not None:
            stats += _field(5, _field(1, 2) + _field(5, tf_op))
        meta = (_field(1, i) + _field(2, long_name)
                + _field(4, f"short.{i}") + stats)
        msg += _field(4, _field(1, i) + _field(2, meta))
    return msg


def test_op_names_read_tf_op_from_the_device_planes_metadata(tmp_path):
    """Keyed by program: the same op text in two programs keeps each
    program's scope path."""
    fusion = "%fusion.8 = f32[2] fusion(...)"
    big = 2 ** 63 + 5                                    # a uint64 id
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(
        _field(1, _plane("/host:CPU", {(1, "%host = f32[]"): "host/op:"}))
        + _field(1, _plane("/device:TPU:0", {
            (big, fusion): "jit(s)/fwd_bwd/dot_general:",
            (9, fusion): "jit(bench_f)/dot_general:",
            (big, "%copy-start = (f32[2]) copy-start(...)"): None})))
    assert pt.op_names(str(path)) == {
        (big, fusion): "jit(s)/fwd_bwd/dot_general",
        (big, "short.1"): "jit(s)/fwd_bwd/dot_general",
        (9, fusion): "jit(bench_f)/dot_general",
        (9, "short.2"): "jit(bench_f)/dot_general"}


# ---- the recorded CPU trace -------------------------------------------------

def test_recorded_program_trace_reduces_to_its_spans():
    r = pt.reduce_program(str(TRACE), layout=CPU_LAYOUT)
    assert r["span_counts"] == {"sae/batch": 6, "sae/step": 6,
                                "sae/epoch_end": 2}
    assert set(r["idle_by_span"]) <= {"", "sae/batch", "sae/step",
                                      "sae/epoch_end"}
    # the idle time of reduce_trace, split over the spans and no more
    t = trace.reduce_trace(str(TRACE), layout=CPU_LAYOUT)
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        t["window_s"] - t["busy_s"], rel=1e-9)
    assert r["scopes"] == {} and r["op_s"] == {}   # no module line on a CPU


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError, match="no host span"):
        pt.reduce_program(str(TRACE), window="bench/absent",
                          layout=CPU_LAYOUT)


# ---- the readers ------------------------------------------------------------

SCOPE_READERS = [
    ("step_fwd_bwd_ms.sae", "jit_sae_step", "fwd_bwd"),
    ("step_update_ms.sae", "jit_sae_step", "proj/update"),
    ("step_fwd_bwd_ms.lm", "jit_lm_train_step", "fwd_bwd"),
    ("step_ssd_ms.lm", "jit_lm_train_step", "ssd/chunk_scan"),
    ("step_update_ms.lm", "jit_lm_train_step", "proj/update"),
    ("step_newton_ms.lm", "jit_lm_train_step", "proj/newton"),
]


@pytest.mark.parametrize("name,module,scope", SCOPE_READERS)
def test_scope_readers_take_the_median_execution(name, module, scope):
    runs = [{scope: 3e-3, "": 1e-4}, {scope: 1e-3}, {"": 2e-3},
            {scope: 2e-3}, {scope: 5e-3}]
    ctx = {"trace": {"scopes": {module: runs, "jit_other": [{scope: 1.0}]}}}
    assert reader(name).read(ctx) == pytest.approx(2.0)   # 0, 1, 2, 3, 5 ms
    assert reader(name).read({"trace": {"scopes": {}}}) is None
    assert reader(name).read({"trace": {"busy_s": 1.0}}) is None


@pytest.mark.parametrize("name,span", [
    ("idle_batch_share.sae", "sae/batch"),
    ("idle_step_call_share.sae", "sae/step"),
    ("idle_epoch_end_share.sae", "sae/epoch_end"),
])
def test_idle_readers_share_the_window(name, span):
    idle = {"": 0.1, "sae/fit": 0.2, span: 0.5}
    ctx = {"trace": {"window_s": 4.0, "idle_by_span": idle}}
    assert reader(name).read(ctx) == pytest.approx(12.5)
    ctx["trace"]["idle_by_span"] = {"": 0.1}
    assert reader(name).read(ctx) == 0.0                  # never idle there
    assert reader(name).read({"trace": {"window_s": 4.0}}) is None


@pytest.mark.parametrize("name,counts,value", [
    ("newton_evals_per_update.sae",
     {"proj/updates": 4, "proj/newton_evals": 10}, 2.5),
    ("newton_evals_per_update.lm",
     {"proj/updates": 4, "proj/newton_evals": 10}, 2.5),
    ("step_traces_per_fit.sae", {"sae/fits": 3, "sae/step_traces": 6}, 2.0),
])
def test_counter_readers_read_the_programs_registry(name, counts, value):
    from repro import obs
    obs.counters_reset()
    try:
        assert reader(name).read({}) is None              # nothing counted
        for k, n in counts.items():
            obs.count(k, n)
        assert reader(name).read({}) == pytest.approx(value)
    finally:
        obs.counters_reset()


@pytest.mark.parametrize("name", ["newton_evals_per_update.sae",
                                  "newton_evals_per_update.lm",
                                  "step_traces_per_fit.sae"])
def test_counter_readers_are_silent_on_a_program_without_counters(
        name, monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.obs", None)   # import fails
    assert reader(name).read({}) is None


def test_every_program_metric_has_a_reader():
    for names in pt.PROGRAM_METRICS.values():
        for name in names:
            assert hasattr(reader(name), "read"), name


# ---- a traced run with the reductions ---------------------------------------

def test_traced_run_adds_the_program_metrics(tiny):
    out = pt.traced_run("sae-table1.l1inf-sparse", 2**31 + 11, 0.1,
                        root=tiny, chip_check=cpu_chip,
                        trace_layout=CPU_LAYOUT)
    assert out["result"]["correct"], out["result"]["compared"]
    m = out["program_metrics"]
    assert set(m) == set(pt.PROGRAM_METRICS["sae"])
    # the module timings need the TPU's module line; the rest read here
    assert m["step_fwd_bwd_ms.sae"] is None and m["step_update_ms.sae"] is None
    assert m["newton_evals_per_update.sae"] >= 1
    assert m["step_traces_per_fit.sae"] >= 1
    shares = [m[k] for k in ("idle_batch_share.sae",
                             "idle_step_call_share.sae",
                             "idle_epoch_end_share.sae")]
    assert all(0 <= v <= 100 for v in shares)
    counts = out["reduced"]["span_counts"]
    assert counts["sae/fit"] == 1 and counts["sae/step"] == \
        counts["sae/batch"] > 0
