"""The reduction from a profiler trace to the per-layer metrics and the
traced run's breakdown: on synthetic events, and on small traces recorded
on a TPU v5e (bench/testdata/, trimmed to the device lines and three host
threads)."""

from __future__ import annotations

import json

import pytest

import benchtest_support  # noqa: F401  (puts bench/ and src/ on the path)
import benchlib
import workcount
import xtrace

DATA = benchlib.BENCH_DIR / "testdata"
PEAKS = json.loads((benchlib.BENCH_DIR / "peaks.json").read_text())[
    "devices"]["TPU v5 lite"]


def _trace(ops, host=(), modules=(), window=(0.0, 1000.0)):
    host_lines = [xtrace.Line("bench-main", [(benchlib.WINDOW_SPAN, window[0],
                                              window[1] - window[0])])]
    host_lines += [xtrace.Line(name, events) for name, events in host]
    return [xtrace.Plane("/device:TPU:0", [
                xtrace.Line(xtrace.OPS_LINE, list(ops)),
                xtrace.Line(xtrace.MODULES_LINE, list(modules))]),
            xtrace.Plane(xtrace.HOST_PLANE, host_lines)]


def test_op_and_module_names():
    assert xtrace.op_name("%cascade_filter.1 = (f32[32,256,8]) custom-call("
                          "f32[32,256,128] %pad.6)") == "cascade_filter"
    assert xtrace.op_name("%transpose_jvp_jit_cascade_loss_bwd___.17 = f32[]"
                          ) == "transpose_jvp_jit_cascade_loss_bwd___"
    assert xtrace.op_name("%while.5 = (s32[]) while(...)") == "while"
    assert xtrace.module_name("jit_impl(8496952077487217284)") == "jit_impl"


def test_self_time_of_nested_events():
    # a loop of 100 ns holding two 30 ns ops
    st = dict(xtrace.self_times([("%while.1", 0.0, 100.0),
                                 ("%a.1", 10.0, 30.0), ("%b.2", 50.0, 30.0)]))
    assert st == {"%while.1": 40.0, "%a.1": 30.0, "%b.2": 30.0}


def test_busy_is_the_union_of_ops_inside_the_window():
    planes = _trace([("%a = f32[]", 100.0, 200.0), ("%b = f32[]", 300.0, 50.0),
                     ("%c = f32[]", 900.0, 300.0)])
    s = xtrace.summarize(planes, min_gap_ns=1.0)
    # [100, 350) and [900, 1000) inside the window [0, 1000)
    assert s.busy_s == pytest.approx(350e-9)
    assert s.window_s == pytest.approx(1000e-9)
    assert s.op_s["a"] == pytest.approx(200e-9)
    assert s.op_s["c"] == pytest.approx(100e-9)


def test_idle_gaps_are_named_by_the_host_thread_that_ran():
    planes = _trace([("%a = f32[]", 0.0, 100.0), ("%b = f32[]", 600.0, 400.0)],
                    host=[("pump0", [("np.asarray(jax.Array)", 120.0, 400.0)]),
                          ("bench-gen", [("gen.submit", 100.0, 50.0)])])
    s = xtrace.summarize(planes, min_gap_ns=1.0)
    assert s.idle_gaps[0][0] == "pump0: np.asarray(jax.Array)"
    assert s.idle_gaps[0][1] == pytest.approx(500e-9)
    assert len(s.breakdown()["idle_gaps"]) <= 10


def test_no_window_span_is_an_error():
    planes = [xtrace.Plane(xtrace.HOST_PLANE, [xtrace.Line("x", [])])]
    with pytest.raises(ValueError):
        xtrace.summarize(planes)


def test_json_round_trip(tmp_path):
    planes = _trace([("%a = f32[]", 0.0, 10.0)])
    xtrace.dump_json(planes, tmp_path / "t.json")
    back = xtrace.load_json(tmp_path / "t.json")
    assert xtrace.summarize(back).busy_s == xtrace.summarize(planes).busy_s


@pytest.fixture(scope="module")
def serve_trace():
    return (xtrace.summarize(xtrace.load_json(DATA / "serve_trace.json")),
            json.loads((DATA / "serve_items.json").read_text()))


@pytest.fixture(scope="module")
def train_trace():
    return (xtrace.summarize(xtrace.load_json(DATA / "train_trace.json")),
            json.loads((DATA / "train_items.json").read_text()))


def test_recorded_serving_trace(serve_trace):
    s, items = serve_trace
    assert s.n_devices == 1
    assert 0 < s.busy_s < s.window_s
    assert s.ops_matching("cascade_filter") > 0
    assert sum(s.module_s.values()) > 0
    names = [k for k, _ in s.idle_gaps]
    assert any(k.startswith(("pump0", "bench-gen", "bench-main"))
               for k in names)
    b = s.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    work = workcount.serve_work(items["items"], 24, 8, 3)
    facts = {"trace": s, "work": work, "peaks": PEAKS}
    roof = benchlib.load_reader("filter_roofline")(facts)
    step = benchlib.load_reader("step_mfu.serve")(facts)
    assert 0 < step <= roof < 100


def test_recorded_training_trace(train_trace):
    s, items = train_trace
    assert s.ops_matching("cascade_loss") > 0
    assert 0 < s.busy_s <= s.window_s
    work = workcount.train_work(items["real_items"], items["groups"], 24, 8, 3)
    roof = benchlib.load_reader("loss_roofline")(
        {"trace": s, "work": work, "peaks": PEAKS})
    assert 0 < roof < 100
    idle = benchlib.load_reader("idle_share.train")({"trace": s})
    assert 0 <= idle < 100
