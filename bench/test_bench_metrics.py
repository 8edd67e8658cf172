"""The benchmark's arithmetic on the CPU: the generator's schedule, the
due-time latency and goodput, the work counts behind the rooflines, the
per-layer readers, and the comparisons that decide `correct`."""

from __future__ import annotations

import json
import types

import numpy as np
import pytest

import benchtest_support  # noqa: F401  (puts bench/ and src/ on the path)
import benchlib
import cell_serve
import checks
import workcount
import xtrace

PEAKS = json.loads((benchlib.BENCH_DIR / "peaks.json").read_text())[
    "devices"]["TPU v5 lite"]


def _log(n=64, g=256):
    rng = np.random.default_rng(0)
    return cell_serve.Log(x=rng.normal(size=(n, g, 3)).astype(np.float32),
                          q=np.eye(8, dtype=np.float32)[rng.integers(0, 8, n)],
                          m_q=rng.integers(200, 9000, n),
                          n_valid=np.linspace(8, g, n).astype(np.int64))


TRAFFIC = {"kind": "serve", "rate_per_s": 500.0, "sample": 8,
           "items": {"dist": "uniform", "lo": 129, "hi": 256}}


# -- the generator's absolute schedule ------------------------------------

def test_schedule_draws_the_same_work_for_every_seed():
    log = _log()
    a = cell_serve.schedule(TRAFFIC, 2.0, 1, log)
    b = cell_serve.schedule(TRAFFIC, 2.0, 2**33 + 7, log)
    assert len(a.offsets_s) == len(b.offsets_s) == 1000
    # the same gaps and sizes, in another order
    gaps_a = np.diff(np.append(a.offsets_s, 2.0))
    gaps_b = np.diff(np.append(b.offsets_s, 2.0))
    assert np.allclose(np.sort(gaps_a), np.sort(gaps_b))
    assert np.array_equal(np.sort(a.sizes), np.sort(b.sizes))
    assert not np.array_equal(a.sizes, b.sizes)
    # absolute due times: from 0, non-decreasing, all inside the window
    assert a.offsets_s[0] == 0.0
    assert (np.diff(a.offsets_s) >= 0).all() and a.offsets_s[-1] < 2.0
    # every request ranks a query with enough logged items
    assert (log.n_valid[a.rows] >= a.sizes).all()


def test_schedule_is_a_function_of_the_seed():
    log = _log()
    a = cell_serve.schedule(TRAFFIC, 1.0, 99, log)
    b = cell_serve.schedule(TRAFFIC, 1.0, 99, log)
    assert np.array_equal(a.offsets_s, b.offsets_s)
    assert np.array_equal(a.rows, b.rows)


def test_poisson_gaps_have_the_offered_mean():
    sch = cell_serve.schedule(dict(TRAFFIC, rate_per_s=2000.0), 3.0, 5, _log())
    gaps = np.diff(sch.offsets_s)
    assert gaps.mean() == pytest.approx(1 / 2000.0, rel=0.01)
    # exponential: the coefficient of variation is about 1
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.1)


@pytest.mark.parametrize("dist,lo,hi", [("uniform", 8, 16),
                                        ("uniform", 129, 256),
                                        ("log_uniform", 8, 256)])
def test_item_counts_cover_their_range(dist, lo, hi):
    sizes = cell_serve.item_counts({"dist": dist, "lo": lo, "hi": hi}, 4000)
    assert sizes.min() == lo and sizes.max() == hi
    if dist == "uniform":
        counts = np.bincount(sizes - lo)
        assert counts.max() - counts.min() <= 1
    else:
        # log-uniform: as many requests in [8, 16) as in [128, 256)
        low = ((sizes >= 8) & (sizes < 16)).sum()
        high = ((sizes >= 128) & (sizes < 256)).sum()
        assert abs(low - high) <= 0.05 * len(sizes)


def test_warm_buckets_are_the_traffic_buckets_and_their_shrink_targets():
    cfg = {"serving": {"group_buckets": [16, 64, 256]}}
    items = lambda lo, hi: {"items": {"lo": lo, "hi": hi}}  # noqa: E731
    assert cell_serve.warm_buckets(cfg, items(8, 16)) == [16]
    assert cell_serve.warm_buckets(cfg, items(129, 256)) == [64, 256]
    assert cell_serve.warm_buckets(cfg, items(8, 256)) == [16, 64, 256]


# -- due-time latency and goodput -----------------------------------------

def _window(due, submitted, resolved, statuses):
    rec = cell_serve.Record(len(due), keep=())
    rec.resolved[:] = resolved
    rec.status[:] = [cell_serve.STATUS.index(s) for s in statuses]
    return cell_serve.Window(t_start=0.0, due=due, submitted=submitted,
                             record=rec, compiles=0)


def test_latency_runs_from_the_due_time_not_the_submit():
    due = np.array([0.0, 0.010, 0.020, 0.030])
    win = _window(due, due + 0.005,                       # a late generator
                  np.array([0.004, 0.017, 0.2, np.nan]),
                  ["ok", "ok", "ok", "unresolved"])
    out = cell_serve.outcome(win, limit_ms=130.0, seconds=2.0)
    assert out["p50_ms"] == pytest.approx(7.0)          # of 4, 7, 180 ms
    assert out["p99_ms"] == pytest.approx(np.percentile([4, 7, 180], 99))
    # 180 ms misses the limit; the unresolved one is failed
    assert out["goodput"] == pytest.approx(2 / 2.0)
    assert out["unresolved"] == 1


def test_shed_and_errored_requests_are_in_no_percentile():
    due = np.zeros(3)
    win = _window(due, due, np.array([0.001, 0.0, 0.5]),
                  ["ok", "shed", "error"])
    out = cell_serve.outcome(win, 130.0, 1.0)
    assert out["p50_ms"] == pytest.approx(1.0)
    assert out["p99_ms"] == pytest.approx(1.0)
    assert (out["shed"], out["errors"]) == (1, 1)
    assert out["goodput"] == pytest.approx(1.0)


def _response(i, status="ok", degraded=()):
    return types.SimpleNamespace(request_id=i, status=status, wait_ms=1.5,
                                 service_ms=2.5, degraded=degraded)


def test_record_keeps_whole_responses_of_the_candidates_only():
    rec = cell_serve.Record(3, keep=[1])
    rec.take(_response(-1), 0.5)            # a warm-up request: not ours
    rec.take(_response(0, "shed"), 1.0)
    rec.take(_response(1, degraded=("shrink_bucket",)), 2.0)
    assert not rec.all_done.is_set()
    rec.take(_response(2), 3.0)
    assert rec.all_done.is_set()
    assert list(rec.kept) == [1]
    assert rec.resolved.tolist() == [1.0, 2.0, 3.0]
    assert [cell_serve.STATUS[s] for s in rec.status] == ["shed", "ok", "ok"]
    assert rec.shrunk.tolist() == [False, True, False]
    assert rec.wait_ms[2] == 1.5 and rec.service_ms[2] == 2.5


def test_check_candidates_hold_the_draw_and_each_buckets_widest():
    sch = cell_serve.schedule(dict(TRAFFIC, items={"dist": "uniform",
                                                   "lo": 8, "hi": 256}),
                              2.0, 11, _log())
    buckets = (16, 64, 256)
    keep = cell_serve.check_candidates(sch, 50, 11, buckets)
    assert len(keep) <= 50 + 3 * cell_serve.WIDEST_KEPT
    assert np.array_equal(keep, cell_serve.check_candidates(sch, 50, 11,
                                                            buckets))
    kept_sizes = set(sch.sizes[keep].tolist())
    for top in (16, 64, 256):
        assert sch.sizes[sch.sizes <= top].max() in kept_sizes


def test_served_width_is_the_requests_not_the_responses():
    assert cell_serve.served_width(200, False, (16, 64, 256)) == 200
    assert cell_serve.served_width(200, True, (16, 64, 256)) == 64
    assert cell_serve.served_width(12, True, (16, 64, 256)) == 12


def test_gc_clock_counts_full_passes_while_open():
    import gc
    gc.disable()  # only the two passes asked for
    clock = benchlib.GcClock()
    try:
        gc.collect()
        gc.collect(0)
    finally:
        clock.close()
        gc.enable()
    gc.collect()
    notes = clock.notes()
    assert notes["gc_passes"] == 2 and notes["gc_full"] == 1
    assert notes["gc_max_ms"] >= 0.0


# -- work counts and rooflines --------------------------------------------

def test_serve_work_counts_real_items_only():
    w = workcount.serve_work([10, 200], d_x=24, d_q=8, t=3)
    n, r = 210, 2
    assert w["flops"] == 2 * 24 * 3 * n + 2 * 8 * 3 * r
    assert w["bytes"] == 4 * (n * (24 + 6) + r * (8 + 1 + 6))


def test_train_work_counts_forward_and_weight_gradient():
    w = workcount.train_work(1000.0, 64.0, d_x=24, d_q=8, t=3)
    assert w["flops"] == 4 * 24 * 3 * 1000
    assert w["bytes"] == 4 * (1000 * 26 + 64 * 9)


def test_least_time_is_the_larger_bound():
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert workcount.least_seconds({"flops": 100, "bytes": 50}, peaks) == 5.0
    assert workcount.least_seconds({"flops": 1000, "bytes": 5}, peaks) == 10.0


def _summary(**kw):
    base = dict(window_s=2.0, n_devices=1, busy_s=0.5, op_s={},
                module_s={}, idle_gaps=[], device_ops=[])
    base.update(kw)
    return xtrace.Summary(**base)


def _reader(name):
    return benchlib.load_reader(name)


def test_filter_roofline_is_least_time_over_kernel_time():
    work = {"flops": 0.0, "bytes": 819e9 * 1e-3}          # 1 ms at peak
    facts = {"trace": _summary(op_s={"cascade_filter": 0.1}),
             "work": work, "peaks": PEAKS}
    assert _reader("filter_roofline")(facts) == pytest.approx(1.0)


def test_a_missing_kernel_reports_nothing_not_zero():
    facts = {"trace": _summary(op_s={"fusion": 0.1}),
             "work": {"flops": 1.0, "bytes": 1.0}, "peaks": PEAKS}
    assert _reader("filter_roofline")(facts) is None
    assert _reader("loss_roofline")(facts) is None
    assert _reader("filter_roofline")({"trace": None}) is None


def test_loss_roofline_sums_forward_and_backward_kernels():
    work = {"flops": 197e12 * 1e-3, "bytes": 0.0}          # 1 ms at peak
    trace = _summary(op_s={"jvp_jit_cascade_loss__": 0.002,
                           "transpose_jvp_jit_cascade_loss_bwd___": 0.003,
                           "fusion": 1.0})
    facts = {"trace": trace, "work": work, "peaks": PEAKS}
    assert _reader("loss_roofline")(facts) == pytest.approx(20.0)


def test_step_shares_and_idle_share():
    work = {"flops": 0.0, "bytes": 819e9 * 1e-3}
    trace = _summary(module_s={"jit_impl": 0.05, "jit__reduce_sum": 0.05})
    assert _reader("step_mfu.serve")(
        {"trace": trace, "work": work, "peaks": PEAKS}) == pytest.approx(1.0)
    assert _reader("idle_share.serve")({"trace": trace}) == pytest.approx(75.0)
    assert _reader("step_mfu.train")(
        {"kind": "train", "work": work, "peaks": PEAKS,
         "window_s": 0.01}) == pytest.approx(10.0)


def test_host_side_readers():
    assert _reader("gen_late_p99_ms")(
        {"gen_late_ms": np.arange(101.0)}) == pytest.approx(99.0)
    assert _reader("queue_wait_p99_ms")(
        {"wait_ms": np.arange(101.0)}) == pytest.approx(99.0)
    assert _reader("service_p50_ms")(
        {"service_ms": np.array([1.0, 2.0, 9.0])}) == pytest.approx(2.0)
    assert _reader("gen_late_p99_ms")({}) is None


def test_every_per_layer_metric_has_a_reader():
    spec = json.loads((benchlib.ROOT / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        assert callable(benchlib.load_reader(m["name"]))


def test_unknown_device_kind_is_an_error():
    with pytest.raises(benchlib.BenchError):
        benchlib.peaks_for("cpu")
    assert benchlib.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


# -- the comparisons that decide `correct` --------------------------------

def _ref_and_served(lp_final, keep_final, tol=1e-4):
    """One request, one stage: lp (n, 1); the reference keeps the best
    keep_final items."""
    lp = np.asarray(lp_final, np.float32)[:, None]
    n = len(lp)
    order = np.argsort(-lp[:, 0], kind="stable")
    surv = np.zeros((n, 1), np.float32)
    surv[order[:keep_final], 0] = 1
    ref = {"lp": lp, "survivors": surv, "keep": np.array([keep_final - 0.5])}
    scores = np.where(surv[:, 0] > 0, lp[:, 0], -np.inf)
    served = {"scores": scores, "survivors": surv[:, 0] > 0,
              "order": np.argsort(-scores, kind="stable"),
              "stage_counts": [keep_final]}
    return served, ref


def test_exact_agreement_reads_zero():
    served, ref = _ref_and_served([-1.0, -3.0, -2.0, -5.0], 2)
    assert checks.compare_one(served, ref, 1e-4)[:2] == (0.0, 0)


def test_a_survivor_off_beyond_rounding_is_a_mismatch():
    served, ref = _ref_and_served([-1.0, -3.0, -2.0, -5.0], 2)
    served["survivors"] = np.array([True, True, False, False])
    assert checks.compare_one(served, ref, 1e-4)[1] >= 1


def test_a_near_tie_may_flip():
    served, ref = _ref_and_served([-1.0, -2.00001, -2.0, -5.0], 2)
    served["survivors"] = np.array([True, True, False, False])
    served["scores"] = np.where(served["survivors"], ref["lp"][:, 0], -np.inf)
    err, bad, near = checks.compare_one(served, ref, 1e-4)
    assert bad == 0 and near >= 2


def test_score_error_is_the_widest_gap_over_common_survivors():
    served, ref = _ref_and_served([-1.0, -3.0, -2.0, -5.0], 2)
    served["scores"] = served["scores"] + np.array([2e-3, 0, 0, 0])
    assert checks.compare_one(served, ref, 1e-4)[0] == pytest.approx(2e-3)


def test_leaf_gap_takes_the_worst_leaf_against_the_median_floor():
    want = {"a": np.ones(4), "b": np.full(4, 2.0), "c": np.full(4, 1e-6)}
    got = {"a": np.ones(4) * 1.01, "b": np.full(4, 2.0),
           "c": np.full(4, 2e-6)}
    # c is tiny: its gap is measured against the median leaf's norm (2)
    assert checks.leaf_gap(got, want, ["a", "b", "c"]) == pytest.approx(0.01)


def test_a_state_left_unchanged_reads_one():
    start = {"w": np.zeros(3)}
    ref = {"losses": np.ones(2), "params": {"w": np.ones(3)},
           "mu": {"w": np.ones(3)}}
    prog = {"losses": np.ones(2), "params": {"w": np.zeros(3)},
            "mu": {"w": np.ones(3)}}
    out = checks.training_readings(prog, ref, start)
    assert out["change_gap"] == pytest.approx(1.0)
    assert out["loss_gap"] == 0.0


def test_judge_holds_each_number_to_its_limit():
    ok, shown = checks.judge({"a": 1.0, "b": 0}, {"a": 2.0, "b": 0})
    assert ok and shown == {"a": [1.0, 2.0], "b": [0, 0]}
    assert not checks.judge({"a": 3.0}, {"a": 2.0})[0]
    assert not checks.judge({"a": float("nan")}, {"a": 2.0})[0]
