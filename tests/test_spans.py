"""Spans of the serving flush (serving/spans.py): the disabled recorder's
one shared no-op, the claim/pack/dispatch/fetch/resolve spans nested in
each pump cycle under one flush id, the response stamps that join them,
bisection under one id, the pump's rows_padded and item counters, the
profiler annotations, and the name of the serving pipeline in a trace."""

import glob
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import cascade as C
from repro.core import losses as L
from repro.data import features as F
from repro.serving import spans as S
from repro.serving.batching import RankRequest, padded_batch_rows
from repro.serving.faults import FaultConfig, FaultInjector
from repro.serving.pump import SessionPump
from repro.serving.session import (STATUS_ERROR, STATUS_OK, CascadeSession,
                                   FlushPolicy, ServingConfig)

BATCH = 4


def _cascade():
    masks = F.default_stage_masks(3)
    cfg = C.CascadeConfig(3, F.N_FEATURES, F.N_QUERY_BUCKETS, masks,
                          F.stage_costs(masks))
    return C.init_params(cfg, jax.random.PRNGKey(0), scale=0.3), cfg


def _req(i, cfg, n_items=6):
    rng = np.random.default_rng(i)
    return RankRequest(request_id=i,
                       q_feat=np.eye(cfg.d_q)[i % cfg.d_q].astype(np.float32),
                       item_feats=rng.normal(size=(n_items, cfg.d_x))
                       .astype(np.float32),
                       m_q=10 * n_items + 1)


def _session(spans=None, faults=None):
    params, cfg = _cascade()
    ses = CascadeSession(params, cfg, L.LossConfig(), faults=faults,
                         spans=spans,
                         scfg=ServingConfig(plan="filter", group_buckets=(8,),
                                            batch_groups=BATCH,
                                            flush=FlushPolicy(max_wait_ms=1.0)))
    ses.warmup()
    return ses, cfg


def _serve_through_pump(ses, cfg, n=36):
    """Offer n requests from a submitter thread in small bursts; every
    future resolves before the pump closes."""
    claimed = []
    claim_due = ses.claim_due

    def counting_claim(now_ms):
        chunk = claim_due(now_ms)
        if chunk is not None:
            claimed.append(padded_batch_rows(len(chunk.entries), BATCH))
        return chunk

    ses.claim_due = counting_claim
    futs = []
    with SessionPump(ses) as pump:
        def submit():
            for i in range(n):
                futs.append(pump.submit(_req(i, cfg)))
                if i % 5 == 4:
                    time.sleep(0.003)

        t = threading.Thread(target=submit)
        t.start()
        t.join()
        resps = [f.result(timeout=30.0) for f in futs]
    return pump, resps, claimed


def _by_flush(rec):
    out = {}
    for name, fid, s, e in rec.spans():
        out.setdefault(fid, []).append((name, s, e))
    return out


def test_disabled_recorder_records_nothing_with_one_shared_noop(monkeypatch):
    rec = S.SpanRecorder()
    assert not rec.enabled and not rec.annotate

    def no_clock():
        raise AssertionError("the disabled recorder read the clock")

    monkeypatch.setattr(S.time, "monotonic_ns", no_clock)
    a = rec.span(S.PACK, 1)
    b = rec.span(S.CLAIM)
    assert a is b is S.OFF
    with b as span:
        span.flush_id = 7
    assert span.flush_id is None
    rec.record(S.CYCLE, 1, 0)
    assert rec.spans() == [] and rec.flush_ms() == {}
    # a session's default recorder is a disabled one
    ses, cfg = _session()
    assert not ses.spans.enabled


def test_recorder_caps_its_list_and_clears(monkeypatch):
    monkeypatch.setattr(S, "MAX_SPANS", 3)
    rec = S.SpanRecorder(enabled=True)
    for k in range(5):
        with rec.span(S.FETCH, k % 2):
            pass
    with rec.span(S.CLAIM):          # claimed nothing: no flush, no span
        pass
    assert len(rec.spans()) == 3 and rec.dropped == 2
    assert set(rec.flush_ms()[S.FETCH]) == {0, 1}
    rec.clear()
    assert rec.spans() == [] and rec.dropped == 0


def test_pump_cycles_nest_their_steps_under_one_flush_id():
    ses, cfg = _session(spans=S.SpanRecorder(enabled=True))
    pump, resps, _ = _serve_through_pump(ses, cfg)
    assert all(r.status == STATUS_OK for r in resps)
    flushes = _by_flush(ses.spans)
    assert len(flushes) == pump.stats["cycles"] > 1
    for fid, spans in flushes.items():
        names = [n for n, _, _ in spans]
        assert names.count(S.CYCLE) == 1, fid
        assert names.count(S.CLAIM) == 1 and names.count(S.RESOLVE) == 1
        assert names.count(S.PACK) >= 1 and names.count(S.DISPATCH) >= 1
        assert names.count(S.FETCH) >= 1
        (c0, c1), = [(s, e) for n, s, e in spans if n == S.CYCLE]
        for name, s, e in spans:
            assert c0 <= s <= e <= c1, (fid, name)
        # the steps run one after another, in order
        steps = sorted((s, e, n) for n, s, e in spans if n != S.CYCLE)
        assert steps[0][2] == S.CLAIM and steps[-1][2] == S.RESOLVE
        assert all(a[1] <= b[0] for a, b in zip(steps, steps[1:]))
    # each response names its cycle; its service_ms runs from the
    # cycle's start to a moment after the last fetch and before resolve
    for r in resps:
        spans = flushes[r.flush_id]
        (c0, _), = [(s, e) for n, s, e in spans if n == S.CYCLE]
        fetched = max(e for n, _, e in spans if n == S.FETCH)
        (resolve0,) = [s for n, s, _ in spans if n == S.RESOLVE]
        done = c0 + r.service_ms * 1e6
        assert fetched - 1e3 <= done <= resolve0 + 1e3
        assert r.wait_ms >= 0.0


def test_rows_padded_sums_the_flushes_padded_rows():
    ses, cfg = _session()
    pump, resps, claimed = _serve_through_pump(ses, cfg)
    assert pump.stats["rows_padded"] == sum(claimed) > 0
    assert pump.stats["cycles"] == len(claimed)
    out = pump.stats_export()
    assert out["rows_padded"] == sum(claimed)
    assert out["served"] == len(resps) <= out["rows_padded"]


def test_item_counters_sum_the_flushes_items():
    """items_padded counts each flush's rows times its bucket's width,
    items_real the requests' own items, capped at the bucket (an 11-item
    request fills its 8-item row and is truncated)."""
    ses, cfg = _session()
    sizes = [3, 8, 11, 5, 1, 7, 8, 2, 11, 6] * 3
    with SessionPump(ses) as pump:
        futs = []
        for i, n in enumerate(sizes):
            futs.append(pump.submit(_req(i, cfg, n_items=n)))
            if i % 4 == 3:
                time.sleep(0.003)
        resps = [f.result(timeout=30.0) for f in futs]
    assert all(r.status == STATUS_OK for r in resps)
    out = pump.stats_export()
    assert out["items_padded"] == 8 * out["rows_padded"] > 0
    assert out["items_real"] == sum(min(n, 8) for n in sizes)
    assert out["items_real"] < out["items_padded"]


def test_bisection_under_a_poison_keeps_one_flush_id():
    rec = S.SpanRecorder(enabled=True)
    ses, cfg = _session(spans=rec,
                        faults=FaultInjector(FaultConfig(poison_ids=(2,))))
    ses._sleep = lambda s: None
    for i in range(BATCH):
        ses.submit(_req(i, cfg), now_ms=0.0)
    resps = ses.flush(1.0)
    assert [r.status for r in resps] == [STATUS_OK, STATUS_OK,
                                         STATUS_ERROR, STATUS_OK]
    (fid,) = {r.flush_id for r in resps}
    assert {f for _, f, _, _ in rec.spans()} == {fid}
    names = [n for n, _, _, _ in rec.spans()]
    assert names.count(S.CLAIM) == 1 and names.count(S.RESOLVE) == 1
    # the chunk and each bisection half stage their rows again; the
    # poison fails its attempts before dispatch, so the two clean halves
    # ([0, 1] and [3]) dispatch and fetch, all under the one id
    assert names.count(S.PACK) == 5
    assert names.count(S.DISPATCH) == names.count(S.FETCH) == 2
    times = rec.flush_ms()
    assert times[S.DISPATCH][fid] > 0.0
    # a later flush takes the next id
    ses.submit(_req(9, cfg), now_ms=2.0)
    (later,) = ses.flush(3.0)
    assert later.flush_id == fid + 1


def test_annotated_steps_land_on_the_host_plane_and_the_cycle_does_not(
        tmp_path):
    rec = S.SpanRecorder(enabled=True, annotate=True)
    ses, cfg = _session(spans=rec)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _, resps, _ = _serve_through_pump(ses, cfg, n=8)
    finally:
        jax.profiler.stop_trace()
    assert {S.CYCLE, *S.STEPS} <= {n for n, _, _, _ in rec.spans()}
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                             / "*.xplane.pb"))
    names = {e.name for plane in jax.profiler.ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events}
    assert set(S.STEPS) <= names
    assert S.CYCLE not in names


def test_serving_pipeline_is_named_cascade_rank():
    ses, cfg = _session()
    b, g = BATCH, 8
    text = ses._rank.lower(ses.params, np.zeros((b, g, cfg.d_x), np.float32),
                           np.zeros((b, cfg.d_q), np.float32),
                           np.ones((b, g), np.float32),
                           np.full((b,), 8.0, np.float32)).as_text()
    assert "@jit_cascade_rank" in text


def test_report_gives_each_span_per_flush_percentiles():
    rec = S.SpanRecorder(enabled=True)
    for fid, ms in enumerate([1.0, 2.0, 3.0, 4.0]):
        rec.record(S.PACK, fid, 0, int(ms * 1e6))
        rec.record(S.PACK, fid, 0, int(ms * 1e6))   # summed per flush
    out = S.report([rec, S.SpanRecorder(enabled=True)])
    assert out["dropped"] == 0
    pack = out["spans"][S.PACK]
    assert pack["flushes"] == 4
    assert pack["p50_ms"] == pytest.approx(5.0)
    assert pack["p99_ms"] == pytest.approx(7.94)
