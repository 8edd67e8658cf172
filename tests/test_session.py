"""CascadeSession lifecycle tests: round-trip parity with CascadeServer,
deadline-triggered flush ordering, shed-at-capacity admission, degraded-
mode hysteresis, submit-order invariance across interleaved flushes, and
zero recompiles after warmup()."""

import contextlib

import jax
import numpy as np
import pytest

from repro.core import cascade as C
from repro.core import losses as L
from repro.data import features as F
from repro.serving.batching import RankRequest, RequestBatcher
from repro.serving.cascade_server import CascadeServer
from repro.serving.loadgen import run_open_loop
from repro.serving.pump import SessionPump
from repro.serving.session import (CascadeSession, DegradePolicy,
                                   FlushPolicy, QueueFull, ServingConfig,
                                   STATUS_OK, STATUS_SHED)


def _cascade():
    masks = F.default_stage_masks(3)
    cfg = C.CascadeConfig(3, F.N_FEATURES, F.N_QUERY_BUCKETS, masks,
                          F.stage_costs(masks))
    params = C.init_params(cfg, jax.random.PRNGKey(0), scale=0.3)
    return params, cfg


def _req(i, n_items, cfg, seed=None):
    rng = np.random.default_rng(n_items if seed is None else seed)
    return RankRequest(request_id=i,
                       q_feat=np.eye(cfg.d_q)[i % cfg.d_q].astype(np.float32),
                       item_feats=rng.normal(size=(n_items, cfg.d_x))
                       .astype(np.float32),
                       m_q=10 * n_items + 1)


def _session(params, cfg, *, buckets=(8, 16), batch_groups=4, **kw):
    defaults = dict(plan="filter", group_buckets=buckets,
                    batch_groups=batch_groups)
    defaults.update(kw)
    return CascadeSession(params, cfg, L.LossConfig(),
                          scfg=ServingConfig(**defaults))


# ---------------------------------------------------------------------------
# Round-trip parity: shedding/degradation disabled, submit-all-then-flush
# must reproduce CascadeServer.serve() bit for bit.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("plan", [
    "filter",
    pytest.param("score", marks=pytest.mark.slow),  # perf-variant parity
])
def test_session_flush_bitwise_matches_server_serve(plan):
    params, cfg = _cascade()
    sizes = [12, 3, 16, 2, 9, 5, 11, 4, 7, 20]
    srv = CascadeServer(params, cfg, L.LossConfig(), fused=plan,
                        batcher=RequestBatcher(batch_groups=4,
                                               group_buckets=(8, 16)))
    for i, n in enumerate(sizes):
        srv.submit(_req(i, n, cfg))
    server_resps = srv.serve()

    ses = _session(params, cfg, plan=plan)
    futs = [ses.submit(_req(i, n, cfg), now_ms=0.0)
            for i, n in enumerate(sizes)]
    ses.flush(0.0)
    for fut, ref in zip(futs, server_resps):
        got = fut.result()
        assert got.request_id == ref.request_id
        assert got.status == STATUS_OK and got.degraded == ()
        np.testing.assert_array_equal(got.scores, ref.scores)
        np.testing.assert_array_equal(got.order, ref.order)
        np.testing.assert_array_equal(got.survivors, ref.survivors)
        assert got.stage_counts == ref.stage_counts
        assert got.est_latency_ms == ref.est_latency_ms


@pytest.mark.slow
def test_submit_order_invariance_across_interleaved_flushes():
    """Per-request results must not depend on WHICH batch a request rode
    in: interleaving submits with step()-driven partial flushes yields the
    same response per request as one big submit-all-then-serve."""
    params, cfg = _cascade()
    sizes = [12, 3, 16, 2, 9, 5, 11, 4]
    srv = CascadeServer(params, cfg, L.LossConfig(),
                        batcher=RequestBatcher(batch_groups=4,
                                               group_buckets=(8, 16)))
    for i, n in enumerate(sizes):
        srv.submit(_req(i, n, cfg))
    ref = {r.request_id: r for r in srv.serve()}

    ses = _session(params, cfg, batch_groups=2,
                   flush=FlushPolicy(max_wait_ms=50.0))
    futs = []
    now = 0.0
    for i, n in enumerate(sizes):
        futs.append(ses.submit(_req(i, n, cfg), now_ms=now))
        # pump aggressively: full 2-request chunks flush as they form,
        # so responses interleave with submits in varying batch shapes
        ses.step(now)
        now += 1.0
    ses.flush(now)
    for fut in futs:
        got, want = fut.result(), ref[fut.request_id]
        np.testing.assert_array_equal(got.scores, want.scores)
        np.testing.assert_array_equal(got.order, want.order)
        np.testing.assert_array_equal(got.survivors, want.survivors)
        assert got.stage_counts == want.stage_counts


# ---------------------------------------------------------------------------
# Admission control: bounded queue sheds (or raises) instead of growing.
# ---------------------------------------------------------------------------

def test_shed_at_capacity_resolves_every_future_with_explicit_status():
    params, cfg = _cascade()
    ses = _session(params, cfg, buckets=(8,), batch_groups=8, max_queue=3)
    futs = [ses.submit(_req(i, 4, cfg), now_ms=0.0) for i in range(6)]
    # the overflow futures resolved IMMEDIATELY at admission
    assert [f.done() for f in futs] == [False] * 3 + [True] * 3
    for f in futs[3:]:
        r = f.result()
        assert r.status == STATUS_SHED
        assert len(r.scores) == 0 and len(r.order) == 0
    assert ses.pending == 3                 # the queue never grew past bound
    ses.flush(1.0)
    statuses = [f.result().status for f in futs]
    assert statuses == [STATUS_OK] * 3 + [STATUS_SHED] * 3
    assert all(f.done() for f in futs)      # every future resolved
    assert ses.stats["shed"] == 3 and ses.stats["completed"] == 3


def test_admission_raise_mode_raises_queuefull():
    params, cfg = _cascade()
    ses = _session(params, cfg, buckets=(8,), batch_groups=8, max_queue=2,
                   admission="raise")
    ses.submit(_req(0, 4, cfg), now_ms=0.0)
    ses.submit(_req(1, 4, cfg), now_ms=0.0)
    with pytest.raises(QueueFull, match="capacity"):
        ses.submit(_req(2, 4, cfg), now_ms=0.0)


def test_admission_accounting_separates_refused_from_shed():
    """Regression (PR 6): with admission="raise" a refused request used to
    increment BOTH stats["submitted"] and stats["shed"] before QueueFull
    was raised, conflating refused-by-raise (no future) with
    shed-with-future. Refusals now count under stats["refused"] only."""
    params, cfg = _cascade()
    # raise mode: 2 admitted, 2 refused — no future, no submitted/shed
    ses = _session(params, cfg, buckets=(8,), batch_groups=8, max_queue=2,
                   admission="raise")
    ses.submit(_req(0, 4, cfg), now_ms=0.0)
    ses.submit(_req(1, 4, cfg), now_ms=0.0)
    for i in (2, 3):
        with pytest.raises(QueueFull):
            ses.submit(_req(i, 4, cfg), now_ms=0.0)
    assert ses.stats["submitted"] == 2      # only requests that got futures
    assert ses.stats["refused"] == 2
    assert ses.stats["shed"] == 0           # nothing was shed-with-future
    ses.flush(1.0)
    assert ses.stats["completed"] == 2
    # shed mode: the overflow request DOES get a resolved shed future
    ses2 = _session(params, cfg, buckets=(8,), batch_groups=8, max_queue=2,
                    admission="shed")
    futs = [ses2.submit(_req(i, 4, cfg), now_ms=0.0) for i in range(3)]
    assert futs[2].result().status == STATUS_SHED
    assert ses2.stats["submitted"] == 3     # all three got futures
    assert ses2.stats["shed"] == 1
    assert ses2.stats["refused"] == 0


def test_result_before_resolve_raises():
    params, cfg = _cascade()
    ses = _session(params, cfg)
    fut = ses.submit(_req(0, 4, cfg), now_ms=0.0)
    assert not fut.done()
    with pytest.raises(RuntimeError, match="still pending"):
        fut.result()


# ---------------------------------------------------------------------------
# Flush policy: full buckets, wait ceilings, and deadline-driven ordering.
# ---------------------------------------------------------------------------

def test_full_bucket_flushes_immediately_partial_waits():
    params, cfg = _cascade()
    ses = _session(params, cfg, buckets=(8,), batch_groups=2,
                   flush=FlushPolicy(max_wait_ms=10.0))
    f0 = ses.submit(_req(0, 4, cfg), now_ms=0.0)
    assert ses.step(0.0) == []              # half a batch, nothing due
    f1 = ses.submit(_req(1, 4, cfg), now_ms=1.0)
    resps = ses.step(1.0)                   # full batch: due immediately
    assert [r.request_id for r in resps] == [0, 1]
    assert f0.done() and f1.done()
    # a lone request waits out max_wait_ms, then flushes
    f2 = ses.submit(_req(2, 4, cfg), now_ms=2.0)
    assert ses.step(5.0) == []
    assert ses.next_due_ms() == pytest.approx(12.0)
    (r2,) = ses.step(12.5)
    assert r2.request_id == 2 and f2.done()
    assert r2.wait_ms == pytest.approx(10.5)


def test_deadline_triggered_flush_ordering():
    """Deadline urgency — not arrival order — decides which bucket
    flushes first, and deadline_slack_ms flushes ahead of the deadline."""
    params, cfg = _cascade()
    ses = _session(params, cfg, buckets=(8, 16), batch_groups=4,
                   flush=FlushPolicy(max_wait_ms=100.0,
                                     deadline_slack_ms=5.0))
    # bucket 8 filled FIRST, but with no deadline (due at t=100)
    ses.submit(_req(0, 4, cfg), now_ms=0.0)
    ses.submit(_req(1, 6, cfg), now_ms=0.0)
    # bucket 16 submitted later with a tight deadline: due at 20 - 5 = 15
    fd = ses.submit(_req(2, 12, cfg), now_ms=1.0, deadline_ms=20.0)
    assert ses.step(10.0) == []             # nothing due yet
    resps = ses.step(15.0)                  # deadline bucket preempts
    assert [r.request_id for r in resps] == [2]
    assert not resps[0].deadline_missed     # flushed before the deadline
    assert ses.pending == 2                 # older bucket still queued
    assert ses.step(50.0) == []             # its wait ceiling is 100
    resps = ses.step(100.0)
    assert [r.request_id for r in resps] == [0, 1]
    # a request flushed only AFTER its deadline is marked missed
    ses.submit(_req(3, 4, cfg), now_ms=200.0, deadline_ms=210.0)
    (late,) = ses.step(300.0)
    assert late.deadline_missed
    assert fd.result().request_id == 2


def test_default_deadline_budget_applies_at_submit():
    params, cfg = _cascade()
    ses = _session(params, cfg, buckets=(8,), batch_groups=4,
                   flush=FlushPolicy(max_wait_ms=1000.0,
                                     deadline_slack_ms=0.0),
                   default_deadline_ms=30.0)
    ses.submit(_req(0, 4, cfg), now_ms=10.0)
    assert ses.next_due_ms() == pytest.approx(40.0)


def test_deadline_missed_accounts_at_service_completion():
    """Regression (PR 6): deadline_missed used to be decided at flush
    START, so a chunk that started before its deadline but finished after
    was reported on-time (loadgen papered over it with a local re-check,
    now deleted). Through the claim/execute/resolve seam the driver passes
    the completion time and the session decides there: service time alone
    blowing the deadline IS a miss."""
    params, cfg = _cascade()
    ses = _session(params, cfg, buckets=(8,), batch_groups=4,
                   flush=FlushPolicy(max_wait_ms=100.0,
                                     deadline_slack_ms=5.0))
    fut = ses.submit(_req(0, 4, cfg), now_ms=0.0, deadline_ms=20.0)
    # flush starts at 15 — BEFORE the deadline — but service takes 30ms
    # of (virtual) time, completing at 45 > 20
    chunk = ses.claim_due(15.0)
    assert chunk is not None
    results = ses.execute_chunk(chunk)
    (resp,) = ses.resolve_chunk(chunk, results, now_ms=15.0, done_ms=45.0)
    assert resp.deadline_missed          # pre-fix: False (15 <= 20)
    assert resp.wait_ms == pytest.approx(15.0)       # queue wait to start
    assert resp.service_ms == pytest.approx(30.0)    # start -> completion
    assert ses.stats["deadline_missed"] == 1
    assert fut.result().deadline_missed
    # same shape, service completing BEFORE the deadline: on-time
    fut2 = ses.submit(_req(1, 4, cfg), now_ms=100.0, deadline_ms=120.0)
    chunk = ses.claim_due(115.0)
    (resp2,) = ses.resolve_chunk(chunk, ses.execute_chunk(chunk),
                                 now_ms=115.0, done_ms=119.0)
    assert not resp2.deadline_missed and fut2.done()


def test_open_loop_reports_service_blown_deadlines():
    """End to end through the DES: a deadline tighter than any real
    service time must be reported missed by the SESSION's response flag
    (loadgen no longer re-derives it)."""
    params, cfg = _cascade()
    ses = _session(params, cfg, buckets=(8,), batch_groups=4,
                   flush=FlushPolicy(max_wait_ms=5.0,
                                     deadline_slack_ms=0.0))
    ses.warmup()
    reqs = [_req(i, 6, cfg, seed=i) for i in range(4)]
    # 1e-6 ms budgets: flush can start in time, but ANY measured service
    # pushes completion past the deadline
    res = run_open_loop(ses, reqs, qps=1.0, deadline_ms=1e-6, seed=3)
    assert res.unresolved == 0 and res.completed == len(reqs)
    assert res.deadline_missed == len(reqs)
    assert all(f.result().deadline_missed for f in res.futures)
    assert ses.stats["deadline_missed"] == len(reqs)


def test_flush_full_ties_flush_smaller_bucket_first():
    """Two FULL buckets are both due at -inf (flush_full): next_due_ms()
    reports -inf and step() must take the SMALLER bucket first — the tie
    rule _due_ms/step document but nothing exercised."""
    params, cfg = _cascade()
    ses = _session(params, cfg, buckets=(8, 16), batch_groups=2,
                   flush=FlushPolicy(max_wait_ms=100.0, flush_full=True))
    ses.submit(_req(0, 4, cfg), now_ms=0.0)     # bucket 8
    ses.submit(_req(1, 12, cfg), now_ms=0.0)    # bucket 16
    ses.submit(_req(2, 12, cfg), now_ms=0.0)    # bucket 16 now FULL
    ses.submit(_req(3, 4, cfg), now_ms=0.0)     # bucket 8 now FULL
    assert ses.next_due_ms() == -np.inf
    first = ses.step(0.0)
    assert [r.request_id for r in first] == [0, 3]      # smaller bucket
    assert ses.next_due_ms() == -np.inf                 # 16 still full-due
    second = ses.step(0.0)
    assert [r.request_id for r in second] == [1, 2]
    assert ses.next_due_ms() is None


# ---------------------------------------------------------------------------
# Degraded modes: watermark hysteresis, recorded degradations.
# ---------------------------------------------------------------------------

def test_degraded_mode_hysteresis_and_recorded_degradations():
    params, cfg = _cascade()
    ses = _session(params, cfg, buckets=(8,), batch_groups=2,
                   degrade=DegradePolicy(high_watermark=4, low_watermark=1,
                                         mq_scale=0.5, shrink_bucket=False))
    futs = [ses.submit(_req(i, 4, cfg), now_ms=0.0) for i in range(6)]
    assert ses.degraded                     # depth crossed the high mark
    # drain chunk by chunk: depth 6 -> 4 -> 2 -> 0. Depth 4 and 2 are
    # BELOW the high mark but above the low mark: hysteresis holds the
    # degraded state through the whole drain.
    for expect_depth in (4, 2, 0):
        resps = ses.step(0.0)
        assert ses.pending == expect_depth
        for r in resps:
            assert "tighten_m_q" in r.degraded
        if expect_depth > 1:
            assert ses.degraded
    # depth 0 <= low watermark: the NEXT pump/admission leaves degraded
    # mode. Same request CONTENT as futs[0] so the latency estimates below
    # differ only by the degradation.
    f = ses.submit(_req(0, 4, cfg), now_ms=1.0)
    assert not ses.degraded
    ses.flush(2.0)
    assert f.result().degraded == ()
    assert ses.stats["degrade_enters"] == 1
    assert ses.stats["degrade_exits"] == 1
    # degradation actually tightened the serving knobs: degraded responses
    # estimate LOWER latency than the same request served undegraded
    # (m_q halved -> fewer expected items through the cascade)
    degraded_lat = futs[0].result().est_latency_ms
    assert degraded_lat < f.result().est_latency_ms


def test_degraded_shrink_bucket_demotes_without_conflating_truncation():
    """Regression (PR 6): a request whose n FITS its natural bucket but is
    demoted by shrink_bucket drops items by DEGRADATION — that must read
    as degraded=("shrink_bucket",), NOT as truncated, which is reserved
    for requests exceeding the largest declared bucket. Pre-fix both
    paths set the same truncated flag and were indistinguishable."""
    params, cfg = _cascade()
    ses = _session(params, cfg, buckets=(8, 16), batch_groups=8,
                   degrade=DegradePolicy(high_watermark=2, low_watermark=0,
                                         mq_scale=1.0, shrink_bucket=True))
    ses.submit(_req(0, 4, cfg), now_ms=0.0)
    ses.submit(_req(1, 4, cfg), now_ms=0.0)
    # degraded now; a 12-item request FITS bucket 16 but is demoted to 8:
    # items dropped by degradation, not truncation
    f_demoted = ses.submit(_req(2, 12, cfg), now_ms=0.0)
    # a 20-item request exceeds the LARGEST bucket: truly truncated (and,
    # degraded, also demoted — both flags carry their own cause)
    f_over = ses.submit(_req(3, 20, cfg), now_ms=0.0)
    ses.flush(1.0)
    r = f_demoted.result()
    assert "shrink_bucket" in r.degraded
    assert not r.truncated and len(r.scores) == 8   # demoted, NOT truncated
    r_over = f_over.result()
    assert r_over.truncated                         # exceeded largest bucket
    assert "shrink_bucket" in r_over.degraded
    assert ses.stats["truncated"] == 1              # only the 20-item one


def test_undegraded_truncation_still_surfaced():
    """The other path: with degradation disabled, only over-largest-bucket
    requests are truncated; in-bucket requests never are."""
    params, cfg = _cascade()
    ses = _session(params, cfg, buckets=(8, 16), batch_groups=4)
    f_over = ses.submit(_req(0, 20, cfg), now_ms=0.0)
    f_fit = ses.submit(_req(1, 12, cfg), now_ms=0.0)
    ses.flush(0.0)
    assert f_over.result().truncated
    assert not f_fit.result().truncated
    assert f_over.result().degraded == () == f_fit.result().degraded
    assert ses.stats["truncated"] == 1


def test_no_degradation_below_watermark():
    params, cfg = _cascade()
    ses = _session(params, cfg, buckets=(8,), batch_groups=4,
                   degrade=DegradePolicy(high_watermark=10, low_watermark=2))
    futs = [ses.submit(_req(i, 4, cfg), now_ms=0.0) for i in range(5)]
    ses.flush(0.0)
    assert not ses.degraded
    assert all(f.result().degraded == () for f in futs)


# ---------------------------------------------------------------------------
# Warmup: zero recompiles under live traffic, degraded modes included.
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_zero_recompiles_after_warmup_including_degraded_flushes():
    params, cfg = _cascade()
    ses = _session(params, cfg, buckets=(8, 16), batch_groups=4,
                   max_queue=32,
                   flush=FlushPolicy(max_wait_ms=10.0),
                   degrade=DegradePolicy(high_watermark=6, low_watermark=1))
    shapes = ses.warmup()
    assert sorted(shapes) == sorted((b, g) for g in (8, 16)
                                    for b in (1, 2, 4))
    n_compiled = ses._rank._cache_size()
    assert n_compiled == len(shapes)
    now = 0.0
    for round_ in range(3):
        futs = [ses.submit(_req(i, n, cfg), now_ms=now)
                for i, n in enumerate([2, 8, 13, 16, 5, 3, 9, 4])]
        while ses.step(now):
            pass
        now += 20.0
        while ses.step(now):                # wait-ceiling flushes
            pass
        ses.flush(now)
        assert all(f.done() for f in futs)
        assert ses._rank._cache_size() == n_compiled, (
            f"round {round_} recompiled the pipeline")


@contextlib.contextmanager
def _compiles():
    """Names of every program lowered inside the block, jitted or eager,
    on any thread: JAX reports each lowering to MLIR as one event."""
    names = []

    def listener(event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            names.append(kw.get("fun_name"))
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield names
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


@pytest.mark.parametrize("driver", ["step", "pump"])
def test_live_flushes_after_warmup_compile_no_program(driver):
    """After warmup(), live flushes through step() and through a
    SessionPump compile nothing at all -- not only no new entry in the
    pipeline's own cache (_rank._cache_size()), which is blind to eager
    ops. This fails on a fetch that sums the stage counts on the host's
    initiative: that sum (jit__reduce_sum) compiled at each shape's first
    live flush, outside the pipeline's cache. jax.clear_caches() first,
    so an earlier test's eager ops cannot hide such a compile."""
    jax.clear_caches()
    params, cfg = _cascade()
    ses = _session(params, cfg, buckets=(8, 16), batch_groups=4,
                   flush=FlushPolicy(max_wait_ms=1.0))
    ses.warmup()
    sizes = [2, 8, 13, 16, 5, 3, 9, 4, 6, 11, 1]
    with _compiles() as compiled:
        if driver == "step":
            futs = [ses.submit(_req(i, n, cfg), now_ms=0.0)
                    for i, n in enumerate(sizes)]
            while ses.step(0.0):
                pass
            ses.flush(10.0)
        else:
            with SessionPump(ses) as pump:
                futs = [pump.submit(_req(i, n, cfg))
                        for i, n in enumerate(sizes)]
                for f in futs:
                    f.result(timeout=30.0)
    assert [f.result().status for f in futs] == [STATUS_OK] * len(sizes)
    assert compiled == []


# ---------------------------------------------------------------------------
# Truncation surfacing (satellite): item lists beyond the largest bucket.
# ---------------------------------------------------------------------------

def test_truncated_flag_on_session_and_server_paths():
    params, cfg = _cascade()
    # session path
    ses = _session(params, cfg, buckets=(8, 16), batch_groups=4)
    f_big = ses.submit(_req(0, 20, cfg), now_ms=0.0)    # > largest bucket
    f_ok = ses.submit(_req(1, 16, cfg), now_ms=0.0)     # exactly fits
    ses.flush(0.0)
    assert f_big.result().truncated
    assert len(f_big.result().scores) == 16             # capped at bucket
    assert len(f_big.result().order) == 16
    assert not f_ok.result().truncated
    assert ses.stats["truncated"] == 1
    # server (shim) path propagates the same flag
    srv = CascadeServer(params, cfg, L.LossConfig(),
                        batcher=RequestBatcher(batch_groups=4,
                                               group_buckets=(8, 16)))
    srv.submit(_req(0, 20, cfg))
    srv.submit(_req(1, 7, cfg))
    r_big, r_ok = srv.serve()
    assert r_big.truncated and not r_ok.truncated
    assert len(r_big.scores) == 16


# ---------------------------------------------------------------------------
# Open-loop driver: overload sheds, nothing is ever dropped.
# ---------------------------------------------------------------------------

def test_open_loop_overload_sheds_and_resolves_everything():
    params, cfg = _cascade()
    ses = _session(params, cfg, buckets=(8,), batch_groups=4, max_queue=8,
                   flush=FlushPolicy(max_wait_ms=5.0),
                   degrade=DegradePolicy(high_watermark=6, low_watermark=2))
    ses.warmup()
    reqs = [_req(i, 6, cfg, seed=i) for i in range(64)]
    # offered rate far above anything a real flush can serve between
    # arrivals (2.5 us inter-arrival): the bounded queue must shed
    res = run_open_loop(ses, reqs, qps=400_000.0, deadline_ms=50.0, seed=1)
    assert res.unresolved == 0
    assert res.shed > 0
    assert res.completed + res.shed == len(reqs)
    assert res.completed == len(res.latency_ms)
    statuses = {f.result().status for f in res.futures}
    assert statuses <= {"ok", "shed"}
    # under that pressure the watermark must have engaged at least once
    assert ses.stats["degrade_enters"] >= 1


def test_open_loop_empty_request_list_returns_zeroed_result():
    params, cfg = _cascade()
    ses = _session(params, cfg, buckets=(8,), batch_groups=4)
    res = run_open_loop(ses, [], qps=100.0, deadline_ms=10.0)
    assert res.n_requests == 0 and res.completed == 0
    assert res.unresolved == 0 and res.shed == 0
    assert res.sim_s == 0.0 and len(res.latency_ms) == 0
    assert np.isnan(res.pct(95))


def test_open_loop_defensive_branch_when_due_chunk_races_away():
    """The DES event loop's defensive branch: next_due_ms() promised work
    but claim_due returned None (in a threaded world the pump may have
    raced it away). The loop must advance the virtual clock to t_flush
    and carry on — every future still resolves."""
    params, cfg = _cascade()
    ses = _session(params, cfg, buckets=(8,), batch_groups=4,
                   flush=FlushPolicy(max_wait_ms=5.0))
    ses.warmup()
    real_claim = ses.claim_due
    raced = {"n": 0}

    def flaky_claim(now_ms):
        if raced["n"] == 0:
            raced["n"] += 1
            return None                 # simulate the chunk racing away
        return real_claim(now_ms)

    ses.claim_due = flaky_claim
    reqs = [_req(i, 6, cfg, seed=i) for i in range(6)]
    res = run_open_loop(ses, reqs, qps=1000.0, seed=4)
    assert raced["n"] == 1              # the branch actually ran
    assert res.unresolved == 0
    assert res.completed == len(reqs)


def test_open_loop_light_load_sheds_nothing():
    params, cfg = _cascade()
    ses = _session(params, cfg, buckets=(8,), batch_groups=4, max_queue=8,
                   flush=FlushPolicy(max_wait_ms=5.0))
    ses.warmup()
    reqs = [_req(i, 6, cfg, seed=i) for i in range(12)]
    # 1 request per simulated second: every chunk drains long before the
    # queue can fill, whatever this host's wall clock does
    res = run_open_loop(ses, reqs, qps=1.0, deadline_ms=None, seed=2)
    assert res.unresolved == 0 and res.shed == 0
    assert res.completed == len(reqs)
    assert (res.latency_ms >= 0).all()
