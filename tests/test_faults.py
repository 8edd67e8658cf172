"""Fault-tolerance tests for the serving stack (serving.faults + the
session's retry/quarantine layer + pump supervision).

The serving contract under test: every future resolves with an explicit
status — "ok", "shed", or "error" — no matter what the executor does.
Transient faults clear under capped exponential backoff; NaN/+Inf output
corruption is caught by the guard and treated as a fault; a poisoned
request is bisected out of its chunk and quarantined as status="error"
while its chunk-mates serve bit-identically to a clean run; an exception
escaping the pump's service seam resolves the claimed chunk as errors
and keeps pumping; a dead service thread is restarted by the watchdog;
and the consecutive-fault circuit breaker degrades, then sheds, then
recovers. Includes the regression tests for the two pre-fix crash bugs
(seam exception killing the pump thread; pack failure leaking the open-
chunk registration) and the slow-marked chaos soak."""

import math

import jax
import numpy as np
import pytest

from repro.core import cascade as C
from repro.core import losses as L
from repro.data import features as F
from repro.serving.batching import RankRequest
from repro.serving.faults import (CorruptOutput, FaultConfig, FaultInjector,
                                  PoisonFault, TransientFault, _hash01)
from repro.serving.loadgen import run_open_loop
from repro.serving.pump import SessionPump, run_wall_clock
from repro.serving.session import (CascadeSession, FlushPolicy, RetryPolicy,
                                   ServingConfig, STATUS_ERROR, STATUS_OK,
                                   STATUS_SHED)


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


FAST_RETRY = RetryPolicy(backoff_ms=0.01, max_backoff_ms=0.1)


def _session(params, cfg, *, buckets=(8,), batch_groups=4, faults=None,
             **kw):
    defaults = dict(plan="filter", group_buckets=buckets,
                    batch_groups=batch_groups, retry=FAST_RETRY)
    defaults.update(kw)
    return CascadeSession(params, cfg, L.LossConfig(), faults=faults,
                          scfg=ServingConfig(**defaults))


# ---------------------------------------------------------------------------
# FaultInjector: seeded determinism, stable poison membership.
# ---------------------------------------------------------------------------

def test_injector_decisions_replay_for_a_seed():
    cfg = FaultConfig(transient_rate=0.4, latency_rate=0.3,
                      latency_spike_ms=0.0, corrupt_rate=0.5, seed=11)
    def trace(inj):
        out = []
        for k in range(40):
            try:
                inj.on_attempt([k])
                out.append("ok")
            except TransientFault:
                out.append("transient")
            res = {"scores": np.zeros((2, 4), np.float32)}
            inj.on_results(res, 2)
            out.append(np.isnan(res["scores"]).any()
                       or np.isinf(res["scores"]).any())
        return out, dict(inj.stats)
    a = trace(FaultInjector(cfg, sleep=lambda s: None))
    b = trace(FaultInjector(cfg, sleep=lambda s: None))
    assert a == b
    assert a[1]["transient"] > 0 and a[1]["corrupt"] > 0


def test_poison_membership_is_stable_and_order_independent():
    inj = FaultInjector(FaultConfig(poison_rate=0.25, seed=3))
    ids = list(range(200))
    member = {i: inj.is_poisoned(i) for i in ids}
    assert 0.1 < sum(member.values()) / len(ids) < 0.45  # rate-ish
    # membership depends only on (id, seed) — not on query order or on
    # how many rng draws happened in between
    inj2 = FaultInjector(FaultConfig(poison_rate=0.25, seed=3))
    for i in reversed(ids):
        assert inj2.is_poisoned(i) == member[i]
    # a different seed poisons a different set
    inj3 = FaultInjector(FaultConfig(poison_rate=0.25, seed=4))
    assert any(inj3.is_poisoned(i) != member[i] for i in ids)
    # explicit ids poison regardless of rate
    inj4 = FaultInjector(FaultConfig(poison_ids=(7,)))
    assert inj4.is_poisoned(7) and not inj4.is_poisoned(8)
    with pytest.raises(PoisonFault, match="request 7"):
        inj4.on_attempt([1, 7])
    assert 0.0 <= _hash01(123, 9) < 1.0


def test_disabled_injector_is_a_no_op():
    inj = FaultInjector(FaultConfig(transient_rate=1.0, corrupt_rate=1.0,
                                    poison_ids=(0,)))
    inj.enabled = False
    inj.on_attempt([0, 1])                   # would raise if enabled
    res = {"scores": np.zeros((1, 4), np.float32)}
    inj.on_results(res, 1)
    assert (res["scores"] == 0).all()
    assert sum(inj.stats.values()) == 0


# ---------------------------------------------------------------------------
# Retry with backoff + the NaN/Inf output guard.
# ---------------------------------------------------------------------------

def test_transient_executor_fault_retries_then_serves():
    params, cfg = _cascade()
    ses = _session(params, cfg)
    real = ses.rank_batch
    calls = {"n": 0}
    def flaky(batch, **kw):
        calls["n"] += 1
        if calls["n"] <= 2:
            raise RuntimeError("executor hiccup")
        return real(batch, **kw)
    ses.rank_batch = flaky
    fut = ses.submit(_req(0, 4, cfg), now_ms=0.0)
    resps = ses.flush(1.0)
    assert resps[0].status == STATUS_OK
    assert resps[0].attempts == 3
    assert fut.result().error is None
    assert ses.stats["faults"] == 2 and ses.stats["retries"] == 2
    assert ses.stats["errors"] == 0
    assert ses._consec_faults == 0          # success closed the breaker


def test_retry_exhaustion_resolves_error_never_raises():
    params, cfg = _cascade()
    ses = _session(params, cfg)
    ses.rank_batch = lambda batch, **kw: (_ for _ in ()).throw(
        RuntimeError("executor down"))
    fut = ses.submit(_req(0, 4, cfg), now_ms=0.0)
    resps = ses.flush(1.0)                  # must NOT raise
    r = resps[0]
    assert r.status == STATUS_ERROR
    assert "executor down" in r.error
    assert r.attempts == FAST_RETRY.max_attempts
    assert fut.done() and fut.result() is r
    assert ses.stats["errors"] == 1 and ses.stats["quarantined"] == 1
    # accounting identity holds with errors in it
    assert ses.stats["submitted"] == (ses.stats["completed"]
                                      + ses.stats["shed"]
                                      + ses.stats["errors"])


def test_backoff_is_capped_exponential():
    params, cfg = _cascade()
    sleeps = []
    ses = _session(params, cfg, retry=RetryPolicy(
        max_attempts=5, backoff_ms=1.0, backoff_factor=4.0,
        max_backoff_ms=6.0))
    ses._sleep = sleeps.append
    ses.rank_batch = lambda batch, **kw: (_ for _ in ()).throw(
        RuntimeError("down"))
    ses.submit(_req(0, 4, cfg), now_ms=0.0)
    ses.flush(1.0)
    # 1ms, 4ms, then capped at 6ms (seconds at the sleep call site)
    assert sleeps == [pytest.approx(v / 1e3) for v in (1.0, 4.0, 6.0, 6.0)]


def test_nan_guard_treats_corrupt_output_as_fault():
    params, cfg = _cascade()
    ses = _session(params, cfg)
    real = ses.rank_batch
    calls = {"n": 0}
    def corrupting(batch, **kw):
        calls["n"] += 1
        out = real(batch, **kw)
        if calls["n"] == 1:
            out = np.asarray(out).copy()
            out[0, 0] = np.nan              # the packed result's scores[0, 0]
        return out
    ses.rank_batch = corrupting
    fut = ses.submit(_req(0, 4, cfg), now_ms=0.0)
    resps = ses.flush(1.0)
    # first attempt corrupt -> guard fired -> retry served clean
    assert resps[0].status == STATUS_OK and resps[0].attempts == 2
    s = fut.result().scores                 # -inf = filtered, legitimate
    assert not np.isnan(s).any() and not np.isposinf(s).any()
    assert ses.stats["faults"] == 1


def test_nan_guard_exhaustion_reports_corrupt_output():
    params, cfg = _cascade()
    ses = _session(params, cfg)
    real = ses.rank_batch
    def always_corrupt(batch, **kw):
        out = np.asarray(real(batch, **kw)).copy()
        out[0, 0] = np.inf                  # scores[0, 0]: +inf is corruption;
        return out                          # -inf a legitimate filtered score
    ses.rank_batch = always_corrupt
    ses.submit(_req(0, 4, cfg), now_ms=0.0)
    r = ses.flush(1.0)[0]
    assert r.status == STATUS_ERROR
    assert CorruptOutput.__name__ in r.error


# ---------------------------------------------------------------------------
# Poisoned-chunk quarantine: bisection isolates the poison request; its
# chunk-mates serve bit-identically to a clean run, with zero recompiles.
# ---------------------------------------------------------------------------

def test_poison_quarantined_while_chunk_mates_serve_bit_identically():
    params, cfg = _cascade()
    inj = FaultInjector(FaultConfig(poison_ids=(2,)))
    ses = _session(params, cfg, faults=inj)
    shapes = ses.warmup()
    n_compiled = ses._rank._cache_size()
    futs = [ses.submit(_req(i, 4, cfg), now_ms=0.0) for i in range(4)]
    resps = ses.flush(1.0)
    assert [r.status for r in resps] == [STATUS_OK, STATUS_OK,
                                         STATUS_ERROR, STATUS_OK]
    assert "poisoned request 2" in resps[2].error
    assert ses.stats["quarantined"] == 1 and ses.stats["errors"] == 1
    assert ses.stats["completed"] == 3
    # bisection ran entirely inside the warmed pow2 shape ladder
    assert ses._rank._cache_size() == n_compiled
    assert ses.pool.allocated <= len(shapes)
    # survivors serve bit-identically to the same requests in a clean,
    # fault-free session
    clean = _session(params, cfg)
    cfuts = [clean.submit(_req(i, 4, cfg), now_ms=0.0) for i in range(4)]
    clean.flush(1.0)
    for i in (0, 1, 3):
        a, b = futs[i].result(), cfuts[i].result()
        np.testing.assert_array_equal(a.scores, b.scores)
        np.testing.assert_array_equal(a.order, b.order)
        assert a.stage_counts == b.stage_counts


@pytest.mark.slow
def test_zero_rate_injector_keeps_serving_bit_identical():
    params, cfg = _cascade()
    ses_inj = _session(params, cfg,
                       faults=FaultInjector(FaultConfig(seed=0)))
    ses_ref = _session(params, cfg)
    f_inj = ses_inj.submit(_req(0, 6, cfg), now_ms=0.0)
    f_ref = ses_ref.submit(_req(0, 6, cfg), now_ms=0.0)
    ses_inj.flush(1.0)
    ses_ref.flush(1.0)
    np.testing.assert_array_equal(f_inj.result().scores,
                                  f_ref.result().scores)
    assert f_inj.result().attempts == 1


@pytest.mark.slow
def test_des_chaos_outcomes_replay_for_a_seed():
    """Explicit-clock chaos is deterministic: same seed, same submit/flush
    sequence -> the same requests error and the same requests serve."""
    params, cfg = _cascade()
    def run():
        inj = FaultInjector(FaultConfig(transient_rate=0.5,
                                        corrupt_rate=0.3,
                                        poison_rate=0.15, seed=5),
                            sleep=lambda s: None)
        ses = _session(params, cfg, faults=inj, batch_groups=4)
        ses._sleep = lambda s: None
        futs = [ses.submit(_req(i, 4, cfg), now_ms=0.0) for i in range(16)]
        ses.flush(1.0)
        return ([f.result().status for f in futs],
                [f.result().attempts for f in futs], dict(inj.stats))
    assert run() == run()


# ---------------------------------------------------------------------------
# Pump supervision. Regression: an exception escaping the service seam
# used to kill the pump thread and hang every outstanding future.
# ---------------------------------------------------------------------------

def test_seam_exception_resolves_chunk_as_error_and_keeps_pumping():
    params, cfg = _cascade()
    ses = _session(params, cfg, flush=FlushPolicy(max_wait_ms=2.0))
    ses.warmup()
    real = ses.execute_chunk
    boom = {"armed": True}
    def exploding(chunk):
        # a bug BEYOND execute_chunk's own fault handling (pre-fix this
        # escaped _service_cycle, killed the thread, and hung the future)
        if boom["armed"]:
            boom["armed"] = False
            raise ValueError("bug in the service seam")
        return real(chunk)
    ses.execute_chunk = exploding
    with SessionPump(ses, idle_wait_s=0.01) as pump:
        crashed = pump.submit(_req(0, 4, cfg))
        r = crashed.result(timeout=30.0)    # pre-fix: hung forever
        assert r.status == STATUS_ERROR and "bug in the service" in r.error
        assert pump.running                 # the thread survived
        healthy = pump.submit(_req(1, 4, cfg))
        assert healthy.result(timeout=30.0).status == STATUS_OK
    assert pump.stats["cycle_errors"] == 1
    assert pump.stats["restarts"] == 0      # contained, not restarted
    assert ses.stats["errors"] == 1 and ses.stats["completed"] == 1


def test_pack_failure_cleans_open_chunk_registration():
    """Regression: pack_chunk raising while a slot-join chunk was `open`
    leaked the chunk in pump._open, silently swallowing that bucket's
    later slot-joins into a chunk nobody would ever execute. Drives the
    pump's service cycle directly (no thread) so the under-full claim is
    deterministic: 3 entries pad to capacity 4 -> the chunk goes open."""
    params, cfg = _cascade()
    ses = _session(params, cfg, batch_groups=4)
    ses.warmup()
    real = ses.pack_chunk
    boom = {"armed": True}
    def exploding_pack(chunk):
        if boom["armed"]:
            boom["armed"] = False
            raise MemoryError("staging buffer failure")
        return real(chunk)
    ses.pack_chunk = exploding_pack
    pump = SessionPump(ses)                 # not started: direct cycles
    futs = [pump.submit(_req(i, 4, cfg)) for i in range(3)]
    pump._service_cycle(claim_at=math.inf)  # must NOT raise (pre-fix: did)
    assert [f.result().status for f in futs] == [STATUS_ERROR] * 3
    with ses.lock:
        assert pump._open == {}             # pre-fix: stale open chunk
    assert pump.stats["cycle_errors"] == 1
    # the bucket keeps serving — a leaked open chunk would swallow this
    # submission's slot-join into a chunk nobody executes
    ok = pump.submit(_req(3, 4, cfg))
    pump._service_cycle(claim_at=math.inf)
    assert ok.result().status == STATUS_OK
    assert ses.stats["errors"] == 3 and ses.stats["completed"] == 1


@pytest.mark.slow
@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_watchdog_restarts_dead_service_thread():
    # the injected bug is SUPPOSED to kill the service thread (that is
    # what the watchdog recovers from) — the escape is not a test leak
    params, cfg = _cascade()
    ses = _session(params, cfg, flush=FlushPolicy(max_wait_ms=2.0))
    ses.warmup()
    real_claim = ses.claim_due
    def lethal_claim(now):
        # one-shot: a bug OUTSIDE the seam guard (claim happens before the
        # containment try) — the service thread dies; restore the real
        # method so the restarted thread can serve
        ses.claim_due = real_claim
        raise RuntimeError("bug in the pump loop itself")
    pump = SessionPump(ses, idle_wait_s=0.01,
                       watchdog_interval_s=0.02).start()
    try:
        ses.claim_due = lethal_claim
        fut = pump.submit(_req(0, 4, cfg))
        # pre-watchdog: the thread death stranded this future forever
        assert fut.result(timeout=30.0).status == STATUS_OK
        assert pump.stats["restarts"] >= 1
        assert pump.running
    finally:
        pump.close()
    assert fut.done()


# ---------------------------------------------------------------------------
# Circuit breaker: consecutive faults degrade first, then shed new work,
# then a probe closes the breaker once the executor recovers.
# ---------------------------------------------------------------------------

def test_breaker_degrades_then_opens_then_probe_recovers():
    params, cfg = _cascade()
    inj = FaultInjector(FaultConfig(transient_rate=1.0, seed=0))
    ses = _session(params, cfg, faults=inj, retry=RetryPolicy(
        max_attempts=1, backoff_ms=0.0, breaker_degrade_after=2,
        breaker_open_after=4))
    assert not ses.degraded
    for i in range(4):
        ses.submit(_req(i, 4, cfg), now_ms=0.0)
        assert ses.flush(1.0)[0].status == STATUS_ERROR
        if i >= 1:
            assert ses.degraded             # degrade stage fired first
    assert ses._consec_faults == 4
    # breaker open: new work sheds while a backlog exists...
    probe = ses.submit(_req(10, 4, cfg), now_ms=0.0)   # queue empty: probe
    assert not probe.done()
    shed = ses.submit(_req(11, 4, cfg), now_ms=0.0)    # backlog -> shed
    assert shed.done() and shed.result().status == STATUS_SHED
    assert ses.stats["breaker_shed"] == 1
    # ...until the executor recovers and the probe's success closes it
    inj.enabled = False
    assert ses.flush(2.0)[0].status == STATUS_OK
    assert probe.result().status == STATUS_OK
    assert ses._consec_faults == 0 and not ses.degraded
    after = ses.submit(_req(12, 4, cfg), now_ms=0.0)
    ses.flush(3.0)
    assert after.result().status == STATUS_OK


# ---------------------------------------------------------------------------
# Chaos soaks: aggressive injection, both clocks — zero unresolved
# futures, accounting closes (submitted = completed + shed + errors).
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_des_chaos_open_loop_accounting_closes():
    params, cfg = _cascade()
    inj = FaultInjector(FaultConfig(transient_rate=0.3, latency_rate=0.1,
                                    latency_spike_ms=0.5, corrupt_rate=0.2,
                                    poison_rate=0.1, poison_ids=(7,),
                                    seed=2))
    # unbounded queue: nothing sheds, so the pinned poison id MUST come
    # back as an explicit error (a shed would mask it)
    ses = _session(params, cfg, faults=inj,
                   flush=FlushPolicy(max_wait_ms=2.0))
    ses.warmup()
    reqs = [_req(i, 4, cfg, seed=i) for i in range(40)]
    res = run_open_loop(ses, reqs, qps=2000.0, deadline_ms=250.0, seed=2)
    assert res.unresolved == 0
    assert all(f.done() for f in res.futures)
    assert res.completed + res.shed + res.errors == len(reqs)
    assert res.errors > 0                   # chaos actually did something
    st = ses.stats
    assert st["submitted"] == st["completed"] + st["shed"] + st["errors"]


@pytest.mark.slow
def test_pump_chaos_soak_zero_unresolved_zero_thread_deaths():
    params, cfg = _cascade()
    inj = FaultInjector(FaultConfig(transient_rate=0.25, latency_rate=0.1,
                                    latency_spike_ms=1.0, corrupt_rate=0.15,
                                    poison_rate=0.08, seed=13))
    ses = _session(params, cfg, buckets=(8, 16), batch_groups=4,
                   max_queue=64, faults=inj,
                   flush=FlushPolicy(max_wait_ms=2.0))
    ses.warmup()
    n_compiled = ses._rank._cache_size()
    rng = np.random.default_rng(13)
    reqs = [_req(i, int(rng.integers(2, 17)), cfg, seed=i)
            for i in range(80)]
    with SessionPump(ses, idle_wait_s=0.01) as pump:
        res = run_wall_clock(pump, reqs, qps=2000.0, deadline_ms=500.0,
                             n_threads=4, seed=13)
        alive_before_close = pump.running
    # every future resolved with an explicit status, even across faults
    # and pump shutdown
    assert res.unresolved == 0
    assert all(f.done() for f in res.futures)
    assert {f.result().status for f in res.futures} <= {
        STATUS_OK, STATUS_SHED, STATUS_ERROR}
    assert res.completed + res.shed + res.errors == len(reqs)
    # the service thread never died: chunk-level faults were contained
    # inside the cycle (a restart would mean containment failed)
    assert alive_before_close
    assert pump.stats["restarts"] == 0
    # lifecycle accounting closes under chaos
    st = ses.stats
    assert st["submitted"] == len(reqs)
    assert st["submitted"] == st["completed"] + st["shed"] + st["errors"]
    assert st["shed"] == res.shed + pump.stats["shutdown_shed"]
    # chaos actually bit: faults were injected and the retry layer worked
    # (a first-attempt fault always spends a retry when max_attempts > 1)
    assert st["faults"] > 0 and st["retries"] > 0
    # no recompiles: retries and bisection reuse the warmed pow2 ladder
    assert ses._rank._cache_size() == n_compiled
