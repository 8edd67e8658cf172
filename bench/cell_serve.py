"""Serving cells: open-loop arrivals against the session pump.

One general generator reads a traffic file (bench/traffic/<mix>.json):

  {"kind": "serve", "rate_per_s": R, "sample": S,
   "items": {"dist": "uniform" | "log_uniform", "lo": a, "hi": b}}

From --seed it draws, before the window opens, the whole schedule: the
absolute due time of every request in the window and every request
itself. Every seed gets the same work: N = R * seconds requests, the
same multiset of Poisson gaps (exponential quantiles) and of item counts
(quantiles of the size distribution), in an order and over query rows
that the seed chooses. A generator thread sleeps to each due time (never
by gaps, so a late submit does not delay the ones after it) and submits.
Each request is timed from its due time to the resolution of its future,
so a stall shows in the latency of every request that waited behind it.
The generator keeps no future: as each resolves, a Record takes its
time, status and stamps, and the whole response only for the check's
candidates, drawn from the seed before the window opens.

After the window: the device's peak memory is read, the pump is
stopped, and the candidates that completed (a draw from the seed, with
the widest requests of every bucket) are compared with the plain
reference (reference.cascade_rank) in blocks.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import itertools
import threading
import time

import numpy as np

import benchlib
import checks
import reference
import system
import workcount

LEAD_S = 0.05            # first due time after the schedule is armed
RESULT_WAIT_S = 60.0     # how long past the window a future may take
REF_BLOCK = 256          # requests per reference block


# ---------------------------------------------------------------------------
# inputs from the seed
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Log:
    x: np.ndarray        # (Q, G, d_x) float32
    q: np.ndarray        # (Q, d_q) float32
    m_q: np.ndarray      # (Q,) recalled-item counts
    n_valid: np.ndarray  # (Q,) logged items per query


def make_log(config: dict, seed: int) -> Log:
    """The search log's arrays that requests draw from, in float32."""
    log = system.make_log(config, seed)
    return Log(x=log.x.astype(np.float32), q=log.q.astype(np.float32),
               m_q=log.m_q.astype(np.int64),
               n_valid=log.mask.sum(1).astype(np.int64))


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def item_counts(items: dict, n: int) -> np.ndarray:
    """n item counts: the quantiles of the traffic's size distribution."""
    lo, hi, u = int(items["lo"]), int(items["hi"]), _quantiles(n)
    if items["dist"] == "uniform":
        sizes = lo + np.floor(u * (hi - lo + 1))
    elif items["dist"] == "log_uniform":
        sizes = np.floor(np.exp(np.log(lo) + u * (np.log(hi + 1) - np.log(lo))))
    else:
        raise benchlib.BenchError(f"unknown item distribution {items['dist']!r}")
    return np.clip(sizes, lo, hi).astype(np.int64)


@dataclasses.dataclass
class Schedule:
    offsets_s: np.ndarray    # due time of each request after the window opens
    sizes: np.ndarray        # items per request
    rows: np.ndarray         # the log's query row each request ranks


def schedule(traffic: dict, seconds: float, seed: int, log: Log) -> Schedule:
    """The window's requests: N = rate * seconds, Poisson gaps as the
    exponential distribution's N quantiles scaled to fill the window,
    item counts as the size distribution's quantiles, both permuted by
    the seed, and for each request a query row with at least that many
    logged items."""
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng(benchlib.split_seed(seed))
    gaps = -np.log1p(-_quantiles(n)) / rate
    gaps = rng.permutation(gaps * (seconds / gaps.sum()))
    offsets = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    sizes = rng.permutation(item_counts(traffic["items"], n))
    order = np.argsort(log.n_valid, kind="stable")
    nv = log.n_valid[order]
    first = np.searchsorted(nv, sizes, side="left")
    if (first >= len(nv)).any():
        raise benchlib.BenchError("the log has no query with "
                                  f"{int(sizes.max())} logged items")
    pick = first + np.floor(rng.random(n) * (len(nv) - first)).astype(np.int64)
    return Schedule(offsets_s=offsets, sizes=sizes, rows=order[pick])


def build_requests(log: Log, sch: Schedule) -> list:
    from repro.serving.batching import RankRequest
    return [RankRequest(request_id=i, q_feat=log.q[r], item_feats=log.x[r, :s],
                        m_q=int(log.m_q[r]))
            for i, (r, s) in enumerate(zip(sch.rows.tolist(),
                                           sch.sizes.tolist()))]


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def warm_buckets(config: dict, traffic: dict) -> list[int]:
    """The buckets this traffic fills, and the ones a degraded flush
    shrinks them to: the only shapes the window can use."""
    from repro.serving.batching import bucket_of
    buckets = tuple(sorted(config["serving"]["group_buckets"]))
    lo, hi = int(traffic["items"]["lo"]), int(traffic["items"]["hi"])
    used = {bucket_of(n, buckets) for n in range(lo, hi + 1)}
    shrunk = {buckets[buckets.index(g) - 1] for g in used if g != buckets[0]}
    return sorted(used | shrunk)


class Server:
    """The system under test: one session and its pump on the cell's
    chip. Threads get OS names for the trace."""

    def __init__(self, config: dict, params, devs):
        from repro.serving.pump import SessionPump
        from repro.serving.session import CascadeSession
        self.session = CascadeSession(params, system.cascade_config(config),
                                      system.loss_config(config),
                                      scfg=system.serving_config(config),
                                      device=devs[0])
        self.pump = SessionPump(self.session, name="pump0")

    def warm(self, buckets: list[int], log: Log) -> int:
        """Serve one full set of shapes through the session's own flush
        path (pack, pipeline, stage counts, unpack): every (rows, bucket)
        the window can use compiles here, not inside the window."""
        from repro.serving.batching import RankRequest, warmup_batch_sizes
        wide = int(np.argmax(log.n_valid))
        ses, n = self.session, 0
        for g in buckets:
            for b in warmup_batch_sizes(ses.scfg.batch_groups):
                for i in range(b):
                    ses.submit(RankRequest(
                        request_id=-1 - i, q_feat=log.q[wide],
                        item_feats=log.x[wide, :g], m_q=int(log.m_q[wide])))
                n += len(ses.flush())
        return n

    def start(self) -> None:
        self.pump.start()
        benchlib.name_thread(self.pump._thread.native_id, "pump0")
        benchlib.name_thread(self.pump._watchdog.native_id, "pump0-watch")

    def submit(self, req):
        return self.pump.submit(req)

    def close(self) -> None:
        self.pump.close()


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

STATUS = ("unresolved", "ok", "shed", "error")


class Record:
    """What the window keeps of each response, taken as its future
    resolves: the time, the status, the session's wait and service
    stamps, whether a degraded flush served it (and whether on a smaller
    bucket), and the whole response only for the requests in `keep`, the
    candidates of the check. The generator drops each future at submit,
    as a client does once it has its answer: responses kept to the end of
    the window would pile up in the collector's oldest generation and
    make it walk the whole heap, a pause no server that answers and
    forgets has."""

    def __init__(self, n: int, keep):
        from repro.serving.session import DEGRADE_SHRINK_BUCKET
        self._shrink = DEGRADE_SHRINK_BUCKET
        self.n = n
        self.resolved = np.full(n, np.nan)
        self.status = np.zeros(n, np.int8)
        self.wait_ms = np.zeros(n)
        self.service_ms = np.zeros(n)
        self.degraded = np.zeros(n, bool)
        self.shrunk = np.zeros(n, bool)
        self.kept: dict[int, object] = {}
        self._keep = frozenset(int(i) for i in keep)
        self._count = itertools.count(1)
        self.all_done = threading.Event()

    def take(self, resp, at: float) -> None:
        i = resp.request_id
        if not 0 <= i < self.n:        # the warm-up's requests
            return
        self.resolved[i] = at
        self.status[i] = STATUS.index(resp.status)
        self.wait_ms[i] = resp.wait_ms
        self.service_ms[i] = resp.service_ms
        self.degraded[i] = bool(resp.degraded)
        self.shrunk[i] = self._shrink in resp.degraded
        if i in self._keep:
            self.kept[i] = resp
        if next(self._count) == self.n:
            self.all_done.set()


@contextlib.contextmanager
def recording(record: Record):
    """Hand every future's response to the record as it resolves, with
    the time on the monotonic clock, for the length of the block."""
    from repro.serving.session import RankFuture
    original = RankFuture._resolve

    def _resolve(self, resp):
        original(self, resp)
        record.take(resp, time.monotonic())

    RankFuture._resolve = _resolve
    try:
        yield record
    finally:
        RankFuture._resolve = original


@dataclasses.dataclass
class Window:
    t_start: float
    due: np.ndarray          # absolute due times (monotonic s)
    submitted: np.ndarray    # when each submit began
    record: Record
    compiles: int            # compilations inside the window
    gc: dict = dataclasses.field(default_factory=dict)  # GcClock.notes()


def run_window(server: Server, reqs: list, sch: Schedule, keep,
               annotate: bool) -> Window:
    """Offer reqs at their due times from one generator thread and wait
    for every response (up to RESULT_WAIT_S past the last due time)."""
    n = len(reqs)
    submitted = np.full(n, np.nan)
    record = Record(n, keep)
    span = benchlib.span if annotate else benchlib.no_span
    clock = benchlib.CompileClock()
    gc.collect()  # every window starts from the same collector state
    gc_clock = benchlib.GcClock()
    try:
        with recording(record):
            t_start = time.monotonic() + LEAD_S
            due = t_start + sch.offsets_s

            def generate():
                benchlib.name_this_thread("bench-gen")
                for i in range(n):
                    wait = due[i] - time.monotonic()
                    if wait > 0:
                        time.sleep(wait)
                    submitted[i] = time.monotonic()
                    with span("gen.submit"):
                        server.submit(reqs[i])

            gen = threading.Thread(target=generate, name="bench-gen")
            with span(benchlib.WINDOW_SPAN):
                time.sleep(max(0.0, t_start - time.monotonic()))
                gen.start()
                gen.join()
                record.all_done.wait(
                    max(0.0, due[-1] + RESULT_WAIT_S - time.monotonic()))
    finally:
        gc_clock.close()
        clock.close()
    return Window(t_start=t_start, due=due, submitted=submitted,
                  record=record, compiles=clock.compiles,
                  gc=gc_clock.notes())


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def _served(resp) -> dict:
    return {"scores": resp.scores, "survivors": resp.survivors,
            "order": resp.order, "stage_counts": resp.stage_counts}


def served_width(n_items: int, shrunk: bool, buckets) -> int:
    """How many of a request's n_items the server is due to score: all of
    them up to its bucket, or up to the next smaller bucket where a
    degraded flush shrank it (the response says so). Taken from the
    request, never from the response, so that a server that drops items
    unannounced is compared on the items it dropped too."""
    from repro.serving.batching import bucket_of
    g = bucket_of(n_items, buckets)
    if shrunk:
        g = buckets[max(0, buckets.index(g) - 1)]
    return min(n_items, g)


def reference_inputs(reqs: list, resps: list, config: dict):
    """The inputs each sampled request is due to be served from, as the
    reference takes them: the items of served_width and M_q as a
    tightened flush scaled it."""
    from repro.serving.session import DEGRADE_SHRINK_BUCKET, DEGRADE_TIGHTEN_MQ
    buckets = tuple(sorted(config["serving"]["group_buckets"]))
    g = buckets[-1]
    r = len(reqs)
    x = np.zeros((r, g, config["d_x"]), np.float32)
    q = np.zeros((r, config["d_q"]), np.float32)
    valid = np.zeros((r, g), np.float32)
    m_q = np.zeros((r,), np.float32)
    for i, (req, resp) in enumerate(zip(reqs, resps)):
        n = served_width(len(req.item_feats),
                         DEGRADE_SHRINK_BUCKET in resp.degraded, buckets)
        x[i, :n] = req.item_feats[:n]
        q[i] = req.q_feat
        valid[i, :n] = 1.0
        m_q[i] = req.m_q
        if DEGRADE_TIGHTEN_MQ in resp.degraded:
            m_q[i] = max(np.float32(m_q[i]) * np.float32(0.5), 1.0)
    return x, q, valid, m_q


def run_reference(params_host: dict, config: dict, x, q, valid, m_q,
                  precision: str, device) -> list[dict]:
    """reference.cascade_rank over blocks of REF_BLOCK requests, on the
    device, returned per request as numpy."""
    import jax
    masks = np.asarray(config["stage_masks"], np.float32)
    out = []
    for s in range(0, len(x), REF_BLOCK):
        sl = slice(s, s + REF_BLOCK)
        pad = REF_BLOCK - len(x[sl])
        blk = [np.pad(a[sl], [(0, pad)] + [(0, 0)] * (a.ndim - 1))
               for a in (x, q, valid, m_q)]
        res = reference.cascade_rank(
            *jax.device_put((params_host["w_x"], params_host["w_q"],
                             params_host["b"], masks, *blk), device),
            precision=precision)
        res = {k: np.asarray(v) for k, v in res.items()}
        for i in range(REF_BLOCK - pad):
            n = int(blk[2][i].sum())
            out.append({"lp": res["lp"][i, :n], "keep": res["keep"][i],
                        "survivors": res["survivors"][i, :n]})
    return out


WIDEST_KEPT = 8  # candidates kept per bucket for its widest request


def check_candidates(sch: Schedule, k: int, seed: int, buckets) -> np.ndarray:
    """The requests whose responses the window keeps for the check,
    chosen before it opens: k drawn from the seed, and the WIDEST_KEPT
    widest of every bucket the schedule fills, so that the widest of each
    is compared even where some of them are shed or served shrunk."""
    from repro.serving.batching import bucket_of
    n = len(sch.sizes)
    rng = np.random.default_rng([*benchlib.split_seed(seed), 1])
    pick = set(rng.choice(n, size=min(k, n), replace=False).tolist())
    by_bucket: dict[int, list[int]] = {}
    for i in np.argsort(-sch.sizes, kind="stable").tolist():
        lst = by_bucket.setdefault(bucket_of(int(sch.sizes[i]), buckets), [])
        if len(lst) < WIDEST_KEPT:
            lst.append(i)
    return np.array(sorted(pick.union(*by_bucket.values())), np.int64)


def sample_for_check(win: Window) -> np.ndarray:
    """The candidates of the check that completed "ok": the seed's draw,
    with the widest requests of every bucket among them."""
    rec = win.record
    return np.array([i for i in sorted(rec.kept)
                     if rec.status[i] == STATUS.index("ok")], np.int64)


def outcome(win: Window, limit_ms: float, seconds: float) -> dict:
    """End-to-end numbers of a window. Each request is timed from its due
    time to its future's resolution; p50 and p99 are over the requests
    that completed "ok"; goodput counts those within limit_ms, over the
    window's seconds. Shed, errored and unresolved requests count as
    failed and are in no percentile."""
    status = win.record.status
    ok = status == STATUS.index("ok")
    lat_ms = (win.record.resolved - win.due) * 1e3
    return {"ok": ok,
            "p50_ms": benchlib.percentile(lat_ms[ok], 50),
            "p99_ms": benchlib.percentile(lat_ms[ok], 99),
            "goodput": float((ok & (lat_ms <= limit_ms)).sum()) / seconds,
            "shed": int((status == STATUS.index("shed")).sum()),
            "errors": int((status == STATUS.index("error")).sum()),
            "unresolved": int((status == STATUS.index("unresolved")).sum())}


def stall_notes(win: Window) -> dict:
    """Where the window's stalls were: the generator's worst lateness,
    how many submits were over 10 ms late, and when shedding began and
    ended (seconds into the window)."""
    late = (win.submitted - win.due) * 1e3
    shed = np.flatnonzero(win.record.status == STATUS.index("shed"))
    off = win.due - win.t_start
    return {"late_max_ms": float(np.nanmax(late)) if len(late) else 0.0,
            "late_over_10ms": int((late > 10.0).sum()),
            "shed_from_s": float(off[shed[0]]) if len(shed) else None,
            "shed_to_s": float(off[shed[-1]]) if len(shed) else None}


def run(cell, seed: int, seconds: float, trace: bool, t0: float, devs,
        peaks: dict, control: bool = False) -> dict:
    """One run of a serving cell. With control, the same sample is also
    ranked by the control (the reference in bfloat16, put in the
    program's place) and compared in the same way, under
    "control_readings"."""
    config, traffic = cell.config, cell.traffic
    buckets = tuple(sorted(config["serving"]["group_buckets"]))
    phases = benchlib.Phases(t0)
    phases.mark("init")
    log = make_log(config, seed)
    phases.mark("log")
    params = system.make_weights(config, seed, config["serve_weight_std"],
                                 devs[0])
    params_host = {k: np.asarray(v) for k, v in params.items()}
    sch = schedule(traffic, seconds, seed, log)
    reqs = build_requests(log, sch)
    phases.mark("requests")
    server = Server(config, params, devs)
    server.warm(warm_buckets(config, traffic), log)
    phases.mark("warm")
    server.start()
    keep = check_candidates(sch, int(traffic["sample"]), seed, buckets)
    tracer = benchlib.Tracer(trace)
    try:
        tracer.start()
        phases.mark("start")
        win = run_window(server, reqs, sch, keep, annotate=trace)
        summary = tracer.stop()
    finally:
        tracer.close()
        memory = benchlib.memory_peak_bytes(devs)
        server.close()
    del server, params

    res = outcome(win, float(config["latency_limit_ms"]), seconds)
    ok = res["ok"]
    rec = win.record

    pick = sample_for_check(win)
    resps = [rec.kept[i] for i in pick]
    x, q, valid, m_q = reference_inputs([reqs[i] for i in pick], resps,
                                        config)
    refs = run_reference(params_host, config, x, q, valid, m_q, "highest",
                         devs[0])
    limits = dict(config["limits"]["serve"])
    tol = float(limits["score_err"])
    pairs = [(_served(r), ref) for r, ref in zip(resps, refs)]
    readings = checks.serving_readings(pairs, tol)
    readings["unresolved"] = res["unresolved"]
    correct, shown = checks.judge(readings, limits)
    correct = correct and readings["compared"] > 0

    served_idx = np.flatnonzero(ok)
    facts = {
        "kind": "serve",
        "peaks": peaks,
        "trace": summary,
        "gen_late_ms": (win.submitted - win.due) * 1e3,
        "wait_ms": rec.wait_ms[ok],
        "service_ms": rec.service_ms[ok],
        "work": workcount.serve_work(
            [served_width(int(sch.sizes[i]), bool(rec.shrunk[i]), buckets)
             for i in served_idx],
            config["d_x"], config["d_q"], config["n_stages"]),
    }
    e2e = {"p50_ms": res["p50_ms"],
           "goodput": res["goodput"], "setup_s": win.t_start - t0}
    notes = {
        "requests": len(reqs), "ok": len(served_idx), "shed": res["shed"],
        "errors": res["errors"], "unresolved": res["unresolved"],
        "p99_ms": res["p99_ms"], "goodput": res["goodput"],
        "degraded": int(rec.degraded[ok].sum()),
        "compiles_in_window": win.compiles,
        **win.gc,
        "gen_late_p99_ms": benchlib.percentile(facts["gen_late_ms"], 99),
        "offered_per_s": len(reqs) / seconds,
        "near_ties": readings["near_ties"], "compared": readings["compared"],
        "setup_phases_s": phases.laps,
        **stall_notes(win),
    }
    out = {"correct": correct, "attempted": len(reqs),
           "failed": len(reqs) - len(served_idx), "e2e": e2e,
           "facts": facts, "memory": memory, "checks": shown,
           "notes": notes, "readings": readings}
    if control:
        ctl = run_reference(params_host, config, x, q, valid, m_q, "bf16",
                            devs[0])
        out["control_readings"] = checks.serving_readings(
            [(checks.as_served(c, len(c["lp"])), ref)
             for c, ref in zip(ctl, refs)], tol)
    return out
