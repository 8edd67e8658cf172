"""Serving launcher: drive the streaming CascadeSession over an OPEN-LOOP
synthetic request stream (Poisson arrivals at a fixed offered rate, per-
request deadlines, bounded admission with load-shedding and degraded
modes) and report the request-lifecycle outcome: shed / degraded /
deadline-miss fractions and end-to-end latency percentiles.

Two clocks, one lifecycle:
  * default: the virtual-clock DES (loadgen.run_open_loop) — arrivals and
    flush policy on a simulated millisecond clock, service times real
    measured compute; deterministic given a host.
  * --pump: WALL-CLOCK mode — a live SessionPump background thread with N
    concurrent submitter threads blocking on their futures; real time
    drives everything. The soak contract: zero unresolved futures across
    pump shutdown.

--spans records the spans of every flush (serving/spans.py: claim, pack,
dispatch, fetch, resolve, and the pump's whole cycle) and reports each
one's p50/p99 per flush, with the pumps' flush fill (served rows over
padded rows), item fill (the requests' items over padded items) and the
host-to-device bytes per real item (h2d_bytes over items_real).

Request generation is timed SEPARATELY from the serve phase — the old
closed-loop launcher started its clock before the submit loop, charging
request construction to the server's QPS.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --requests 500 --qps 400 \
      [--pump [--threads 4]] [--deadline-ms 130] [--max-queue 128] \
      [--neural ARCH] [--spans] [--report BENCH_serve.json]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as CFG
from repro.checkpoint import load_pytree, save_pytree
from repro.core import baselines as B
from repro.core import cascade as C
from repro.core import losses as L
from repro.core import trainer as T
from repro.data import LogConfig, generate_log
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import replica_devices
from repro.serving.batching import RankRequest
from repro.serving.cascade_server import NeuralScorer
from repro.serving.faults import FaultConfig, FaultInjector
from repro.serving.loadgen import run_open_loop, run_open_loop_router
from repro.serving.pump import SessionPump, run_wall_clock
from repro.serving.router import ReplicaRouter, RouterConfig, make_replicas
from repro.serving.session import (CascadeSession, DegradePolicy,
                                   FlushPolicy, ServingConfig)
from repro.serving.spans import SpanRecorder
from repro.serving.spans import report as span_report


def build_serving_config(*, plan="filter", max_queue=128,
                         max_wait_ms=5.0) -> ServingConfig:
    """The launcher's serving profile: bounded queue with load-shedding,
    degradation watermarks derived from the queue bound (enter at 3/4
    capacity, exit at 1/4 — the hysteresis band). Under a router the same
    bound and watermarks apply to the GLOBAL depth — one admission
    controller over the fleet."""
    degrade = (DegradePolicy(high_watermark=max(1, (3 * max_queue) // 4),
                             low_watermark=max_queue // 4)
               if max_queue else DegradePolicy(high_watermark=None))
    return ServingConfig(plan=plan,
                         max_queue=max_queue or None,
                         flush=FlushPolicy(max_wait_ms=max_wait_ms),
                         degrade=degrade)


def build_session(params, cfg, lcfg=None, *, neural=None, plan="filter",
                  max_queue=128, max_wait_ms=5.0,
                  faults=None) -> CascadeSession:
    return CascadeSession(
        params, cfg, lcfg, neural_stage=neural, faults=faults,
        scfg=build_serving_config(plan=plan, max_queue=max_queue,
                                  max_wait_ms=max_wait_ms))


def build_router(params, cfg, lcfg=None, *, n, neural=None, plan="filter",
                 max_queue=128, max_wait_ms=5.0, fault_rate=0.0,
                 kill_replica=False, seed=0) -> ReplicaRouter:
    """N replicas behind one admission point, each pinned to a device of
    the local fleet (round-robin; on a one-device box they co-locate and
    share a warmed jit cache). --faults gives every replica its own
    seeded injector (seed+k: independent fault streams, reproducible);
    --kill-replica gives replica 0 an always-failing executor instead, so
    the chaos smoke exercises breaker-open failover: its backlog must
    drain to survivors and the run must still exit zero."""
    scfg = build_serving_config(plan=plan, max_queue=max_queue,
                                max_wait_ms=max_wait_ms)
    faults: list[FaultInjector | None] | None = None
    if kill_replica:
        faults = [FaultInjector(FaultConfig(transient_rate=1.0,
                                            seed=seed))]
        faults += [build_injector(fault_rate, seed + 1 + k)
                   for k in range(n - 1)]
    elif fault_rate > 0:
        faults = [build_injector(fault_rate, seed + k) for k in range(n)]
    return ReplicaRouter(
        make_replicas(params, cfg, lcfg, n, neural_stage=neural,
                      scfg=scfg, faults=faults,
                      devices=replica_devices(n)),
        RouterConfig())


def compiled_count(sessions) -> int:
    """Total jit-cache entries across the fleet's distinct pipelines
    (co-located replicas share compilations — count each function once).
    The delta of this across the serve phase is the recompile count the
    warm-restart contract pins to zero."""
    fns = {}
    for s in sessions:
        fns[id(s._rank)] = s._rank
        fns[id(s._rank_noneural)] = s._rank_noneural
    return sum(f._cache_size() for f in fns.values())


def flush_fill(pump_stats: list[dict]) -> float | None:
    """Percent of the pumps' padded batch rows that carried a request
    (None without a pump: the DES counts no padded rows)."""
    rows = sum(p["rows_padded"] for p in pump_stats)
    if not rows:
        return None
    return 100.0 * sum(p["served"] for p in pump_stats) / rows


def item_fill(pump_stats: list[dict]) -> float | None:
    """Percent of the pumps' padded items (rows x bucket width) that were
    a request's own: the padding inside each row, beside flush_fill's
    padded rows (None without a pump)."""
    items = sum(p["items_padded"] for p in pump_stats)
    if not items:
        return None
    return 100.0 * sum(p["items_real"] for p in pump_stats) / items


def h2d_bytes_per_item(pump_stats: list[dict]) -> float | None:
    """Staging bytes copied to the device per real item: what a flush
    moves for each item it serves, its padding included (None without a
    pump)."""
    items = sum(p["items_real"] for p in pump_stats)
    if not items:
        return None
    return sum(p["h2d_bytes"] for p in pump_stats) / items


def save_serving_state(serve_dir: str, ses: CascadeSession) -> None:
    """The graceful-shutdown write: everything a restarted server needs to
    serve its first request with zero recompiles — params, the configs
    that rebuild the session, and the warmup manifest (also mirrored as
    plain JSON for humans/CI artifacts). Crash-safe via save_pytree."""
    manifest = ses.warmup_manifest()
    cfg = ses.cfg
    save_pytree(Path(serve_dir) / "serve_state", {
        "params": jax.device_get(ses.params),
        "cfg": {"n_stages": cfg.n_stages, "d_x": cfg.d_x, "d_q": cfg.d_q,
                "masks": cfg.masks, "stage_times": cfg.stage_times},
        "lcfg": dataclasses.asdict(ses.lcfg),
        "manifest": manifest,
    })
    with open(Path(serve_dir) / "warmup_manifest.json", "w") as f:
        json.dump(manifest, f, indent=2)


def load_serving_state(serve_dir: str):
    """Restore what save_serving_state wrote (verified: a torn/corrupt
    state raises instead of warm-starting a wrong server).
    Returns (params, CascadeConfig, LossConfig, warmup manifest)."""
    state = load_pytree(Path(serve_dir) / "serve_state")
    cfg = C.CascadeConfig(**state["cfg"])
    lcfg = L.LossConfig(**state["lcfg"])
    return state["params"], cfg, lcfg, state["manifest"]


def build_injector(rate: float, seed: int) -> FaultInjector | None:
    """Chaos profile for --faults RATE: transients at the full rate,
    latency spikes and score corruption at half, poison at a quarter —
    one knob that exercises every fault class, seeded so a DES chaos run
    replays deterministically."""
    if rate <= 0:
        return None
    return FaultInjector(FaultConfig(
        transient_rate=rate, latency_rate=rate / 2,
        latency_spike_ms=5.0, corrupt_rate=rate / 2,
        poison_rate=rate / 4, seed=seed))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=500)
    ap.add_argument("--qps", type=float, default=400.0,
                    help="offered load (Poisson arrival rate)")
    ap.add_argument("--deadline-ms", type=float, default=130.0,
                    help="per-request deadline budget (0 = no deadlines)")
    ap.add_argument("--max-queue", type=int, default=128,
                    help="admission bound (0 = unbounded, never sheds)")
    ap.add_argument("--max-wait-ms", type=float, default=5.0)
    ap.add_argument("--pump", action="store_true",
                    help="wall-clock mode: live SessionPump + concurrent "
                         "submitter threads (default: virtual-clock DES)")
    ap.add_argument("--threads", type=int, default=4,
                    help="submitter threads in --pump mode")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through a ReplicaRouter over N replica "
                         "sessions (1 = the single-session path)")
    ap.add_argument("--kill-replica", action="store_true",
                    help="chaos smoke: replica 0's executor always fails "
                         "— the router must fail it over and the run "
                         "must still exit zero (requires --replicas > 1)")
    ap.add_argument("--faults", type=float, default=0.0,
                    help="chaos mode: injected-fault rate (transient "
                         "exceptions, latency spikes, NaN corruption, "
                         "poison requests; 0 = off)")
    ap.add_argument("--plan", default="filter",
                    help="pipeline plan (core.pipeline.PLANS entry)")
    ap.add_argument("--neural", default="",
                    help="arch id for the neural final stage (smoke variant)")
    ap.add_argument("--beta", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--spans", action="store_true",
                    help="record each flush's spans and report their "
                         "p50/p99, the flush fill and the item fill")
    ap.add_argument("--report", default="",
                    help="write the latency/lifecycle report as JSON here")
    ap.add_argument("--serve-dir", default="",
                    help="durable serving state: graceful shutdown drains "
                         "the pumps then writes params + warmup manifest "
                         "here (crash-safe)")
    ap.add_argument("--warm-restart", action="store_true",
                    help="restore params from --serve-dir and replay its "
                         "warmup manifest instead of training — the first "
                         "live request must hit zero recompiles (enforced)")
    args = ap.parse_args()
    enable_compile_cache()

    serve_dir = args.serve_dir or None
    if args.warm_restart and not serve_dir:
        raise SystemExit("[serve] --warm-restart requires --serve-dir")
    if serve_dir and args.neural:
        raise SystemExit("[serve] --serve-dir persists the cascade params "
                         "only — the neural stage's weights are not "
                         "durable state; drop --neural")

    log = generate_log(LogConfig(n_queries=800, seed=args.seed))
    tr, te = log.split(0.8)
    lcfg = None             # session default unless a restore overrides it
    if args.warm_restart:
        t0 = time.perf_counter()
        params, cfg, lcfg, manifest = load_serving_state(serve_dir)
        train_s = time.perf_counter() - t0
        print(f"[serve] warm restart from {serve_dir}: restored params + "
              f"manifest ({len(manifest['shapes'])} shapes) in "
              f"{train_s:.2f}s, no training")
    else:
        manifest = None
        print("[serve] training cascade...")
        t0 = time.perf_counter()
        params, cfg = B.fit_cloes(tr, lcfg=L.LossConfig(beta=args.beta),
                                  tcfg=T.TrainConfig(loss="l3", epochs=4,
                                                     lr=0.01))
        train_s = time.perf_counter() - t0
    neural = None
    if args.neural:
        ncfg = dataclasses.replace(CFG.get_smoke(args.neural),
                                   dtype=jnp.float32)
        neural = NeuralScorer.create(ncfg, jax.random.PRNGKey(7))
        print(f"[serve] neural final stage: {ncfg.name}")
    if args.kill_replica and args.replicas < 2:
        raise SystemExit("[serve] --kill-replica needs --replicas >= 2 "
                         "(a survivor must exist to absorb the backlog)")
    router = None
    if args.replicas > 1:
        if args.faults > 0 or args.kill_replica:
            print(f"[serve] CHAOS MODE: rate {args.faults}"
                  + (", replica 0 FORCED DEAD" if args.kill_replica else "")
                  + f" (seed {args.seed})")
        router = build_router(params, cfg, lcfg, n=args.replicas,
                              neural=neural,
                              plan=args.plan, max_queue=args.max_queue,
                              max_wait_ms=args.max_wait_ms,
                              fault_rate=args.faults,
                              kill_replica=args.kill_replica,
                              seed=args.seed)
        ses = router.replicas[0]
        sessions = router.replicas
        t0 = time.perf_counter()
        if manifest is not None:
            # warm restart: replay the restored manifest on every replica
            # (co-located replicas share one jit cache — cache hits)
            for r in router.replicas:
                shapes = r.warm_restart(manifest)
        else:
            shapes = router.warmup()
        warmup_s = time.perf_counter() - t0
        print(f"[serve] warmed {len(shapes)} shape buckets across "
              f"{args.replicas} replicas in {warmup_s:.1f}s "
              "(co-located replicas share one jit cache)")
    else:
        injector = build_injector(args.faults, args.seed)
        if injector is not None:
            print(f"[serve] CHAOS MODE: fault injection at rate "
                  f"{args.faults} (seed {args.seed})")
        ses = build_session(params, cfg, lcfg, neural=neural, plan=args.plan,
                            max_queue=args.max_queue,
                            max_wait_ms=args.max_wait_ms, faults=injector)
        sessions = [ses]
        t0 = time.perf_counter()
        shapes = (ses.warm_restart(manifest) if manifest is not None
                  else ses.warmup())
        warmup_s = time.perf_counter() - t0
        print(f"[serve] warmed {len(shapes)} shape buckets in "
              f"{warmup_s:.1f}s")
    compiled_after_warmup = compiled_count(sessions)
    if args.spans:
        for s in sessions:
            s.spans = SpanRecorder(enabled=True)

    # -- request generation, timed on its own (NOT charged to the server) --
    rng = np.random.default_rng(args.seed)
    n_te = te.x.shape[0]
    t0 = time.perf_counter()
    reqs = []
    for i in range(args.requests):
        qi = int(rng.integers(0, n_te))
        n_items = int(rng.integers(8, 64))
        reqs.append(RankRequest(
            request_id=i, q_feat=te.q[qi].astype(np.float32),
            item_feats=te.x[qi, :n_items].astype(np.float32),
            m_q=int(te.m_q[qi])))
    gen_s = time.perf_counter() - t0
    if not reqs:
        print("[serve] no requests submitted — nothing to report")
        return
    print(f"[serve] generated {len(reqs)} requests in {gen_s:.2f}s "
          f"({len(reqs)/max(gen_s, 1e-9):.0f} req/s generation rate)")

    # -- the serve phase: wall-clock pump or virtual-clock DES -------------
    deadline = args.deadline_ms if args.deadline_ms > 0 else None
    pump_stats = None
    router_stats = None
    if args.pump and router is not None:
        pumps = [SessionPump(s, name=f"pump-{s.name}").start()
                 for s in router.replicas]
        router.attach_pumps(pumps)
        res = run_wall_clock(router, reqs, args.qps, deadline_ms=deadline,
                             n_threads=args.threads, seed=args.seed)
        # graceful shutdown (--serve-dir): drain the queues so every
        # future resolves with a real result before state is persisted
        router.close(drain=bool(serve_dir))
        router_stats = router.stats_export()
        unresolved_after_close = sum(1 for f in res.futures if not f.done())
        print(f"[serve] router pump mode: offered {res.offered_qps:.0f} "
              f"QPS from {args.threads} threads over {args.replicas} "
              f"replicas; served {res.completed}/{res.n_requests} in "
              f"{res.wall_s:.2f}s wall ({res.achieved_qps:.0f} QPS)")
        serve_s = res.wall_s
    elif args.pump:
        pump = SessionPump(ses).start()
        res = run_wall_clock(pump, reqs, args.qps, deadline_ms=deadline,
                             n_threads=args.threads, seed=args.seed)
        pump.close(drain=bool(serve_dir))
        pump_stats = pump.stats_export()
        unresolved_after_close = sum(1 for f in res.futures if not f.done())
        print(f"[serve] pump mode: offered {res.offered_qps:.0f} QPS from "
              f"{args.threads} threads; served {res.completed}/"
              f"{res.n_requests} in {res.wall_s:.2f}s wall "
              f"({res.achieved_qps:.0f} QPS achieved)")
        print(f"[serve] pump stats: {pump_stats}")
        serve_s = res.wall_s
    elif router is not None:
        res = run_open_loop_router(router, reqs, args.qps,
                                   deadline_ms=deadline, seed=args.seed)
        router.close(drain=bool(serve_dir))
        router_stats = router.stats_export()
        unresolved_after_close = res.unresolved
        print(f"[serve] router DES: offered {res.offered_qps:.0f} QPS over "
              f"{args.replicas} replicas; served {res.completed}/"
              f"{res.n_requests} over {res.sim_s:.2f}s simulated "
              f"({res.achieved_qps:.0f} QPS achieved, {res.serve_s:.2f}s "
              "compute)")
        serve_s = res.serve_s
    else:
        res = run_open_loop(ses, reqs, args.qps, deadline_ms=deadline,
                            seed=args.seed)
        unresolved_after_close = res.unresolved
        print(f"[serve] offered {res.offered_qps:.0f} QPS; served "
              f"{res.completed}/{res.n_requests} over {res.sim_s:.2f}s "
              f"simulated ({res.achieved_qps:.0f} QPS achieved, "
              f"{res.serve_s:.2f}s compute)")
        serve_s = res.serve_s
    print(f"[serve] shed {res.shed} ({100*res.shed_frac:.1f}%), errors "
          f"{res.errors}, degraded {res.degraded}, deadline-missed "
          f"{res.deadline_missed}, truncated {res.truncated}")
    if len(res.latency_ms):
        print(f"[serve] end-to-end latency: p50 {res.pct(50):.1f}ms "
              f"p95 {res.pct(95):.1f}ms p99 {res.pct(99):.1f}ms")
    if router_stats is not None:
        print(f"[serve] router stats: "
              f"{ {k: router_stats[k] for k in ('routed', 'failovers', 'drained', 'probes', 'recoveries', 'failed')} }")
        session_stats = router_stats["global"]
    else:
        # snapshot taken inside stats_export under the session lock —
        # a still-live pump thread cannot tear the counters mid-read
        session_stats = ses.stats_export()
    print(f"[serve] session stats: {session_stats}")
    spans = None
    if args.spans:
        spans = span_report([s.spans for s in sessions])
        pumped = ([pump_stats] if pump_stats is not None
                  else router_stats["replicas"] if args.pump and router_stats
                  else [])
        spans["flush_fill"] = flush_fill(pumped)
        spans["item_fill"] = item_fill(pumped)
        spans["h2d_bytes_per_item"] = h2d_bytes_per_item(pumped)
        for name, row in spans["spans"].items():
            print(f"[serve] span {name}: p50 {row['p50_ms']:.3f}ms p99 "
                  f"{row['p99_ms']:.3f}ms over {row['flushes']} flushes")
        fill, items = spans["flush_fill"], spans["item_fill"]
        h2d = spans["h2d_bytes_per_item"]
        print("[serve] flush fill: "
              + (f"{fill:.1f}% of padded rows served" if fill is not None
                 else "not counted (no pump)")
              + f"; spans dropped {spans['dropped']}")
        print("[serve] item fill: "
              + (f"{items:.1f}% of padded items a request's own"
                 if items is not None else "not counted (no pump)")
              + "; host-to-device: "
              + (f"{h2d:.1f} bytes per real item" if h2d is not None
                 else "not counted (no pump)"))

    if res.unresolved or unresolved_after_close:
        raise SystemExit(
            f"[serve] FAIL: {max(res.unresolved, unresolved_after_close)} "
            "futures never resolved — every submitted request must come "
            "back with an explicit status")
    st = session_stats
    # GLOBAL accounting identity: over the whole fleet (or the single
    # session), every admitted request ends in exactly one terminal state.
    # Work drained off a dead replica completes on a survivor, so the
    # drained/adopted legs cancel in the aggregate.
    if st["submitted"] != st["completed"] + st["shed"] + st["errors"]:
        raise SystemExit(
            f"[serve] FAIL: lifecycle accounting does not close — "
            f"submitted {st['submitted']} != completed {st['completed']} "
            f"+ shed {st['shed']} + errors {st['errors']}")
    print("[serve] all futures resolved (zero dropped; "
          "submitted = completed + shed + errors"
          + (" globally across replicas)" if router_stats else ")"))
    # Only the chaos legs may end in errors: without injected faults an
    # error is a real executor failure (a Mosaic compile error, a device
    # fault on a shape warmup missed), and exit 0 would hide it.
    if st["errors"] and not (args.faults > 0 or args.kill_replica):
        raise SystemExit(
            f"[serve] FAIL: {st['errors']} request(s) ended in status "
            "'error' with no faults injected")

    # The warm-restart contract: every compilation the serve phase needed
    # existed before the first live request. Measured as the jit-cache
    # delta across the serve phase; a normal (cold-warmup) run reports
    # the same number, the warm-restart path HARD-FAILS on it.
    recompiles = compiled_count(sessions) - compiled_after_warmup
    print(f"[serve] recompiles after warmup: {recompiles}")
    if args.warm_restart and recompiles:
        raise SystemExit(
            f"[serve] FAIL: warm restart promised zero recompiles but the "
            f"serve phase compiled {recompiles} new pipeline shape(s)")

    if serve_dir:
        save_serving_state(serve_dir, ses)
        print(f"[serve] graceful shutdown: wrote serving state "
              f"(params + warmup manifest) to {serve_dir}")

    if args.report:
        report = {
            "config": {"requests": args.requests, "offered_qps": args.qps,
                       "deadline_ms": args.deadline_ms,
                       "max_queue": args.max_queue, "plan": args.plan,
                       "neural": args.neural or None, "seed": args.seed,
                       "faults": args.faults,
                       "replicas": args.replicas,
                       "kill_replica": args.kill_replica,
                       "mode": "pump" if args.pump else "des",
                       "threads": args.threads if args.pump else None,
                       "serve_dir": serve_dir,
                       "warm_restart": args.warm_restart,
                       "backend": jax.default_backend()},
            "recompiles_after_warmup": recompiles,
            "phases_s": {"train": train_s, "warmup": warmup_s,
                         "generate": gen_s, "serve": serve_s},
            "generation_rate_rps": len(reqs) / max(gen_s, 1e-9),
            ("wall_clock" if args.pump else "open_loop"): res.summary(),
            "session_stats": session_stats,
        }
        if pump_stats is not None:
            report["pump_stats"] = pump_stats
        if router_stats is not None:
            report["router_stats"] = router_stats
        if spans is not None:
            report["spans"] = spans
        with open(args.report, "w") as f:
            json.dump(report, f, indent=2)
        print(f"[serve] wrote {args.report}")


if __name__ == "__main__":
    main()
