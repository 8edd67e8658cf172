"""Prove that the cascade serves and trains on a TPU.

Drives CLOES (src/repro/configs/cloes.py: 3 stages, d_x = 24, LOSS with
beta 5) once through the functions the launchers call, on a log made by
generate_log from --seed, and checks what comes out against the XLA
reference run at highest matmul precision. Phases:

  device   fail unless jax.devices()[0].platform == "tpu"
  train    a few L3 epochs through fit_cloes: the loss is finite and
           falls, and the compiled training step holds the fused loss
           kernel (a tpu_custom_call), not its XLA reference
  serve    plan "filter": warm all 18 shapes, serve requests in every
           bucket (16/64/256 items) through run_open_loop; every one comes
           back "ok" with no errors, faults or retries and no recompile
           after warmup. Plan "score" once as well
  compare  served lp and survivors against run_cascade(fused="none"),
           and the fused loss value and gradients against
           cascade_loss_ref

--four-chips runs only the paths that span chips, each with what it is
compared with: a 4-replica ReplicaRouter (one replica per chip) against
one session on chip 0, and the 4-way shard_map data-parallel fit against
the 1-device fit.

Numbers go on earlier lines. Any failure exits non-zero without the ok
line; the last line of stdout is, on success, exactly
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.

Usage, from the repo root:  python chip_smoke.py [--four-chips] [--seed N]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# lp (cumulative log pass-probability) tolerance against the reference.
# Both sides score in f32 at HIGHEST matmul precision, so they differ by
# summation order and by the chip's exp/log approximations: a few ulp of
# |logit| <= ~30, i.e. ~1e-5. A bf16 path (DEFAULT precision rounds the
# features or weights to 8 mantissa bits) errs by ~1e-2 and must fail.
LP_ATOL = 1e-4
LP_RTOL = 1e-5
# Fused L3 partials and their gradients against cascade_loss_ref, as a
# norm-wise relative error. The reference works in probability space and
# the kernel in log space, and both sum 64 x 256 items in different
# orders; on the CPU the two agree to ~1e-6.
LOSS_RTOL = 1e-4
# The data-parallel fit normalizes each shard's loss over its own slice of
# the minibatch (core/trainer.py fit), so its losses differ from the
# 1-device fit's by that approximation, not by rounding. On 4 virtual CPU
# devices, same data and seed: 1.2e-3 at step 0 and 6e-3 on the last
# epoch's mean; the limits leave ~10x for the chip's own rounding.
DP_STEP0_RTOL = 0.02
DP_FINAL_RTOL = 0.05

KERNEL_OP = "tpu_custom_call"


class SmokeFailure(RuntimeError):
    pass


def fail(msg: str):
    raise SmokeFailure(msg)


@dataclasses.dataclass(frozen=True)
class Size:
    """The smoke's data and run size. The default is the serve launcher's
    log (800 queries) with groups as wide as the largest bucket."""
    n_queries: int = 800
    items_per_query: int = 256
    epochs: int = 4
    batch_groups: int = 64
    n_requests: int = 96
    qps: float = 400.0


class CompileClock:
    """Sums the time JAX spends in backend compilation (a persistent-cache
    hit takes the place of a compile and is counted apart)."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self._event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == self._event:
            self.seconds += duration
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


def require_tpu() -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"[smoke] device platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        fail(f"JAX found no TPU: platform is {dev['platform']!r}")
    return dev


def check_kernel(compiled_text: str, what: str) -> None:
    """The compiled program must hold a Pallas kernel, not its reference."""
    if KERNEL_OP not in compiled_text:
        fail(f"{what}: no {KERNEL_OP} in the compiled program")
    print(f"[smoke] {what}: {KERNEL_OP} present", flush=True)


def make_log(size: Size, seed: int):
    from repro.data import LogConfig, generate_log
    return generate_log(LogConfig(n_queries=size.n_queries,
                                  items_per_query=size.items_per_query,
                                  seed=seed))


def make_requests(te, size: Size, seed: int, buckets) -> list:
    """Requests cycling through the buckets, each drawing its item count
    within its bucket and its items from a test query that has that many
    valid items (the widest one when none has)."""
    from repro.serving.batching import RankRequest
    rng = np.random.default_rng(seed)
    n_valid = te.mask.sum(axis=1).astype(int)
    lows = (1,) + tuple(b + 1 for b in buckets[:-1])
    reqs = []
    for i in range(size.n_requests):
        lo, hi = lows[i % len(buckets)], buckets[i % len(buckets)]
        n_items = int(rng.integers(lo, hi + 1))
        fits = np.flatnonzero(n_valid >= n_items)
        qi = (int(rng.choice(fits)) if len(fits)
              else int(np.argmax(n_valid)))
        reqs.append(RankRequest(
            request_id=i, q_feat=te.q[qi].astype(np.float32),
            item_feats=te.x[qi, :n_items].astype(np.float32),
            m_q=int(te.m_q[qi])))
    return reqs


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def phase_train(tr, size: Size, seed: int, *, mesh=None, label="train"):
    """Fit through fit_cloes; returns (params, cfg, lcfg, per-step losses)."""
    from repro.core import baselines as B
    from repro.core import losses as L
    from repro.core import trainer as T
    lcfg = L.LossConfig(beta=5.0)
    tcfg = T.TrainConfig(loss="l3", epochs=size.epochs, lr=0.01,
                         batch_groups=size.batch_groups, seed=seed,
                         log_every=1)
    losses: list[float] = []
    t0 = time.perf_counter()
    params, cfg = B.fit_cloes(tr, lcfg=lcfg, tcfg=tcfg, mesh=mesh,
                              callback=lambda step, loss: losses.append(loss))
    fit_s = time.perf_counter() - t0
    steps, _ = T.epoch_steps(tr.x.shape[0], size.batch_groups)
    first, last = np.mean(losses[:steps]), np.mean(losses[-steps:])
    print(f"[smoke] {label}: {size.epochs} epochs x {steps} steps of "
          f"{size.batch_groups} groups in {fit_s:.3f}s; mean loss first "
          f"epoch {first:.6f} last epoch {last:.6f}", flush=True)
    if not np.isfinite(losses).all():
        fail(f"{label}: non-finite loss")
    if not last < first:
        fail(f"{label}: loss did not fall ({first} -> {last})")
    return params, cfg, lcfg, losses


def check_train_kernel(params, cfg, lcfg, tr, size: Size, seed: int) -> None:
    """The training step compiled on this device holds the fused loss."""
    from repro.core import trainer as T
    from repro.optim.sgd import momentum_sgd
    tcfg = T.TrainConfig()
    opt = momentum_sgd(tcfg.lr, tcfg.momentum)
    batch = next(T.batches(tr, size.batch_groups, seed))
    text = T.train_step.lower(params, opt.init(params), batch, cfg, lcfg,
                              "l3", opt.update).compile().as_text()
    check_kernel(text, "train step (fused L3 loss)")


def compare_loss(params, cfg, lcfg, tr, size: Size, seed: int) -> None:
    """cascade_loss_fused against cascade_loss_ref on one engine batch:
    the three partials and the gradients of a random functional of them,
    as norm-wise relative errors."""
    import jax
    import jax.numpy as jnp
    from repro.core import trainer as T
    from repro.kernels import ops as K
    item, group = T._engine_pack(tr, lcfg)
    xc = item[:size.batch_groups]
    q = group[:size.batch_groups, :cfg.d_q]
    rng = np.random.default_rng(seed)
    with jax.default_matmul_precision("highest"):
        w_eff = params["w_x"] * jnp.asarray(cfg.masks, jnp.float32)
        zq = q @ params["w_q"].T + params["b"]
        outs = K.cascade_loss_ref(xc, w_eff, zq, zq)
        cots = [jnp.asarray(rng.normal(size=o.shape), jnp.float32)
                for o in outs]

        def functional(fn):
            def f(w, z, zp):
                return sum((o * c).sum() for o, c in zip(fn(xc, w, z, zp),
                                                         cots))
            return jax.jit(jax.grad(f, (0, 1, 2)))

        got = jax.jit(K.cascade_loss_fused)(xc, w_eff, zq, zq)
        g_got = functional(K.cascade_loss_fused)(w_eff, zq, zq)
        g_ref = functional(K.cascade_loss_ref)(w_eff, zq, zq)
    names = ["ll", "cost_pp", "cnt_pp", "d w_eff", "d zq", "d zq_pen"]
    errs = {}
    for name, a, b in zip(names, list(got) + list(g_got),
                          list(outs) + list(g_ref)):
        a, b = np.asarray(a), np.asarray(b)
        errs[name] = float(np.abs(a - b).max()
                           / max(np.abs(b).max(), np.finfo(np.float32).tiny))
    print("[smoke] compare loss: fused vs ref at highest precision, "
          "norm-wise relative error "
          + " ".join(f"{k}={v:.3e}" for k, v in errs.items())
          + f" (limit {LOSS_RTOL})", flush=True)
    if not max(errs.values()) <= LOSS_RTOL:
        fail(f"fused loss differs from the reference: {errs}")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def phase_serve(params, cfg, lcfg, reqs, plan: str, size: Size, seed: int):
    """Warm a session, serve `reqs` open-loop, hold it to the lifecycle
    contract. Returns {request_id: RankResponse}."""
    import jax
    from repro.launch.serve import build_session, compiled_count
    from repro.serving.batching import bucket_of, staging_width
    from repro.serving.loadgen import run_open_loop
    ses = build_session(params, cfg, lcfg, plan=plan, max_queue=0)
    t0 = time.perf_counter()
    shapes = ses.warmup()
    warm_s = time.perf_counter() - t0
    before = compiled_count([ses])
    res = run_open_loop(ses, reqs, size.qps, seed=seed)
    recompiles = compiled_count([ses]) - before
    st = ses.stats_export()
    resps = {f.result().request_id: f.result()
             for f in res.futures if f.done()}
    per_bucket = {g: 0 for g in ses.buckets}
    for req in reqs:
        r = resps.get(req.request_id)
        if r is not None and r.status == "ok":
            per_bucket[bucket_of(len(req.item_feats), ses.buckets)] += 1
    print(f"[smoke] serve plan={plan}: warmed {len(shapes)} shapes in "
          f"{warm_s:.3f}s; {sum(per_bucket.values())}/{len(reqs)} ok "
          f"(per bucket {per_bucket}); errors {st['errors']} faults "
          f"{st['faults']} retries {st['retries']} shed {st['shed']}; "
          f"recompiles after warmup {recompiles}; compute "
          f"{res.serve_s:.3f}s", flush=True)
    if res.unresolved or len(resps) != len(reqs):
        fail(f"serve {plan}: {len(reqs) - len(resps)} futures unresolved")
    bad = [r for r in resps.values() if r.status != "ok"]
    if bad:
        fail(f"serve {plan}: {len(bad)} responses not ok, first "
             f"{bad[0].status}: {bad[0].error}")
    if st["errors"] or st["faults"] or st["retries"]:
        fail(f"serve {plan}: errors/faults/retries in {st}")
    if recompiles:
        fail(f"serve {plan}: {recompiles} recompiles after warmup")
    if not all(per_bucket.values()):
        fail(f"serve {plan}: a bucket served no request: {per_bucket}")
    b, g = ses.scfg.batch_groups, max(ses.buckets)

    buf = jax.ShapeDtypeStruct((b, staging_width(g, cfg.d_x, cfg.d_q)),
                               np.float32)
    text = ses._rank.lower(ses.params, buf).compile().as_text()
    check_kernel(text, f"serve plan={plan} pipeline ({b}, {g})")
    return resps


def _tol(ref):
    return LP_ATOL + LP_RTOL * np.abs(ref)


def _near_ties(ref, got_keep, n):
    """Items whose stage membership rounding may legitimately flip: where
    a stage's cut (between its n_keep-th and next-best reference lp among
    that stage's entrants) is narrower than the tolerance, the items
    within tolerance of either side; and where the two paths' ceil'd keep
    counts differ, the items ranked between them. ref: one group's
    reference outputs (numpy)."""
    lp, surv, keep = ref["lp"][:n], ref["survivors"][:n], ref["n_keep"]
    alive = ref["mask"][:n] > 0
    near = np.zeros(n, bool)
    for j in range(lp.shape[-1]):
        s = np.where(alive, lp[:, j], -np.inf)
        order = np.argsort(-s, kind="stable")
        k_ref, k_got = int(keep[j]), int(got_keep[j])
        if k_ref != k_got:
            near[order[min(k_ref, k_got):max(k_ref, k_got)]] = True
        if k_ref < n:
            a, b = s[order[k_ref - 1]], s[order[k_ref]]
            if np.isfinite(b) and a - b <= _tol(a):
                near |= alive & ((np.abs(s - a) <= _tol(a))
                                 | (np.abs(s - b) <= _tol(b)))
        alive = surv[:, j] > 0
    return near


def compare_serving(params, cfg, plan: str, reqs, resps) -> dict:
    """Each served response, and the plan's own lp at every stage, against
    run_cascade(fused="none") at highest precision on the same request."""
    import jax
    from repro.core import pipeline as P
    from repro.serving.batching import bucket_of, pack_requests
    from repro.serving.session import ServingConfig

    def run(fused):
        return jax.jit(lambda p, x, q, mask, m_q: P.run_cascade(
            p, cfg, x, q, mask, m_q, fused=fused))

    buckets = tuple(sorted(ServingConfig().group_buckets))
    with jax.default_matmul_precision("highest"):
        ref_fn = run("none")
    got_fn = run(plan)
    lp_err = score_err = 0.0
    n_near = n_bad = n_keep_diff = 0
    for req in reqs:
        n = len(req.item_feats)
        batch = pack_requests([req], bucket_of(n, buckets), 1)
        args = (params, batch["x"], batch["q"], batch["mask"], batch["m_q"])
        with jax.default_matmul_precision("highest"):
            ref = {k: np.asarray(v)[0] for k, v in ref_fn(*args).items()}
        got = {k: np.asarray(v)[0] for k, v in got_fn(*args).items()}
        ref["mask"] = batch["mask"][0]
        err = np.abs(got["lp"][:n] - ref["lp"][:n])
        if (err > _tol(ref["lp"][:n])).any():
            fail(f"compare {plan}: request {req.request_id} lp off by "
                 f"{err.max()} (limit {LP_ATOL} + {LP_RTOL}|lp|)")
        lp_err = max(lp_err, float(err.max()))
        n_keep_diff += int((got["n_keep"] != ref["n_keep"]).sum())
        near = _near_ties(ref, got["n_keep"], n)
        n_near += int(near.sum())
        r = resps[req.request_id]
        ref_final = ref["survivors"][:n, -1] > 0
        for what, mine in (("served", r.survivors.astype(bool)),
                           ("pipeline", got["survivors"][:n, -1] > 0)):
            off = (mine != ref_final) & ~near
            if off.any():
                fail(f"compare {plan}: request {req.request_id} {what} "
                     f"survivors differ from the reference at "
                     f"{np.flatnonzero(off).tolist()} (not near ties)")
            n_bad += int((mine != ref_final).sum())
        both = r.survivors.astype(bool) & ref_final
        if both.any():
            d = np.abs(r.scores[both] - ref["lp"][:n, -1][both])
            if (d > _tol(ref["lp"][:n, -1][both])).any():
                fail(f"compare {plan}: request {req.request_id} served "
                     f"scores off by {d.max()}")
            score_err = max(score_err, float(d.max()))
    out = {"max_lp_err": lp_err, "max_score_err": score_err,
           "near_ties": n_near, "survivor_diffs_at_near_ties": n_bad,
           "n_keep_diffs": n_keep_diff}
    print(f"[smoke] compare serve plan={plan} vs none@highest over "
          f"{len(reqs)} requests: max |lp err| {lp_err:.3e}, max |served "
          f"score err| {score_err:.3e} (limit {LP_ATOL} + {LP_RTOL}|lp|); "
          f"near-tie items {n_near}; survivor diffs (all at near ties) "
          f"{n_bad}; keep-count diffs {n_keep_diff}", flush=True)
    return out


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def compare_data_parallel(tr, size: Size, seed: int):
    """The 4-way shard_map fit against the 1-device fit on the same data.
    Returns the 1-device fit's (params, cfg, lcfg)."""
    from repro.core import trainer as T
    from repro.launch.mesh import data_parallel_mesh
    mesh = data_parallel_mesh(size.batch_groups)
    shards = 1 if mesh is None else mesh.shape["data"]
    if shards != 4:
        fail(f"data_parallel_mesh gave {shards} shards, expected 4")
    p1, cfg, lcfg, l1 = phase_train(tr, size, seed, label="train 1 device")
    _, _, _, l4 = phase_train(tr, size, seed, mesh=mesh,
                              label="train 4-way data parallel")
    steps, _ = T.epoch_steps(tr.x.shape[0], size.batch_groups)
    step0 = abs(l4[0] - l1[0]) / abs(l1[0])
    f1, f4 = np.mean(l1[-steps:]), np.mean(l4[-steps:])
    final = abs(f4 - f1) / abs(f1)
    print(f"[smoke] data parallel vs 1 device: step-0 loss {l4[0]:.6f} vs "
          f"{l1[0]:.6f} (rel {step0:.3e}, limit {DP_STEP0_RTOL}); last-epoch "
          f"mean {f4:.6f} vs {f1:.6f} (rel {final:.3e}, limit "
          f"{DP_FINAL_RTOL})", flush=True)
    if not (step0 <= DP_STEP0_RTOL and final <= DP_FINAL_RTOL):
        fail("the data-parallel fit's loss left its tolerance")
    return p1, cfg, lcfg


def compare_router(params, cfg, lcfg, reqs, size: Size, seed: int) -> None:
    """A 4-replica router, one replica per chip, against one session on
    chip 0: every request's scores and survivors."""
    from repro.launch.serve import build_router, compiled_count
    from repro.serving.loadgen import run_open_loop_router
    router = build_router(params, cfg, lcfg, n=4, max_queue=0)
    ids = sorted(r.device.id for r in router.replicas)
    if len(set(ids)) != 4:
        fail(f"router replicas are not on 4 distinct devices: {ids}")
    t0 = time.perf_counter()
    router.warmup()
    warm_s = time.perf_counter() - t0
    before = compiled_count(router.replicas)
    res = run_open_loop_router(router, reqs, size.qps, seed=seed)
    router.close()
    recompiles = compiled_count(router.replicas) - before
    st = router.stats_export()
    served = [rep["completed"] for rep in st["replicas"]]
    resps = {f.result().request_id: f.result()
             for f in res.futures if f.done()}
    print(f"[smoke] router: 4 replicas on devices {ids}, warmed in "
          f"{warm_s:.3f}s; completed per replica {served}; global "
          f"{ {k: st['global'][k] for k in ('errors', 'faults', 'retries', 'shed')} }; "
          f"recompiles after warmup {recompiles}", flush=True)
    if res.unresolved or len(resps) != len(reqs):
        fail("router: futures unresolved")
    bad = [r for r in resps.values() if r.status != "ok"]
    if bad or st["global"]["errors"] or st["global"]["faults"]:
        fail(f"router: {len(bad)} responses not ok, stats {st['global']}")
    if recompiles or not all(served):
        fail(f"router: recompiles {recompiles}, per-replica {served}")
    one = phase_serve(params, cfg, lcfg, reqs, "filter", size, seed)
    score_diff = 0.0
    for req in reqs:
        a, b = resps[req.request_id], one[req.request_id]
        if not np.array_equal(a.survivors, b.survivors):
            fail(f"router vs chip 0: request {req.request_id} survivors "
                 "differ")
        fin = np.isfinite(b.scores)
        if not np.array_equal(np.isfinite(a.scores), fin):
            fail(f"router vs chip 0: request {req.request_id} filtered "
                 "items differ")
        if fin.any():
            d = np.abs(a.scores[fin] - b.scores[fin])
            if (d > _tol(b.scores[fin])).any():
                fail(f"router vs chip 0: request {req.request_id} scores "
                     f"off by {d.max()}")
            score_diff = max(score_diff, float(d.max()))
    print(f"[smoke] router vs one session on chip 0: {len(reqs)} requests, "
          f"survivors equal, max |score diff| {score_diff:.3e}", flush=True)


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def run_one_chip(size: Size, seed: int) -> None:
    from repro.serving.session import ServingConfig
    log = make_log(size, seed)
    tr, te = log.split(0.8)
    params, cfg, lcfg, _ = phase_train(tr, size, seed)
    check_train_kernel(params, cfg, lcfg, tr, size, seed)
    reqs = make_requests(te, size, seed,
                         tuple(sorted(ServingConfig().group_buckets)))
    for plan in ("filter", "score"):
        resps = phase_serve(params, cfg, lcfg, reqs, plan, size, seed)
        compare_serving(params, cfg, plan, reqs, resps)
    compare_loss(params, cfg, lcfg, tr, size, seed)


def run_four_chips(size: Size, seed: int) -> None:
    from repro.serving.session import ServingConfig
    log = make_log(size, seed)
    tr, te = log.split(0.8)
    params, cfg, lcfg = compare_data_parallel(tr, size, seed)
    reqs = make_requests(te, size, seed,
                         tuple(sorted(ServingConfig().group_buckets)))
    compare_router(params, cfg, lcfg, reqs, size, seed)


SIZE = Size()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Serve and train the CLOES cascade on a TPU and check "
                    "the results against the XLA reference.")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip paths: a 4-replica router "
                         "and the 4-way data-parallel fit")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    try:
        dev = require_tpu()
        if args.four_chips and dev["count"] != 4:
            fail(f"--four-chips needs 4 devices, found {dev['count']}")
        try:
            from repro.launch.compile_cache import enable_compile_cache
        except ImportError as e:
            fail(f"cannot import the repro package from src/: {e}")
        print(f"[smoke] compile cache: {enable_compile_cache()}", flush=True)
        clock = CompileClock()
        try:
            (run_four_chips if args.four_chips else run_one_chip)(SIZE,
                                                                  args.seed)
        finally:
            clock.close()
        print(f"[smoke] compile {clock.seconds:.3f}s over {clock.compiles} "
              f"programs ({clock.cache_hits} from the persistent cache); "
              f"total {time.perf_counter() - t0:.3f}s", flush=True)
    except SmokeFailure as e:
        print(f"[smoke] FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
