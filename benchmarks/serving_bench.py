"""Serving-path throughput: items/sec through the hard cascade for the
serving implementations, over the batcher's shape buckets.

  unfused-xla         — the pre-pipeline serving path, reproduced here as
                        the baseline: separate XLA scoring, a SECOND
                        scoring pass for the Eq-10 counts, a Python stage
                        loop of double argsorts, and a THIRD scoring pass
                        for the Eq-16 latency estimate, all dispatched
                        eagerly (this is what CascadeServer.rank_batch did
                        before core/pipeline.py existed).
  fused-score-vmap    — the PR-2 fused="score" pipeline, reproduced here
                        as the vmap baseline: jax.vmap of the SINGLE-GROUP
                        scorer op over the batch (grid restructured through
                        the batching rule), XLA stage chain.
  batched-kernel      — the shipped fused="score" pipeline: the native
                        batched (B, G) scorer entry point (one 2-D
                        (batch, item-block) grid, zero vmap wrapping of
                        the kernel) + the XLA stage chain.
  fused-score+filter  — the jitted pipeline around the fused score+filter
                        kernel: one scoring pass, no argsorts, latency
                        from the pipeline's own counts (ops backend
                        dispatch: Pallas on TPU, jitted XLA reference
                        elsewhere).

Writes BENCH_serving.json (gitignored — machine-local numbers). --smoke
(the CI leg) times one small bucket on untrained params and skips the
throughput assertions — it only proves the bench runs and writes the
report.
"""

from __future__ import annotations

import argparse
import json
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_call, trained_cloes
from repro.core import cascade as C
from repro.core import losses as L
from repro.core import pipeline as P
from repro.data import features as F
from repro.kernels import ops as K
from repro.serving.batching import alloc_batch
from repro.serving.cascade_server import CascadeServer

BUCKETS = [(32, 64), (32, 256)]
BENCH_JSON = "BENCH_serving.json"


def _batch(b, g, d_x, d_q, seed=0):
    rng = np.random.default_rng(seed)
    batch = alloc_batch(b, g, d_x, d_q)
    batch["x"][...] = rng.normal(size=(b, g, d_x))
    batch["q"][...] = np.eye(d_q)[rng.integers(0, d_q, b)]
    batch["mask"][...] = 1.0
    batch["m_q"][...] = rng.integers(g, 20 * g, b)
    return batch


def _seed_rank_batch(params, cfg, lcfg, batch):
    """The pre-refactor CascadeServer.rank_batch, kept verbatim as the
    unfused-XLA baseline (three scoring passes, 2T argsorts, eager)."""
    x = jnp.asarray(batch["x"], jnp.float32)
    q = jnp.asarray(batch["q"], jnp.float32)
    mask = jnp.asarray(batch["mask"], jnp.float32)
    m_q = jnp.asarray(batch["m_q"], jnp.float32)
    G = x.shape[1]
    lp = C.log_pass_probs(params, cfg, x, q)
    counts = C.expected_counts_per_query(params, cfg, x, q, mask, m_q)
    n_keep = jnp.clip(jnp.ceil(counts * mask.sum(-1, keepdims=True)
                               / jnp.maximum(m_q[:, None], 1.0)), 1, G)
    surv = mask
    for j in range(cfg.n_stages):
        s = jnp.where(surv > 0, lp[..., j], -jnp.inf)
        rank = jnp.argsort(jnp.argsort(-s, axis=-1), axis=-1)
        surv = surv * (rank < n_keep[:, j:j + 1]).astype(mask.dtype)
    scores = jnp.where(surv > 0, lp[..., -1], -jnp.inf)
    lat = L.expected_latency_per_query(params, cfg, lcfg, x, q, mask, m_q)
    return scores, surv, lat


def _vmap_score_pipeline(cfg, lcfg):
    """The PR-2 fused="score" pipeline body: vmap of the single-group
    scorer op, then the shared keep-count / stage-chain / latency tail."""
    @jax.jit
    def pipeline(p, x, q, mask, m_q):
        w_eff = p["w_x"] * jnp.asarray(cfg.masks, jnp.float32)
        zq = q @ p["w_q"].T + p["b"]
        lp = jax.vmap(
            lambda xb, zqb: K.cascade_score(xb, w_eff, zqb))(x, zq)
        counts, n_keep = P.keep_counts_from_lp(lp, mask, m_q)
        surv = P.filter_chain(lp, mask, n_keep)
        lat = P.latency_from_counts(counts, m_q, cfg, lcfg.latency_scale,
                                    lcfg.latency_convention)
        return lp[..., -1], surv[..., -1], lat
    return pipeline


def run(*, smoke: bool = False, plan: str = "filter"):
    # Resolve the serving plan through the one registry BEFORE any
    # training/compute — the bench rejects an unknown plan with the same
    # error as run_cascade/CascadeServer/CascadeSession.
    P.resolve_plan(plan)
    if smoke:
        # untrained params: throughput does not depend on weight values,
        # and the smoke leg must not pay a multi-epoch training warmup
        masks = F.default_stage_masks(3)
        cfg = C.CascadeConfig(3, F.N_FEATURES, F.N_QUERY_BUCKETS, masks,
                              F.stage_costs(masks))
        params = C.init_params(cfg, jax.random.PRNGKey(0), scale=0.3)
        lcfg = L.LossConfig(beta=5.0)
        # 20 iters, not 3: a 3-sample median on this container once read as
        # a ~10% batched-vs-vmap regression that 900-sample timing showed
        # to be pure wall-clock noise (ratio 1.00x, see ROADMAP). The smoke
        # rows land in CI artifacts, so they must be quiet enough not to
        # manufacture phantom signals; the asserted contract stays with the
        # non-smoke (32, 256) bucket.
        buckets, iters = [(8, 64)], 20
    else:
        params, cfg, lcfg = trained_cloes()
        buckets, iters = BUCKETS, 10
    # no srv.warmup(): time_call's own warmup compiles the one shape each
    # variant uses — warming all 18 batcher buckets would only add wall time
    srv = CascadeServer(params, cfg, lcfg, fused=plan)

    @partial(jax.jit, static_argnames=())
    def batched_kernel_pipeline(p, x, q, mask, m_q):
        out = P.run_cascade(p, cfg, x, q, mask, m_q, fused="score")
        lat = P.latency_from_counts(out["expected_counts"], m_q, cfg,
                                    lcfg.latency_scale,
                                    lcfg.latency_convention)
        return out["scores"], out["survivors"][..., -1], lat

    vmap_pipeline = _vmap_score_pipeline(cfg, lcfg)

    results = {}
    for b, g in buckets:
        batch = _batch(b, g, cfg.d_x, cfg.d_q)
        jb = {k: jnp.asarray(v) for k, v in batch.items()}
        items = b * g
        args = (params, jb["x"], jb["q"], jb["mask"], jb["m_q"])

        us_unfused = time_call(
            lambda: _seed_rank_batch(params, cfg, lcfg, batch), iters=iters)
        us_vmap = time_call(lambda: vmap_pipeline(*args), iters=iters)
        us_batched = time_call(lambda: batched_kernel_pipeline(*args),
                               iters=iters)
        us_filter = time_call(lambda: srv.rank_batch(batch)["scores"],
                              iters=iters)

        rows = [("unfused_xla", us_unfused), ("fused_score_vmap", us_vmap),
                ("batched_kernel", us_batched),
                ("fused_score_filter", us_filter)]
        for name, us in rows:
            ips = items / (us / 1e6)
            emit(f"serving/{name}_b{b}_g{g}", us,
                 f"items_per_sec={ips:.0f};speedup_vs_unfused="
                 f"{us_unfused / us:.2f}x")
        results[(b, g)] = dict(rows)

    report = {
        "config": {"buckets": [list(bg) for bg in buckets], "iters": iters,
                   "smoke": smoke, "plan": plan,
                   "backend": jax.default_backend()},
        "variants": {f"b{b}_g{g}": {name: {"us_per_call": us,
                                           "items_per_sec": b * g / (us / 1e6)}
                                    for name, us in r.items()}
                     for (b, g), r in results.items()},
    }
    with open(BENCH_JSON, "w") as f:
        json.dump(report, f, indent=2)
    print(f"serving/report,, wrote {BENCH_JSON}")

    if not smoke:
        r = results[(32, 256)]
        assert r["fused_score_filter"] <= r["unfused_xla"], (
            "fused score+filter pipeline must at least match unfused-XLA "
            f"throughput on (32, 256): {r}")
        # 1.15x slack absorbs CPU wall-clock noise: off-TPU both paths jit
        # to near-identical XLA (the win being measured is the TPU grid
        # restructuring), so "no slower than vmap" is the honest floor.
        assert r["batched_kernel"] <= 1.15 * r["fused_score_vmap"], (
            "batched-kernel pipeline must at least match the vmap path's "
            f"throughput on (32, 256): {r}")
    return results


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small bucket, untrained params, no assertions "
                    "(CI leg: asserts the bench runs and writes "
                    f"{BENCH_JSON})")
    ap.add_argument("--plan", default="filter",
                    help="pipeline plan for the server row "
                    "(core.pipeline.PLANS entry)")
    args = ap.parse_args()
    run(smoke=args.smoke, plan=args.plan)


if __name__ == "__main__":
    main()
