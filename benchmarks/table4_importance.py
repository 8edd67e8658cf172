"""Paper Table 4: importance-weight sweep (epsilon = purchase weight,
mu = price weight) -> CTR / #orders / GMV / unit price deltas vs the
epsilon=1, mu=1 variant, under the simulated-user online model."""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from benchmarks.common import bench_split, emit, trained_cloes
from repro.core import losses as L
from repro.core import metrics as M
from repro.core import pipeline as P


def _online_metrics(params, cfg, lcfg, te, seed=0):
    x = jnp.asarray(te.x, jnp.float32)
    q = jnp.asarray(te.q, jnp.float32)
    mask = jnp.asarray(te.mask, jnp.float32)
    m_q = jnp.asarray(te.m_q, jnp.float32)
    res = P.run_cascade(params, cfg, x, q, mask, m_q, fused="none")
    scores = np.where(np.asarray(res["survivors"][..., -1]) > 0,
                      np.asarray(res["scores"]), -np.inf)
    lat = np.asarray(L.expected_latency_per_query(
        params, cfg, lcfg, x, q, mask, m_q))
    return M.simulate_session(scores, te.relevance, te.price, te.mask, lat,
                              seed=seed)


def run():
    _, te = bench_split()
    t0 = time.perf_counter()
    settings = [(1.0, 1.0), (10.0, 1.0), (10.0, 2.0), (10.0, 3.0), (10.0, 4.0)]
    paper = {  # Table 4 deltas (%) vs 2-stage baseline; we report vs eps1mu1
        (1.0, 1.0): (1.58, -1.35, -1.76, -0.42),
        (10.0, 1.0): (0.25, 1.89, -0.64, -2.49),
        (10.0, 2.0): (0.17, 1.65, 0.24, -1.39),
        (10.0, 3.0): (0.12, 0.36, 1.32, 0.95),
        (10.0, 4.0): (-0.13, -0.25, -0.92, 1.65),
    }
    rows = []
    base = None
    for eps, mu in settings:
        params, cfg, lcfg = trained_cloes(beta=5.0, eps_purchase=eps,
                                          mu_price=mu)
        m = _online_metrics(params, cfg, lcfg, te)
        if base is None:
            base = m
        rows.append(((eps, mu), m))
    elapsed = (time.perf_counter() - t0) * 1e6 / len(settings)
    for (eps, mu), m in rows:
        d = lambda k: 100.0 * (m[k] - base[k]) / max(abs(base[k]), 1e-9)
        pp = paper[(eps, mu)]
        emit(f"table4/eps{eps:g}_mu{mu:g}", elapsed,
             f"dCTR={d('ctr'):+.2f}%;dOrders={d('orders'):+.2f}%;"
             f"dGMV={d('gmv'):+.2f}%;dUnitPrice={d('unit_price'):+.2f}%;"
             f"paper=({pp[0]:+.2f},{pp[1]:+.2f},{pp[2]:+.2f},{pp[3]:+.2f})")
    # qualitative claim: purchase weighting lifts orders or GMV vs eps=1
    gmv_by = {k: m["gmv"] for k, m in rows}
    orders_by = {k: m["orders"] for k, m in rows}
    assert max(gmv_by[(10.0, m)] for m in (1.0, 2.0, 3.0)) >= gmv_by[(1.0, 1.0)] \
        or max(orders_by[(10.0, m)] for m in (1.0, 2.0, 3.0)) >= orders_by[(1.0, 1.0)], \
        "purchase-weighted variants should lift transactions"
    return rows


if __name__ == "__main__":
    run()
