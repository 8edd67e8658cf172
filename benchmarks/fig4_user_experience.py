"""Paper Fig 4: user experience per query, hot vs long-tail, with and
without modeling user experience (the delta/epsilon penalties of Eq 15).

Reproduced claims:
  1. hot-query latency drops below the 130 ms budget with UX modeling;
  2. long-tail result counts rise toward N_o with UX modeling;
  3. escape rate falls for hot queries; overall CTR improves or holds.
"""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from benchmarks.common import bench_split, emit, trained_cloes
from repro.core import cascade as C
from repro.core import losses as L
from repro.core import metrics as M
from repro.core import pipeline as P


def _per_query(params, cfg, lcfg, te):
    x = jnp.asarray(te.x, jnp.float32)
    q = jnp.asarray(te.q, jnp.float32)
    mask = jnp.asarray(te.mask, jnp.float32)
    m_q = jnp.asarray(te.m_q, jnp.float32)
    counts = np.asarray(C.expected_counts_per_query(params, cfg, x, q, mask,
                                                    m_q))[:, -1]
    lat = np.asarray(L.expected_latency_per_query(params, cfg, lcfg, x, q,
                                                  mask, m_q))
    res = P.run_cascade(params, cfg, x, q, mask, m_q, fused="none")
    scores = np.where(np.asarray(res["survivors"][..., -1]) > 0,
                      np.asarray(res["scores"]), -np.inf)
    sess = M.simulate_session(scores, te.relevance, te.price, te.mask, lat)
    return counts, lat, sess


def run():
    _, te = bench_split()
    t0 = time.perf_counter()
    # Stress calibration: latency_scale x6.7 over the default places the
    # accuracy-tuned (beta=1) cascade WITHOUT UX modeling at the paper's
    # pre-CLOES hot-query operating point (~170 ms, Fig 4 'storage box');
    # eps_latency=0.2 rebalances the paper's eps=0.05 for this scale. Both
    # arms share beta and the scale, isolating the delta/epsilon effect.
    p_ux, cfg_ux, lcfg = trained_cloes(beta=1.0, delta=1.0, eps_latency=0.2,
                                       latency_scale=0.01)
    p_no, cfg_no, _ = trained_cloes(beta=1.0, delta=0.0, eps_latency=0.0,
                                    latency_scale=0.01)
    c_ux, l_ux, s_ux = _per_query(p_ux, cfg_ux, lcfg, te)
    c_no, l_no, s_no = _per_query(p_no, cfg_no, lcfg, te)

    hot = te.m_q > np.percentile(te.m_q, 90)
    tail = te.m_q < np.percentile(te.m_q, 50)
    elapsed = (time.perf_counter() - t0) * 1e6

    emit("fig4/hot_latency_ms", elapsed / 6,
         f"without_ux={l_no[hot].mean():.1f};with_ux={l_ux[hot].mean():.1f};"
         f"budget=130;paper=170_to_108")
    emit("fig4/hot_over_budget_frac", elapsed / 6,
         f"without_ux={(l_no[hot] > 130).mean():.2f};"
         f"with_ux={(l_ux[hot] > 130).mean():.2f}")
    emit("fig4/tail_result_count", elapsed / 6,
         f"without_ux={c_no[tail].mean():.1f};with_ux={c_ux[tail].mean():.1f};"
         f"target=min(200,M_q);paper=floor_wax_8x_increase")
    emit("fig4/escape_rate", elapsed / 6,
         f"without_ux={s_no['escape_rate']:.3f};with_ux={s_ux['escape_rate']:.3f}")
    emit("fig4/overall_ctr", elapsed / 6,
         f"without_ux={s_no['ctr']:.3f};with_ux={s_ux['ctr']:.3f}")
    emit("fig4/mean_latency_ms", elapsed / 6,
         f"without_ux={s_no['mean_latency_ms']:.1f};"
         f"with_ux={s_ux['mean_latency_ms']:.1f}")

    assert l_ux[hot].mean() < l_no[hot].mean(), \
        "UX modeling must reduce hot-query latency (Fig 4 top)"
    assert c_ux[tail].mean() > c_no[tail].mean(), \
        "UX modeling must raise tail result counts (Fig 4 bottom)"
    return {"lat_hot": (l_no[hot].mean(), l_ux[hot].mean()),
            "cnt_tail": (c_no[tail].mean(), c_ux[tail].mean())}


if __name__ == "__main__":
    run()
