"""Unit tests for the CLOES core: Eqs 1-17 against hand/scipy oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.special

from repro.core import cascade as C
from repro.core import losses as L
from repro.core import metrics as M
from repro.core import pipeline as P
from repro.data import features as F
from repro.data.synthetic import BEHAVIOR_CLICK, BEHAVIOR_NONE, BEHAVIOR_PURCHASE


@pytest.fixture(scope="module")
def setup():
    masks = F.default_stage_masks(3)
    cfg = C.CascadeConfig(3, F.N_FEATURES, F.N_QUERY_BUCKETS, masks,
                          F.stage_costs(masks))
    params = C.init_params(cfg, jax.random.PRNGKey(0), scale=0.3)
    rng = np.random.default_rng(0)
    B, G = 4, 8
    x = jnp.asarray(rng.normal(size=(B, G, F.N_FEATURES)), jnp.float32)
    q = jnp.asarray(np.eye(F.N_QUERY_BUCKETS)[rng.integers(0, 8, B)], jnp.float32)
    return cfg, params, x, q


def test_stage_probs_match_manual_sigmoid(setup):
    cfg, params, x, q = setup
    probs = np.asarray(C.stage_probs(params, cfg, x, q))
    masks = np.asarray(cfg.masks)
    for j in range(cfg.n_stages):
        z = (np.asarray(x) @ (np.asarray(params["w_x"][j]) * masks[j])
             + (np.asarray(q) @ np.asarray(params["w_q"][j]))[:, None]
             + float(params["b"][j]))
        want = scipy.special.expit(z)
        np.testing.assert_allclose(probs[..., j], want, rtol=1e-5, atol=1e-6)


def test_final_prob_is_product_of_stages(setup):
    """Eq 2: p(y=1|q,x) = prod_j p_j."""
    cfg, params, x, q = setup
    probs = np.asarray(C.stage_probs(params, cfg, x, q))
    final = np.asarray(C.final_prob(params, cfg, x, q))
    np.testing.assert_allclose(final, probs.prod(-1), rtol=1e-5)


def test_pass_probs_monotone_nonincreasing(setup):
    """Eq 6: p_pass_k is non-increasing in k (each stage can only reject)."""
    cfg, params, x, q = setup
    pp = np.asarray(C.pass_probs(params, cfg, x, q))
    assert (np.diff(pp, axis=-1) <= 1e-7).all()


def test_log_pass_probs_stable_and_consistent(setup):
    cfg, params, x, q = setup
    lp = np.asarray(C.log_pass_probs(params, cfg, x, q))
    pp = np.asarray(C.pass_probs(params, cfg, x, q))
    np.testing.assert_allclose(np.exp(lp), pp, rtol=1e-5, atol=1e-7)


def test_smooth_hinge_approximates_hinge():
    """Eq 14: gap to hinge vanishes as gamma grows."""
    z = jnp.linspace(-50, 400, 200)
    target = 200.0
    hinge = np.maximum(target - np.asarray(z), 0.0)
    for gamma, tol in [(0.1, 7.0), (1.0, 0.7), (10.0, 0.07)]:
        g = np.asarray(L.smooth_hinge(z, target, gamma))
        assert np.abs(g - hinge).max() < tol
        # differentiable + monotone decreasing in z
        grad = jax.vmap(jax.grad(lambda zz: L.smooth_hinge(zz, target, gamma)))(z)
        assert (np.asarray(grad) <= 0).all()


def test_expected_counts_scaling(setup):
    """Eq 10: E[Count_{q,j}] scales linearly in M_q."""
    cfg, params, x, q = setup
    mask = jnp.ones(x.shape[:2])
    m1 = jnp.full((4,), 100.0)
    c1 = np.asarray(C.expected_counts_per_query(params, cfg, x, q, mask, m1))
    c2 = np.asarray(C.expected_counts_per_query(params, cfg, x, q, mask, 3 * m1))
    np.testing.assert_allclose(3 * c1, c2, rtol=1e-5)


def test_expected_cost_decomposition(setup):
    """Eq 8: T(w) = sum_j E[Count_{j-1}] * t_j / N with Count_0 = N."""
    cfg, params, x, q = setup
    mask = jnp.ones(x.shape[:2])
    got = float(L.expected_cost(params, cfg, x, q, mask))
    pp = np.asarray(C.pass_probs(params, cfg, x, q))
    n = mask.size
    t = cfg.t
    want = (n * t[0] + pp[..., 0].sum() * t[1] + pp[..., 1].sum() * t[2]) / n
    assert abs(got - want) < 1e-4


def test_importance_weights_eq17():
    lcfg = L.LossConfig(eps_purchase=10.0, mu_price=3.0)
    behavior = jnp.asarray([BEHAVIOR_NONE, BEHAVIOR_CLICK, BEHAVIOR_PURCHASE])
    price = jnp.asarray([50.0, 50.0, 50.0])
    w = np.asarray(L.importance_weights(behavior, price, lcfg))
    assert w[0] == 1.0
    np.testing.assert_allclose(w[1], 3.0 * np.log(50.0), rtol=1e-5)
    np.testing.assert_allclose(w[2], 30.0 * np.log(50.0), rtol=1e-5)
    # purchases of pricier items weigh more
    w2 = np.asarray(L.importance_weights(
        jnp.asarray([BEHAVIOR_PURCHASE]), jnp.asarray([500.0]), lcfg))
    assert w2[0] > w[2]


def test_weighted_nll_matches_manual(setup):
    cfg, params, x, q = setup
    y = jnp.asarray(np.random.default_rng(1).integers(0, 2, x.shape[:2]),
                    jnp.float32)
    mask = jnp.ones_like(y)
    lcfg = L.LossConfig()
    got = float(L.weighted_nll(params, cfg, lcfg, x, q, y, mask))
    p = np.asarray(C.final_prob(params, cfg, x, q))
    yn = np.asarray(y)
    want = -(yn * np.log(p) + (1 - yn) * np.log1p(-p)).mean()
    assert abs(got - want) < 1e-5


def test_latency_conventions(setup):
    """'entering' includes the mandatory stage-1 scan of all M_q items."""
    cfg, params, x, q = setup
    mask = jnp.ones(x.shape[:2])
    m_q = jnp.full((4,), 1000.0)
    lat_paper = L.expected_latency_per_query(
        params, cfg, L.LossConfig(latency_convention="paper"), x, q, mask, m_q)
    lat_enter = L.expected_latency_per_query(
        params, cfg, L.LossConfig(latency_convention="entering"), x, q, mask, m_q)
    scale = L.LossConfig().latency_scale
    # entering >= t_1 * M_q * scale always
    assert (np.asarray(lat_enter) >= cfg.t[0] * 1000.0 * scale - 1e-5).all()
    assert (np.asarray(lat_enter) > np.asarray(lat_paper)).all()


@pytest.mark.slow      # two full loss_l3 grad compiles (~3 s) — deep
# routing equivalence belongs with the slow equivalence sweeps
def test_l3_penalties_route_to_query_path_only(setup):
    """UX-penalty gradients must not touch w_x or b (see losses.loss_l3)."""
    cfg, params, x, q = setup
    batch = {
        "x": x, "q": q,
        "y": jnp.zeros(x.shape[:2]), "mask": jnp.ones(x.shape[:2]),
        "behavior": jnp.zeros(x.shape[:2], jnp.int32),
        "price": jnp.ones(x.shape[:2]),
        "m_q": jnp.full((4,), 50.0),
    }
    lcfg_pen = L.LossConfig(alpha=0.0, beta=0.0, delta=1.0, eps_latency=1.0)
    lcfg_none = L.LossConfig(alpha=0.0, beta=0.0, delta=0.0, eps_latency=0.0)
    g_pen = jax.grad(L.loss_l3)(params, cfg, lcfg_pen, batch)
    g_none = jax.grad(L.loss_l3)(params, cfg, lcfg_none, batch)
    np.testing.assert_allclose(np.asarray(g_pen["w_x"]),
                               np.asarray(g_none["w_x"]), rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(g_pen["b"]),
                               np.asarray(g_none["b"]), rtol=1e-5, atol=1e-7)
    # but they DO move w_q
    assert not np.allclose(np.asarray(g_pen["w_q"]), np.asarray(g_none["w_q"]))


def test_auc_oracle():
    scores = np.array([0.9, 0.8, 0.7, 0.2, 0.1])
    labels = np.array([1, 0, 1, 0, 0])
    # pairs: (0.9 vs .8,.2,.1)=3 wins, (0.7 vs .8)=0, vs .2,.1 = 2 wins
    assert abs(M.auc(scores, labels) - 5 / 6) < 1e-9
    # ties count half
    assert abs(M.auc(np.array([1., 1.]), np.array([1, 0])) - 0.5) < 1e-9


def test_group_auc_ignores_per_query_offsets():
    rng = np.random.default_rng(0)
    scores = rng.normal(size=(10, 20))
    labels = (rng.random((10, 20)) < 0.3).astype(float)
    base = M.group_auc(scores, labels)
    shifted = scores + rng.normal(size=(10, 1)) * 100  # per-query shift
    assert abs(M.group_auc(shifted, labels) - base) < 1e-9


def test_hard_cascade_respects_thresholds(setup):
    cfg, params, x, q = setup
    mask = jnp.ones(x.shape[:2])
    m_q = jnp.full((4,), 8.0)     # recall == group: counts map 1:1
    res = P.run_cascade(params, cfg, x, q, mask, m_q, fused="none")
    kept = np.asarray(res["kept_per_stage"])
    assert (np.diff(kept, axis=-1) <= 0).all()       # monotone filtering
    assert (kept >= 1).all()
