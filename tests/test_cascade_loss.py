"""Parity suite for the fused L3 training-step reduction op (PR 4).

Pins four contracts:
  (a) the fused-loss L3 (the default `losses.loss_l3` path — one
      kernels.ops.cascade_loss_fused call) matches the unfused
      score-then-reduce graph (`losses.loss_l3_unfused`) in value
      (relative 1e-6) and param grads (1e-5) across the
      cost_mask_positives x latency-convention grid, on raw AND engine
      batches, including fully padded (mask-zero) groups;
  (b) the Pallas kernel bodies (interpret mode) match the XLA reference —
      forward partials and the backward kernel against the closed-form
      backward — over non-block-multiple B/G/T, G=1, T=1/MAX_STAGES and
      fully padded rows;
  (c) the routed-autodiff reference gradients implement the Eq-15
      stop-gradient routing exactly: per cotangent stream they match the
      closed-form backward, and the penalty stream touches zq_pen only;
  (d) the op's error contract: rank mismatches and a packed width that
      does not equal d_x + 4 fail loudly at the public API.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cascade as C
from repro.core import losses as L
from repro.data import features as F
from repro.kernels import ops as K
from repro.kernels.cascade_loss.kernel import (MAX_STAGES, cascade_loss,
                                               cascade_loss_bwd)
from repro.kernels.cascade_loss.ref import (cascade_loss_bwd_ref,
                                            cascade_loss_ref)


def _case(b, g, t, d, seed=0, dead_group=True):
    """Random packed inputs; group 0 fully masked when dead_group."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(b, g, d)).astype(np.float32)
    y = rng.integers(0, 2, (b, g)).astype(np.float32)
    mask = (rng.random((b, g)) < 0.85).astype(np.float32)
    if dead_group:
        mask[0] = 0.0
    wgt = rng.uniform(0.5, 3.0, (b, g)).astype(np.float32) * mask
    cost_w = rng.uniform(0.0, 50.0, (b, g)).astype(np.float32) * mask
    xc = jnp.asarray(np.concatenate(
        [x, y[..., None], mask[..., None], wgt[..., None],
         cost_w[..., None]], axis=-1))
    w = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    zq = jnp.asarray(rng.normal(size=(b, t)), jnp.float32)
    return xc, w, zq


def _raw_batch(seed=0, b=8, g=16):
    rng = np.random.default_rng(seed)
    return {
        "x": jnp.asarray(rng.normal(size=(b, g, F.N_FEATURES)), jnp.float32),
        "q": jnp.asarray(np.eye(F.N_QUERY_BUCKETS)[rng.integers(0, 8, b)],
                         jnp.float32),
        "y": jnp.asarray(rng.integers(0, 2, (b, g)), jnp.float32),
        "mask": jnp.asarray(rng.random((b, g)) < 0.9, jnp.float32),
        "behavior": jnp.asarray(rng.integers(0, 3, (b, g)), jnp.int32),
        "price": jnp.asarray(np.exp(rng.normal(3, 1, (b, g))), jnp.float32),
        "m_q": jnp.asarray(rng.integers(50, 5000, b), jnp.float32),
    }


@pytest.fixture(scope="module")
def cfg():
    masks = F.default_stage_masks(3)
    return C.CascadeConfig(3, F.N_FEATURES, F.N_QUERY_BUCKETS, masks,
                           F.stage_costs(masks))


@pytest.fixture(scope="module")
def params(cfg):
    return C.init_params(cfg, jax.random.PRNGKey(0), scale=0.3)


# ---------------------------------------------------------------------------
# (a) fused vs unfused L3 — the headline parity contract.
# ---------------------------------------------------------------------------

def _assert_l3_parity(params, cfg, lcfg, batch, rtol_v=1e-6, rtol_g=1e-5):
    v_f, g_f = jax.value_and_grad(L.loss_l3)(params, cfg, lcfg, batch)
    v_u, g_u = jax.value_and_grad(L.loss_l3_unfused)(params, cfg, lcfg, batch)
    assert np.isfinite(float(v_f))
    assert abs(float(v_f) - float(v_u)) <= rtol_v * max(1.0, abs(float(v_u)))
    for k in g_u:
        np.testing.assert_allclose(np.asarray(g_f[k]), np.asarray(g_u[k]),
                                   rtol=rtol_g, atol=rtol_g)


@pytest.mark.parametrize("cost_mask_positives", [False, True])
@pytest.mark.parametrize("convention", [
    "entering",     # the default convention stays in the fast loop;
    # the non-default row recompiles both graphs — slow-marked (full
    # tier-1 still runs the whole grid)
    pytest.param("paper", marks=pytest.mark.slow)])
def test_fused_l3_matches_unfused_grid(cfg, params, cost_mask_positives,
                                       convention):
    lcfg = L.LossConfig(beta=2.0, eps_purchase=3.0, mu_price=2.0,
                        cost_mask_positives=cost_mask_positives,
                        latency_convention=convention)
    _assert_l3_parity(params, cfg, lcfg, _raw_batch())


def test_fused_l3_engine_batch_matches_raw(cfg, params):
    """The engine-batch columns (wgt/cost_w/mn/n_o_eff + the packed xc) and
    the raw-batch derivation must hit the same fused value/grads."""
    lcfg = L.LossConfig(beta=2.0, eps_purchase=3.0, mu_price=2.0)
    batch = _raw_batch()
    n_q = jnp.maximum(batch["mask"].sum(-1), 1.0)
    mn = batch["m_q"] / n_q
    wgt = L.importance_weights(batch["behavior"], batch["price"], lcfg)
    cost_w = batch["mask"] * mn[:, None]
    engine = {
        "x": batch["x"], "q": batch["q"], "y": batch["y"],
        "mask": batch["mask"], "m_q": batch["m_q"],
        "wgt": wgt, "cost_w": cost_w, "mn": mn,
        "n_o_eff": jnp.minimum(lcfg.n_o, batch["m_q"]),
        "xc": jnp.concatenate(
            [batch["x"], batch["y"][..., None], batch["mask"][..., None],
             wgt[..., None], cost_w[..., None]], axis=-1),
    }
    v_raw, g_raw = jax.value_and_grad(L.loss_l3)(params, cfg, lcfg, batch)
    v_eng, g_eng = jax.value_and_grad(L.loss_l3)(params, cfg, lcfg, engine)
    assert abs(float(v_raw) - float(v_eng)) <= 1e-6 * abs(float(v_raw))
    for k in g_raw:
        np.testing.assert_allclose(np.asarray(g_raw[k]), np.asarray(g_eng[k]),
                                   rtol=1e-5, atol=1e-6)


def test_fused_l3_fully_padded_groups(cfg, params):
    """Groups with mask == 0 everywhere must contribute nothing and produce
    no NaNs/infs through either path."""
    lcfg = L.LossConfig(beta=2.0)
    batch = _raw_batch(seed=3)
    mask = np.array(batch["mask"])
    mask[:3] = 0.0
    batch["mask"] = jnp.asarray(mask)
    _assert_l3_parity(params, cfg, lcfg, batch)


def test_fused_l3_jits_and_matches_eager(cfg, params):
    lcfg = L.LossConfig(beta=2.0)
    batch = _raw_batch(seed=5)
    eager = jax.value_and_grad(L.loss_l3)(params, cfg, lcfg, batch)
    jitted = jax.jit(jax.value_and_grad(
        lambda p: L.loss_l3(p, cfg, lcfg, batch)))(params)
    assert float(eager[0]) == pytest.approx(float(jitted[0]), rel=1e-6)
    for k in eager[1]:
        np.testing.assert_allclose(np.asarray(eager[1][k]),
                                   np.asarray(jitted[1][k]),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# (b) Pallas kernel bodies (interpret mode) vs the XLA reference.
# ---------------------------------------------------------------------------

# fast loop: ONE case (non-block-multiple G, a fully masked group); the
# sweep carries the rest (ROADMAP fast-loop budget: interpreter runs are
# the expensive part of this file)
FWD_CASES = [(3, 7, 3, 24)]
FWD_CASES_SLOW = [(1, 1, 1, 5), (8, 16, 3, 24), (2, 130, 8, 40),
                  (4, 512, 3, 24), (5, 9, 2, 129)]


def _assert_kernel_parity(b, g, t, d):
    xc, w, zq = _case(b, g, t, d, seed=b * 100 + g + t + d)
    got = cascade_loss(xc, w, zq, d_x=d, interpret=True)
    want = cascade_loss_ref(xc, w, zq)
    assert got[0].shape == (b,) and got[1].shape == (t,)
    assert got[2].shape == (b, t)
    for a, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-5, atol=1e-5)
    rng = np.random.default_rng(1)
    g_ll = jnp.asarray(rng.normal(size=(b,)), jnp.float32)
    g_cost = jnp.asarray(rng.normal(size=(t,)), jnp.float32)
    g_cnt = jnp.asarray(rng.normal(size=(b, t)), jnp.float32)
    got_b = cascade_loss_bwd(xc, w, zq, g_ll, g_cost, g_cnt, d_x=d,
                             interpret=True)
    want_b = cascade_loss_bwd_ref(xc, w, zq, g_ll, g_cost, g_cnt)
    for a, r in zip(got_b, want_b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-4, atol=5e-5)


@pytest.mark.parametrize("b,g,t,d", FWD_CASES)
def test_loss_kernel_matches_ref_interpret(b, g, t, d):
    _assert_kernel_parity(b, g, t, d)


@pytest.mark.slow
@pytest.mark.parametrize("b,g,t,d", FWD_CASES_SLOW)
def test_loss_kernel_matches_ref_interpret_sweep(b, g, t, d):
    """Non-block-multiple G (130, 9), full BLOCK_ITEMS groups, T at
    MAX_STAGES and a lane-boundary feature width."""
    _assert_kernel_parity(b, g, t, d)


def test_loss_kernel_rejects_too_many_stages():
    xc, w, zq = _case(2, 4, 1, 8)
    w9 = jnp.zeros((MAX_STAGES + 1, 8))
    zq9 = jnp.zeros((2, MAX_STAGES + 1))
    with pytest.raises(AssertionError, match="stages"):
        cascade_loss(xc, w9, zq9, d_x=8, interpret=True)


# ---------------------------------------------------------------------------
# (c) gradient routing: routed autodiff == closed form, per stream.
# ---------------------------------------------------------------------------

def _streams(b, g, t, d, seed=9):
    xc, w, zq = _case(b, g, t, d, seed=seed, dead_group=False)
    rng = np.random.default_rng(seed + 1)
    g_ll = jnp.asarray(rng.normal(size=(b,)), jnp.float32)
    g_ct = jnp.asarray(rng.normal(size=(t,)), jnp.float32)
    g_cn = jnp.asarray(rng.normal(size=(b, t)), jnp.float32)
    return xc, w, zq, g_ll, g_ct, g_cn


@pytest.mark.slow           # heavy double-compile (autodiff + closed form)
def test_routed_autodiff_matches_closed_form_bwd():
    """jax.grad through cascade_loss_ref (the production CPU path, routing
    expressed algebraically) must equal the hand-derived backward."""
    b, g, t, d = 4, 16, 3, 24
    xc, w, zq, g_ll, g_ct, g_cn = _streams(b, g, t, d)

    def scalarized(w_, zq_, zq_pen_):
        ll, cost_pp, cnt_pp = cascade_loss_ref(xc, w_, zq_, zq_pen_)
        return ((ll * g_ll).sum() + (cost_pp * g_ct).sum()
                + (cnt_pp * g_cn).sum())

    dw_a, dzq_a, dzqp_a = jax.grad(scalarized, (0, 1, 2))(w, zq, zq)
    _, dw_c, dzq_c, dzqp_c = cascade_loss_bwd_ref(xc, w, zq, g_ll, g_ct,
                                                  g_cn)
    np.testing.assert_allclose(np.asarray(dw_a), np.asarray(dw_c),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dzq_a), np.asarray(dzq_c),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dzqp_a), np.asarray(dzqp_c),
                               rtol=1e-4, atol=1e-5)


def test_penalty_stream_routes_to_zq_pen_only():
    """With only counts cotangents, w_eff and zq must see ZERO gradient
    (the Eq-15 stop-gradient contract) while zq_pen carries the stream."""
    b, g, t, d = 4, 16, 3, 24
    xc, w, zq, _, _, g_cn = _streams(b, g, t, d, seed=13)

    def cnt_only(w_, zq_, zq_pen_):
        return (cascade_loss_ref(xc, w_, zq_, zq_pen_)[2] * g_cn).sum()

    dw, dzq, dzqp = jax.grad(cnt_only, (0, 1, 2))(w, zq, zq)
    assert float(jnp.abs(dw).max()) == 0.0
    assert float(jnp.abs(dzq).max()) == 0.0
    assert float(jnp.abs(dzqp).max()) > 0.0


@pytest.mark.slow           # 8-stage underflow construction recompiles both
#                             graphs; the 3-stage NLL parity stays fast
def test_ref_nll_survives_pass_prob_underflow():
    """A cascade whose TOTAL log pass-probability is below log(FLT_MIN)
    (~-87 nats, e.g. 8 stages at -12 each) must keep the NLL partial
    finite and matching the log-space kernel — the probability-space
    product underflows f32 there, and a naive log(prod) NaNs the y=0 rows
    via 0 * -inf."""
    b, g, t, d = 2, 8, 8, 4
    xc, w, zq = _case(b, g, t, d, seed=7, dead_group=False)
    zq = jnp.full((b, t), -12.0)        # lp_T = -96 nats: prod underflows
    got = cascade_loss(xc, w * 0.0, zq, d_x=d, interpret=True)
    want = cascade_loss_ref(xc, w * 0.0, zq, zq)
    assert np.all(np.isfinite(np.asarray(want[0])))
    np.testing.assert_allclose(np.asarray(want[0]), np.asarray(got[0]),
                               rtol=1e-4, atol=1e-4)
    grads = jax.grad(lambda z: cascade_loss_ref(xc, w * 0.0, z, z)[0].sum())(
        zq)
    assert np.all(np.isfinite(np.asarray(grads)))


def test_zq_pen_primal_is_value_inert():
    """zq_pen only routes gradients: the three partials' VALUES must be
    identical with and without the routing tap."""
    xc, w, zq = _case(3, 8, 3, 24, seed=21)
    plain = cascade_loss_ref(xc, w, zq)
    routed = cascade_loss_ref(xc, w, zq, zq)
    for a, r in zip(routed, plain):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(r))


# ---------------------------------------------------------------------------
# (d) error contracts at the public op.
# ---------------------------------------------------------------------------

def test_op_rank_errors():
    xc, w, zq = _case(2, 4, 2, 8)
    with pytest.raises(ValueError, match="cascade_loss_fused"):
        K.cascade_loss_fused(xc[0], w, zq)
    with pytest.raises(ValueError, match="zq_pen"):
        K.cascade_loss_fused(xc, w, zq, zq[0])


def test_kernel_rejects_bad_packed_width():
    xc, w, zq = _case(2, 4, 2, 8)
    with pytest.raises(AssertionError, match="packed item width"):
        cascade_loss(xc[..., :-1], w, zq, d_x=8, interpret=True)
