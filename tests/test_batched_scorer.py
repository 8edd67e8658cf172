"""Parity lockdown for the batched (B, G) cascade scorer (the training
losses' scoring entry point — see kernels/cascade_score/kernel.py).

Pins four contracts, all in Pallas interpret mode:
  (a) the batched kernel matches the XLA reference to a few f32 ulp on
      lp and exactly on the keep counts and survivor masks built from lp,
      across B/G/d/T grids that are not multiples of the block sizes (and
      G=1, and all-padded batch rows);
  (b) the batched backward kernel matches autodiff of the reference
      (<= 1e-5 grad parity through the custom VJP, incl. under vmap/jit);
  (c) the public wrappers reject rank-mismatched inputs with one
      consistent ValueError instead of a pallas_call shape error;
  (d) run_cascade validates its plan up front, and the training forward
      scores through the batched op, never through jax.vmap.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cascade as C
from repro.core import pipeline as P
from repro.data import features as F
from repro.kernels import ops
from repro.kernels.cascade_score.kernel import (BLOCK_ITEMS, SUBLANE,
                                                cascade_score_batched,
                                                cascade_score_batched_bwd)
from repro.kernels.cascade_score.ref import cascade_score_batched_ref


def _case(b, g, d, t, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(b, g, d)), jnp.float32)
    w = jnp.asarray(0.3 * rng.normal(size=(t, d)), jnp.float32)
    zq = jnp.asarray(rng.normal(size=(b, t)), jnp.float32)
    return x, w, zq


# ---------------------------------------------------------------------------
# (a) forward: batched kernel ~= XLA ref on lp, == on every discrete
# decision.
# ---------------------------------------------------------------------------

# lp is a running sum of <= 8 non-positive log-sigmoids, so no cancellation:
# the kernel's sequential stage sum (XLA's cumsum uses a prefix tree past
# three stages) and XLA-CPU's per-fusion choice of vectorized or scalar
# log/exp (observed: 1 ulp, e.g. 5.96e-08 at (d, t) = (8, 1)) move it by a
# few ulp relative to its own magnitude. 1e-6 is 8 ulp.
LP_RTOL = 1e-6


def assert_lp_and_decisions_match_ref(got, ref, seed):
    """lp within LP_RTOL of the reference, and the Eq-10 keep counts and
    per-stage survivor masks derived from each lp exactly equal — the
    decisions run_cascade's plan parity rests on (pipeline.py)."""
    np.testing.assert_allclose(got, ref, rtol=LP_RTOL, atol=0)
    rng = np.random.default_rng(seed)
    b, g, _ = got.shape
    mask = jnp.asarray(rng.random((b, g)) < 0.9, jnp.float32)
    m_q = jnp.asarray(rng.integers(1, 4 * g + 2, b), jnp.float32)
    decisions = []
    for lp in (jnp.asarray(got), jnp.asarray(ref)):
        _, n_keep = P.keep_counts_from_lp(lp, mask, m_q)
        decisions.append((np.asarray(n_keep),
                          np.asarray(P.filter_chain(lp, mask, n_keep))))
    np.testing.assert_array_equal(decisions[0][0], decisions[1][0])
    np.testing.assert_array_equal(decisions[0][1], decisions[1][1])

# B and G deliberately include non-multiples of every block size in play
# (SUBLANE=8 item blocks for small G, BLOCK_ITEMS=512 tiles past that) and
# the degenerate G=1 / B=1 corners.
@pytest.mark.parametrize("b,g", [(1, 1), (3, 7),
                                 pytest.param(2, 64, marks=pytest.mark.slow),
                                 pytest.param(5, 130, marks=pytest.mark.slow),
                                 pytest.param(2, 513, marks=pytest.mark.slow),
                                 pytest.param(16, 256, marks=pytest.mark.slow)])
@pytest.mark.parametrize("d,t", [(24, 3), (8, 1), (40, 5)])
def test_batched_matches_vmap_and_ref_bitwise(b, g, d, t):
    x, w, zq = _case(b, g, d, t, seed=b * 1009 + g * 13 + d)
    got = np.asarray(cascade_score_batched(x, w, zq, interpret=True))
    ref = np.asarray(cascade_score_batched_ref(x, w, zq))
    assert got.shape == (b, g, t)
    assert_lp_and_decisions_match_ref(got, ref, seed=g)


@pytest.mark.slow
def test_batched_block_boundaries():
    """G one below/at/above the sublane block and the BLOCK_ITEMS tile."""
    for g in (SUBLANE - 1, SUBLANE, SUBLANE + 1,
              BLOCK_ITEMS - 1, BLOCK_ITEMS + 1):
        x, w, zq = _case(2, g, 24, 3, seed=g)
        got = np.asarray(cascade_score_batched(x, w, zq, interpret=True))
        ref = np.asarray(cascade_score_batched_ref(x, w, zq))
        assert_lp_and_decisions_match_ref(got, ref, seed=g)


def test_batched_all_padded_rows_are_inert():
    """Rows the RequestBatcher pads (all-zero features AND bias) must not
    perturb the real rows, and must themselves match the reference."""
    x, w, zq = _case(6, 32, 24, 3, seed=0)
    x = x.at[2].set(0.0).at[5].set(0.0)
    zq = zq.at[2].set(0.0).at[5].set(0.0)
    got = np.asarray(cascade_score_batched(x, w, zq, interpret=True))
    ref = np.asarray(cascade_score_batched_ref(x, w, zq))
    np.testing.assert_array_equal(got, ref)
    # a zero row scores log sigmoid(0) = -log 2 cumulatively at every stage
    want_pad = np.cumsum(np.full((32, 3), np.log(0.5), np.float32), axis=-1)
    np.testing.assert_allclose(got[2], want_pad, rtol=1e-6)
    # and removing the padded rows does not change the real rows' bits
    keep = np.asarray([0, 1, 3, 4])
    alone = np.asarray(cascade_score_batched(x[keep], w, zq[keep],
                                             interpret=True))
    np.testing.assert_array_equal(got[keep], alone)


# ---------------------------------------------------------------------------
# (b) backward: the batched Pallas VJP vs autodiff of the reference.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,g,d,t", [
    (1, 1, 8, 1), (3, 7, 24, 3),
    pytest.param(2, 130, 40, 5, marks=pytest.mark.slow),
    pytest.param(4, 64, 24, 3, marks=pytest.mark.slow),
    # one group each, the single-group scorer's old sweep: 7 items at
    # d 5, 64 and 130 at CLOES's d_x, a whole BLOCK_ITEMS tile at
    # MAX_STAGES
    (1, 7, 5, 1), (1, 64, 24, 3), (1, 130, 24, 3), (1, 512, 40, 8)])
def test_batched_backward_kernel_matches_ref_vjp(b, g, d, t):
    x, w, zq = _case(b, g, d, t, seed=b + g + d)
    ct = jnp.asarray(np.random.default_rng(g).normal(size=(b, g, t)),
                     jnp.float32)
    _, vjp = jax.vjp(cascade_score_batched_ref, x, w, zq)
    want = vjp(ct)
    got = cascade_score_batched_bwd(x, w, zq, ct, interpret=True)
    assert [a.shape for a in got] == [x.shape, w.shape, zq.shape]
    # rtol/atol allow f32 reassociation between the kernel's grid-step
    # accumulation and autodiff's single reduction
    for a, want_a in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(want_a),
                                   rtol=1e-4, atol=5e-5)


@pytest.mark.parametrize("b,g,seed", [(3, 48, 11), (1, 50, 1)])
def test_batched_custom_vjp_grads_match_ref_autodiff(b, g, seed):
    """End-to-end grads through ops.cascade_score_batched with
    interpret=True (Pallas forward AND backward) vs plain autodiff of the
    batched reference — parity <= 1e-5."""
    x, w, zq = _case(b, g, 24, 3, seed=seed)

    def loss_pallas(x_, w_, zq_):
        return (ops.cascade_score_batched(x_, w_, zq_,
                                          interpret=True) ** 2).sum()

    def loss_ref(x_, w_, zq_):
        return (cascade_score_batched_ref(x_, w_, zq_) ** 2).sum()

    got = jax.grad(loss_pallas, (0, 1, 2))(x, w, zq)
    want = jax.grad(loss_ref, (0, 1, 2))(x, w, zq)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("outer,b", [
    (2, 3),
    # one group a minibatch, four minibatches: the interpreter's vmap
    # compile is the cost here
    pytest.param(4, 1, marks=pytest.mark.slow)])
def test_batched_custom_vjp_under_jit_and_vmap(outer, b):
    """The op must stay differentiable when jitted and when vmap'd over an
    outer axis (e.g. an ensemble of minibatches sharing the weights)."""
    rng = np.random.default_rng(4)
    xs = jnp.asarray(rng.normal(size=(outer, b, 16, 24)), jnp.float32)
    zs = jnp.asarray(rng.normal(size=(outer, b, 3)), jnp.float32)
    w = jnp.asarray(0.3 * rng.normal(size=(3, 24)), jnp.float32)

    def loss(fn, w_):
        return jax.vmap(lambda xb, zb: fn(xb, w_, zb))(xs, zs).sum()

    g_pl = jax.jit(jax.grad(lambda w_: loss(
        lambda *a: ops.cascade_score_batched(*a, interpret=True), w_)))(w)
    g_ref = jax.grad(lambda w_: loss(cascade_score_batched_ref, w_))(w)
    np.testing.assert_allclose(np.asarray(g_pl), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# (c) consistent wrapper errors for rank-mismatched inputs.
# ---------------------------------------------------------------------------

def test_wrappers_reject_rank_mismatch_consistently():
    x2 = jnp.zeros((4, 8))
    x3 = jnp.zeros((2, 4, 8))
    w = jnp.zeros((3, 8))
    zq1 = jnp.zeros((3,))
    zq2 = jnp.zeros((2, 3))
    mask = jnp.zeros((2, 4))
    m_q = jnp.zeros((2,))
    cases = [
        (lambda: ops.cascade_score_batched(x2, w, zq2),
         "cascade_score_batched:"),
        (lambda: ops.cascade_score_batched(x3, w, zq1),
         "cascade_score_batched:"),
        (lambda: ops.cascade_filter(x3, w, zq2, mask[0], m_q),
         "cascade_filter:"),
        (lambda: ops.cascade_filter(x2, w, zq2, mask, m_q),
         "cascade_filter:"),
    ]
    for fn, prefix in cases:
        with pytest.raises(ValueError, match="rank-mismatched inputs"):
            fn()
        try:
            fn()
        except ValueError as e:   # one consistent, op-named message shape
            assert str(e).startswith(prefix)
            assert "expected rank" in str(e)


def test_wrapper_rank_check_sees_per_example_shape_under_vmap():
    """vmap'ing the op over an outer axis of minibatches presents rank-3
    per-example tracers — the check must not fire."""
    x, w, zq = _case(6, 8, 24, 3, seed=1)
    out = jax.vmap(lambda xb, zb: ops.cascade_score_batched(
        xb, w, zb, interpret=True))(x.reshape(2, 3, 8, 24),
                                    zq.reshape(2, 3, 3))
    assert out.shape == (2, 3, 8, 3)


# ---------------------------------------------------------------------------
# (d) pipeline integration: up-front plan validation; the training forward.
# ---------------------------------------------------------------------------

def _pipeline_case(seed=0, b=4, g=48):
    masks = F.default_stage_masks(3)
    cfg = C.CascadeConfig(3, F.N_FEATURES, F.N_QUERY_BUCKETS, masks,
                          F.stage_costs(masks))
    params = C.init_params(cfg, jax.random.PRNGKey(seed), scale=0.3)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(b, g, cfg.d_x)), jnp.float32)
    q = jnp.asarray(np.eye(cfg.d_q)[rng.integers(0, 8, b)], jnp.float32)
    mask = jnp.asarray(rng.random((b, g)) < 0.9, jnp.float32)
    m_q = jnp.asarray(rng.integers(10, 3000, b), jnp.float32)
    return params, cfg, x, q, mask, m_q


@pytest.mark.parametrize("plan", ["bogus", "score"])
def test_run_cascade_rejects_unknown_mode_before_computing(plan):
    """The plan check must fire before w_eff/zq are computed — garbage
    params that would blow up the scoring setup must not be touched. The
    old batched-scorer plan 'score' is gone and fails the same way."""
    _, cfg, x, q, mask, m_q = _pipeline_case()
    bad_params = {"w_x": jnp.zeros((1, 2))}     # would KeyError/shape-error
    with pytest.raises(ValueError, match=f"unknown pipeline plan: '{plan}'"):
        P.run_cascade(bad_params, cfg, x, q, mask, m_q, fused=plan)


def test_cascade_forward_scores_through_batched_entry_point(monkeypatch):
    """The trainer's fused forward must score through the batched op —
    and never jax.vmap — for both the primal and the penalty-variant
    scorer."""
    from repro.core import losses as L
    calls = []
    real = ops.cascade_score_batched

    def spy(x, w_eff, zq, **kw):
        calls.append(x.shape)
        return real(x, w_eff, zq, **kw)

    monkeypatch.setattr(ops, "cascade_score_batched", spy)

    def boom(*a, **k):                          # any vmap use is a fail
        raise AssertionError("cascade_forward must not use jax.vmap")

    monkeypatch.setattr(L.jax, "vmap", boom)
    params, cfg, x, q, *_ = _pipeline_case(seed=5)
    lp, lp_pen = L.cascade_forward(params, cfg, x, q, penalty_variant=True)
    assert len(calls) == 2 and lp.shape == lp_pen.shape == x.shape[:2] + (3,)
