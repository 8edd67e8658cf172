"""CLOES objectives (paper §3.2–3.3, Eqs 4–17) — single-forward engine.

All losses take the query-grouped batch layout: x (B, G, d_x), q (B, d_q),
y/mask/price/behavior (B, G), m_q (B,). Every term is differentiable and the
full L3 objective is a single scalar optimized by SGD (paper §3.2).

Every objective derives from ONE shared cascade forward: `cascade_forward`
computes the (B, G, T) cumulative log pass-probabilities once — through the
fused scorer (kernels.ops.cascade_score_batched, a custom-VJP Pallas
kernel with a 2-D (batch, item-block) grid on TPU, the jitted XLA
reference elsewhere) — plus the one stop-gradient variant L3's w_q-only
penalty routing needs. NLL (Eq 4/17), expected cost (Eq 8), per-query
counts (Eq 10) and the size and latency penalties (Eqs 14–16) are all
cheap reductions of that tensor; the pre-refactor implementation
re-scored the batch four times per L3 step.

The deployed L3 objective goes one step further: it runs through
kernels.ops.cascade_loss_fused, which emits those per-item reductions from
the SAME VMEM pass that computes the scores (and bakes the penalty
stop-gradient routing into its VJP), collapsing the score-then-many-small-
reductions step graph into one kernel launch. The unfused graph is
loss_l3_unfused, the reference the fused objective is tested against.

Engine-batch protocol: every batch term that does not depend on the params
is a pure function of (log, lcfg), so the scan trainer precomputes it ONCE
per fit (see trainer._engine_pack) and ships it in the batch under the
optional keys

    wgt      (B, G)  Eq-17 importance weights (from behavior/price)
    cost_w   (B, G)  Eq-8 cost weights: mask [* (1-y)] * (M_q / N_q)
    mn       (B,)    Eq-10 extrapolation factor M_q / N_q
    n_o_eff  (B,)    min(N_o, M_q) result-size floor
    xc       (B, G, d_x+4)  the packed [x | y | mask | wgt | cost_w] item
                     tensor itself — exactly what kernels.ops.
                     cascade_loss_fused consumes (the fused L3 default)

The losses use these when present and fall back to computing them from the
raw batch (behavior/price/mask/y/m_q) otherwise — same float ops either
way, so the two paths are value-identical.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import cascade as C
from repro.data.synthetic import BEHAVIOR_CLICK, BEHAVIOR_PURCHASE
from repro.kernels import ops as K
from repro.kernels.cascade_loss.kernel import pack_items


@dataclasses.dataclass(frozen=True)
class LossConfig:
    alpha: float = 1e-4      # l2 regularization (Eq 5)
    beta: float = 1.0        # CPU-cost trade-off (Eq 9); paper sweeps 1..10
    delta: float = 1.0       # result-size penalty weight (Eq 15); paper: 1
    eps_latency: float = 0.05  # latency penalty weight (Eq 15 'epsilon'); paper: 0.05
    gamma: float = 0.05      # smooth-hinge sharpness (Eq 14)
    n_o: float = 200.0       # minimum result size N_o (paper: 200)
    t_l: float = 130.0       # latency budget T_l in ms (paper: 130 ms)
    # Converts per-item cost units to ms. Calibrated jointly with the
    # synthetic recall distribution so (a) the mandatory stage-1 scan of the
    # hottest queries (~5e5 items x 0.05 units) stays well under the 130 ms
    # budget, and (b) an accuracy-tuned cascade WITHOUT the UX penalties
    # lands hot queries near the paper's pre-CLOES ~170 ms (Fig 4).
    latency_scale: float = 0.0015
    # importance weights (Eq 17)
    eps_purchase: float = 1.0  # 'epsilon': purchase weight multiplier (paper: 10)
    mu_price: float = 1.0      # 'mu': price weight multiplier (paper sweeps 1..4)
    # Eq 16 as printed uses t_j * E[Count_{q,j}]; 'entering' uses the Eq-8
    # convention t_j * E[Count_{q,j-1}] with Count_{q,0} = M_q (items entering
    # stage j pay t_j). The printed form omits the mandatory stage-1 scan of
    # all M_q recalled items, which physically dominates hot-query latency
    # (Fig 4), so we default to 'entering' and treat Eq 16's index as a typo.
    latency_convention: str = "entering"
    # Beyond-paper refinement: count the expected cost (Eq 8) over NEGATIVE
    # instances only. Positives are ~9% of instances (and the items we *want*
    # to pay for), so this changes T(w) by <10% while removing the Eq-8
    # pathology where the cost gradient preferentially suppresses confident
    # positives' pass-probabilities and inverts early-stage ranking at
    # intermediate beta (see EXPERIMENTS.md §Perf, cascade-objective study).
    cost_mask_positives: bool = False


# ---------------------------------------------------------------------------
# The shared forward: one fused scoring pass (+ the L3 penalty variant).
# ---------------------------------------------------------------------------

def cascade_forward(params: C.Params, cfg: C.CascadeConfig,
                    x: jax.Array, q: jax.Array, *,
                    penalty_variant: bool = False
                    ) -> tuple[jax.Array, jax.Array | None]:
    """(B, G, T) cumulative log pass-probabilities through the fused scorer.

    x: (B, G, d_x), q: (B, d_q). Scores through
    kernels.ops.cascade_score_batched: one 2-D (batch, item-block) grid,
    no jax.vmap wrapping.

    With penalty_variant, also returns the stop-gradient routing L3's UX
    penalties need: the same primal values, but with w_x and b held
    constant so penalty gradients flow only into the query-only weights
    w_q (see loss_l3). The x-side matmul dominates the forward; the
    variant re-runs only the scorer on already-computed inputs with the
    gradient taps moved, not a new loss formulation.
    """
    masks = jnp.asarray(cfg.masks, dtype=x.dtype)
    w_eff = params["w_x"] * masks                                   # (T, d_x)
    zq = q @ params["w_q"].T + params["b"]                          # (B, T)
    lp = K.cascade_score_batched(x, w_eff, zq)
    if not penalty_variant:
        return lp, None
    w_pen = jax.lax.stop_gradient(w_eff)
    zq_pen = q @ params["w_q"].T + jax.lax.stop_gradient(params["b"])
    lp_pen = K.cascade_score_batched(x, w_pen, zq_pen)
    return lp, lp_pen


# ---------------------------------------------------------------------------
# Eq 17 — importance weights for multi-behavior e-commerce effectiveness.
# ---------------------------------------------------------------------------

def importance_weights(behavior: jax.Array, price: jax.Array,
                       lcfg: LossConfig) -> jax.Array:
    """wgt_i = eps*mu*log(price) (purchase) | mu*log(price) (click) | 1."""
    logp = jnp.log(jnp.maximum(price, 1.0 + 1e-6))  # guard: log(price) >= ~0
    w_click = lcfg.mu_price * logp
    w_buy = lcfg.eps_purchase * w_click
    return jnp.where(behavior == BEHAVIOR_PURCHASE, w_buy,
                     jnp.where(behavior == BEHAVIOR_CLICK, w_click, 1.0))


# ---------------------------------------------------------------------------
# Derivations from the shared forward. Each takes the (B, G, T) cumulative
# log pass-probs `lp` and reduces — no re-scoring.
# ---------------------------------------------------------------------------

def _batch_wgt(batch, lcfg: LossConfig):
    """Eq-17 weights: precomputed engine column, or derived from the raw
    batch; None when the batch carries no behavior signal (unweighted)."""
    wgt = batch.get("wgt")
    if wgt is None and batch.get("behavior") is not None:
        wgt = importance_weights(batch["behavior"], batch["price"], lcfg)
    return wgt


def nll_from_lp(lp: jax.Array, y, mask, wgt=None) -> jax.Array:
    """-l(w): negative (importance-weighted) log-likelihood, Eqs 4/17.

    log p_i = lp[..., -1] is already the stable log-sigmoid cumsum;
    log(1 - p_i) is computed via log1p(-exp(log_p)) with clamping.
    """
    log_p = jnp.minimum(lp[..., -1], -1e-7)                    # keep 1-p > 0
    log_1mp = jnp.log1p(-jnp.exp(log_p))
    ll = y * log_p + (1.0 - y) * log_1mp
    if wgt is not None:
        ll = ll * wgt
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def _cost_core(lp: jax.Array, cfg: C.CascadeConfig, w, n) -> jax.Array:
    """Eq-8 reduction given ready cost weights w (B, G) and normalizer n."""
    pp = jnp.exp(lp) * w[..., None]                            # (B, G, T)
    counts = jnp.concatenate([n[None], pp.sum(axis=(0, 1))[:-1]])  # (T,)
    t = jnp.asarray(cfg.t, dtype=lp.dtype)                     # (T,)
    return (counts * t).sum() / n


def cost_from_lp(lp: jax.Array, cfg: C.CascadeConfig,
                 mask, y=None, m_q=None) -> jax.Array:
    """T(w) = sum_{j=0}^{T-1} E[Count_j] * t_{j+1}  (Eq 8), normalized per
    INDEX item so beta is scale-free across batch sizes.

    E[Count_j] is computed in index-item units via the Eq-10 extrapolation
    (each logged instance of query q stands for M_q/N_q recalled items).
    The paper notes "the off-line evaluation cost is quite consistent with
    the online cost" — that only holds with this extrapolation: a hot query
    recalls ~5e5 items and owns essentially all the CPU; a tail query's 50
    items are free. Without it, the cost term fights the result-size floor
    on tail queries (whose real cost is negligible) and destroys them.

    With y given (cost_mask_positives), only negative instances contribute
    gradient pressure — see LossConfig.cost_mask_positives.
    E[Count_0] = sum_q M_q (every recalled item enters stage 1).
    """
    w = mask if y is None else mask * (1.0 - y)
    if m_q is not None:
        n_q = jnp.maximum(mask.sum(axis=-1), 1.0)              # (B,)
        w = w * (m_q / n_q)[:, None]
        n = jnp.maximum(m_q.sum(), 1.0)
    else:
        n = jnp.maximum(mask.sum(), 1.0)
    return _cost_core(lp, cfg, w, n)


def counts_from_lp(lp: jax.Array, mask, m_q, mn=None) -> jax.Array:
    """E[Count_{q,j}] ≈ (M_q / N_q) * sum_i p_pass_j  (Eq 10). Returns (B, T).

    mn is the precomputed M_q / N_q engine column (see module docstring)."""
    pp = jnp.exp(lp) * mask[..., None]                         # (B, G, T)
    if mn is None:
        mn = m_q / jnp.maximum(mask.sum(axis=-1), 1.0)         # (B,)
    return mn[..., None] * pp.sum(axis=-2)


def latency_from_counts_q(counts: jax.Array, m_q, cfg: C.CascadeConfig,
                          lcfg: LossConfig) -> jax.Array:
    """E[Latency_{q,T}] = sum_j t_j * E[Count_{q,·}]  (Eq 16). Returns (B,).

    Shares core.cascade.latency_from_counts with the serving session —
    training and serving estimate latency from counts with the same code.
    """
    return C.latency_from_counts(counts, m_q, cfg, lcfg.latency_scale,
                               lcfg.latency_convention)


# ---------------------------------------------------------------------------
# Standalone term APIs (evaluation / benchmarks). Each runs ONE forward and
# derives — same signatures and values as the pre-refactor implementations.
# ---------------------------------------------------------------------------

def weighted_nll(params: C.Params, cfg: C.CascadeConfig, lcfg: LossConfig,
                 x, q, y, mask, behavior=None, price=None) -> jax.Array:
    """-l(w): negative (importance-weighted) log-likelihood, Eqs 4/17."""
    lp, _ = cascade_forward(params, cfg, x, q)
    wgt = (importance_weights(behavior, price, lcfg)
           if behavior is not None else None)
    return nll_from_lp(lp, y, mask, wgt)


def l2_penalty(params: C.Params, lcfg: LossConfig) -> jax.Array:
    """alpha * ||w||_2^2 (Eq 5)."""
    leaves = jax.tree_util.tree_leaves(params)
    return lcfg.alpha * sum(jnp.sum(l ** 2) for l in leaves)


def expected_cost(params: C.Params, cfg: C.CascadeConfig,
                  x, q, mask, y=None, m_q=None) -> jax.Array:
    """T(w) (Eq 8) from a fresh forward — see cost_from_lp for the math."""
    lp, _ = cascade_forward(params, cfg, x, q)
    return cost_from_lp(lp, cfg, mask, y, m_q)


def expected_latency_per_query(params: C.Params, cfg: C.CascadeConfig,
                               lcfg: LossConfig, x, q, mask, m_q) -> jax.Array:
    """E[Latency_{q,T}] (Eq 16) from a fresh forward. Returns (B,)."""
    lp, _ = cascade_forward(params, cfg, x, q)
    return latency_from_counts_q(counts_from_lp(lp, mask, m_q), m_q, cfg, lcfg)


# ---------------------------------------------------------------------------
# Eq 14 — smooth hinge g'(z, N_o) = (1/gamma) ln(1 + exp(gamma (N_o - z))).
# ---------------------------------------------------------------------------

def smooth_hinge(z: jax.Array, target: jax.Array, gamma: float) -> jax.Array:
    """Differentiable approximation of max(target - z, 0); -> hinge as gamma↑."""
    return jax.nn.softplus(gamma * (target - z)) / gamma


# ---------------------------------------------------------------------------
# Full objectives L1 (Eq 5), L2 (Eq 9), L3 (Eq 15) — one forward each.
# ---------------------------------------------------------------------------

def _nll_cost_from_lp(lp, cfg: C.CascadeConfig, lcfg: LossConfig,
                      batch) -> tuple[jax.Array, jax.Array]:
    """(NLL, Eq-8 cost) from the shared forward's lp — the L2/L3 core,
    using the engine batch's precomputed cost weights when present."""
    nll = nll_from_lp(lp, batch["y"], batch["mask"], _batch_wgt(batch, lcfg))
    cost_w = batch.get("cost_w")
    if cost_w is not None:                 # engine batch: weights precomputed
        cost = _cost_core(lp, cfg, cost_w,
                          jnp.maximum(batch["m_q"].sum(), 1.0))
    else:
        y_for_cost = batch["y"] if lcfg.cost_mask_positives else None
        cost = cost_from_lp(lp, cfg, batch["mask"], y_for_cost,
                            batch.get("m_q"))
    return nll, cost


def _l2_from_lp(params, lp, cfg: C.CascadeConfig, lcfg: LossConfig,
                batch) -> jax.Array:
    """L2 (Eq 9) given the shared forward's lp."""
    nll, cost = _nll_cost_from_lp(lp, cfg, lcfg, batch)
    return nll + l2_penalty(params, lcfg) + lcfg.beta * cost


def loss_l1(params, cfg: C.CascadeConfig, lcfg: LossConfig, batch) -> jax.Array:
    lp, _ = cascade_forward(params, cfg, batch["x"], batch["q"])
    return (nll_from_lp(lp, batch["y"], batch["mask"],
                        _batch_wgt(batch, lcfg))
            + l2_penalty(params, lcfg))


def loss_l2(params, cfg: C.CascadeConfig, lcfg: LossConfig, batch) -> jax.Array:
    lp, _ = cascade_forward(params, cfg, batch["x"], batch["q"])
    return _l2_from_lp(params, lp, cfg, lcfg, batch)


def _l3_tail(params, cfg: C.CascadeConfig, lcfg: LossConfig,
             nll, cost, counts_pen, m_q, n_o) -> jax.Array:
    """Assemble Eq 15 from the already-reduced terms: the UX hinges over the
    per-query penalty counts + the shared NLL / l2 / Eq-8 cost core.

    result-size floor: penalize E[Count_{q,T}] < N_o — but never ask for more
    results than the query recalls (tail queries with M_q < N_o are exempt
    up to their recall size). Eq 11 introduces one slack xi_i per *instance*,
    so the penalty is (with equal-size query groups) a mean over queries;
    the penalty unit is "missing results" — normalized by N_o so delta is
    scale-free against the per-instance NLL. The latency cap
    g'(T_l, Latency) penalizes Latency > T_l (unit: excess ms)."""
    size_pen = smooth_hinge(counts_pen[:, -1], n_o, lcfg.gamma).mean()
    lat = latency_from_counts_q(counts_pen, m_q, cfg, lcfg)
    lat_pen = smooth_hinge(jnp.full_like(lat, lcfg.t_l), lat, lcfg.gamma).mean()
    return (nll + l2_penalty(params, lcfg) + lcfg.beta * cost
            + lcfg.delta * size_pen + lcfg.eps_latency * lat_pen)


def loss_l3(params, cfg: C.CascadeConfig, lcfg: LossConfig,
            batch) -> jax.Array:
    """The deployed CLOES objective (Eq 15), through ONE
    kernels.ops.cascade_loss_fused call.

    Gradient routing: the two user-experience penalties adjust only the
    query-only parameters w_q. The paper states the query-only feature
    "is used to control the magnitude of the prediction probability (thus to
    control the result number and cost per query) but does not affect the
    rank order". Letting the penalties push the *item* weights w_x (or the
    global bias b, which the cost term then fights via w_x) saturates
    tail-query probabilities and inverts within-query ordering — so w_x and b
    are stop-gradient'd inside the penalty terms: per-query size/latency
    control lives entirely in the per-recall-bucket weights w_q.

    The op computes the logits once and emits the three per-group partial
    reductions (NLL terms, Eq-8 cost accumulators, Eq-10 keep counts) in
    the same VMEM pass — everything left here is O(B*T). The Eq-15
    stop-gradient routing above is baked into the op's VJP: zq_pen is the
    gradient tap the counts stream flows into, so the value-identical
    penalty-variant re-scoring pass of the unfused graph disappears
    entirely.

    Engine batches (trainer._engine_pack) arrive with the wgt/cost_w/mn/
    n_o_eff columns precomputed AND the packed [x | y | mask | wgt |
    cost_w] item tensor itself under "xc" — the kernel consumes it with
    zero per-step re-packing. Raw batches derive the columns and pack here
    (same float ops, value-identical)."""
    x, q, y = batch["x"], batch["q"], batch["y"]
    mask, m_q = batch["mask"], batch["m_q"]
    mn = batch.get("mn")
    if mn is None:
        mn = m_q / jnp.maximum(mask.sum(axis=-1), 1.0)
    n_o = batch.get("n_o_eff")
    if n_o is None:
        n_o = jnp.minimum(lcfg.n_o, m_q.astype(x.dtype))
    xc = batch.get("xc")
    if xc is None:
        wgt = _batch_wgt(batch, lcfg)
        if wgt is None:
            wgt = jnp.ones_like(mask)
        cost_w = batch.get("cost_w")
        if cost_w is None:
            base = mask * (1.0 - y) if lcfg.cost_mask_positives else mask
            cost_w = base * mn[:, None]
        xc = pack_items(x, y, mask, wgt, cost_w)
    masks = jnp.asarray(cfg.masks, dtype=x.dtype)
    w_eff = params["w_x"] * masks                                   # (T, d_x)
    zq = q @ params["w_q"].T + params["b"]                          # (B, T)
    zq_pen = q @ params["w_q"].T + jax.lax.stop_gradient(params["b"])
    ll, cost_pp, cnt_pp = K.cascade_loss_fused(xc, w_eff, zq, zq_pen)
    nll = -ll.sum() / jnp.maximum(mask.sum(), 1.0)
    n = jnp.maximum(m_q.sum(), 1.0)
    counts = jnp.concatenate([n[None], cost_pp[:-1]])               # (T,)
    cost = (counts * jnp.asarray(cfg.t, dtype=ll.dtype)).sum() / n
    counts_pen = mn[:, None] * cnt_pp                               # (B, T)
    return _l3_tail(params, cfg, lcfg, nll, cost, counts_pen, m_q, n_o)


def loss_l3_unfused(params, cfg: C.CascadeConfig, lcfg: LossConfig,
                    batch) -> jax.Array:
    """loss_l3 as the score-then-reduce graph: the scorer's shared forward
    and its penalty variant (both penalties reducing lp_pen), then the
    NLL, Eq-8 cost and Eq-15 tail reductions one by one. The reference the
    fused objective must match in value and gradients; not a training
    option."""
    x, q, mask, m_q = batch["x"], batch["q"], batch["mask"], batch["m_q"]
    lp, lp_pen = cascade_forward(params, cfg, x, q, penalty_variant=True)
    counts_pen = counts_from_lp(lp_pen, mask, m_q, batch.get("mn"))  # (B, T)
    n_o = batch.get("n_o_eff")
    if n_o is None:
        n_o = jnp.minimum(lcfg.n_o, m_q.astype(x.dtype))
    nll, cost = _nll_cost_from_lp(lp, cfg, lcfg, batch)
    return _l3_tail(params, cfg, lcfg, nll, cost, counts_pen, m_q, n_o)


LOSSES = {"l1": loss_l1, "l2": loss_l2, "l3": loss_l3}
