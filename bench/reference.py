"""Plain float32 references for what the benchmark compares.

Written from the paper (CLOES, KDD 2017, Eqs 1-2, 6, 8, 10, 14-17) and the
configuration files, in straightforward jax.numpy. Nothing here imports
the program or takes anything it made: weights, stage masks, stage costs
and loss constants come from the benchmark's own configuration file and
seed, and the inputs are the rows the window served or trained on.

`precision` selects how the two matrix products are computed:
  "highest"  float32 products (the reference proper);
  "bf16"     operands rounded to bfloat16, products accumulated in
             float32: the one-pass precision that a TPU uses for a float32
             product at DEFAULT precision. This is the control, the step
             below the configuration's float32 that a later change would be
             tempted to take; it has to fail the comparison.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "bf16")

# Eq 4 clamp: log p kept <= -1e-7 so that 1 - p stays positive in f32.
LOG_P_CLAMP = -1e-7
BEHAVIOR_CLICK, BEHAVIOR_PURCHASE = 1, 2


def _dot(spec: str, a, b, precision: str):
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "bf16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    raise ValueError(f"unknown precision {precision!r}; expected one of "
                     f"{PRECISIONS}")


# ---------------------------------------------------------------------------
# Serving: the hard cascade (Eqs 1-2, 6, 10)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("precision",))
def cascade_rank(w_x, w_q, b, masks, x, q, valid, m_q, *,
                 precision: str = "highest") -> dict:
    """Rank R padded requests through the T-stage hard cascade.

    w_x (T, d), w_q (T, d_q), b (T,), masks (T, d) 0/1 feature sets;
    x (R, G, d), q (R, d_q), valid (R, G) 0/1, m_q (R,) recalled counts.

    Stage j scores lp_j = sum_{k<=j} log sigmoid(x . (w_x,k * mask_k)
    + q . w_q,k + b_k) (Eqs 1-2, 6), expects E[Count_j] = (M_q / N_q) *
    sum_valid exp(lp_j) items (Eq 10), keeps ceil of that count rescaled
    to the N_q scored items, bounded by [1, G], and keeps the best that
    many of the previous stage's survivors, ties to the lower index.

    Returns lp (R, G, T), keep (R, T) the rescaled count before the
    ceiling, n_keep (R, T), and survivors (R, G, T) as 0/1."""
    x = x.astype(jnp.float32)
    valid = valid.astype(jnp.float32)
    m_q = m_q.astype(jnp.float32)
    w_eff = w_x.astype(jnp.float32) * masks.astype(jnp.float32)
    zq = _dot("rd,td->rt", q.astype(jnp.float32), w_q.astype(jnp.float32),
              precision) + b.astype(jnp.float32)
    logits = _dot("rgd,td->rgt", x, w_eff, precision) + zq[:, None, :]
    lp = jnp.cumsum(jax.nn.log_sigmoid(logits), axis=-1)
    n_q = valid.sum(-1)
    counts = ((m_q / jnp.maximum(n_q, 1.0))[:, None]
              * (jnp.exp(lp) * valid[..., None]).sum(1))
    keep = counts * n_q[:, None] / jnp.maximum(m_q, 1.0)[:, None]
    g = x.shape[1]
    n_keep = jnp.clip(jnp.ceil(keep), 1.0, float(g))
    alive = valid > 0
    surv = []
    for j in range(lp.shape[-1]):
        s = jnp.where(alive, lp[..., j], -jnp.inf)
        order = jnp.argsort(-s, axis=-1, stable=True)
        rank = jnp.argsort(order, axis=-1)
        alive = alive & (rank < n_keep[:, j:j + 1])
        surv.append(alive)
    return {"lp": lp, "keep": keep, "n_keep": n_keep,
            "survivors": jnp.stack(surv, -1).astype(jnp.float32)}


# ---------------------------------------------------------------------------
# Training: the L3 objective (Eq 15) and momentum SGD
# ---------------------------------------------------------------------------

def importance_weights(behavior, price, c: dict):
    """Eq 17: eps * mu * log(price) for a purchase, mu * log(price) for a
    click, 1 otherwise (price floored just above 1 so the log is >= 0)."""
    logp = jnp.log(jnp.maximum(price, 1.0 + 1e-6))
    click = c["mu_price"] * logp
    return jnp.where(behavior == BEHAVIOR_PURCHASE, c["eps_purchase"] * click,
                     jnp.where(behavior == BEHAVIOR_CLICK, click, 1.0))


def _softplus_hinge(z, gamma):
    """Eq 14's smooth hinge, (1 / gamma) ln(1 + exp(gamma z))."""
    return jax.nn.softplus(gamma * z) / gamma


def l3_loss(params: dict, batch: dict, c: dict, precision: str = "highest"):
    """The L3 objective (Eq 15) on one minibatch of query groups.

    params: w_x (T, d), w_q (T, d_q), b (T,). batch: x (B, G, d),
    q (B, d_q), y, behavior, price, mask (B, G), m_q (B,). c: the loss
    constants and the stage masks and costs from the configuration file.

      NLL      importance-weighted log-likelihood of the final stage,
               over valid items (Eqs 4, 17)
      l2       alpha * |params|^2 (Eq 5)
      cost     Eq 8: sum_j t_j * E[items entering stage j] per recalled
               item, each logged item standing for M_q / N_q recalled ones
               (Eq 10's extrapolation), E[entering stage 1] = sum M_q
      size     mean over queries of the hinge on N_o_eff - E[Count_q,T],
               N_o_eff = min(N_o, M_q) (Eqs 11-14)
      latency  mean of the hinge on latency_scale * sum_j t_j *
               E[entering_q,j] - T_l (Eq 16, items entering each stage)

    The two penalties move only the query weights w_q: their scores hold
    w_x and b constant (stop-gradient), as the paper's query-only feature
    sets the result size without changing the order."""
    f32 = jnp.float32
    x = batch["x"].astype(f32)
    q = batch["q"].astype(f32)
    y = batch["y"].astype(f32)
    mask = batch["mask"].astype(f32)
    m_q = batch["m_q"].astype(f32)
    masks = jnp.asarray(c["stage_masks"], f32)
    t = jnp.asarray(c["stage_times"], f32)
    w_eff = params["w_x"] * masks
    zq = _dot("bd,td->bt", q, params["w_q"], precision)
    lp = jnp.cumsum(jax.nn.log_sigmoid(
        _dot("bgd,td->bgt", x, w_eff, precision)
        + (zq + params["b"])[:, None, :]), axis=-1)
    lp_pen = jnp.cumsum(jax.nn.log_sigmoid(
        _dot("bgd,td->bgt", x, jax.lax.stop_gradient(w_eff), precision)
        + (zq + jax.lax.stop_gradient(params["b"]))[:, None, :]), axis=-1)

    wgt = importance_weights(batch["behavior"], batch["price"].astype(f32), c)
    log_p = jnp.minimum(lp[..., -1], LOG_P_CLAMP)
    ll = y * log_p + (1.0 - y) * jnp.log1p(-jnp.exp(log_p))
    nll = -(ll * wgt * mask).sum() / jnp.maximum(mask.sum(), 1.0)

    l2 = c["alpha"] * sum(jnp.sum(v ** 2) for v in params.values())

    mn = m_q / jnp.maximum(mask.sum(-1), 1.0)
    n = jnp.maximum(m_q.sum(), 1.0)
    entering = (jnp.exp(lp) * (mask * mn[:, None])[..., None]).sum((0, 1))
    cost = (jnp.concatenate([n[None], entering[:-1]]) * t).sum() / n

    counts = mn[:, None] * (jnp.exp(lp_pen) * mask[..., None]).sum(1)
    n_o = jnp.minimum(c["n_o"], m_q)
    size_pen = _softplus_hinge(n_o - counts[:, -1], c["gamma"]).mean()
    lat = c["latency_scale"] * (
        jnp.concatenate([m_q[:, None], counts[:, :-1]], -1) * t).sum(-1)
    lat_pen = _softplus_hinge(lat - c["t_l"], c["gamma"]).mean()
    return (nll + l2 + c["beta"] * cost + c["delta"] * size_pen
            + c["eps_latency"] * lat_pen)


@functools.partial(jax.jit, static_argnames=("precision",))
def train_ref(params: dict, mu: dict, steps: dict, c: dict, *,
              precision: str = "highest"):
    """Momentum SGD on L3 over a stack of minibatches (leading axis: the
    step): g = grad L3, mu <- momentum * mu + g, params <- params -
    lr * mu. Returns (params, mu, losses (S,))."""
    def step(carry, batch):
        p, m = carry
        loss, g = jax.value_and_grad(l3_loss)(p, batch, c, precision)
        m = jax.tree.map(lambda a, b: c["momentum"] * a + b, m, g)
        p = jax.tree.map(lambda a, b: a - c["lr"] * b, p, m)
        return (p, m), loss

    (params, mu), losses = jax.lax.scan(step, (params, mu), steps)
    return params, mu, losses
