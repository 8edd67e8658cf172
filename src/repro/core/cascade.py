"""The CLOES cascade model (paper §3.1, Eqs 1–3).

A T-stage cascade of logistic classifiers. Stage j uses a fixed binary feature
mask f_{C_j} over the query-item features x and the full query-only features
g(q):

    p_{q,x,j} = sigma( w_{x,j}^T f_{C_j}(x) + w_{q,j}^T g(q) )            (Eq 1)
    p(y=1|q,x) = prod_j p_{q,x,j}                                          (Eq 2)

Parameters are a flat pytree so jax.grad / SGD apply directly. All functions
are pure and jit-safe; shapes use the query-grouped batch layout
(B groups, G items per group).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Params = dict[str, jax.Array]


@dataclasses.dataclass(frozen=True)
class CascadeConfig:
    n_stages: int
    d_x: int
    d_q: int
    # static (T, d_x) binary feature masks — which features each stage sees.
    # Stored as nested tuples so the config is hashable (jit static arg).
    masks: Any = None
    # per-item evaluation cost t_j of each stage (newly-computed features)
    stage_times: Any = None     # tuple (T,)

    def __post_init__(self):
        assert self.masks is not None and self.stage_times is not None
        object.__setattr__(self, "masks",
                           tuple(tuple(float(v) for v in row)
                                 for row in np.asarray(self.masks)))
        object.__setattr__(self, "stage_times",
                           tuple(float(v) for v in np.asarray(self.stage_times)))

    @property
    def t(self) -> np.ndarray:
        return np.asarray(self.stage_times)


def init_params(cfg: CascadeConfig, key: jax.Array, scale: float = 0.01) -> Params:
    """Paper §3.2: 'parameters are first initialized to be random values
    around zero'."""
    kx, kq, kb = jax.random.split(key, 3)
    return {
        "w_x": scale * jax.random.normal(kx, (cfg.n_stages, cfg.d_x)),
        "w_q": scale * jax.random.normal(kq, (cfg.n_stages, cfg.d_q)),
        "b": jnp.zeros((cfg.n_stages,)),
    }


def stage_logits(params: Params, cfg: CascadeConfig,
                 x: jax.Array, q: jax.Array) -> jax.Array:
    """Per-stage pre-sigmoid scores.

    x: (..., d_x) query-item features; q: (..., d_q) query-only features
    (broadcast over the item axis). Returns (..., T).
    """
    masks = jnp.asarray(cfg.masks, dtype=x.dtype)            # (T, d_x)
    w_eff = params["w_x"] * masks                              # (T, d_x)
    zx = jnp.einsum("...d,td->...t", x, w_eff)
    zq = jnp.einsum("...d,td->...t", q, params["w_q"])
    if zq.ndim < zx.ndim:  # q is (B, d_q) while x is (B, G, d_x)
        zq = zq[..., None, :] if zx.ndim - zq.ndim == 1 else zq
    return zx + zq + params["b"]


def stage_probs(params: Params, cfg: CascadeConfig,
                x: jax.Array, q: jax.Array) -> jax.Array:
    """p_{q,x,j} for every stage: (..., T)."""
    return jax.nn.sigmoid(stage_logits(params, cfg, x, q))


def pass_probs(params: Params, cfg: CascadeConfig,
               x: jax.Array, q: jax.Array) -> jax.Array:
    """Cumulative pass probability p_{q,x,pass_k} = prod_{j<=k} p_j (Eq 6).

    Returns (..., T): element k is the probability of passing stages 1..k+1.
    """
    return jnp.cumprod(stage_probs(params, cfg, x, q), axis=-1)


def log_pass_probs(params: Params, cfg: CascadeConfig,
                   x: jax.Array, q: jax.Array) -> jax.Array:
    """log of Eq 6 via log-sigmoid cumsum — numerically stable for the NLL."""
    return jnp.cumsum(jax.nn.log_sigmoid(stage_logits(params, cfg, x, q)), axis=-1)


def final_prob(params: Params, cfg: CascadeConfig,
               x: jax.Array, q: jax.Array) -> jax.Array:
    """p(y=1|q,x) = product over all T stages (Eq 2)."""
    return pass_probs(params, cfg, x, q)[..., -1]


def final_score(params: Params, cfg: CascadeConfig,
                x: jax.Array, q: jax.Array) -> jax.Array:
    """Ranking score = log p(y=1|q,x); monotone in Eq 2, stable."""
    return log_pass_probs(params, cfg, x, q)[..., -1]


# ---------------------------------------------------------------------------
# Serving-time hard cascade: Eq 10 expected counts become stage thresholds.
# ---------------------------------------------------------------------------

def expected_counts_per_query(params: Params, cfg: CascadeConfig,
                              x: jax.Array, q: jax.Array,
                              mask: jax.Array, m_q: jax.Array) -> jax.Array:
    """E[Count_{q,j}] ≈ (M_q / N_q) * sum_i p_pass_j  (Eq 10).

    x: (B, G, d_x), mask: (B, G), m_q: (B,). Returns (B, T).
    """
    pp = pass_probs(params, cfg, x, q) * mask[..., None]   # (B, G, T)
    n_q = jnp.maximum(mask.sum(axis=-1), 1.0)              # (B,)
    return (m_q / n_q)[..., None] * pp.sum(axis=-2)


def actual_cost_per_query(survivors: jax.Array, mask: jax.Array,
                          cfg: CascadeConfig) -> jax.Array:
    """Realized serving cost of the hard cascade, per query group:
    cost = sum_j (#items entering stage j) * t_j, scaled per scored item."""
    t = jnp.asarray(cfg.t)
    entering = jnp.concatenate(
        [mask.sum(-1, keepdims=True), survivors.sum(1)[:, :-1]], axis=-1)  # (B, T)
    return (entering * t).sum(-1)


def latency_from_counts(counts: jax.Array, m_q: jax.Array,
                        cfg: CascadeConfig, latency_scale: float,
                        convention: str = "entering") -> jax.Array:
    """Eq-16 latency model from already-computed expected counts (B, T).

    The one formula training (losses.latency_from_counts_q) and serving
    (the pipeline's expected counts, in serving.session) both use:
    sum_j t_j * E[Count], with 'entering' charging stage j for the items
    entering it (Count_{q,0} = M_q) and 'paper' for those leaving it, as
    Eq 16 is printed."""
    t = jnp.asarray(cfg.t, dtype=counts.dtype)
    if convention == "entering":
        entering = jnp.concatenate(
            [m_q[:, None].astype(counts.dtype), counts[:, :-1]], axis=-1)
        lat = (entering * t).sum(-1)
    else:  # as printed in the paper
        lat = (counts * t).sum(-1)
    return latency_scale * lat
