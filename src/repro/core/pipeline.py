"""The serving-time hard cascade — ONE implementation, shared by
`core.cascade.hard_cascade_filter` and `serving.CascadeServer`.

The paper's deployed system (§4, Eq 10) runs T chained stage filters:
stage j keeps the top-E[Count_{q,j}] surviving items by cumulative
score. Before this module, core and serving each carried their own
copy of that stage loop (a double argsort per stage); both now call
`run_cascade`, which routes either through the fused Pallas
score+filter kernel (one VMEM pass per query group — see
kernels/cascade_filter/kernel.py) or through the XLA stage chain
below.

All functions are pure and jit-safe; `run_cascade` is the body that
CascadeServer jits end-to-end per shape bucket.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import cascade as C
from repro.kernels import ops as K


# ---------------------------------------------------------------------------
# The pipeline-plan registry — THE single source of truth for serving-mode
# resolution. Every consumer (run_cascade, losses.cascade_forward's scorer
# seam, serving.CascadeSession / CascadeServer, the benches) resolves its
# mode string through resolve_plan, so an unknown plan fails with the SAME
# error everywhere and no module carries its own mode validation.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PipelinePlan:
    """One named way to execute the cascade.

    scorer: (x (B, G, d), w_eff (T, d), zq (B, T), *, interpret=None)
            -> lp (B, G, T) — the shared scoring entry point this plan uses
            (losses.cascade_forward scores through it too).
    fused_filter: run the fully fused score+filter kernel instead of
            scorer + the XLA stage chain.
    """
    name: str
    description: str
    scorer: Callable[..., jax.Array]
    fused_filter: bool = False


def _score_ref(x, w_eff, zq, *, interpret=None):
    del interpret  # the XLA reference has no kernel body to interpret
    return K.cascade_score_batched_ref(x, w_eff, zq)


PLANS: dict[str, PipelinePlan] = {
    "none": PipelinePlan(
        "none", "XLA reference scorer + XLA stage chain", _score_ref),
    "score": PipelinePlan(
        "score", "batched fused Pallas scorer + XLA stage chain",
        K.cascade_score_batched),
    "filter": PipelinePlan(
        "filter", "fully fused score+filter kernel (one VMEM pass)",
        K.cascade_score_batched, fused_filter=True),
}

# Back-compat alias (pre-registry modules iterated this tuple).
FUSED_MODES = tuple(PLANS)


def resolve_plan(name: str) -> PipelinePlan:
    """Resolve a plan name, raising the one shared unknown-plan error."""
    plan = PLANS.get(name)
    if plan is None:
        raise ValueError(f"unknown pipeline plan: {name!r} "
                         f"(expected one of {tuple(PLANS)})")
    return plan


def keep_counts_from_lp(lp: jax.Array, mask: jax.Array,
                        m_q: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Eq-10 expected counts and per-stage keep counts from cumulative log
    pass-probs. lp: (B, G, T), mask: (B, G), m_q: (B,) -> ((B, T), (B, T)).

    Keep counts are the expected counts rescaled from the M_q recalled
    items to the G scored items, bounded by [1, G]."""
    g = mask.shape[-1]
    maskf = mask.astype(jnp.float32)
    n_q = jnp.maximum(maskf.sum(-1), 1.0)
    pp = jnp.exp(lp) * maskf[..., None]
    counts = (m_q.astype(jnp.float32) / n_q)[:, None] * pp.sum(-2)
    n_keep = jnp.clip(
        jnp.ceil(counts * maskf.sum(-1, keepdims=True)
                 / jnp.maximum(m_q[:, None].astype(jnp.float32), 1.0)),
        1.0, float(g))
    return counts, n_keep


def filter_chain(lp: jax.Array, mask: jax.Array,
                 n_keep: jax.Array) -> jax.Array:
    """XLA stage chain: per stage, stable top-n_keep of the current
    survivors by lp[..., j] ('this expected number ... served as the
    threshold for filtering out items in the corresponding stage').

    Returns the per-stage survivor masks (B, G, T)."""
    surv = mask.astype(jnp.float32)
    cols = []
    for j in range(lp.shape[-1]):
        s = jnp.where(surv > 0, lp[..., j], -jnp.inf)
        rank = jnp.argsort(jnp.argsort(-s, axis=-1), axis=-1).astype(jnp.float32)
        surv = surv * (rank < n_keep[:, j:j + 1]).astype(jnp.float32)
        cols.append(surv)
    return jnp.stack(cols, axis=-1)


def run_cascade(params: C.Params, cfg: C.CascadeConfig,
                x: jax.Array, q: jax.Array, mask: jax.Array, m_q: jax.Array,
                *, fused: str = "none",
                interpret: bool | None = None) -> dict[str, jax.Array]:
    """Score + hard-filter a padded (B, G) candidate batch.

    fused names a PLANS entry:
           'none'   — XLA scorer + XLA stage chain (the reference path);
           'score'  — batched fused Pallas scorer, XLA stage chain;
           'filter' — fully fused score+filter kernel (one VMEM pass).

    Returns lp (B, G, T), survivors (B, G, T), scores (B, G),
    expected_counts (B, T), n_keep (B, T), kept_per_stage (B, T)."""
    # Resolve the plan BEFORE any compute: an unknown plan must not cost
    # a scoring setup (w_eff/zq) or surface as a downstream shape error.
    plan = resolve_plan(fused)
    # One scoring formulation for every mode (precomputed w_eff / zq, the
    # kernel's decomposition): the fused and unfused paths must agree not
    # just to tolerance but on every DISCRETE decision (ceil'd keep
    # counts, tie-breaks), which only holds if they run the same float
    # ops in the same order. The bias dot asks for HIGHEST precision: at
    # DEFAULT a TPU rounds w_q to bf16 (~2e-3 relative), which would shift
    # every item's lp by a query-wide offset the kernels cannot undo.
    w_eff = params["w_x"] * jnp.asarray(cfg.masks, jnp.float32)
    zq = (jnp.matmul(q, params["w_q"].T, precision=jax.lax.Precision.HIGHEST)
          + params["b"])
    if plan.fused_filter:
        out = K.cascade_filter(x, w_eff, zq, mask, m_q, interpret=interpret)
        lp, surv = out["lp"], out["survivors"]
        counts, n_keep = out["expected_counts"], out["n_keep"]
    else:
        lp = plan.scorer(x, w_eff, zq, interpret=interpret)
        counts, n_keep = keep_counts_from_lp(lp, mask, m_q)
        surv = filter_chain(lp, mask, n_keep)
    return {
        "lp": lp,
        "survivors": surv,
        "scores": lp[..., -1],
        "expected_counts": counts,
        "n_keep": n_keep,
        "kept_per_stage": surv.sum(1),
    }


def latency_from_counts(counts: jax.Array, m_q: jax.Array,
                        cfg: C.CascadeConfig, latency_scale: float,
                        convention: str = "entering") -> jax.Array:
    """Eq-16 latency model from already-computed expected counts (B, T) —
    the serving pipeline's latency estimate without re-scoring the batch
    (cf. losses.expected_latency_per_query, which scores from params)."""
    t = jnp.asarray(cfg.t, dtype=counts.dtype)
    if convention == "entering":
        entering = jnp.concatenate(
            [m_q[:, None].astype(counts.dtype), counts[:, :-1]], axis=-1)
        lat = (entering * t).sum(-1)
    else:  # as printed in the paper
        lat = (counts * t).sum(-1)
    return latency_scale * lat
