"""The serving-time hard cascade — ONE implementation, shared by the
serving session and the offline tables.

The paper's deployed system (§4, Eq 10) runs T chained stage filters:
stage j keeps the top-E[Count_{q,j}] surviving items by cumulative
score. `run_cascade` runs them in one of two plans:

    'filter' — the fused Pallas score+filter kernel, one VMEM pass per
               query group (kernels/cascade_filter/kernel.py): what the
               service runs;
    'none'   — the XLA reference: the reference scorer, then the
               keep-count and stage-chain semantics of
               kernels/cascade_filter/ref.py, which the tests and the
               chip smoke compare the kernel against.

All functions are pure and jit-safe; `run_cascade` is the body the
serving session jits end-to-end per shape bucket.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import cascade as C
from repro.kernels import ops as K
from repro.kernels.cascade_filter.ref import filter_chain, keep_counts_from_lp

PLAN_NAMES = ("filter", "none")


def check_plan(plan: str) -> None:
    """The one unknown-plan error, raised by run_cascade and by the
    serving session at construction."""
    if plan not in PLAN_NAMES:
        raise ValueError(f"unknown pipeline plan: {plan!r} "
                         f"(expected one of {PLAN_NAMES})")


def run_cascade(params: C.Params, cfg: C.CascadeConfig,
                x: jax.Array, q: jax.Array, mask: jax.Array, m_q: jax.Array,
                *, fused: str = "none",
                interpret: bool | None = None) -> dict[str, jax.Array]:
    """Score + hard-filter a padded (B, G) candidate batch.

    fused names the plan: 'filter' (the fused kernel) or 'none' (the XLA
    reference scorer and stage chain).

    Returns lp (B, G, T), survivors (B, G, T), scores (B, G),
    expected_counts (B, T), n_keep (B, T), kept_per_stage (B, T)."""
    # Check the plan BEFORE any compute: an unknown plan must not cost
    # a scoring setup (w_eff/zq) or surface as a downstream shape error.
    check_plan(fused)
    # One scoring formulation for both plans (precomputed w_eff / zq, the
    # kernel's decomposition): the fused and unfused paths must agree not
    # just to tolerance but on every DISCRETE decision (ceil'd keep
    # counts, tie-breaks), which only holds if they run the same float
    # ops in the same order. The bias dot asks for HIGHEST precision: at
    # DEFAULT a TPU rounds w_q to bf16 (~2e-3 relative), which would shift
    # every item's lp by a query-wide offset the kernels cannot undo.
    w_eff = params["w_x"] * jnp.asarray(cfg.masks, jnp.float32)
    zq = (jnp.matmul(q, params["w_q"].T, precision=jax.lax.Precision.HIGHEST)
          + params["b"])
    if fused == "filter":
        out = K.cascade_filter(x, w_eff, zq, mask, m_q, interpret=interpret)
        lp, surv = out["lp"], out["survivors"]
        counts, n_keep = out["expected_counts"], out["n_keep"]
    else:
        lp = K.cascade_score_batched_ref(x, w_eff, zq)
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
