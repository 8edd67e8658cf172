"""Pure-jnp oracle for the fused cascade filter — and the XLA fallback the
serving path dispatches to on non-TPU backends (see kernels/ops.py).

Semantics (must stay bit-compatible with kernel.py):

    lp[i, j]    = sum_{k<=j} log sigmoid(x[i] . w_eff[k] + zq[k])
    counts[j]   = (M_q / N_q) * sum_{i valid} exp(lp[i, j])          (Eq 10)
    n_keep[j]   = clip(ceil(counts[j] * N_q / max(M_q, 1)), 1, G)
    surv_j      = top-n_keep[j] of surv_{j-1} by lp[., j], STABLE
                  descending order (ties keep the lowest index)

keep_counts_from_lp and filter_chain are those keep-count and stage-chain
semantics, defined once here: core.pipeline's XLA reference plan runs
them too, so the production non-TPU path never forks from the pipeline.
filter_chain's stable top-k is the double argsort, the very construct
the kernel replaces with a counting select, so the parity sweeps still
compare two algorithmically independent formulations of the selection.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


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


@jax.jit
def cascade_filter_ref(x: jax.Array, w_eff: jax.Array, zq: jax.Array,
                       mask: jax.Array, m_q: jax.Array) -> dict[str, jax.Array]:
    """x: (B, G, d), w_eff: (T, d), zq: (B, T), mask: (B, G), m_q: (B,).

    Returns the same dict as kernel.cascade_filter.
    """
    logits = (jnp.einsum("bgd,td->bgt", x.astype(jnp.float32),
                         w_eff.astype(jnp.float32))
              + zq.astype(jnp.float32)[:, None, :])
    lp = jnp.cumsum(jax.nn.log_sigmoid(logits), axis=-1)       # (B, G, T)
    counts, n_keep = keep_counts_from_lp(lp, mask, m_q)
    return {
        "lp": lp,
        "survivors": filter_chain(lp, mask, n_keep),
        "expected_counts": counts,
        "n_keep": n_keep,
    }
