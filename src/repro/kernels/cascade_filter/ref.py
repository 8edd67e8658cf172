"""Pure-jnp oracle for the fused cascade filter — and the XLA fallback the
serving path dispatches to on non-TPU backends (see kernels/ops.py).

Semantics (must stay bit-compatible with kernel.py):

    lp[i, j]    = sum_{k<=j} log sigmoid(x[i] . w_eff[k] + zq[k])
    counts[j]   = (M_q / N_q) * sum_{i valid} exp(lp[i, j])          (Eq 10)
    n_keep[j]   = clip(ceil(counts[j] * N_q / max(M_q, 1)), 1, G)
    surv_j      = top-n_keep[j] of surv_{j-1} by lp[., j], STABLE
                  descending order (ties keep the lowest index)

The keep-count and stage-chain semantics are core.pipeline's
keep_counts_from_lp / filter_chain — imported, not copied, since this
function doubles as the production non-TPU path and must never fork
from the pipeline. filter_chain's stable top-k is the double argsort,
the very construct the kernel replaces with a counting select, so the
parity sweeps still compare two algorithmically independent
formulations of the selection.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.jit
def cascade_filter_ref(x: jax.Array, w_eff: jax.Array, zq: jax.Array,
                       mask: jax.Array, m_q: jax.Array) -> dict[str, jax.Array]:
    """x: (B, G, d), w_eff: (T, d), zq: (B, T), mask: (B, G), m_q: (B,).

    Returns the same dict as kernel.cascade_filter.
    """
    # local import: kernels.ops -> this module; core.pipeline -> kernels.ops
    from repro.core.pipeline import filter_chain, keep_counts_from_lp
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
