"""Pure-jnp oracle for the fused cascade scorer.

Computes, for every item of every query group, the per-stage cumulative
log pass-probability of the CLOES cascade (Eqs 1-2, 6):

    logit[b, i, j] = x[b, i] . w_eff[j] + zq[b, j]
    out[b, i, j]   = sum_{k<=j} log sigmoid(logit[b, i, k])

w_eff is the stage weight vector already masked by the stage feature mask;
zq[b, j] = w_q[j] . g(q_b) + b[j] is the per-stage query-side bias of
group b.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def cascade_score_batched_ref(x: jax.Array, w_eff: jax.Array,
                              zq: jax.Array) -> jax.Array:
    """x (B, G, d), w_eff (T, d), zq (B, T) -> (B, G, T) f32.

    Both the parity oracle for the batched Pallas kernel and the
    production non-TPU path (natively autodiff-able, see kernels/ops.py).
    """
    logits = (jnp.einsum("bgd,td->bgt", x.astype(jnp.float32),
                         w_eff.astype(jnp.float32))
              + zq.astype(jnp.float32)[:, None, :])
    return jnp.cumsum(jax.nn.log_sigmoid(logits), axis=-1)
