"""Fused cascade score+filter — Pallas TPU kernel.

One VMEM pass per query group runs the ENTIRE serving-time hard cascade
(paper Eqs 1-2, 6, 10): score every candidate through all T stages,
derive the per-stage Eq-10 keep counts, and chain the per-stage
survivor masks — emitting cumulative log pass-probabilities, survivor
masks, expected counts, and keep counts without ever leaving VMEM.

This replaces the serving path's T× double-argsort (Python stage loop
over (B, G) argsorts) with a single kernel launch over a grid of query
groups.

Kernel memory-layout note — why THRESHOLD/RANK SELECT, not sorts
----------------------------------------------------------------
The TPU has no fast general sort: a (B, G) argsort lowers to a
multi-pass scalar-heavy program, and the serving loop needs TWO of
them per stage (order, then inverse order) just to turn "keep the
top-k by score" into a mask. But the cascade never needs the sorted
ORDER — it only needs, per item, the item's descending stable RANK so
it can be compared against the Eq-10 keep count (a per-group scalar
broadcast into the block). With a whole query group resident in VMEM,
that rank is one all-pairs comparison:

    rank[i] = #{k : s[k] > s[i]}  +  #{k < i : s[k] == s[i]}

i.e. a (G, G) boolean outer comparison reduced along lanes — exactly
the broadcast+reduce shape the 8x128 VPU is built for (and, as a 0/1
matrix product, MXU-friendly). The tie term reproduces the STABLE
argsort tie-break (lowest index wins), so the kernel's survivor sets
are bit-identical to the unfused XLA path's double-argsort, ties
included. G^2 comparisons beat G log G sort passes here because G is
a few hundred (the paper's per-stage working set after recall), the
comparisons vectorize perfectly, and the operands never touch HBM.

Layout (mirrors the feature-major note in cascade_score/kernel.py):
items are mapped one QUERY GROUP per grid step, so the group axis G
must land on lanes for both the score matmul and the (G, G) rank
matrices — G is padded to the 128-lane width, features to sublanes
via the shared d-pad. The stage axis (T <= 8) stays resident as the
minor dim of a (G, T_pad) accumulator; keep counts and expected
counts are (1, T_pad) row vectors broadcast against it. Every per-group
row (bias, mask, m_q, counts, keep counts) travels as a (B, 1, ·) array
in (1, 1, ·) blocks, the block shape Mosaic accepts at any B (see
cascade_score/kernel.py); the stage prefix sum is `stage_cumsum` and the
score dot runs at HIGHEST precision, as in the scorer. Worst case
per block at G = 512: a 512x128 f32 feature tile (256 KiB) plus three
512x512 f32 rank temporaries (3 MiB) — comfortably inside the ~16 MiB
VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.cascade_score.kernel import (HIGHEST, LANE, MAX_STAGES,
                                                stage_cumsum)

MAX_GROUP = 512     # one group per block; (G, G) temps cap the block size


def _kernel(x_ref, w_ref, zq_ref, mask_ref, mq_ref,
            lp_ref, surv_ref, counts_ref, nkeep_ref, *, t: int, g_cap: int):
    """Per-group fused score + Eq-10 keep counts + chained rank-select.

    x: (1, G_pad, d_pad), w: (T_pad, d_pad), zq: (1, 1, T_pad),
    mask: (1, 1, G_pad), mq: (1, 1, 1) ->
    lp/surv: (1, G_pad, T_pad), counts/nkeep: (1, 1, T_pad).
    """
    x = x_ref[0].astype(jnp.float32)                    # (G_pad, d_pad)
    w = w_ref[...].astype(jnp.float32)                  # (T_pad, d_pad)
    zq = zq_ref[0].astype(jnp.float32)                  # (1, T_pad)
    valid = mask_ref[0].astype(jnp.float32)[0]          # (G_pad,)
    m_q = mq_ref[0].astype(jnp.float32)[0, 0]

    # -- fused scorer (same math as cascade_score): one MXU matmul ---------
    logits = jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32) + zq        # (G_pad, T_pad)
    lp = stage_cumsum(jax.nn.log_sigmoid(logits))
    lp_ref[0] = lp

    # -- Eq 10: expected counts -> per-stage keep counts (scalars/stage) ---
    n_q = jnp.maximum(jnp.sum(valid), 1.0)
    pp = jnp.exp(lp) * valid[:, None]                   # pass probs, masked
    counts = (m_q / n_q) * jnp.sum(pp, axis=0)          # (T_pad,)
    n_keep = jnp.clip(jnp.ceil(counts * jnp.sum(valid) / jnp.maximum(m_q, 1.0)),
                      1.0, float(g_cap))
    counts_ref[0] = counts[None, :]
    nkeep_ref[0] = n_keep[None, :]

    # -- chained rank-select: stable descending rank vs broadcast keep -----
    g_pad = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (g_pad, g_pad), 0)   # i
    col = jax.lax.broadcasted_iota(jnp.int32, (g_pad, g_pad), 1)   # k
    surv = valid
    cols = []
    for j in range(MAX_STAGES):
        if j < t:
            s = jnp.where(surv > 0, lp[:, j], -jnp.inf)            # (G_pad,)
            sc, sr = s[:, None], s[None, :]
            higher = (sr > sc).astype(jnp.float32)                 # s_k > s_i
            tie_lo = ((sr == sc) & (col < row)).astype(jnp.float32)
            rank = jnp.sum(higher + tie_lo, axis=1)                # (G_pad,)
            surv = surv * (rank < n_keep[j]).astype(jnp.float32)
            cols.append(surv)
        else:
            cols.append(jnp.zeros_like(surv))
    surv_ref[0] = jnp.stack(cols, axis=-1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def cascade_filter(x: jax.Array, w_eff: jax.Array, zq: jax.Array,
                   mask: jax.Array, m_q: jax.Array,
                   *, interpret: bool = False) -> dict[str, jax.Array]:
    """Fused hard cascade over query groups.

    x: (B, G, d) item features, w_eff: (T, d) mask-gated stage weights,
    zq: (B, T) per-group query-side biases, mask: (B, G) validity,
    m_q: (B,) recalled-item counts.

    Returns dict with lp (B, G, T) cumulative log pass-probs,
    survivors (B, G, T) per-stage 0/1 masks, expected_counts (B, T),
    n_keep (B, T). Pads G to the lane width, d to the lane width, T to
    MAX_STAGES; unpads on return.
    """
    b, g, d = x.shape
    t = w_eff.shape[0]
    assert t <= MAX_STAGES, f"cascade of {t} stages > {MAX_STAGES}"
    assert g <= MAX_GROUP, f"group of {g} items > {MAX_GROUP} (one block/group)"
    g_pad = (-g) % LANE
    d_pad = (-d) % LANE
    xp = jnp.pad(x, ((0, 0), (0, g_pad), (0, d_pad)))
    wp = jnp.pad(w_eff, ((0, MAX_STAGES - t), (0, d_pad)))
    zqp = jnp.pad(zq, ((0, 0), (0, MAX_STAGES - t)))[:, None, :]
    maskp = jnp.pad(mask.astype(jnp.float32), ((0, 0), (0, g_pad)))[:, None, :]
    mqp = m_q.astype(jnp.float32).reshape(b, 1, 1)
    gp = g + g_pad
    dp = d + d_pad
    lp, surv, counts, nkeep = pl.pallas_call(
        functools.partial(_kernel, t=t, g_cap=g),
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, gp, dp), lambda i: (i, 0, 0)),
            pl.BlockSpec((MAX_STAGES, dp), lambda i: (0, 0)),
            pl.BlockSpec((1, 1, MAX_STAGES), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, gp), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, gp, MAX_STAGES), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, gp, MAX_STAGES), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, MAX_STAGES), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, MAX_STAGES), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, gp, MAX_STAGES), jnp.float32),
            jax.ShapeDtypeStruct((b, gp, MAX_STAGES), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, MAX_STAGES), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, MAX_STAGES), jnp.float32),
        ],
        interpret=interpret,
        name="cascade_filter",
    )(xp, wp, zqp, maskp, mqp)
    return {
        "lp": lp[:, :g, :t],
        "survivors": surv[:, :g, :t],
        "expected_counts": counts[:, 0, :t],
        "n_keep": nkeep[:, 0, :t],
    }
