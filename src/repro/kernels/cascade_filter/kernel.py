"""Fused cascade score+filter — Pallas TPU kernel.

One VMEM pass per query group runs the ENTIRE serving-time hard cascade
(paper Eqs 1-2, 6, 10): score every candidate through all T stages,
derive the per-stage Eq-10 keep counts, and chain the per-stage
survivor masks — emitting cumulative log pass-probabilities, survivor
masks, expected counts, and keep counts without ever leaving VMEM.

This replaces the serving path's T× double-argsort (Python stage loop
over (B, G) argsorts) with a single kernel launch over a grid of query
groups.

Kernel memory-layout note — why a COUNTING SELECT, not sorts or ranks
----------------------------------------------------------------------
The TPU has no fast general sort: a (B, G) argsort lowers to a
multi-pass scalar-heavy program, and the serving loop needs TWO of
them per stage (order, then inverse order) just to turn "keep the
top-k by score" into a mask. But the cascade never needs the sorted
ORDER, nor even each item's rank: only the set of the n_keep best
survivors, where n_keep (Eq 10) is one scalar per group and stage. An
all-pairs rank (a (G, G) comparison per stage) finds that set, but its
temporaries grow as G^2: 64 MiB each at G = 4096, where VMEM holds 16.
The kernel instead finds the THRESHOLD by counting:

  * key = the f32 score's bits as an int32, negatives' magnitude bits
    flipped, so that key order is score order (-0.0 is first made +0.0,
    which the stable argsort treats as equal to it). Dead items (masked
    or filtered by an earlier stage) are never counted.
  * tau = the largest key with #{alive: key >= tau} >= n_keep, i.e. the
    n_keep-th best score, found a digit at a time from the top: each
    pass counts, for every value of the next digit at once (one per
    sublane row of a (2^bits, G) comparison), how many alive keys reach
    it, and keeps the largest value whose count still reaches n_keep.
    Digits are 8 bits while G_pad <= 256 (4 passes settle the 32 bits)
    and 4 bits above (8 passes), so that comparison stays at 256 KiB.
  * every alive item above tau survives; of the items tied AT tau, the
    r = n_keep - #{key > tau} lowest-indexed do, found by the same
    counting search over the item index (1 pass to G_pad = 256, 3 at
    4096). That is the stable argsort's tie-break, so the survivor
    sets are bit-identical to the unfused path's double argsort
    (ref.filter_chain), ties included.
  * fewer alive items than n_keep: no candidate reaches n_keep, tau
    stays below every key, and all alive items survive, as there.

Each pass is a compare, a select and a lane reduction: O(2^bits G)
work per pass and 5 to 11 passes per stage, no sort, no G^2 term. The
passes of a search are one fori_loop, so the traced kernel (and the
time to lower it, paid again at every process start even when the
compiled program comes from a cache) does not grow with them.

Layout (items on lanes): one QUERY GROUP per grid step. Features stay
item-major in HBM, padded to 128 lanes (the layout the session packs);
the score dot contracts them against the (T_pad, d_pad) stage weights
into a (T_pad, G_pad) block, stages on the 8 sublanes and items on
lanes, so each stage's scores are one sublane row and the per-group
scalars (bias, expected and keep counts) are (T_pad, 1) columns. G is
padded to the 128-lane width, T to MAX_STAGES; the stage prefix sum is
`stage_cumsum` over sublanes and the score dot runs at HIGHEST
precision, as in the scorer. Per grid step at G = 4096 (f32): the
feature block 4096 x 128 (2 MiB, 4 MiB double-buffered), the lp and
survivor output blocks 8 x 4096 (128 KiB each, 512 KiB double-
buffered), the mask row (128 KiB padded to 8 sublanes, 256 KiB
double-buffered), a handful of 8 x 4096 temporaries (128 KiB each) and
the select's 16 x 4096 comparison (256 KiB): about 6.5 MiB, inside the
16 MiB scoped VMEM limit. An item-major (G, 8) output block would pad
to 128 lanes: 2 MiB a buffer at G = 4096.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.cascade_score.kernel import (HIGHEST, LANE, MAX_STAGES,
                                                stage_cumsum)

MAX_GROUP = 4096    # one group per block; the feature block caps it
INT32_MIN = -2 ** 31


def _digit_bits(g_pad: int) -> int:
    """Bits a counting pass settles: the pass compares the group against
    2^bits candidates, one per sublane row of a (2^bits, G_pad) array.
    8 bits (a 32-bit key in 4 passes) while that array holds at most 64K
    f32 (256 KiB), else 4 (8 passes, 256 KiB at G_pad = 4096); both
    divide 32."""
    return 8 if (1 << 8) * g_pad <= 1 << 16 else 4


def _search(fits, n_bits: int, digit_bits: int) -> jax.Array:
    """The largest pattern v below 2^n_bits (n_bits rounded up to whole
    digits) with fits(v) true, where fits is true on a prefix of the
    patterns in unsigned order, found a digit at a time from the top:
    each pass tries every value of the next digit at once, one per
    sublane, and keeps the largest that fits (0 if none does). fits maps
    a (2^digit_bits, 1) int32 column of candidates to a bool column. The
    passes are one fori_loop, so the traced kernel stays the same size
    however many there are."""
    n_digits = -(-n_bits // digit_bits)
    k = jax.lax.broadcasted_iota(jnp.int32, (1 << digit_bits, 1), 0)
    kf = k.astype(jnp.float32)

    def digit(i, v):
        shift = (n_digits - 1 - i) * digit_bits
        ok = fits(v | (k << shift))
        best = jnp.max(jnp.where(ok, kf, 0.0), axis=0, keepdims=True)
        return v | (best.astype(jnp.int32) << shift)

    return jax.lax.fori_loop(0, n_digits, digit,
                             jnp.zeros((1, 1), jnp.int32))


def _count(hit) -> jax.Array:
    """(C, G) bool -> (C, 1) f32 row counts (exact below 2^24)."""
    return jnp.sum(jnp.where(hit, 1.0, 0.0), axis=1, keepdims=True)


def _select(s, alive, n_keep, idx, digit_bits: int):
    """Stable top-n_keep of the alive items by score s (ties keep the
    lowest index); s, alive, idx: (1, G_pad) rows, n_keep: (1, 1), and
    digit_bits divides the key's 32 bits. Returns the survivors as a
    (1, G_pad) bool row."""
    assert 32 % digit_bits == 0, digit_bits
    s = jnp.where(s == 0.0, 0.0, s)                 # -0.0 ranks as +0.0
    bits = jax.lax.bitcast_convert_type(s, jnp.int32)
    key = jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)   # order = score order
    # dead items take the one key no score has: no candidate above
    # pattern 0 counts them, and pattern 0 is every search's fallback
    key = jnp.where(alive, key, INT32_MIN)

    # the n_keep-th best key; the search runs on key ^ INT32_MIN, whose
    # unsigned order is the key's signed order
    tau = _search(lambda c: _count(key >= (c ^ INT32_MIN)) >= n_keep,
                  32, digit_bits) ^ INT32_MIN
    above = alive & (key > tau)
    tied = alive & (key == tau)
    r = n_keep - _count(above)                      # ties to keep, >= 1
    # the r-th lowest index among the ties: keep the ties up to it
    g_pad = idx.shape[1]
    tied_idx = jnp.where(tied, idx, g_pad)          # past every index
    last = _search(lambda c: _count(tied_idx < c) < r,
                   (g_pad - 1).bit_length(), digit_bits)
    return above | (tied & (idx <= last))


def _kernel(x_ref, w_ref, zq_ref, mask_ref, mq_ref,
            lp_ref, surv_ref, counts_ref, nkeep_ref, *, t: int, g_cap: int):
    """Per-group fused score + Eq-10 keep counts + chained select.

    x: (1, G_pad, d_pad), w: (T_pad, d_pad), zq: (1, T_pad, 1),
    mask: (1, 1, G_pad), mq: (1, 1, 1) ->
    lp/surv: (1, T_pad, G_pad), counts/nkeep: (1, T_pad, 1).
    """
    x = x_ref[0].astype(jnp.float32)                    # (G_pad, d_pad)
    w = w_ref[...].astype(jnp.float32)                  # (T_pad, d_pad)
    zq = zq_ref[0].astype(jnp.float32)                  # (T_pad, 1)
    valid = mask_ref[0].astype(jnp.float32)             # (1, G_pad)
    m_q = mq_ref[0].astype(jnp.float32)                 # (1, 1)

    # -- fused scorer (the scorer kernel's math): one MXU matmul ---------
    logits = jax.lax.dot_general(
        w, x, (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32) + zq        # (T_pad, G_pad)
    lp = stage_cumsum(jax.nn.log_sigmoid(logits), axis=0)
    lp_ref[0] = lp

    # -- Eq 10: expected counts -> per-stage keep counts (a column) --------
    n_valid = jnp.sum(valid, axis=1, keepdims=True)     # (1, 1)
    pp = jnp.exp(lp) * valid                            # pass probs, masked
    counts = ((m_q / jnp.maximum(n_valid, 1.0))
              * jnp.sum(pp, axis=1, keepdims=True))     # (T_pad, 1)
    n_keep = jnp.clip(jnp.ceil(counts * n_valid / jnp.maximum(m_q, 1.0)),
                      1.0, float(g_cap))
    counts_ref[0] = counts
    nkeep_ref[0] = n_keep

    # -- chained select: each stage keeps its n_keep best survivors --------
    g_pad = x.shape[0]
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, g_pad), 1)
    surv_ref[0] = jnp.zeros_like(lp)
    alive = valid > 0
    for j in range(t):
        alive = _select(lp[j:j + 1], alive, n_keep[j:j + 1], idx,
                        _digit_bits(g_pad))
        surv_ref[0, j:j + 1, :] = alive.astype(jnp.float32)


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
    zqp = jnp.pad(zq, ((0, 0), (0, MAX_STAGES - t)))[:, :, None]
    maskp = jnp.pad(mask.astype(jnp.float32), ((0, 0), (0, g_pad)))[:, None, :]
    mqp = m_q.astype(jnp.float32).reshape(b, 1, 1)
    gp = g + g_pad
    dp = d + d_pad
    column = pl.BlockSpec((1, MAX_STAGES, 1), lambda i: (i, 0, 0))
    stage_rows = pl.BlockSpec((1, MAX_STAGES, gp), lambda i: (i, 0, 0))
    lp, surv, counts, nkeep = pl.pallas_call(
        functools.partial(_kernel, t=t, g_cap=g),
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, gp, dp), lambda i: (i, 0, 0)),
            pl.BlockSpec((MAX_STAGES, dp), lambda i: (0, 0)),
            column,
            pl.BlockSpec((1, 1, gp), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_specs=[stage_rows, stage_rows, column, column],
        out_shape=[
            jax.ShapeDtypeStruct((b, MAX_STAGES, gp), jnp.float32),
            jax.ShapeDtypeStruct((b, MAX_STAGES, gp), jnp.float32),
            jax.ShapeDtypeStruct((b, MAX_STAGES, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, MAX_STAGES, 1), jnp.float32),
        ],
        interpret=interpret,
        name="cascade_filter",
    )(xp, wp, zqp, maskp, mqp)
    return {
        "lp": jnp.swapaxes(lp[:, :t, :g], 1, 2),
        "survivors": jnp.swapaxes(surv[:, :t, :g], 1, 2),
        "expected_counts": counts[:, :t, 0],
        "n_keep": nkeep[:, :t, 0],
    }
