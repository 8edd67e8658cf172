"""Fused cascade training-step reduction kernel — Pallas TPU.

The L3 training step (paper Eqs 4/8/10/14-17) is, after PR 2/3, one batched
scoring pass followed by ~35 small XLA reductions: the NLL term, the Eq-8
expected-cost accumulators and the Eq-10 expected keep counts (for the size
and latency penalties) are each per-item reductions over the SAME (B, G, T)
cumulative log pass-probabilities the fused scorer already materializes in
VMEM — plus a second, value-identical penalty-variant scoring pass whose
only purpose is gradient routing (stop-gradients on w_eff and b). On the
small-group shapes of the default TrainConfig that step graph is kernel-
launch bound (ROADMAP "CPU step-graph floor").

This kernel extends the batched (B, G) scorer: in the same VMEM pass that
computes the logits it emits the three per-group partial reductions L3
needs, so the scores never leave VMEM and one launch replaces the
score-then-many-small-reductions graph:

    ll[b]         = sum_g wgt*mask * (y * lpc_T + (1-y) * log1p(-exp(lpc_T)))
    cost_pp[t]    = sum_bg cost_w * exp(lp_t)         (Eq-8 accumulator)
    cnt_pp[b, t]  = sum_g  mask   * exp(lp_t)         (Eq-10 accumulator)

with lp the cumulative log pass-probabilities and lpc_T = min(lp_T, -1e-7)
the NLL's clamped FINAL stage (keeps 1 - p > 0 — same guard as
losses.nll_from_lp; Eq 4 only reads stage T, so the NLL partial is a
per-group scalar and the log1p/exp chain runs once, not per stage; the
Eq-8 accumulator is a GLOBAL per-stage sum because Eq 8 reduces over the
batch anyway, while the Eq-10 counts stay per-group for the per-query
penalties). Everything L3 still does outside the kernel is O(B*T):
NLL = -ll summed over groups / mask-count, cost = Eq-8 over cost_pp,
counts_pen = mn * cnt_pp feeding the size/latency hinges.

Packed-item layout — the engine-batch protocol on the wire
----------------------------------------------------------
The kernel takes the trainer's packed item array AS IS (trainer._engine_pack
stores exactly [x | y | mask | wgt | cost_w] along the feature axis):

    xc (B, G, d_x + 4)   xc[..., :d_x] = features, then y, mask, wgt, cost_w

The stage weights are zero-padded over the 4 data columns (and up to the
lane width), so the in-kernel matmul over the FULL packed width produces
logits bit-identical to an x-only matmul — zero weight times finite data
is exactly zero — and the data columns are recovered by static lane slices.
Callers without an engine batch concatenate the four columns on the fly
(one cheap concat; see losses.loss_l3).

Layout and padding contract (mirrors kernels/cascade_score — forward and
backward identically):

  * grid = (B, G_pad // BLOCK_GROUP) with BLOCK_GROUP =
    min(BLOCK_ITEMS, G rounded up to the 8-row sublane); G is padded to a
    multiple of BLOCK_GROUP, the packed width d_x+4 to the 128 LANE width,
    T to MAX_STAGES.
  * per grid step (b, j): one (1, BLOCK_GROUP, d_pad) packed tile of group
    b, the full (MAX_STAGES, d_pad) weight block (resident across the whole
    grid), and group b's bias row. Per-group rows (bias, ll/cnt partials
    and their cotangents) travel as (B, 1, MAX_STAGES) arrays in
    (1, 1, MAX_STAGES) blocks, as in the batched scorer.
  * padded items / stages / features are zero: every partial is weighted by
    mask, wgt*mask or cost_w (all zero on padded rows), so padded rows
    contribute nothing; padded stage columns are garbage and sliced off.
  * the ll/cnt (B, MAX_STAGES) outputs accumulate across group b's item
    blocks in their resident rows (init at j == 0, += after), exactly like
    the batched scorer backward accumulates dzq; the cost row
    (1, MAX_STAGES) accumulates across the WHOLE sequential grid like the
    backward's dw block. Both grid axes are therefore "arbitrary".
  * backward: one pass recomputes the logits and fuses the dNLL/dcost/
    dcount cotangents into TWO logit-gradient streams — the main stream
    (NLL + cost, flowing to w_eff and zq) and the penalty stream (counts,
    flowing ONLY to zq_pen — the Eq-15 stop-gradient routing baked into
    the VJP instead of a second scoring pass). dxc is emitted per block
    ((main+pen) @ w; the data columns land exactly zero because their
    weight columns are zero), dw accumulates across the whole grid from
    the main stream only, dzq[b]/dzq_pen[b] across group b's blocks.

Gradient contract: the y/mask/wgt/cost_w data columns are treated as
constants (their cotangents are the structural zeros of dxc's data lanes) —
they are batch data, never parameters.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.cascade_score.kernel import (ACCUMULATE_2D, HIGHEST, LANE,
                                                MAX_STAGES, _block_group,
                                                stage_cumsum)

# Number of data columns packed after the d_x features: y, mask, wgt, cost_w.
N_DATA_COLS = 4


def pack_items(x, y, mask, wgt, cost_w):
    """THE packed-item layout: [x | y | mask | wgt | cost_w] along the
    feature axis. Single definition of the column order the kernels and
    the XLA ref slice by — trainer._engine_pack and the raw-batch path in
    losses.loss_l3 both pack through here."""
    return jnp.concatenate(
        [x, y[..., None], mask[..., None], wgt[..., None],
         cost_w[..., None]], axis=-1)

# The NLL clamp: log p kept <= -1e-7 so 1 - p stays positive in f32 (the
# same literal as losses.nll_from_lp — the backward's clamp-boundary test
# depends on the two sites agreeing).
LOG_P_CLAMP = -1e-7


def _pad_loss(xc, w_eff, zq):
    """Shared padding for forward/backward: G to a multiple of the block,
    the packed width to LANE, T to MAX_STAGES. w_eff is zero-padded over
    the data columns so the full-width matmul is exact."""
    b, g, dc = xc.shape
    t, d = w_eff.shape
    assert t <= MAX_STAGES, f"cascade of {t} stages > {MAX_STAGES}"
    assert dc == d + N_DATA_COLS, (
        f"packed item width {dc} != d_x + {N_DATA_COLS} (d_x={d})")
    bg = _block_group(g)
    xp = jnp.pad(xc, ((0, 0), (0, (-g) % bg), (0, (-dc) % LANE)))
    wp = jnp.pad(w_eff, ((0, MAX_STAGES - t), (0, xp.shape[2] - d)))
    zqp = jnp.pad(zq, ((0, 0), (0, MAX_STAGES - t)))[:, None, :]
    return xp, wp, zqp, bg


def _lp_and_cols(xc, w, zq, d_x):
    """Shared forward recompute: logits/lp from the packed tile + the four
    data columns as (BG, 1) lane slices. All f32 in-VMEM."""
    xf = xc.astype(jnp.float32)
    logits = jax.lax.dot_general(
        xf, w.astype(jnp.float32), (((1,), (1,)), ((), ())),
        precision=HIGHEST,
        preferred_element_type=jnp.float32) + zq.astype(jnp.float32)
    lp = stage_cumsum(jax.nn.log_sigmoid(logits))            # (BG, T_pad)
    y = xf[:, d_x:d_x + 1]
    mask = xf[:, d_x + 1:d_x + 2]
    wgt = xf[:, d_x + 2:d_x + 3]
    cost_w = xf[:, d_x + 3:d_x + 4]
    return logits, lp, y, mask, wgt, cost_w


def _loss_kernel(d_x, t, xc_ref, w_ref, zq_ref, ll_ref, cost_ref, cnt_ref):
    """xc: (1, BG, d_pad), w: (T_pad, d_pad), zq: (1, 1, T_pad) ->
    partial rows: ll/cnt (1, 1, T_pad) accumulated over group b's item
    blocks (the scalar NLL partial is broadcast across its row's lanes),
    cost (1, T_pad) accumulated across the whole grid."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    _, lp, y, mask, wgt, cost_w = _lp_and_cols(
        xc_ref[0], w_ref[...], zq_ref[0], d_x)
    lpc = jnp.minimum(lp[:, t - 1:t], LOG_P_CLAMP)           # (BG, 1)
    ll = (wgt * mask) * (y * lpc + (1.0 - y) * jnp.log1p(-jnp.exp(lpc)))
    pp = jnp.exp(lp)
    ll_blk = jnp.broadcast_to(ll.sum(axis=0, keepdims=True),
                              (1, MAX_STAGES))[None]         # (1, 1, T_pad)
    cost_blk = (pp * cost_w).sum(axis=0, keepdims=True)      # (1, T_pad)
    cnt_blk = (pp * mask).sum(axis=0, keepdims=True)[None]   # (1, 1, T_pad)

    @pl.when(j == 0)
    def _init():
        ll_ref[...] = ll_blk
        cnt_ref[...] = cnt_blk

    @pl.when(j > 0)
    def _accum():
        ll_ref[...] += ll_blk
        cnt_ref[...] += cnt_blk

    @pl.when((i == 0) & (j == 0))
    def _init_cost():
        cost_ref[...] = cost_blk

    @pl.when((i > 0) | (j > 0))
    def _accum_cost():
        cost_ref[...] += cost_blk


@functools.partial(jax.jit, static_argnames=("d_x", "interpret"))
def cascade_loss(xc: jax.Array, w_eff: jax.Array, zq: jax.Array,
                 *, d_x: int, interpret: bool = False
                 ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused L3 partial reductions. xc: (B, G, d_x+4) packed items,
    w_eff: (T, d_x), zq: (B, T) -> (ll (B,), cost_pp (T,),
    cnt_pp (B, T)). Layout/padding contract in the module docstring."""
    b, g, _ = xc.shape
    t = w_eff.shape[0]
    xp, wp, zqp, bg = _pad_loss(xc, w_eff, zq)
    gp, dp = xp.shape[1], xp.shape[2]
    outs = pl.pallas_call(
        functools.partial(_loss_kernel, d_x, t),
        grid=(b, gp // bg),
        in_specs=[
            pl.BlockSpec((1, bg, dp), lambda i, j: (i, j, 0)),
            pl.BlockSpec((MAX_STAGES, dp), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1, MAX_STAGES), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, MAX_STAGES), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, MAX_STAGES), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1, MAX_STAGES), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, MAX_STAGES), jnp.float32),
            jax.ShapeDtypeStruct((1, MAX_STAGES), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, MAX_STAGES), jnp.float32),
        ],
        compiler_params=ACCUMULATE_2D,
        interpret=interpret,
        name="cascade_loss",
    )(xp, wp, zqp)
    return outs[0][:, 0, 0], outs[1][0, :t], outs[2][:, 0, :t]


def _loss_bwd_kernel(d_x, t, xc_ref, w_ref, zq_ref, gll_ref, gcost_ref,
                     gcnt_ref, dxc_ref, dw_ref, dzq_ref, dzqp_ref):
    """One recompute pass fusing the three cotangent streams — see the
    module docstring. Cotangent rows: gll (1, 1, T_pad), a per-group
    scalar broadcast across lanes that only stage t-1 taps; gcost
    (1, T_pad), the one global Eq-8 row, resident across the whole grid;
    gcnt (1, 1, T_pad), per group."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    w = w_ref[...].astype(jnp.float32)
    logits, lp, y, mask, wgt, cost_w = _lp_and_cols(
        xc_ref[0], w, zq_ref[0], d_x)
    gll = gll_ref[0].astype(jnp.float32)                     # (1, T_pad)
    gcost = gcost_ref[...].astype(jnp.float32)
    gcnt = gcnt_ref[0].astype(jnp.float32)
    pp = jnp.exp(lp)
    lpl = lp[:, t - 1:t]                                     # (BG, 1)
    ppc = jnp.exp(jnp.minimum(lpl, LOG_P_CLAMP))
    # d ll / d lpc_T, gated by the clamp's pass-through (lax.min routes the
    # tangent to the first operand on ties, hence <=)
    dll = (wgt * mask) * (y - (1.0 - y) * ppc / (1.0 - ppc))
    g_nll = jnp.where(lpl <= LOG_P_CLAMP, gll[:, :1] * dll, 0.0)
    stage = jax.lax.broadcasted_iota(jnp.int32, lp.shape, 1)
    g_lp_main = (jnp.where(stage == t - 1, g_nll, 0.0)
                 + gcost * pp * cost_w)
    g_lp_pen = gcnt * pp * mask
    sig = jax.nn.sigmoid(-logits)

    def back(g_lp):
        # reverse cumsum over stages: gc[:, k] = sum_{t>=k} g_lp[:, t]
        return stage_cumsum(g_lp, reverse=True) * sig

    gm = back(g_lp_main)                                     # (BG, T_pad)
    gp_ = back(g_lp_pen)
    dxc_ref[0] = jax.lax.dot_general(
        gm + gp_, w, (((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)                  # (BG, d_pad)
    dw_blk = jax.lax.dot_general(
        gm, xc_ref[0].astype(jnp.float32), (((0,), (0,)), ((), ())),
        precision=HIGHEST,
        preferred_element_type=jnp.float32)                  # (T_pad, d_pad)
    dzq_blk = gm.sum(axis=0, keepdims=True)[None]            # (1, 1, T_pad)
    dzqp_blk = gp_.sum(axis=0, keepdims=True)[None]

    @pl.when((i == 0) & (j == 0))
    def _init_dw():
        dw_ref[...] = dw_blk

    @pl.when((i > 0) | (j > 0))
    def _accum_dw():
        dw_ref[...] += dw_blk

    @pl.when(j == 0)
    def _init_dzq():
        dzq_ref[...] = dzq_blk
        dzqp_ref[...] = dzqp_blk

    @pl.when(j > 0)
    def _accum_dzq():
        dzq_ref[...] += dzq_blk
        dzqp_ref[...] += dzqp_blk


@functools.partial(jax.jit, static_argnames=("d_x", "interpret"))
def cascade_loss_bwd(xc: jax.Array, w_eff: jax.Array, zq: jax.Array,
                     g_ll: jax.Array, g_cost: jax.Array, g_cnt: jax.Array,
                     *, d_x: int, interpret: bool = False
                     ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Backward of `cascade_loss`: cotangents g_ll (B,) for the NLL
    partial, g_cost (T,) and g_cnt (B, T) for the accumulators ->
    (dxc (B, G, d_x+4), dw_eff (T, d_x), dzq (B, T), dzq_pen (B, T)).
    Same padding as the forward; padded stage columns of the cotangents
    are zero-filled so they contribute nothing."""
    b, g, dc = xc.shape
    t, d = w_eff.shape
    xp, wp, zqp, bg = _pad_loss(xc, w_eff, zq)
    gp_, dp = xp.shape[1], xp.shape[2]
    gs = [jnp.broadcast_to(g_ll.astype(jnp.float32)[:, None, None],
                           (b, 1, MAX_STAGES)),
          jnp.pad(g_cost.astype(jnp.float32),
                  (0, MAX_STAGES - t)).reshape(1, MAX_STAGES),
          jnp.pad(g_cnt.astype(jnp.float32),
                  ((0, 0), (0, MAX_STAGES - t)))[:, None, :]]
    dxc, dw, dzq, dzqp = pl.pallas_call(
        functools.partial(_loss_bwd_kernel, d_x, t),
        grid=(b, gp_ // bg),
        in_specs=[
            pl.BlockSpec((1, bg, dp), lambda i, j: (i, j, 0)),
            pl.BlockSpec((MAX_STAGES, dp), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1, MAX_STAGES), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, MAX_STAGES), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, MAX_STAGES), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1, MAX_STAGES), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bg, dp), lambda i, j: (i, j, 0)),
            pl.BlockSpec((MAX_STAGES, dp), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1, MAX_STAGES), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, MAX_STAGES), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, gp_, dp), jnp.float32),
            jax.ShapeDtypeStruct((MAX_STAGES, dp), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, MAX_STAGES), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, MAX_STAGES), jnp.float32),
        ],
        compiler_params=ACCUMULATE_2D,
        interpret=interpret,
        name="cascade_loss_bwd",
    )(xp, wp, zqp, *gs)
    return dxc[:, :g, :dc], dw[:t, :d], dzq[:, 0, :t], dzqp[:, 0, :t]
