"""Fused cascade scorer — Pallas TPU kernel.

The CLOES serving hot loop: score EVERY recalled item through all T cascade
stages. The unfused XLA version reads the (N, d) feature matrix from HBM
once per stage (T times) and materializes T intermediate logit tensors; this
kernel tiles items into VMEM blocks, keeps all T stage weight vectors
resident in VMEM, and produces the cumulative log pass-probabilities in one
pass — one HBM read of the feature matrix total.

TPU adaptation notes (vs the paper's CPU fleet): the per-stage *feature
gating* of the paper is a cost-model construct (features are columns of a
precomputed matrix here); the fused kernel realizes the TPU-native analogue
of "cheap pass over all items" — a single streaming pass at one item-block
per grid step with MXU-aligned (block, 128)-shaped tiles.

Batched (B, G) layout — the shared serving/training entry point
---------------------------------------------------------------
Serving scores padded batches of query groups (B groups of G candidates,
one query-side bias row zq[b] per group) and the trainer scores the same
layout per minibatch. `cascade_score_batched` runs that natively on a 2-D
(batch, item-block) grid instead of `jax.vmap` over the single-group
kernel — vmap restructures the grid through the batching rule, forcing
per-group dispatch and re-deriving block maps on TPU.

Layout and padding contract (forward and backward identically):

  * grid = (B, G_pad // BLOCK_GROUP) with BLOCK_GROUP =
    min(BLOCK_ITEMS, G rounded up to the 8-row sublane); G is padded to a
    multiple of BLOCK_GROUP, d to the 128 LANE width, T to MAX_STAGES.
  * per grid step (b, j): one (1, BLOCK_GROUP, d_pad) feature tile of
    group b, the full (MAX_STAGES, d_pad) weight block (resident across
    the whole grid), and group b's bias row. Per-group rows travel as
    (B, 1, MAX_STAGES) arrays in (1, 1, MAX_STAGES) blocks: Mosaic
    requires a block's last two dims to tile (8, 128) or to equal the
    array's, which a (1, MAX_STAGES) block of a (B, MAX_STAGES) array
    does only at B = 1.
  * padded items / stages / features are zero: zero features and zero
    weights leave each real item's dot product bit-identical, so the
    unpadded (B, G, T) slice equals the single-group kernel's output
    bit for bit (same float ops in the same order, per item).
  * backward: dx is emitted per block; dw accumulates across the whole
    (sequential) grid in its resident block; dzq[b] accumulates across
    group b's item blocks. Padded rows/stages carry zero cotangent and
    contribute nothing. Both accumulate in resident output blocks, so both
    grid axes are declared "arbitrary" (sequential on one core).

Chip lowering: Mosaic has no cumsum, so the stage prefix sums go through
`stage_cumsum` (unrolled masked adds in stage order), and every in-kernel
dot asks for HIGHEST precision — at DEFAULT the MXU rounds f32 operands
to bf16, which would move lp (and the ceil'd keep counts built on it)
away from the f32 reference. On CPU the precision changes nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Item-block per grid step. 512 x 128 f32 feature tile = 256 KiB in VMEM,
# weights (8, 128) are negligible: comfortably within the ~16 MiB VMEM.
BLOCK_ITEMS = 512
LANE = 128          # feature dim padded to the TPU lane width
MAX_STAGES = 8      # stage dim padded to the sublane width
SUBLANE = 8         # feature-major layout: features padded to sublanes
HIGHEST = jax.lax.Precision.HIGHEST

# Grid semantics of kernels whose resident output blocks (dw/dzq, the loss
# partials) accumulate across grid steps: those steps must run in order.
ACCUMULATE_2D = pltpu.CompilerParams(dimension_semantics=("arbitrary",
                                                          "arbitrary"))
ACCUMULATE_1D = pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def stage_cumsum(v: jax.Array, axis: int = -1, *,
                 reverse: bool = False) -> jax.Array:
    """Prefix sums over the stage axis (<= MAX_STAGES wide) as unrolled
    masked adds, in stage order (suffix sums with reverse=True).

    Mosaic has no cumsum lowering. Each step pulls one stage out with a
    masked reduction (the stage's value plus exact zeros) and adds it to a
    running total, so the sum is sequential and exact to the same rounding
    as a plain loop. For T <= 3 this is bit-identical to XLA's cumsum; past
    that XLA's prefix tree may round differently in the last ulp."""
    axis = axis % v.ndim
    idx = jax.lax.broadcasted_iota(jnp.int32, v.shape, axis)
    order = range(v.shape[axis])
    acc = None
    out = jnp.zeros_like(v)
    for k in (reversed(order) if reverse else order):
        col = jnp.sum(jnp.where(idx == k, v, 0.0), axis=axis, keepdims=True)
        acc = col if acc is None else acc + col
        out = jnp.where(idx == k, acc, out)
    return out


def _kernel(x_ref, w_ref, zq_ref, out_ref):
    """x: (BN, d_pad), w: (T_pad, d_pad), zq: (1, T_pad) -> out (BN, T_pad)."""
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    zq = zq_ref[...].astype(jnp.float32)
    logits = jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)            # (BN, T_pad) on MXU
    logits = logits + zq                                # broadcast (1, T_pad)
    out_ref[...] = stage_cumsum(jax.nn.log_sigmoid(logits))


@functools.partial(jax.jit, static_argnames=("interpret",))
def cascade_score(x: jax.Array, w_eff: jax.Array, zq: jax.Array,
                  *, interpret: bool = False) -> jax.Array:
    """x: (N, d), w_eff: (T, d), zq: (T,) -> (N, T) cumulative log pass-probs.

    Pads N to BLOCK_ITEMS, d to LANE, T to MAX_STAGES; unpads on return.
    """
    n, d = x.shape
    t = w_eff.shape[0]
    assert t <= MAX_STAGES, f"cascade of {t} stages > {MAX_STAGES}"
    n_pad = (-n) % BLOCK_ITEMS
    d_pad = (-d) % LANE
    xp = jnp.pad(x, ((0, n_pad), (0, d_pad)))
    wp = jnp.pad(w_eff, ((0, MAX_STAGES - t), (0, d_pad)))
    zqp = jnp.pad(zq, (0, MAX_STAGES - t)).reshape(1, MAX_STAGES)
    grid = (xp.shape[0] // BLOCK_ITEMS,)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_ITEMS, xp.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec((MAX_STAGES, xp.shape[1]), lambda i: (0, 0)),
            pl.BlockSpec((1, MAX_STAGES), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_ITEMS, MAX_STAGES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], MAX_STAGES), jnp.float32),
        interpret=interpret,
    )(xp, wp, zqp)
    return out[:n, :t]


# ---------------------------------------------------------------------------
# Backward kernel (training): grads of the cumulative log pass-probs w.r.t.
# x, w_eff and zq in one pass over the items.
#
# With out[i, j] = sum_{k<=j} log sigmoid(logit[i, k]) and cotangent g:
#
#     g_logit[i, k] = (sum_{j>=k} g[i, j]) * sigmoid(-logit[i, k])
#     dx     = g_logit @ w_eff          (N, d)
#     dw_eff = g_logit^T @ x            (T, d)
#     dzq    = sum_i g_logit[i, :]      (T,)
#
# The reverse cumsum is a sequential suffix sum (stage_cumsum reverse=True).
# Like the forward, each grid step streams one item block through VMEM and
# recomputes its logits — no (N, T) residual ever hits HBM. dw/dzq are
# accumulated across the (sequential) TPU grid in their output blocks.
# ---------------------------------------------------------------------------


def _bwd_kernel(x_ref, w_ref, zq_ref, g_ref, dx_ref, dw_ref, dzq_ref):
    """x: (BN, d_pad), w: (T_pad, d_pad), zq: (1, T_pad), g: (BN, T_pad)."""
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    zq = zq_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    logits = jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32) + zq            # (BN, T_pad)
    # reverse cumsum over stages: gc[:, k] = sum_{j>=k} g[:, j]
    gc = stage_cumsum(g, reverse=True)
    g_logit = gc * jax.nn.sigmoid(-logits)                  # (BN, T_pad)
    dx_ref[...] = jax.lax.dot_general(
        g_logit, w, (((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)                 # (BN, d_pad)
    dw_blk = jax.lax.dot_general(
        g_logit, x, (((0,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)                 # (T_pad, d_pad)
    dzq_blk = g_logit.sum(axis=0, keepdims=True)            # (1, T_pad)

    @pl.when(i == 0)
    def _init():
        dw_ref[...] = dw_blk
        dzq_ref[...] = dzq_blk

    @pl.when(i > 0)
    def _accum():
        dw_ref[...] += dw_blk
        dzq_ref[...] += dzq_blk


@functools.partial(jax.jit, static_argnames=("interpret",))
def cascade_score_bwd(x: jax.Array, w_eff: jax.Array, zq: jax.Array,
                      g: jax.Array, *, interpret: bool = False
                      ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Backward of `cascade_score`: cotangent g (N, T) -> (dx, dw_eff, dzq).

    Same padding scheme as the forward; padded rows/stages carry zero
    cotangent so they contribute nothing to the accumulated grads.
    """
    n, d = x.shape
    t = w_eff.shape[0]
    assert t <= MAX_STAGES, f"cascade of {t} stages > {MAX_STAGES}"
    n_pad = (-n) % BLOCK_ITEMS
    d_pad = (-d) % LANE
    xp = jnp.pad(x, ((0, n_pad), (0, d_pad)))
    wp = jnp.pad(w_eff, ((0, MAX_STAGES - t), (0, d_pad)))
    zqp = jnp.pad(zq, (0, MAX_STAGES - t)).reshape(1, MAX_STAGES)
    gp = jnp.pad(g.astype(jnp.float32), ((0, n_pad), (0, MAX_STAGES - t)))
    grid = (xp.shape[0] // BLOCK_ITEMS,)
    dx, dw, dzq = pl.pallas_call(
        _bwd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_ITEMS, xp.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec((MAX_STAGES, xp.shape[1]), lambda i: (0, 0)),
            pl.BlockSpec((1, MAX_STAGES), lambda i: (0, 0)),
            pl.BlockSpec((BLOCK_ITEMS, MAX_STAGES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK_ITEMS, xp.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec((MAX_STAGES, xp.shape[1]), lambda i: (0, 0)),
            pl.BlockSpec((1, MAX_STAGES), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((xp.shape[0], xp.shape[1]), jnp.float32),
            jax.ShapeDtypeStruct((MAX_STAGES, xp.shape[1]), jnp.float32),
            jax.ShapeDtypeStruct((1, MAX_STAGES), jnp.float32),
        ],
        compiler_params=ACCUMULATE_1D,
        interpret=interpret,
    )(xp, wp, zqp, gp)
    return dx[:n, :d], dw[:t, :d], dzq[0, :t]


# ---------------------------------------------------------------------------
# Batched (B, G) entry point — see the module docstring's layout section.
# One forward/backward pair on a 2-D (batch, item-block) grid, shared by
# the serving pipeline (fused="score"), the trainer's fused forward, and
# CascadeServer. The kernel bodies mirror _kernel/_bwd_kernel exactly so
# the per-item math is bit-identical to the single-group kernel.
# ---------------------------------------------------------------------------


def _block_group(g: int) -> int:
    """Item-block size for a (B, G) batch: whole group when it fits in one
    sublane-aligned block, BLOCK_ITEMS tiles otherwise."""
    return min(BLOCK_ITEMS, g + (-g) % SUBLANE)


def _pad_batched(x, w_eff, zq):
    """Shared padding for the batched forward/backward: G to a multiple of
    the block, d to LANE, T to MAX_STAGES; zq as (B, 1, MAX_STAGES) rows."""
    b, g, d = x.shape
    t = w_eff.shape[0]
    assert t <= MAX_STAGES, f"cascade of {t} stages > {MAX_STAGES}"
    bg = _block_group(g)
    xp = jnp.pad(x, ((0, 0), (0, (-g) % bg), (0, (-d) % LANE)))
    wp = jnp.pad(w_eff, ((0, MAX_STAGES - t), (0, (-d) % LANE)))
    zqp = jnp.pad(zq, ((0, 0), (0, MAX_STAGES - t)))[:, None, :]
    return xp, wp, zqp, bg


def _batched_kernel(x_ref, w_ref, zq_ref, out_ref):
    """x: (1, BG, d_pad), w: (T_pad, d_pad), zq: (1, 1, T_pad) ->
    out (1, BG, T_pad)."""
    x = x_ref[0].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    zq = zq_ref[0].astype(jnp.float32)
    logits = jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)            # (BG, T_pad) on MXU
    logits = logits + zq                                # broadcast (1, T_pad)
    out_ref[0] = stage_cumsum(jax.nn.log_sigmoid(logits))


@functools.partial(jax.jit, static_argnames=("interpret",))
def cascade_score_batched(x: jax.Array, w_eff: jax.Array, zq: jax.Array,
                          *, interpret: bool = False) -> jax.Array:
    """x: (B, G, d), w_eff: (T, d), zq: (B, T) -> (B, G, T) cumulative log
    pass-probs. The batched layout/padding contract is in the module
    docstring."""
    b, g, d = x.shape
    t = w_eff.shape[0]
    xp, wp, zqp, bg = _pad_batched(x, w_eff, zq)
    gp, dp = xp.shape[1], xp.shape[2]
    out = pl.pallas_call(
        _batched_kernel,
        grid=(b, gp // bg),
        in_specs=[
            pl.BlockSpec((1, bg, dp), lambda i, j: (i, j, 0)),
            pl.BlockSpec((MAX_STAGES, dp), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1, MAX_STAGES), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bg, MAX_STAGES), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b, gp, MAX_STAGES), jnp.float32),
        interpret=interpret,
        name="cascade_score_batched",
    )(xp, wp, zqp)
    return out[:, :g, :t]


def _batched_bwd_kernel(x_ref, w_ref, zq_ref, g_ref,
                        dx_ref, dw_ref, dzq_ref):
    """Backward of the batched scorer — same math as _bwd_kernel, with dw
    accumulated across the whole grid and dzq[b] across group b's blocks.
    x/g: (1, BG, ·), w: (T_pad, d_pad), zq/dzq: (1, 1, T_pad)."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    x = x_ref[0].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    zq = zq_ref[0].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)
    logits = jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32) + zq            # (BG, T_pad)
    # reverse cumsum over stages: gc[:, k] = sum_{j>=k} g[:, j]
    gc = stage_cumsum(g, reverse=True)
    g_logit = gc * jax.nn.sigmoid(-logits)                  # (BG, T_pad)
    dx_ref[0] = jax.lax.dot_general(
        g_logit, w, (((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)                 # (BG, d_pad)
    dw_blk = jax.lax.dot_general(
        g_logit, x, (((0,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)                 # (T_pad, d_pad)
    dzq_blk = g_logit.sum(axis=0, keepdims=True)[None]      # (1, 1, T_pad)

    @pl.when((i == 0) & (j == 0))
    def _init_dw():
        dw_ref[...] = dw_blk

    @pl.when((i > 0) | (j > 0))
    def _accum_dw():
        dw_ref[...] += dw_blk

    @pl.when(j == 0)
    def _init_dzq():
        dzq_ref[...] = dzq_blk

    @pl.when(j > 0)
    def _accum_dzq():
        dzq_ref[...] += dzq_blk


@functools.partial(jax.jit, static_argnames=("interpret",))
def cascade_score_batched_bwd(x: jax.Array, w_eff: jax.Array, zq: jax.Array,
                              g: jax.Array, *, interpret: bool = False
                              ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Backward of `cascade_score_batched`: cotangent g (B, G, T) ->
    (dx (B, G, d), dw_eff (T, d), dzq (B, T)). Same padding as the forward;
    padded rows/stages carry zero cotangent."""
    b, g_items, d = x.shape
    t = w_eff.shape[0]
    xp, wp, zqp, bg = _pad_batched(x, w_eff, zq)
    gp, dp = xp.shape[1], xp.shape[2]
    gct = jnp.pad(g.astype(jnp.float32),
                  ((0, 0), (0, gp - g_items), (0, MAX_STAGES - t)))
    dx, dw, dzq = pl.pallas_call(
        _batched_bwd_kernel,
        grid=(b, gp // bg),
        in_specs=[
            pl.BlockSpec((1, bg, dp), lambda i, j: (i, j, 0)),
            pl.BlockSpec((MAX_STAGES, dp), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1, MAX_STAGES), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, bg, MAX_STAGES), lambda i, j: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bg, dp), lambda i, j: (i, j, 0)),
            pl.BlockSpec((MAX_STAGES, dp), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1, MAX_STAGES), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, gp, dp), jnp.float32),
            jax.ShapeDtypeStruct((MAX_STAGES, dp), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, MAX_STAGES), jnp.float32),
        ],
        compiler_params=ACCUMULATE_2D,
        interpret=interpret,
        name="cascade_score_batched_bwd",
    )(xp, wp, zqp, gct)
    return dx[:, :g_items, :d], dw[:t, :d], dzq[:, 0, :t]


# ---------------------------------------------------------------------------
# Feature-major variant (§Perf kernel iteration): the item-major layout pads
# the d_x features (24 for the paper's registry) up to the 128-lane width —
# a 5.3x read amplification that erases the fusion win. Storing the
# candidate matrix FEATURE-MAJOR (d, N) puts the small axis on sublanes
# (pad 24 -> 24, multiples of 8) and the huge item axis on lanes: fused HBM
# traffic drops ~2.3x below the unfused XLA path. The serving store keeps
# candidates feature-major.
# ---------------------------------------------------------------------------


def _kernel_fm(xt_ref, w_ref, zq_ref, out_ref):
    """xt: (d_pad, BN), w: (T_pad, d_pad), zq: (T_pad, 1) -> out (T_pad, BN)."""
    xt = xt_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    zq = zq_ref[...].astype(jnp.float32)
    logits = jax.lax.dot_general(
        w, xt, (((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)             # (T_pad, BN)
    logp = jax.nn.log_sigmoid(logits + zq)              # zq (T_pad,1) bcast
    out_ref[...] = stage_cumsum(logp, axis=0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def cascade_score_fm(xt: jax.Array, w_eff: jax.Array, zq: jax.Array,
                     *, interpret: bool = False) -> jax.Array:
    """Feature-major fused scorer. xt: (d, N); returns (N, T) like the
    item-major kernel (transposed on the way out)."""
    d, n = xt.shape
    t = w_eff.shape[0]
    assert t <= MAX_STAGES
    d_pad = (-d) % SUBLANE
    n_pad = (-n) % BLOCK_ITEMS
    xp = jnp.pad(xt, ((0, d_pad), (0, n_pad)))
    wp = jnp.pad(w_eff, ((0, MAX_STAGES - t), (0, d_pad)))
    zqp = jnp.pad(zq, (0, MAX_STAGES - t)).reshape(MAX_STAGES, 1)
    grid = (xp.shape[1] // BLOCK_ITEMS,)
    out = pl.pallas_call(
        _kernel_fm,
        grid=grid,
        in_specs=[
            pl.BlockSpec((xp.shape[0], BLOCK_ITEMS), lambda i: (0, i)),
            pl.BlockSpec((MAX_STAGES, xp.shape[0]), lambda i: (0, 0)),
            pl.BlockSpec((MAX_STAGES, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((MAX_STAGES, BLOCK_ITEMS), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((MAX_STAGES, xp.shape[1]), jnp.float32),
        interpret=interpret,
    )(xp, wp, zqp)
    return out[:t, :n].T
