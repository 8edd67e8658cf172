"""Public jit'd wrappers for the Pallas kernels.

Backend dispatch: the kernels are written for TPU (Mosaic). With
``interpret=None`` (the default) each wrapper picks the fastest correct
implementation for the current backend — the compiled Pallas kernel on
TPU; on CPU/GPU the cascade serving wrappers dispatch to their
identical-semantics jitted XLA reference (interpreter speed would be
prohibitive on the serving hot path), while ``swa_decode`` runs the
Pallas interpreter. Passing ``interpret=True`` always forces the Pallas
interpreter — that is what the parity test sweeps use to validate the
kernel bodies; passing ``interpret=False`` demands the compiled kernel.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.cascade_filter.kernel import cascade_filter as _cascade_filter
from repro.kernels.cascade_filter.ref import cascade_filter_ref
from repro.kernels.cascade_loss.kernel import (
    cascade_loss as _cascade_loss,
    cascade_loss_bwd as _cascade_loss_bwd)
from repro.kernels.cascade_loss.ref import (cascade_loss_bwd_ref,
                                            cascade_loss_ref)
from repro.kernels.cascade_score.kernel import (
    cascade_score_batched as _cascade_score_batched,
    cascade_score_batched_bwd as _cascade_score_batched_bwd)
from repro.kernels.cascade_score.ref import cascade_score_batched_ref
from repro.kernels.swa_decode.kernel import swa_decode as _swa_decode, NO_WINDOW
from repro.kernels.swa_decode.ref import swa_decode_ref


def _auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _require_ranks(op: str, **named) -> None:
    """One consistent ValueError for rank-mismatched wrapper inputs, raised
    at the public API instead of as a shape error from inside pallas_call.
    Each kwarg maps a name to (array, expected_rank)."""
    bad = [f"{name} has rank {getattr(arr, 'ndim', None)} "
           f"(shape {tuple(getattr(arr, 'shape', ()))}), expected rank {want}"
           for name, (arr, want) in named.items()
           if getattr(arr, "ndim", None) != want]
    if bad:
        raise ValueError(f"{op}: rank-mismatched inputs: " + "; ".join(bad))


# ---------------------------------------------------------------------------
# cascade_score_batched is differentiable: the training losses score
# through it. The Pallas path carries a custom VJP (autodiff cannot see
# through pallas_call) whose backward is itself a fused Pallas kernel; the
# XLA reference on non-TPU backends is natively autodiff-able, and wrapping
# it in the custom VJP would only block XLA's cross-term fusion/CSE of the
# training graph (measured ~25% slower L3 steps on CPU).
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _cascade_score_batched_pallas(interpret, x, w_eff, zq):
    return _cascade_score_batched(x, w_eff, zq, interpret=interpret)


def _cascade_score_batched_fwd(interpret, x, w_eff, zq):
    return (_cascade_score_batched_pallas(interpret, x, w_eff, zq),
            (x, w_eff, zq))


def _cascade_score_batched_bwd_rule(interpret, res, g):
    x, w_eff, zq = res
    return _cascade_score_batched_bwd(x, w_eff, zq, g, interpret=interpret)


_cascade_score_batched_pallas.defvjp(_cascade_score_batched_fwd,
                                     _cascade_score_batched_bwd_rule)


# ---------------------------------------------------------------------------
# cascade_loss_fused — the L3 training-step reduction op. The Pallas paths
# carry a custom VJP (autodiff cannot see through pallas_call) whose
# backward is one fused recompute pass in VMEM with the Eq-15 stop-gradient
# routing hand-built in: the counts (penalty) cotangent stream flows to
# zq_pen only. The XLA ref rides plain autodiff — same policy (and same
# measured reason: ~20% slower L3 steps with a VJP boundary, which blocks
# XLA from fusing the backward into the forward's loop fusions) as the
# scorer — with the identical routing expressed algebraically inside
# the ref (exact-Jacobian zq_pen tap; see kernels/cascade_loss/ref.py).
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _cascade_loss_pallas(interpret, xc, w_eff, zq, zq_pen):
    del zq_pen  # value-identical to zq by contract; a gradient tap only
    return _cascade_loss(xc, w_eff, zq, d_x=w_eff.shape[1],
                         interpret=interpret)


def _cascade_loss_fwd(interpret, xc, w_eff, zq, zq_pen):
    return (_cascade_loss_pallas(interpret, xc, w_eff, zq, zq_pen),
            (xc, w_eff, zq))


def _cascade_loss_bwd_rule(interpret, res, g):
    xc, w_eff, zq = res
    g_ll, g_cost, g_cnt = g
    return _cascade_loss_bwd(xc, w_eff, zq, g_ll, g_cost, g_cnt,
                             d_x=w_eff.shape[1], interpret=interpret)


_cascade_loss_pallas.defvjp(_cascade_loss_fwd, _cascade_loss_bwd_rule)


def cascade_loss_fused(xc, w_eff, zq, zq_pen=None, *,
                       interpret: bool | None = None):
    """Fused L3 training-step reductions: xc (B, G, d_x+4) packed items
    ([x | y | mask | wgt | cost_w] — the trainer's engine-batch layout),
    w_eff (T, d_x), zq (B, T) -> (ll (B,), cost_pp (T,), cnt_pp (B, T)).

    One VMEM pass computes the logits and emits the per-group partials of
    the NLL (Eq 4/17), the Eq-8 expected-cost accumulators and the Eq-10
    expected keep counts — see kernels/cascade_loss/kernel.py for the
    layout/padding contract and the reduction definitions.

    zq_pen MUST equal zq in value (it is the same query bias with the Eq-15
    stop-gradients applied); it exists purely as a gradient-routing tap: the
    counts (penalty) cotangent stream flows into zq_pen, the NLL + cost
    streams into zq and w_eff. Defaults to zq itself (no routing split).
    Differentiable on every path — custom VJP with a fused Pallas backward
    kernel on TPU/interpret, plain autodiff through the routing-aware XLA
    reference elsewhere; the y/mask/wgt/cost_w data columns are treated as
    constants."""
    _require_ranks("cascade_loss_fused", xc=(xc, 3), w_eff=(w_eff, 2),
                   zq=(zq, 2),
                   **({} if zq_pen is None else {"zq_pen": (zq_pen, 2)}))
    if interpret is None:
        if _auto_interpret():
            return cascade_loss_ref(xc, w_eff, zq, zq_pen)
        interpret = False
    return _cascade_loss_pallas(interpret, xc, w_eff, zq,
                                zq if zq_pen is None else zq_pen)


def cascade_score_batched(x, w_eff, zq, *, interpret: bool | None = None):
    """The fused scorer: x (B, G, d) padded query groups, w_eff (T, d),
    zq (B, T) per-group biases -> (B, G, T) cumulative log pass-probs.
    See kernels/cascade_score/kernel.py.

    The training losses score through it (losses.cascade_forward): a
    native 2-D (batch, item-block) grid with no jax.vmap wrapping of the
    kernel. Differentiable on every path — custom VJP with the Pallas
    backward kernel on TPU/interpret, plain autodiff through the XLA
    reference elsewhere. interpret=True forces the Pallas interpreter on
    both passes (parity tests)."""
    _require_ranks("cascade_score_batched",
                   x=(x, 3), w_eff=(w_eff, 2), zq=(zq, 2))
    if interpret is None:
        if _auto_interpret():
            return cascade_score_batched_ref(x, w_eff, zq)
        interpret = False
    return _cascade_score_batched_pallas(interpret, x, w_eff, zq)


def cascade_filter(x, w_eff, zq, mask, m_q, *, interpret: bool | None = None):
    """Fused score+filter hard cascade: x (B, G, d), zq (B, T),
    mask (B, G), m_q (B,) -> dict(lp, survivors, expected_counts, n_keep).

    The serving hot path: on TPU this is one kernel launch per batch; on
    other backends it dispatches to the jitted XLA reference (identical
    semantics — see kernels/cascade_filter/ref.py) rather than crawling
    through the Pallas interpreter. interpret=True forces the interpreter
    for kernel-body parity testing."""
    _require_ranks("cascade_filter", x=(x, 3), w_eff=(w_eff, 2), zq=(zq, 2),
                   mask=(mask, 2), m_q=(m_q, 1))
    if interpret is None:
        if _auto_interpret():
            return cascade_filter_ref(x, w_eff, zq, mask, m_q)
        interpret = False
    return _cascade_filter(x, w_eff, zq, mask, m_q, interpret=interpret)


def swa_decode(q, k, v, cache_len, *, window: int = NO_WINDOW,
               interpret: bool | None = None):
    """Flash-decode attention of one token against a (sliding-window) KV
    cache. q: (B, H, hd), k/v: (B, S, Hkv, hd) -> (B, H, hd)."""
    if interpret is None:
        interpret = _auto_interpret()
    return _swa_decode(q, k, v, cache_len, window=window, interpret=interpret)


__all__ = ["cascade_loss_fused", "cascade_loss_ref", "cascade_loss_bwd_ref",
           "cascade_score_batched", "cascade_score_batched_ref",
           "cascade_filter", "cascade_filter_ref", "swa_decode",
           "swa_decode_ref", "NO_WINDOW"]
