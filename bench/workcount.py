"""The work the cascade's math requires, from request and batch shapes.

Counted for real items only, never for padded rows or lanes, so the count
is the same whatever implements the kernels and a change that removes
padding cannot read above its roofline:

  serving, per request of n items: each item's d_x float32 features read
    once and its T cumulative log pass-probabilities and T survivor flags
    written once; the query's d_q features and M_q read once and its T
    expected counts and T keep counts written once; 2 * d_x * T
    operations per item (the stage products) and 2 * d_q * T per query.
  training, per query group of n real items: each item's d_x features,
    its label and its importance weight read once; the group's d_q
    features and M_q read once; 2 * d_x * T operations per item forward
    and 2 * d_x * T for the weight gradient (the gradient with respect to
    the features is not needed).

The least time the chip could take is the larger of operations over the
peak operation rate and bytes over the peak memory bandwidth.
"""

from __future__ import annotations

F32 = 4


def serve_work(items_per_request, d_x: int, d_q: int, t: int) -> dict:
    n = float(sum(items_per_request))
    r = float(len(items_per_request))
    return {"flops": 2.0 * d_x * t * n + 2.0 * d_q * t * r,
            "bytes": F32 * (n * (d_x + 2 * t) + r * (d_q + 1 + 2 * t))}


def train_work(real_items: float, groups: float, d_x: int, d_q: int,
               t: int) -> dict:
    return {"flops": 4.0 * d_x * t * real_items,
            "bytes": F32 * (real_items * (d_x + 2) + groups * (d_q + 1))}


def least_seconds(work: dict, peaks: dict) -> float:
    return max(work["flops"] / peaks["flops_per_s"],
               work["bytes"] / peaks["hbm_bytes_per_s"])
