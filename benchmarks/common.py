"""Shared benchmark infrastructure: dataset, trained models, output."""

from __future__ import annotations

from functools import lru_cache

from repro.core import baselines as B
from repro.core import losses as L
from repro.core import trainer as T
from repro.data import generate_log, LogConfig


@lru_cache(maxsize=1)
def bench_log():
    """The offline benchmark dataset (paper: 2M instances; scaled to run in
    CI: ~40k instances, same structure)."""
    return generate_log(LogConfig(n_queries=1200, items_per_query=64, seed=42))


@lru_cache(maxsize=1)
def bench_split():
    return bench_log().split(0.8, seed=0)


@lru_cache(maxsize=8)
def trained_cloes(beta: float = 5.0, delta: float = 1.0,
                  eps_latency: float = 0.05, eps_purchase: float = 1.0,
                  mu_price: float = 1.0, loss: str = "l3",
                  cost_mask_positives: bool = False,
                  latency_scale: float | None = None):
    tr, _ = bench_split()
    kw = {} if latency_scale is None else {"latency_scale": latency_scale}
    lcfg = L.LossConfig(beta=beta, delta=delta, eps_latency=eps_latency,
                        eps_purchase=eps_purchase, mu_price=mu_price,
                        cost_mask_positives=cost_mask_positives, **kw)
    params, cfg = B.fit_cloes(
        tr, lcfg=lcfg, tcfg=T.TrainConfig(loss=loss, epochs=6, lr=0.01))
    return params, cfg, lcfg


def emit(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.1f},{derived}")
