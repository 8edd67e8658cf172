"""The system under test, built from a configuration file.

The only module of the benchmark, with the cell runners, that imports the
program (src/repro): its configuration objects are made here from the
benchmark's own configuration file, so the file is what runs.
"""

from __future__ import annotations

import benchlib


def cascade_config(config: dict):
    from repro.core.cascade import CascadeConfig
    return CascadeConfig(n_stages=config["n_stages"], d_x=config["d_x"],
                         d_q=config["d_q"], masks=config["stage_masks"],
                         stage_times=config["stage_times"])


def loss_config(config: dict):
    from repro.core.losses import LossConfig
    return LossConfig(**config["loss"])


def serving_config(config: dict):
    from repro.serving.session import (DegradePolicy, FlushPolicy,
                                       ServingConfig)
    s = config["serving"]
    return ServingConfig(
        plan=s["plan"], group_buckets=tuple(s["group_buckets"]),
        batch_groups=s["batch_groups"], max_queue=s["max_queue"],
        flush=FlushPolicy(max_wait_ms=s["max_wait_ms"]),
        degrade=DegradePolicy(high_watermark=s["high_watermark"],
                              low_watermark=s["low_watermark"]))


def make_weights(config: dict, seed: int, std: float, device=None) -> dict:
    """Cascade weights w_x (T, d_x), w_q (T, d_q), b (T,) drawn N(0, std)
    from the seed on the device, in one jitted call."""
    import jax
    import jax.numpy as jnp
    t, d_x, d_q = config["n_stages"], config["d_x"], config["d_q"]

    @jax.jit
    def draw(key):
        kx, kq, kb = jax.random.split(key, 3)
        return {"w_x": std * jax.random.normal(kx, (t, d_x), jnp.float32),
                "w_q": std * jax.random.normal(kq, (t, d_q), jnp.float32),
                "b": std * jax.random.normal(kb, (t,), jnp.float32)}

    key = benchlib.device_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return draw(key)


def make_log(config: dict, seed: int):
    """The paper-scale search log (repro.data.generate_log), made on the
    host from the seed."""
    from repro.data import LogConfig, generate_log
    return generate_log(LogConfig(n_queries=config["n_queries"],
                                  items_per_query=config["items_per_query"],
                                  seed=seed))
