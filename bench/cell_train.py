"""Training cells: the L3 fit through the scan engine's jitted epoch.

One general feed reads a traffic file (bench/traffic/<mix>.json):

  {"kind": "train", "order": "shuffle_per_epoch"}

Set-up makes the paper-scale log on the host from --seed, uploads it once
through the engine's own pack (core/trainer.py), draws the starting
weights on the device, builds the compiled epoch with its momentum state,
and drives that object through its first call: one epoch of
n_queries // batch_groups steps, each on its own batch_groups query groups
(a permutation of the log drawn from the seed, so every row differs).
That same object then trains for the window, epoch after epoch, one call
in flight while the host prepares the next; groups_per_s counts every step
of the window over all of its time. Before each call of the window a
device copy of the state it is fed is kept (the call donates its state),
so that the window's last epoch can be followed too.

After the window the device's peak memory is read and the program's state
is freed; then the plain reference (reference.train_ref) follows two
calls on the same minibatches: the first, from the seed's weights, and
the window's last, from the parameters and momentum that the window had
reached. checks.training_readings compares each, and every number is the
worse of the two.
"""

from __future__ import annotations

import time

import numpy as np

import benchlib
import checks
import reference
import system
import workcount


def epoch_order(seed: int, epoch: int, n_groups: int, batch_groups: int
                ) -> np.ndarray:
    """(steps, batch_groups) query-group indices of one epoch."""
    steps = n_groups // batch_groups
    rng = np.random.default_rng([*benchlib.split_seed(seed), 2, epoch])
    return rng.permutation(n_groups)[:steps * batch_groups].reshape(
        steps, batch_groups)


def reference_steps(log, idx: np.ndarray) -> dict:
    """The raw minibatches of one epoch, stacked on a leading step axis."""
    return {"x": log.x[idx].astype(np.float32),
            "q": log.q[idx].astype(np.float32),
            "y": log.y[idx].astype(np.float32),
            "behavior": log.behavior[idx],
            "price": log.price[idx].astype(np.float32),
            "mask": log.mask[idx].astype(np.float32),
            "m_q": log.m_q[idx].astype(np.float32)}


def reference_constants(config: dict) -> dict:
    c = {k: v for k, v in config["loss"].items()
         if not isinstance(v, (str, bool))}
    c.update(stage_masks=config["stage_masks"],
             stage_times=config["stage_times"],
             lr=config["training"]["lr"],
             momentum=config["training"]["momentum"])
    return c


def follow_reference(config: dict, log, idx: np.ndarray, start: dict,
                     device, mu: dict | None = None,
                     precision: str = "highest") -> dict:
    """reference.train_ref from `start` (and momentum `mu`, zero when not
    given) over the minibatches `idx`, on `device`: losses per step, and
    the parameters and momentum after."""
    import jax
    if mu is None:
        mu = {k: np.zeros_like(v) for k, v in start.items()}
    p, mu, losses = reference.train_ref(
        jax.device_put(start, device), jax.device_put(mu, device),
        jax.device_put(reference_steps(log, idx), device),
        reference_constants(config), precision=precision)
    return {"losses": np.asarray(losses),
            "params": {k: np.asarray(v) for k, v in p.items()},
            "mu": {k: np.asarray(v) for k, v in mu.items()}}


def build_program(config: dict, log, params, precision: str):
    """The engine's compiled epoch and its state, as fit() builds them:
    (epoch_fn, theta, opt_state, item, group, unravel)."""
    import jax
    from jax.flatten_util import ravel_pytree
    from repro.core import losses as L
    from repro.core import trainer as T
    from repro.optim.sgd import momentum_sgd
    tr = config["training"]
    cfg, lcfg = system.cascade_config(config), system.loss_config(config)
    item, group = T._engine_pack(log, lcfg, precision)
    theta, unravel = ravel_pytree(params)
    opt = momentum_sgd(tr["lr"], tr["momentum"])
    epoch_fn = T._make_epoch_fn(cfg, lcfg, L.LOSSES["l3"], opt.update, None,
                                unravel, 1.0)
    # The optimizer state is placed on theta's device before the first
    # call: its step counter starts as an uncommitted scalar, and an epoch
    # fed an uncommitted counter compiles again once it is fed its own
    # (committed) output.
    opt_state = jax.device_put(opt.init(theta), theta.sharding)
    return epoch_fn, theta, opt_state, item, group, unravel


def run(cell, seed: int, seconds: float, trace: bool, t0: float, devs,
        peaks: dict, precision: str | None = None) -> dict:
    """One run of a training cell. precision="bf16" runs the engine's own
    bfloat16 storage path (the control) instead of the configuration's."""
    import jax
    import jax.numpy as jnp
    config = cell.config
    tr = config["training"]
    if tr["engine"] != "scan":
        raise benchlib.BenchError(f"unknown engine {tr['engine']!r}")
    precision = precision or tr["precision"]
    bg = int(tr["batch_groups"])
    phases = benchlib.Phases(t0)
    phases.mark("init")
    log = system.make_log(config, seed)
    phases.mark("log")
    n_groups = log.x.shape[0]
    params = system.make_weights(config, seed, config["train_init_std"],
                                 devs[0])
    start = {k: np.asarray(v) for k, v in params.items()}
    epoch_fn, theta, opt_state, item, group, unravel = build_program(
        config, log, params, precision)
    phases.mark("upload")

    def host_state(theta, opt_state) -> dict:
        return {"params": {k: np.asarray(v)
                           for k, v in unravel(theta).items()},
                "mu": {k: np.asarray(v)
                       for k, v in unravel(opt_state["mu"]).items()}}

    idx0 = epoch_order(seed, 0, n_groups, bg)
    theta, opt_state, losses = epoch_fn(theta, opt_state, item, group,
                                        jnp.asarray(idx0))
    first = {"losses": np.asarray(losses), **host_state(theta, opt_state)}
    # the window copies the state it feeds each call: compile that here
    jax.block_until_ready(jax.tree.map(jnp.copy, (theta, opt_state)))
    phases.mark("first_call")

    tracer = benchlib.Tracer(trace)
    span = benchlib.span if trace else benchlib.no_span
    clock = benchlib.CompileClock()
    epochs = 0
    try:
        tracer.start()
        t_start = time.monotonic()
        with span(benchlib.WINDOW_SPAN):
            pending = None
            while True:
                idx = jnp.asarray(epoch_order(seed, epochs + 1, n_groups, bg))
                fed = jax.tree.map(jnp.copy, (theta, opt_state))
                theta, opt_state, losses = epoch_fn(theta, opt_state, item,
                                                    group, idx)
                if pending is not None:
                    pending.block_until_ready()
                pending = losses
                epochs += 1
                if time.monotonic() - t_start >= seconds:
                    break
            last_losses = np.asarray(pending)
            t_end = time.monotonic()
        summary = tracer.stop()
    finally:
        tracer.close()
        clock.close()
    memory = benchlib.memory_peak_bytes(devs)
    last = {"losses": last_losses, **host_state(theta, opt_state)}
    last_start = host_state(*fed)
    del epoch_fn, theta, opt_state, item, group, params, pending, fed

    steps = idx0.shape[0]
    window_s = t_end - t_start
    real = sum(float(log.mask[epoch_order(seed, e + 1, n_groups, bg)].sum())
               for e in range(epochs))
    work = workcount.train_work(real, epochs * steps * bg, config["d_x"],
                                config["d_q"], config["n_stages"])

    ref = follow_reference(config, log, idx0, start, devs[0])
    idx_last = epoch_order(seed, epochs, n_groups, bg)
    ref_last = follow_reference(config, log, idx_last, last_start["params"],
                                devs[0], mu=last_start["mu"])
    readings = checks.worse_of(
        checks.training_readings(first, ref, start),
        checks.training_readings(last, ref_last, last_start["params"]))
    limits = dict(config["limits"]["train"])
    correct, shown = checks.judge(readings, limits)

    facts = {"kind": "train", "peaks": peaks, "trace": summary,
             "work": work, "window_s": window_s, "steps": epochs * steps}
    e2e = {"groups_per_s": epochs * steps * bg / window_s,
           "setup_s": t_start - t0}
    notes = {"epochs": epochs, "steps_per_epoch": steps,
             "compiles_in_window": clock.compiles,
             "first_epoch_loss": float(first["losses"].mean()),
             "last_epoch_loss": float(last_losses.mean()),
             "loss_gap_step0": readings["loss_gap_step0"],
             "setup_phases_s": phases.laps}
    return {"correct": correct, "attempted": epochs * steps, "failed": 0,
            "e2e": e2e, "facts": facts, "memory": memory, "checks": shown,
            "notes": notes, "readings": readings}
