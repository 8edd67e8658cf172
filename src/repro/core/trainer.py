"""SGD trainer for the CLOES cascade (paper §3.2: minibatch SGD, params
initialized near zero). Batches are query groups so the per-query reductions
of Eqs 10/16 are local sums.

Two engines behind the same `fit()` API:

  * ``engine="scan"`` (default) — the fused training engine: the log is
    packed and uploaded to the device ONCE (with the param-independent
    loss terms precomputed — see `_engine_pack`), each epoch permutes it
    on device and runs as one `jax.lax.scan` whose donated carry is the
    raveled (params, momentum) pair. Minibatch order comes from the same
    host-side RNG permutations as the loop engine, so the loss trajectory
    is reproduced step for step (to f32 re-association noise).
    With a `mesh`, the per-step minibatch is sharded over the mesh's data
    axis via shard_map (batch shard + gradient mean; single-device meshes
    degenerate to the plain scan). The packed item array is laid out
    exactly as the fused L3 step kernel consumes it (kernels/cascade_loss),
    so the default objective is one kernel call per step; with
    TrainConfig.precision="bf16" the item array is stored in bfloat16
    (f32 accumulation everywhere) and TrainConfig.loss_scale scales the
    optimized objective.
  * ``engine="loop"`` — the original per-step Python loop (one jitted step
    per minibatch, seven host->device uploads each). Kept as the benchmark
    baseline and the trajectory-parity oracle.
"""

from __future__ import annotations

import dataclasses
import os
from functools import partial
from typing import Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, PartitionSpec as PS

from repro.checkpoint import CheckpointStore
from repro.core import cascade as C
from repro.core import losses as L
from repro.data.synthetic import SearchLog
from repro.kernels.cascade_loss.kernel import pack_items
from repro.optim.sgd import apply_updates, momentum_sgd

# Exit code of the deterministic crash seam (fit(crash_after_epoch=k)):
# os._exit at this code models SIGKILL — no finally blocks, no atexit, no
# flush — so the restart smoke exercises exactly what a preemption leaves
# behind. 9 on purpose (the SIGKILL signal number).
CRASH_EXIT_CODE = 9


@dataclasses.dataclass
class TrainConfig:
    loss: str = "l3"           # l1 | l2 | l3
    lr: float = 0.05
    momentum: float = 0.9
    batch_groups: int = 64     # query groups per minibatch
    epochs: int = 10
    seed: int = 0
    log_every: int = 200
    engine: str = "scan"       # scan | loop (see module docstring)
    # Engine-pack storage precision (scan engine only). "bf16" stores the
    # packed ITEM array (the (B, G, d_x+4) bulk of the device-resident log)
    # in bfloat16, halving its footprint and the per-epoch permute traffic;
    # every consumer accumulates in f32 (the losses/kernels up-cast
    # in-kernel, _engine_unpack up-casts the minibatch view), so only the
    # one storage rounding separates the trajectories. The small group
    # array stays f32: m_q/mn/n_o_eff reach the thousands, where bf16's
    # 8-bit mantissa would visibly shift the Eq-10/14 penalty targets.
    precision: str = "f32"     # f32 | bf16
    # Static loss-scale for the mixed-precision path: the scanned step
    # optimizes loss * loss_scale and unscales grads before the update.
    # Power-of-two scales are exact in f32 (the trajectory is invariant —
    # locked by tests); plumbed for the Eq-8/Eq-16 reductions over 5e5-item
    # hot queries, whose tiny per-item cost gradients underflow first when
    # cotangents ever ride a 16-bit backward.
    loss_scale: float = 1.0
    # Snapshot (params + momentum + epoch + rng key) to fit()'s
    # checkpoint_dir every this-many epochs (scan engine only; 0 with a
    # checkpoint_dir means every epoch). The final epoch is always
    # snapshotted. Because an epoch is a pure function of the restored
    # carry — minibatch order is re-derived from seed+epoch — a resumed
    # run is bit-identical to the uninterrupted one.
    checkpoint_every: int = 0


def epoch_steps(n_groups: int, batch_groups: int) -> tuple[int, int]:
    """(full minibatches per epoch, query groups DROPPED from the tail).

    The tail partial batch is dropped deliberately: every step of the
    epoch scan (and every jitted loop step) then sees the same
    (batch_groups, G) shapes — no recompiles, no masked partial step.
    With the default 64 groups that is < 64 of B groups per epoch, and a
    fresh permutation each epoch means no group is systematically lost.
    """
    steps = n_groups // batch_groups
    return steps, n_groups - steps * batch_groups


def _epoch_perm(n_groups: int, batch_groups: int, seed: int) -> np.ndarray:
    """Host-side minibatch index plan for one epoch: (steps, batch_groups).

    The SAME RNG stream as `batches()` — the scan engine consumes these
    indices on device, so both engines visit identical minibatches.
    """
    steps, _ = epoch_steps(n_groups, batch_groups)
    perm = np.random.default_rng(seed).permutation(n_groups)
    return perm[:steps * batch_groups].reshape(steps, batch_groups)


def _log_arrays(log: SearchLog) -> dict[str, jax.Array]:
    """The full log as device arrays — uploaded once per fit()."""
    return {
        "x": jnp.asarray(log.x, jnp.float32),
        "q": jnp.asarray(log.q, jnp.float32),
        "y": jnp.asarray(log.y, jnp.float32),
        "mask": jnp.asarray(log.mask, jnp.float32),
        "behavior": jnp.asarray(log.behavior),
        "price": jnp.asarray(log.price, jnp.float32),
        "m_q": jnp.asarray(log.m_q, jnp.float32),
    }


def batches(log: SearchLog, batch_groups: int, seed: int) -> Iterator[dict]:
    """Host-side minibatch iterator (the loop engine's data path).

    NOTE: the tail partial batch is dropped — see `epoch_steps`, which
    also reports how many groups that discards per epoch.
    """
    idx_plan = _epoch_perm(log.x.shape[0], batch_groups, seed)
    for idx in idx_plan:
        yield {
            "x": jnp.asarray(log.x[idx], jnp.float32),
            "q": jnp.asarray(log.q[idx], jnp.float32),
            "y": jnp.asarray(log.y[idx], jnp.float32),
            "mask": jnp.asarray(log.mask[idx], jnp.float32),
            "behavior": jnp.asarray(log.behavior[idx]),
            "price": jnp.asarray(log.price[idx], jnp.float32),
            "m_q": jnp.asarray(log.m_q[idx], jnp.float32),
        }


def _resolve_loss(loss_name) -> Callable:
    return L.LOSSES[loss_name] if isinstance(loss_name, str) else loss_name


@partial(jax.jit, static_argnames=("cfg", "lcfg", "loss_name", "opt_update"))
def train_step(params, opt_state, batch, cfg: C.CascadeConfig,
               lcfg: L.LossConfig, loss_name, opt_update):
    loss_fn = _resolve_loss(loss_name)
    loss, grads = jax.value_and_grad(loss_fn)(params, cfg, lcfg, batch)
    updates, opt_state = opt_update(grads, opt_state, params)
    return apply_updates(params, updates), opt_state, loss


# ---------------------------------------------------------------------------
# Scan engine: one XLA computation per epoch, device-resident data, donated
# parameter/optimizer buffers. Optionally shard_map'd over a data mesh.
#
# The per-step graph is kept minimal: everything in the objective that does
# not depend on the params — importance weights, Eq-8 cost weights, Eq-10
# extrapolation factors, the result-size floor — is a pure function of
# (log, lcfg) and is precomputed ONCE per fit (`_engine_pack`, the
# engine-batch protocol in core.losses). The packed log is TWO arrays
# (item-level and group-level), so each epoch permutes with two gathers and
# the scan slices two xs, not seven. Params and momentum ride the scan
# carry as single raveled vectors (one optimizer kernel instead of one per
# leaf); the update math is element-wise identical, so trajectories match
# the loop engine bit for bit.
# ---------------------------------------------------------------------------

def _engine_pack(log: SearchLog, lcfg: L.LossConfig,
                 precision: str = "f32") -> tuple[jax.Array, jax.Array]:
    """Upload the log once, with param-independent loss terms precomputed.

    Returns (item (B, G, d_x+4), group (B, d_q+3)):
      item  = [x | y | mask | wgt | cost_w]
      group = [q | m_q | mn | n_o_eff]

    The item layout is exactly the packed tensor kernels.ops.
    cascade_loss_fused consumes — the fused L3 step scores and reduces it
    without any per-step re-packing. With precision="bf16" the item array
    is stored in bfloat16 (see TrainConfig.precision); the binary y/mask
    columns and the one-hot x registry features are bf16-exact, so the
    rounding touches only the dense feature/wgt/cost_w values.
    """
    if precision not in ("f32", "bf16"):
        raise ValueError(f"unknown engine precision: {precision!r} "
                         "(expected 'f32' or 'bf16')")
    d = _log_arrays(log)
    wgt = L.importance_weights(d["behavior"], d["price"], lcfg)
    n_q = jnp.maximum(d["mask"].sum(-1), 1.0)
    mn = d["m_q"] / n_q
    base_w = (d["mask"] * (1.0 - d["y"]) if lcfg.cost_mask_positives
              else d["mask"])
    cost_w = base_w * mn[:, None]
    n_o_eff = jnp.minimum(lcfg.n_o, d["m_q"])
    item = pack_items(d["x"], d["y"], d["mask"], wgt, cost_w)
    group = jnp.concatenate(
        [d["q"], d["m_q"][:, None], mn[:, None], n_o_eff[:, None]], axis=-1)
    if precision == "bf16":
        item = item.astype(jnp.bfloat16)
    return item, group


def _engine_unpack(item: jax.Array, group: jax.Array,
                   d_x: int, d_q: int) -> dict[str, jax.Array]:
    """Packed minibatch -> the engine-batch dict the losses consume.

    Up-casts to f32 first (a no-op for f32 packs): storage precision is the
    pack's concern, every downstream reduction accumulates in f32. The
    packed item tensor rides along under "xc" — it is exactly the layout
    kernels.ops.cascade_loss_fused consumes, so the fused L3 step scores
    and reduces it without re-packing."""
    item = item.astype(jnp.float32)
    group = group.astype(jnp.float32)
    return {
        "xc": item,
        "x": item[..., :d_x], "y": item[..., d_x],
        "mask": item[..., d_x + 1], "wgt": item[..., d_x + 2],
        "cost_w": item[..., d_x + 3],
        "q": group[..., :d_q], "m_q": group[..., d_q],
        "mn": group[..., d_q + 1], "n_o_eff": group[..., d_q + 2],
    }


def _make_epoch_fn(cfg: C.CascadeConfig, lcfg: L.LossConfig, loss_fn,
                   opt_update, mesh: Mesh | None, unravel,
                   loss_scale: float = 1.0):
    """Build the jitted epoch function:
    (theta, opt_state, item, group, idx (steps, batch_groups)) ->
    (theta, opt_state, losses (steps,)). theta is the raveled param vector
    (unravel maps it back to the param dict for the loss). loss_scale
    scales the optimized objective and unscales grads/reported losses
    before the update (see TrainConfig.loss_scale)."""

    def epoch(theta, opt_state, item, group, idx):
        steps, bg = idx.shape
        # Permute ON DEVICE, once per epoch: one gather per packed array,
        # reshaped to (steps, batch_groups, ...) and consumed as the
        # scan's xs — each step reads its minibatch by dynamic slice.
        # Costs one transient copy of the log. A bf16 pack is gathered in
        # bf16 (the halved permute traffic) and up-cast HERE, once per
        # epoch — a per-step convert would break the step's loop fusions
        # (measured 3x slower on CPU).
        flat = idx.reshape(-1)
        xs = (item[flat].reshape(steps, bg, *item.shape[1:])
              .astype(jnp.float32),
              group[flat].reshape(steps, bg, *group.shape[1:])
              .astype(jnp.float32))

        def step(carry, mb):
            theta, opt_state = carry
            batch = _engine_unpack(mb[0], mb[1], cfg.d_x, cfg.d_q)
            loss, grads = jax.value_and_grad(
                lambda th: loss_fn(unravel(th), cfg, lcfg, batch)
                * loss_scale)(theta)
            if loss_scale != 1.0:
                loss = loss / loss_scale
                grads = grads / loss_scale      # theta rides as one ravel
            if mesh is not None:
                # data parallelism: each shard computed its loss on its
                # slice of the minibatch groups; average grads (and the
                # reported loss) across shards before the (replicated)
                # update.
                grads = jax.lax.pmean(grads, "data")
                loss = jax.lax.pmean(loss, "data")
            updates, opt_state = opt_update(grads, opt_state, theta)
            return (apply_updates(theta, updates), opt_state), loss

        (theta, opt_state), losses = jax.lax.scan(
            step, (theta, opt_state), xs)
        return theta, opt_state, losses

    if mesh is None:
        return jax.jit(epoch, donate_argnums=(0, 1))

    sharded = jax.shard_map(
        epoch, mesh=mesh,
        # theta/opt_state replicated, the packed log replicated, the
        # per-step minibatch group axis sharded over the data axis.
        in_specs=(PS(), PS(), PS(), PS(), PS(None, "data")),
        out_specs=(PS(), PS(), PS()),
        check_vma=False)       # pmean'd grads make the outputs replicated
    return jax.jit(sharded, donate_argnums=(0, 1))


def _train_sig(tcfg: TrainConfig, cfg: C.CascadeConfig, n_groups: int) -> dict:
    """The run identity a checkpoint is only valid under. Saved in every
    checkpoint's meta and strict-equality-checked on resume: resuming a
    trajectory under a different objective/optimizer/data-order config
    would silently produce a hybrid run, so it is rejected instead."""
    return {
        "loss": tcfg.loss if isinstance(tcfg.loss, str) else "<custom>",
        "lr": tcfg.lr, "momentum": tcfg.momentum,
        "batch_groups": tcfg.batch_groups, "seed": tcfg.seed,
        "precision": tcfg.precision, "loss_scale": tcfg.loss_scale,
        "n_groups": n_groups, "d_x": cfg.d_x, "d_q": cfg.d_q,
        "n_stages": cfg.n_stages,
    }


def fit(log: SearchLog, cfg: C.CascadeConfig, lcfg: L.LossConfig,
        tcfg: TrainConfig | None = None,
        callback: Callable[[int, float], None] | None = None,
        *, loss_fn: Callable | None = None,
        mesh: Mesh | None = None,
        checkpoint_dir: str | None = None, resume: bool = False,
        keep_checkpoints: int = 3, crash_after_epoch: int | None = None,
        train_info: dict | None = None) -> C.Params:
    """Train CLOES params on the log. See module docstring for the engines.

    loss_fn overrides the objective looked up from tcfg.loss (used by the
    training benchmark to pin a reference implementation). mesh enables
    the shard_map data-parallel path (scan engine only): tcfg.batch_groups
    must divide by the mesh's data-axis size.

    checkpoint_dir (scan engine only) makes training crash-safe: every
    tcfg.checkpoint_every-th epoch (and the last) the raveled params,
    momentum state, completed-epoch count and rng key are committed to a
    CheckpointStore. resume=True restores the latest good checkpoint
    (falling back past torn ones) and continues — bit-identically,
    because an epoch is a pure function of (theta, opt_state, epoch): the
    minibatch order is re-derived from seed+epoch, not from mutable rng
    state. A checkpoint written under a different TrainConfig identity is
    rejected (see _train_sig). crash_after_epoch hard-exits the process
    (os._exit(CRASH_EXIT_CODE), a SIGKILL stand-in) after that many
    epochs — the deterministic crash seam the CI restart smoke uses.
    train_info, when given, receives {"restored_epoch", "epochs_run"}.

    Data-parallel semantics (the standard approximation): each shard
    normalizes its loss over ITS slice of the minibatch (mask.sum(),
    m_q.sum() are per-shard) and gradients are pmean'd — grad of the mean
    of per-shard losses, not grad of the global-batch loss. With >1
    device the trajectory therefore deviates from single-device training
    when shards carry unequal valid-item mass; a 1-device mesh is exact.
    """
    tcfg = tcfg or TrainConfig()
    key = jax.random.PRNGKey(tcfg.seed)
    params = C.init_params(cfg, key)
    opt = momentum_sgd(tcfg.lr, tcfg.momentum)
    opt_state = opt.init(params)
    loss_fn = loss_fn or L.LOSSES[tcfg.loss]

    if tcfg.engine == "loop":
        assert mesh is None, "the loop engine has no data-parallel path"
        if checkpoint_dir is not None:
            raise ValueError(
                "checkpointing is a scan-engine feature (the loop engine "
                "is the no-moving-parts baseline/oracle)")
        if tcfg.precision != "f32" or tcfg.loss_scale != 1.0:
            raise ValueError(
                "precision/loss_scale are scan-engine features (the loop "
                "engine is the plain-f32 baseline/oracle); got "
                f"precision={tcfg.precision!r}, loss_scale={tcfg.loss_scale}")
        step = 0
        for epoch in range(tcfg.epochs):
            for batch in batches(log, tcfg.batch_groups, tcfg.seed + epoch):
                params, opt_state, loss = train_step(
                    params, opt_state, batch, cfg, lcfg, loss_fn, opt.update)
                if callback and step % tcfg.log_every == 0:
                    callback(step, float(loss))
                step += 1
        return params
    if tcfg.engine != "scan":
        raise ValueError(f"unknown trainer engine: {tcfg.engine!r}")

    if mesh is not None:
        n_data = mesh.shape["data"]
        if tcfg.batch_groups % n_data:
            raise ValueError(f"batch_groups={tcfg.batch_groups} must divide "
                             f"by the data-axis size {n_data}")
    B = log.x.shape[0]
    steps_per_epoch, _ = epoch_steps(B, tcfg.batch_groups)
    if steps_per_epoch == 0:
        return params
    item, group = _engine_pack(log, lcfg, tcfg.precision)  # ONE upload/fit
    theta, unravel = ravel_pytree(params)
    opt_state = opt.init(theta)                     # momentum on the ravel
    epoch_fn = _make_epoch_fn(cfg, lcfg, loss_fn, opt.update, mesh, unravel,
                              tcfg.loss_scale)

    store = None
    start_epoch = 0
    if checkpoint_dir is not None:
        sig = _train_sig(tcfg, cfg, B)
        ckpt_every = max(1, tcfg.checkpoint_every)
        store = CheckpointStore(checkpoint_dir, keep=keep_checkpoints)
        if resume:
            latest = store.load_latest()    # skips torn/corrupt steps
            if latest is not None:
                _, state, meta = latest
                saved_sig = (meta or {}).get("train_sig")
                if saved_sig != sig:
                    raise ValueError(
                        "checkpoint was written under a different training "
                        f"config: saved {saved_sig} != current {sig}")
                # exact restore: theta and momentum bytes are crc-verified,
                # so the resumed carry IS the killed run's carry
                theta = jnp.asarray(state["theta"])
                opt_state = jax.tree.map(jnp.asarray, state["opt_state"])
                start_epoch = int(state["epoch"])
    if train_info is not None:
        train_info["restored_epoch"] = start_epoch
        train_info["epochs_run"] = max(0, tcfg.epochs - start_epoch)

    for epoch in range(start_epoch, tcfg.epochs):
        idx = jnp.asarray(
            _epoch_perm(B, tcfg.batch_groups, tcfg.seed + epoch))
        theta, opt_state, losses = epoch_fn(theta, opt_state, item, group,
                                            idx)
        if callback:
            base = epoch * steps_per_epoch
            for i in range(steps_per_epoch):
                if (base + i) % tcfg.log_every == 0:
                    callback(base + i, float(losses[i]))
        done = epoch + 1
        if store is not None and (done % ckpt_every == 0
                                  or done == tcfg.epochs):
            # theta/opt_state here are the epoch's RETURNED values — the
            # host fetch in save copies them before the next epoch_fn call
            # donates their buffers
            store.save(done, {"theta": theta, "opt_state": opt_state,
                              "epoch": done, "rng_key": key},
                       meta={"train_sig": sig})
        if crash_after_epoch is not None and done >= crash_after_epoch:
            os._exit(CRASH_EXIT_CODE)
    return unravel(theta)


def evaluate(params: C.Params, cfg: C.CascadeConfig, log: SearchLog,
             lcfg: L.LossConfig | None = None) -> dict[str, float]:
    """Offline metrics: AUC of the final score + expected cost per instance
    (Eq 8) + expected per-query latency (Eq 16) + final result size.

    ONE cascade forward: scores, cost, counts and latency are all derived
    from the same (B, G, T) log pass-probabilities (the pre-refactor
    version re-scored the log four times).
    """
    from repro.core import metrics as M
    lcfg = lcfg or L.LossConfig()
    x = jnp.asarray(log.x, jnp.float32)
    q = jnp.asarray(log.q, jnp.float32)
    mask = jnp.asarray(log.mask, jnp.float32)
    m_q = jnp.asarray(log.m_q, jnp.float32)
    lp, _ = L.cascade_forward(params, cfg, x, q)
    scores = np.asarray(lp[..., -1])
    cost = float(L.cost_from_lp(lp, cfg, mask, m_q=m_q))
    counts = L.counts_from_lp(lp, mask, m_q)                    # (B, T)
    lat = np.asarray(L.latency_from_counts_q(counts, m_q, cfg, lcfg))
    counts_T = np.asarray(counts)[:, -1]
    return {
        "auc": M.group_auc(scores, log.y, log.mask),
        "pooled_auc": M.auc(scores, log.y, log.mask),
        "expected_cost_per_item": cost,
        "mean_expected_latency": float(lat.mean()),
        "p95_expected_latency": float(np.percentile(lat, 95)),
        "mean_final_count": float(counts_T.mean()),
        "frac_queries_below_no": float(
            (counts_T < np.minimum(lcfg.n_o, log.m_q)).mean()),
    }
