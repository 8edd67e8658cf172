"""Training launcher.

Two paths:
  * `--target cloes`  — train the paper's cascade on the synthetic log,
    data-parallel via shard_map over whatever mesh is available (a 1-D
    ("data",) mesh of the local devices; clean fallback to the plain scan
    engine on one device). The loss's per-query reductions are
    group-local, so data parallelism is a batch shard + gradient mean —
    per-shard loss normalization, the standard approximation (see
    core.trainer.fit for the exact semantics).
  * `--target lm --arch <id>` — train a (reduced) assigned architecture as
    the neural final-stage ranker substrate.

Usage:
  PYTHONPATH=src python -m repro.launch.train --target cloes --epochs 6
  PYTHONPATH=src python -m repro.launch.train --target lm --arch starcoder2-3b \
      --smoke --steps 50
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import baselines as B
from repro.core import losses as L
from repro.core import trainer as T
from repro.data import LogConfig, generate_log
from repro.launch.compile_cache import enable_compile_cache


def params_digest(params) -> str:
    """sha256 over the params' (path, shape, bytes) in sorted-path order —
    a stable identity for trajectory-parity checks: the CI restart smoke
    compares this line between the resumed and uninterrupted runs."""
    import hashlib

    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in sorted(leaves, key=lambda kv: str(kv[0])):
        a = np.asarray(jax.device_get(leaf))
        h.update(str(path).encode())
        h.update(str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def train_cloes(args) -> None:
    from repro.launch.mesh import data_parallel_mesh

    log = generate_log(LogConfig(n_queries=args.queries, seed=args.seed))
    tr, te = log.split(0.8)
    lcfg = L.LossConfig(beta=args.beta)
    devices = jax.devices()
    mesh = data_parallel_mesh(args.batch_groups)
    shards = mesh.shape["data"] if mesh is not None else 1
    print(f"[train] CLOES on {len(devices)} device(s) "
          f"({shards}-way data parallel), {tr.n_instances} instances")
    t0 = time.perf_counter()
    info: dict = {}
    params, cfg = B.fit_cloes(
        tr, lcfg=lcfg,
        tcfg=T.TrainConfig(loss="l3", epochs=args.epochs, lr=args.lr,
                           batch_groups=args.batch_groups,
                           checkpoint_every=args.checkpoint_every),
        mesh=mesh,
        checkpoint_dir=args.checkpoint_dir or None,
        resume=args.resume,
        crash_after_epoch=args.crash_after_epoch,
        train_info=info)
    restored = info.get("restored_epoch", 0)
    print(f"[train] done in {time.perf_counter()-t0:.1f}s "
          f"(restored_epoch={restored} epochs_run={info.get('epochs_run', args.epochs)})")
    print(f"[train] params sha256={params_digest(params)}")
    for split, data in [("train", tr), ("test", te)]:
        m = T.evaluate(params, cfg, data, lcfg)
        print(f"[eval:{split}] " + " ".join(f"{k}={v:.4f}" for k, v in m.items()))
    if args.save:
        from repro.checkpoint import save_pytree
        save_pytree(args.save, {"params": params,
                                "lcfg": dataclasses.asdict(lcfg)})
        print(f"[ckpt] saved to {args.save}")


def train_lm(args) -> None:
    import repro.configs as CFG
    from repro.models import base as MB
    from repro.models import zoo as Z
    from repro.optim import adam

    cfg = CFG.get_smoke(args.arch) if args.smoke else CFG.get(args.arch)
    cfg = dataclasses.replace(cfg, dtype=jnp.float32 if args.smoke else cfg.dtype)
    key = jax.random.PRNGKey(args.seed)
    params = MB.materialize(Z.templates(cfg), key)
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    print(f"[train] {cfg.name}: {n_params/1e6:.1f}M params, {args.steps} steps")
    opt = adam(args.lr)
    opt_state = opt.init(params)
    rng = np.random.default_rng(args.seed)
    bsz, s = args.batch, args.seq
    step_fn = jax.jit(lambda p, o, b: Z.train_step(p, o, b, cfg, opt.update))
    t0 = time.perf_counter()
    for step in range(args.steps):
        tok = rng.integers(0, cfg.vocab, (bsz, s + 1))
        batch = {"tokens": jnp.asarray(tok[:, :-1]),
                 "targets": jnp.asarray(tok[:, 1:])}
        if cfg.arch_type == "encdec":
            batch["frontend"] = jnp.asarray(
                0.1 * rng.normal(size=(bsz, 16, cfg.d_model)), jnp.float32)
        elif cfg.frontend_positions:
            p_ = cfg.frontend_positions
            batch["frontend"] = jnp.asarray(
                0.1 * rng.normal(size=(bsz, p_, cfg.d_model)), jnp.float32)
            batch["tokens"] = batch["tokens"][:, :s - p_]
            batch["targets"] = batch["targets"][:, :s - p_]
        params, opt_state, loss = step_fn(params, opt_state, batch)
        if step % max(1, args.steps // 10) == 0:
            print(f"  step {step:4d} loss {float(loss):.4f} "
                  f"({(time.perf_counter()-t0)/(step+1):.2f}s/step)")
    print(f"[train] final loss {float(loss):.4f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--target", choices=["cloes", "lm"], default="cloes")
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--queries", type=int, default=1200)
    ap.add_argument("--epochs", type=int, default=6)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch-groups", type=int, default=64)
    ap.add_argument("--beta", type=float, default=5.0)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--save", default="")
    ap.add_argument("--checkpoint-dir", default="",
                    help="crash-safe per-epoch checkpoints (cloes target)")
    ap.add_argument("--checkpoint-every", type=int, default=1,
                    help="epochs between checkpoints (with --checkpoint-dir)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest good checkpoint")
    ap.add_argument("--crash-after-epoch", type=int, default=None,
                    help="test seam: hard-exit (code 9) after N epochs")
    args = ap.parse_args()
    enable_compile_cache()
    if args.target == "cloes":
        train_cloes(args)
    else:
        train_lm(args)


if __name__ == "__main__":
    main()
