"""The serving program's one transfer each way: `rank_batch` copies a
batch's one staging buffer (`alloc_batch`) to the device, `cascade_rank`
unpacks it and returns one packed float32 array, and `split_results`
reads that into host views that must equal, bit for bit, what
`core.pipeline.run_cascade` and the Eq-16 latency give directly on the
same x, q, mask and m_q -- scores with their -infs, last-stage
survivors, latency, and per-stage counts equal to the per-stage survivor
masks summed over items."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as CFG
from repro.core import cascade as C
from repro.core import losses as L
from repro.core import pipeline as P
from repro.data import features as F
from repro.serving.batching import alloc_batch, staging_width
from repro.serving.cascade_server import NeuralScorer
from repro.serving.session import CascadeSession, ServingConfig, split_results

BATCH_GROUPS = 4
WIDE_LADDER = (16, 64, 256, 1024, 4096)


def _cascade():
    masks = F.default_stage_masks(3)
    cfg = C.CascadeConfig(3, F.N_FEATURES, F.N_QUERY_BUCKETS, masks,
                          F.stage_costs(masks))
    params = C.init_params(cfg, jax.random.PRNGKey(0), scale=0.3)
    return params, cfg


def _neural():
    ncfg = dataclasses.replace(CFG.get_smoke("starcoder2-3b"),
                               dtype=jnp.float32)
    return NeuralScorer.create(ncfg, jax.random.PRNGKey(3))


def _batch(b, g, cfg, seed):
    """A staging batch (alloc_batch) filled as pack_into stages it:
    ragged valid items (masked ones zero), one-hot query buckets, m_q
    above the valid count."""
    rng = np.random.default_rng(seed)
    n_valid = rng.integers(1, g + 1, b)
    n_valid[0] = g
    mask = (np.arange(g)[None, :] < n_valid[:, None]).astype(np.float32)
    batch = alloc_batch(b, g, cfg.d_x, cfg.d_q)
    batch["x"][...] = (rng.normal(size=(b, g, cfg.d_x)).astype(np.float32)
                       * mask[..., None])
    batch["q"][...] = np.eye(cfg.d_q)[rng.integers(0, cfg.d_q, b)]
    batch["mask"][...] = mask
    batch["m_q"][...] = n_valid * rng.integers(1, 40, b)
    return batch


def _direct(ses, batch, with_neural):
    """The served quantities straight from run_cascade and the Eq-16
    latency, with the per-stage survivor masks left unreduced."""
    cfg, lcfg = ses.cfg, ses.lcfg

    @jax.jit
    def direct(params, x, q, mask, m_q):
        out = P.run_cascade(params, cfg, x, q, mask, m_q,
                            fused=ses.scfg.plan)
        surv = out["survivors"][..., -1]
        scores = jnp.where(surv > 0, out["scores"], -jnp.inf)
        lat = C.latency_from_counts(out["expected_counts"], m_q, cfg,
                                    lcfg.latency_scale,
                                    lcfg.latency_convention)
        if with_neural:
            b, g, _ = x.shape
            nscore = ses.neural.score(x.reshape(b * g, -1)).reshape(b, g)
            scores = jnp.where(surv > 0,
                               scores + nscore.astype(jnp.float32), -jnp.inf)
            lat = lat + (lcfg.latency_scale * ses.scfg.neural_cost
                         * surv.sum(-1) / jnp.maximum(mask.sum(-1), 1)
                         * jnp.minimum(m_q, 6000.0))
        return scores, surv, lat, out["survivors"]

    args = [jnp.asarray(batch[k]) for k in ("x", "q", "mask", "m_q")]
    return [np.asarray(v) for v in direct(ses.params, *args)]


@pytest.mark.parametrize("plan,g,rows,neural", [
    ("filter", 4096, 2, None),                   # the wide ladder's top
    ("filter", 16, BATCH_GROUPS, None),
    ("filter", 64, BATCH_GROUPS, None),
    ("filter", 256, BATCH_GROUPS, None),
    ("none", 16, BATCH_GROUPS, None),
    ("none", 64, BATCH_GROUPS, None),
    ("none", 256, BATCH_GROUPS, None),
    ("filter", 64, BATCH_GROUPS // 2, None),     # pow2 rows below the batch
    ("filter", 16, BATCH_GROUPS, "neural"),
    ("filter", 16, BATCH_GROUPS, "skip_neural"),
])
def test_split_result_is_bit_identical_to_the_pipeline(plan, g, rows, neural):
    params, cfg = _cascade()
    ses = CascadeSession(
        params, cfg, L.LossConfig(),
        neural_stage=_neural() if neural else None,
        scfg=ServingConfig(plan=plan, group_buckets=WIDE_LADDER
                           if g > 256 else (16, 64, 256),
                           batch_groups=BATCH_GROUPS))
    batch = _batch(rows, g, cfg, seed=g + rows)
    packed = ses.rank_batch(batch, skip_neural=neural == "skip_neural")
    # one array leaf: a flush fetches its result in one transfer
    leaves = jax.tree_util.tree_leaves(packed)
    assert len(leaves) == 1 and isinstance(packed, jax.Array)
    assert packed.shape == (rows, 2 * g + 1 + cfg.n_stages)
    assert packed.dtype == jnp.float32

    host = np.asarray(packed)
    got = split_results(host, cfg.n_stages)
    for v in got.values():                      # views, not copies
        assert np.shares_memory(v, host)
    scores, surv, lat, stage_surv = _direct(ses, batch,
                                            with_neural=neural == "neural")
    np.testing.assert_array_equal(got["scores"], scores)
    np.testing.assert_array_equal(got["survivors"], surv)
    np.testing.assert_array_equal(got["lat"], lat)
    np.testing.assert_array_equal(got["stage_counts"], stage_surv.sum(1))
    # the filter really filtered: masked items and cut survivors are -inf
    assert np.isneginf(got["scores"][batch["mask"] == 0]).all()
    assert np.isneginf(got["scores"]).sum() > (batch["mask"] == 0).sum()


@pytest.mark.parametrize("neural", [None, "neural"])
def test_cascade_rank_takes_the_params_and_one_array(neural, monkeypatch):
    """The lowered jit_cascade_rank takes the params' leaves plus exactly
    ONE array, the (B, W) staging buffer, and rank_batch copies that one
    buffer to the device once per call."""
    params, cfg = _cascade()
    ses = CascadeSession(
        params, cfg, L.LossConfig(),
        neural_stage=_neural() if neural else None,
        scfg=ServingConfig(plan="filter", group_buckets=(16,),
                           batch_groups=BATCH_GROUPS))
    batch = _batch(BATCH_GROUPS, 16, cfg, seed=7)
    width = staging_width(16, cfg.d_x, cfg.d_q)
    assert batch["buf"].shape == (BATCH_GROUPS, width)
    lowered = ses._rank.lower(ses.params, batch["buf"])
    n_params = len(jax.tree_util.tree_leaves(ses.params))
    args = jax.tree_util.tree_leaves(lowered.args_info)
    assert len(args) == n_params + 1
    assert args[-1].shape == (BATCH_GROUPS, width)
    assert "jit_cascade_rank" in lowered.as_text()

    real_put, puts = jax.device_put, []

    def counting_put(x, *a, **k):
        puts.append(x)
        return real_put(x, *a, **k)

    monkeypatch.setattr(jax, "device_put", counting_put)
    np.asarray(ses.rank_batch(batch))
    assert len(puts) == 1 and puts[0] is batch["buf"]
