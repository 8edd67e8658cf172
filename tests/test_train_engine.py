"""Parity suite for the fused training engine (PR 2).

Pins two contracts (the scorer's custom-VJP backward is pinned in
test_batched_scorer.py):
  (a) the single-forward losses (core.losses) match the pre-refactor
      multi-forward implementations — kept verbatim below as the oracle —
      in value (<= 1e-6) and grads (<= 1e-5) across the config variants;
  (b) the scan engine's fit() reproduces the per-step loop's loss
      trajectory and final params, and evaluate() derives the same metrics
      from its single forward as the old four-pass version.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cascade as C
from repro.core import losses as L
from repro.core import trainer as T
from repro.data import LogConfig, features as F, generate_log


# ---------------------------------------------------------------------------
# Pre-refactor reference implementations (the multi-forward originals),
# kept verbatim as the oracle for the single-forward engine.
# ---------------------------------------------------------------------------

def ref_weighted_nll(params, cfg, lcfg, x, q, y, mask, behavior=None,
                     price=None):
    log_p = C.log_pass_probs(params, cfg, x, q)[..., -1]
    log_p = jnp.minimum(log_p, -1e-7)
    log_1mp = jnp.log1p(-jnp.exp(log_p))
    ll = y * log_p + (1.0 - y) * log_1mp
    if behavior is not None:
        ll = ll * L.importance_weights(behavior, price, lcfg)
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def ref_expected_cost(params, cfg, x, q, mask, y=None, m_q=None):
    w = mask if y is None else mask * (1.0 - y)
    if m_q is not None:
        n_q = jnp.maximum(mask.sum(axis=-1), 1.0)
        w = w * (m_q / n_q)[:, None]
        n = jnp.maximum(m_q.sum(), 1.0)
    else:
        n = jnp.maximum(mask.sum(), 1.0)
    pp = C.pass_probs(params, cfg, x, q) * w[..., None]
    counts = jnp.concatenate([n[None], pp.sum(axis=(0, 1))[:-1]])
    t = jnp.asarray(cfg.t, dtype=x.dtype)
    return (counts * t).sum() / n


def ref_expected_latency_per_query(params, cfg, lcfg, x, q, mask, m_q):
    counts = C.expected_counts_per_query(params, cfg, x, q, mask, m_q)
    t = jnp.asarray(cfg.t, dtype=x.dtype)
    if lcfg.latency_convention == "entering":
        entering = jnp.concatenate(
            [m_q[:, None].astype(x.dtype), counts[:, :-1]], axis=-1)
        lat = (entering * t).sum(-1)
    else:
        lat = (counts * t).sum(-1)
    return lcfg.latency_scale * lat


def ref_loss_l1(params, cfg, lcfg, batch):
    return (ref_weighted_nll(params, cfg, lcfg, batch["x"], batch["q"],
                             batch["y"], batch["mask"],
                             batch.get("behavior"), batch.get("price"))
            + L.l2_penalty(params, lcfg))


def ref_loss_l2(params, cfg, lcfg, batch):
    y_for_cost = batch["y"] if lcfg.cost_mask_positives else None
    return (ref_loss_l1(params, cfg, lcfg, batch)
            + lcfg.beta * ref_expected_cost(params, cfg, batch["x"],
                                            batch["q"], batch["mask"],
                                            y_for_cost, batch.get("m_q")))


def ref_loss_l3(params, cfg, lcfg, batch):
    x, q, mask, m_q = batch["x"], batch["q"], batch["mask"], batch["m_q"]
    params_pen = dict(params,
                      w_x=jax.lax.stop_gradient(params["w_x"]),
                      b=jax.lax.stop_gradient(params["b"]))
    counts_T = C.expected_counts_per_query(params_pen, cfg, x, q, mask,
                                           m_q)[:, -1]
    n_o = jnp.minimum(lcfg.n_o, m_q.astype(x.dtype))
    size_pen = L.smooth_hinge(counts_T, n_o, lcfg.gamma).mean()
    lat = ref_expected_latency_per_query(params_pen, cfg, lcfg, x, q, mask,
                                         m_q)
    lat_pen = L.smooth_hinge(jnp.full_like(lat, lcfg.t_l), lat,
                             lcfg.gamma).mean()
    return (ref_loss_l2(params, cfg, lcfg, batch)
            + lcfg.delta * size_pen + lcfg.eps_latency * lat_pen)


REF_LOSSES = {"l1": ref_loss_l1, "l2": ref_loss_l2, "l3": ref_loss_l3}


@pytest.fixture(scope="module")
def setup():
    masks = F.default_stage_masks(3)
    cfg = C.CascadeConfig(3, F.N_FEATURES, F.N_QUERY_BUCKETS, masks,
                          F.stage_costs(masks))
    params = C.init_params(cfg, jax.random.PRNGKey(0), scale=0.3)
    rng = np.random.default_rng(0)
    B, G = 8, 16
    batch = {
        "x": jnp.asarray(rng.normal(size=(B, G, F.N_FEATURES)), jnp.float32),
        "q": jnp.asarray(np.eye(F.N_QUERY_BUCKETS)[rng.integers(0, 8, B)],
                         jnp.float32),
        "y": jnp.asarray(rng.integers(0, 2, (B, G)), jnp.float32),
        "mask": jnp.asarray(rng.random((B, G)) < 0.9, jnp.float32),
        "behavior": jnp.asarray(rng.integers(0, 3, (B, G)), jnp.int32),
        "price": jnp.asarray(np.exp(rng.normal(3, 1, (B, G))), jnp.float32),
        "m_q": jnp.asarray(rng.integers(50, 5000, B), jnp.float32),
    }
    return cfg, params, batch


# ---------------------------------------------------------------------------
# (a) single-forward losses vs the multi-forward reference.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("loss", ["l1", "l2", "l3"])
@pytest.mark.parametrize("cost_mask_positives", [False, True])
@pytest.mark.parametrize("convention", ["entering", "paper"])
def test_single_forward_value_and_grad_parity(setup, loss,
                                              cost_mask_positives,
                                              convention):
    cfg, params, batch = setup
    lcfg = L.LossConfig(beta=2.0, eps_purchase=3.0, mu_price=2.0,
                        cost_mask_positives=cost_mask_positives,
                        latency_convention=convention)
    v_new, g_new = jax.value_and_grad(L.LOSSES[loss])(params, cfg, lcfg,
                                                      batch)
    v_ref, g_ref = jax.value_and_grad(REF_LOSSES[loss])(params, cfg, lcfg,
                                                        batch)
    # 1e-6 RELATIVE: the beta-weighted L3 sits at O(100), where 1e-6
    # relative is about one f32 ulp — the fused-kernel L3 (per-group
    # partial sums, probability-space pass-probs on the CPU ref) is
    # reassociated float math, not a different objective.
    assert abs(float(v_new) - float(v_ref)) <= 1e-6 * max(1.0,
                                                          abs(float(v_ref)))
    for k in params:
        np.testing.assert_allclose(np.asarray(g_new[k]), np.asarray(g_ref[k]),
                                   rtol=1e-5, atol=1e-5)


def test_engine_batch_protocol_matches_raw_batch(setup):
    """Losses fed the precomputed engine columns (wgt/cost_w/mn/n_o_eff)
    must equal losses fed the raw behavior/price batch."""
    cfg, params, batch = setup
    lcfg = L.LossConfig(beta=2.0, eps_purchase=3.0, mu_price=2.0)
    n_q = jnp.maximum(batch["mask"].sum(-1), 1.0)
    mn = batch["m_q"] / n_q
    engine_batch = {
        "x": batch["x"], "q": batch["q"], "y": batch["y"],
        "mask": batch["mask"], "m_q": batch["m_q"],
        "wgt": L.importance_weights(batch["behavior"], batch["price"], lcfg),
        "cost_w": batch["mask"] * mn[:, None],
        "mn": mn,
        "n_o_eff": jnp.minimum(lcfg.n_o, batch["m_q"]),
    }
    for loss in ["l1", "l2", "l3"]:
        v_raw, g_raw = jax.value_and_grad(L.LOSSES[loss])(params, cfg, lcfg,
                                                          batch)
        v_eng, g_eng = jax.value_and_grad(L.LOSSES[loss])(params, cfg, lcfg,
                                                          engine_batch)
        assert abs(float(v_raw) - float(v_eng)) <= 1e-6
        for k in params:
            np.testing.assert_allclose(np.asarray(g_raw[k]),
                                       np.asarray(g_eng[k]),
                                       rtol=1e-5, atol=1e-6)


def test_standalone_term_wrappers_match_reference(setup):
    cfg, params, batch = setup
    lcfg = L.LossConfig()
    x, q, y, mask, m_q = (batch["x"], batch["q"], batch["y"], batch["mask"],
                          batch["m_q"])
    np.testing.assert_allclose(
        float(L.weighted_nll(params, cfg, lcfg, x, q, y, mask,
                             batch["behavior"], batch["price"])),
        float(ref_weighted_nll(params, cfg, lcfg, x, q, y, mask,
                               batch["behavior"], batch["price"])),
        rtol=1e-6)
    np.testing.assert_allclose(
        float(L.expected_cost(params, cfg, x, q, mask, m_q=m_q)),
        float(ref_expected_cost(params, cfg, x, q, mask, m_q=m_q)),
        rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(L.expected_latency_per_query(params, cfg, lcfg, x, q,
                                                mask, m_q)),
        np.asarray(ref_expected_latency_per_query(params, cfg, lcfg, x, q,
                                                  mask, m_q)),
        rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# (b) scan engine vs loop engine, and the single-forward evaluate().
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_log():
    return generate_log(LogConfig(n_queries=120, items_per_query=32, seed=7))


@pytest.fixture(scope="module")
def train_cfg():
    masks = F.default_stage_masks(3)
    return C.CascadeConfig(3, F.N_FEATURES, F.N_QUERY_BUCKETS, masks,
                           F.stage_costs(masks))


@pytest.mark.slow
def test_scan_fit_reproduces_loop_trajectory(tiny_log, train_cfg):
    lcfg = L.LossConfig(beta=2.0)
    traj = {}
    for engine in ["loop", "scan"]:
        losses = []
        tcfg = T.TrainConfig(loss="l3", epochs=3, lr=0.01, batch_groups=32,
                             log_every=1, engine=engine)
        params = T.fit(tiny_log, train_cfg, lcfg, tcfg,
                       callback=lambda s, l: losses.append((s, l)))
        traj[engine] = (losses, params)
    (steps_a, loss_a), (steps_b, loss_b) = (list(zip(*traj["loop"][0])),
                                            list(zip(*traj["scan"][0])))
    assert steps_a == steps_b                     # same step numbering
    np.testing.assert_allclose(loss_a, loss_b, rtol=1e-5, atol=1e-5)
    for k in traj["loop"][1]:
        np.testing.assert_allclose(np.asarray(traj["loop"][1][k]),
                                   np.asarray(traj["scan"][1][k]),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_scan_fit_mesh_single_device_fallback(tiny_log, train_cfg):
    """A 1-device data mesh must reproduce the plain scan path."""
    lcfg = L.LossConfig(beta=2.0)
    tcfg = T.TrainConfig(loss="l3", epochs=2, lr=0.01, batch_groups=32)
    p_plain = T.fit(tiny_log, train_cfg, lcfg, tcfg)
    mesh = jax.make_mesh((1,), ("data",))
    p_mesh = T.fit(tiny_log, train_cfg, lcfg, tcfg, mesh=mesh)
    for k in p_plain:
        np.testing.assert_allclose(np.asarray(p_plain[k]),
                                   np.asarray(p_mesh[k]),
                                   rtol=1e-6, atol=1e-7)


def test_unknown_engine_rejected(tiny_log, train_cfg):
    with pytest.raises(ValueError, match="unknown trainer engine"):
        T.fit(tiny_log, train_cfg, L.LossConfig(),
              T.TrainConfig(engine="bogus"))


def test_epoch_steps_reports_dropped_tail():
    assert T.epoch_steps(120, 32) == (3, 24)     # 24 groups dropped
    assert T.epoch_steps(128, 32) == (4, 0)
    assert T.epoch_steps(20, 32) == (0, 20)      # fewer groups than a batch
    # batches() yields exactly the reported number of full minibatches
    log = generate_log(LogConfig(n_queries=120, items_per_query=16, seed=1))
    got = list(T.batches(log, 32, seed=0))
    assert len(got) == 3
    assert all(b["x"].shape[0] == 32 for b in got)


@pytest.mark.slow           # four extra evaluation compiles
def test_evaluate_single_forward_matches_four_pass(tiny_log, train_cfg):
    """evaluate() derives all metrics from one forward; the four-pass
    derivation (scores / cost / latency / counts each re-scoring) must
    agree to 1e-6."""
    lcfg = L.LossConfig(beta=2.0)
    params = C.init_params(train_cfg, jax.random.PRNGKey(3), scale=0.3)
    got = T.evaluate(params, train_cfg, tiny_log, lcfg)
    from repro.core import metrics as M
    log = tiny_log
    x = jnp.asarray(log.x, jnp.float32)
    q = jnp.asarray(log.q, jnp.float32)
    mask = jnp.asarray(log.mask, jnp.float32)
    m_q = jnp.asarray(log.m_q, jnp.float32)
    scores = np.asarray(C.final_score(params, train_cfg, x, q))
    cost = float(ref_expected_cost(params, train_cfg, x, q, mask, m_q=m_q))
    lat = np.asarray(ref_expected_latency_per_query(
        params, train_cfg, lcfg, x, q, mask, m_q))
    counts_T = np.asarray(C.expected_counts_per_query(
        params, train_cfg, x, q, mask, m_q))[:, -1]
    want = {
        "auc": M.group_auc(scores, log.y, log.mask),
        "pooled_auc": M.auc(scores, log.y, log.mask),
        "expected_cost_per_item": cost,
        "mean_expected_latency": float(lat.mean()),
        "p95_expected_latency": float(np.percentile(lat, 95)),
        "mean_final_count": float(counts_T.mean()),
        "frac_queries_below_no": float(
            (counts_T < np.minimum(lcfg.n_o, log.m_q)).mean()),
    }
    assert got.keys() == want.keys()
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# (d) bf16 engine pack + loss scale (TrainConfig.precision / .loss_scale).
# ---------------------------------------------------------------------------

def test_bf16_engine_pack_round_trip(tiny_log, train_cfg):
    """bf16 packs store the ITEM array in bfloat16 (group stays f32) and
    unpack to f32 within bf16 rounding of the f32 pack; binary y/mask
    columns survive exactly."""
    lcfg = L.LossConfig(beta=2.0)
    item32, group32 = T._engine_pack(tiny_log, lcfg, "f32")
    item16, group16 = T._engine_pack(tiny_log, lcfg, "bf16")
    assert item32.dtype == jnp.float32 and item16.dtype == jnp.bfloat16
    assert group16.dtype == jnp.float32
    d_x = train_cfg.d_x
    b32 = T._engine_unpack(item32, group32, d_x, train_cfg.d_q)
    b16 = T._engine_unpack(item16, group16, d_x, train_cfg.d_q)
    assert all(v.dtype == jnp.float32 for v in b16.values())
    np.testing.assert_array_equal(np.asarray(b16["y"]), np.asarray(b32["y"]))
    np.testing.assert_array_equal(np.asarray(b16["mask"]),
                                  np.asarray(b32["mask"]))
    for k in ["x", "wgt", "cost_w"]:
        np.testing.assert_allclose(np.asarray(b16[k]), np.asarray(b32[k]),
                                   rtol=8e-3, atol=1e-6)  # bf16: 8-bit mant.
    for k in ["q", "m_q", "mn", "n_o_eff"]:                # group stays f32
        np.testing.assert_array_equal(np.asarray(b16[k]), np.asarray(b32[k]))


def test_engine_pack_rejects_unknown_precision(tiny_log):
    with pytest.raises(ValueError, match="unknown engine precision"):
        T._engine_pack(tiny_log, L.LossConfig(), "fp8")


def test_loop_engine_rejects_mixed_precision(tiny_log, train_cfg):
    for kw in [{"precision": "bf16"}, {"loss_scale": 128.0}]:
        with pytest.raises(ValueError, match="scan-engine features"):
            T.fit(tiny_log, train_cfg, L.LossConfig(),
                  T.TrainConfig(engine="loop", epochs=1, **kw))


@pytest.mark.slow
def test_loss_scale_invariance(tiny_log, train_cfg):
    """Power-of-two loss scales are exact in f32: the scanned trajectory
    must be BITWISE identical to loss_scale=1."""
    lcfg = L.LossConfig(beta=2.0)
    base = T.TrainConfig(loss="l3", epochs=2, lr=0.01, batch_groups=32)
    p1 = T.fit(tiny_log, train_cfg, lcfg, base)
    p1024 = T.fit(tiny_log, train_cfg, lcfg,
                  dataclasses.replace(base, loss_scale=1024.0))
    for k in p1:
        np.testing.assert_array_equal(np.asarray(p1[k]), np.asarray(p1024[k]))


@pytest.mark.slow
def test_bf16_fit_tracks_f32(tiny_log, train_cfg):
    """bf16 storage + f32 accumulation: only the one storage rounding
    separates the trajectories, so short fits stay within ~1e-3."""
    lcfg = L.LossConfig(beta=2.0)
    base = T.TrainConfig(loss="l3", epochs=2, lr=0.01, batch_groups=32)
    p32 = T.fit(tiny_log, train_cfg, lcfg, base)
    p16 = T.fit(tiny_log, train_cfg, lcfg,
                dataclasses.replace(base, precision="bf16"))
    for k in p32:
        assert np.all(np.isfinite(np.asarray(p16[k])))
        np.testing.assert_allclose(np.asarray(p32[k]), np.asarray(p16[k]),
                                   rtol=0, atol=2e-3)


@pytest.mark.slow
def test_fit_loss_fn_override(tiny_log, train_cfg):
    """The bench pins reference objectives through fit(loss_fn=...)."""
    lcfg = L.LossConfig(beta=2.0)
    tcfg = T.TrainConfig(loss="l3", epochs=1, lr=0.01, batch_groups=32)
    p_name = T.fit(tiny_log, train_cfg, lcfg, tcfg)
    p_fn = T.fit(tiny_log, train_cfg, lcfg, tcfg, loss_fn=L.loss_l3)
    for k in p_name:
        np.testing.assert_allclose(np.asarray(p_name[k]),
                                   np.asarray(p_fn[k]), rtol=0, atol=0)
