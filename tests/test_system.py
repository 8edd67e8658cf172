"""End-to-end behaviour tests for the CLOES system: train -> thresholds ->
serve -> user-experience invariants, on a small but real pipeline."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as CFG
from repro.core import baselines as B
from repro.core import cascade as C
from repro.core import losses as L
from repro.core import pipeline as P
from repro.core import trainer as T
from repro.data import LogConfig, generate_log
from repro.serving.batching import RankRequest, alloc_batch
from repro.serving.cascade_server import CascadeServer, NeuralScorer


@pytest.fixture(scope="module")
def trained():
    log = generate_log(LogConfig(n_queries=300, items_per_query=48, seed=5))
    tr, te = log.split(0.8, seed=1)
    lcfg = L.LossConfig(beta=2.0)
    params, cfg = B.fit_cloes(
        tr, lcfg=lcfg, tcfg=T.TrainConfig(loss="l3", epochs=4, lr=0.01))
    return params, cfg, lcfg, tr, te


def test_training_beats_untrained(trained):
    params, cfg, lcfg, tr, te = trained
    r = T.evaluate(params, cfg, te, lcfg)
    fresh = C.init_params(cfg, jax.random.PRNGKey(9))
    r0 = T.evaluate(fresh, cfg, te, lcfg)
    assert r["auc"] > 0.75
    # random init can land anywhere near chance; trained must clearly beat it
    assert r["auc"] > r0["auc"] + 0.1


def test_cascade_cheaper_than_single_stage(trained):
    params, cfg, lcfg, tr, te = trained
    r = T.evaluate(params, cfg, te, lcfg)
    single = B.single_stage_all_features()
    p1 = T.fit(tr, single, L.LossConfig(),
               T.TrainConfig(loss="l1", epochs=4, lr=0.01))
    r1 = T.evaluate(p1, single, te)
    assert r["expected_cost_per_item"] < 0.5 * r1["expected_cost_per_item"]
    assert r["auc"] > r1["auc"] - 0.1


def test_server_end_to_end(trained):
    params, cfg, lcfg, tr, te = trained
    srv = CascadeServer(params, cfg, lcfg)
    rng = np.random.default_rng(0)
    n = te.x.shape[0]
    for i in range(12):
        qi = int(rng.integers(0, n))
        k = int(rng.integers(8, 48))
        srv.submit(RankRequest(request_id=i,
                               q_feat=te.q[qi].astype(np.float32),
                               item_feats=te.x[qi, :k].astype(np.float32),
                               m_q=int(te.m_q[qi])))
    resps = srv.serve()
    assert len(resps) == 12
    for r in resps:
        # monotone cascade: later stages keep subsets
        assert all(a >= b for a, b in zip(r.stage_counts, r.stage_counts[1:]))
        assert r.survivors.sum() == r.stage_counts[-1]
        assert np.isfinite(r.est_latency_ms)
        # ranked order puts survivors first
        ranked_surv = r.survivors[r.order]
        first_nonsurv = (~ranked_surv).argmax() if (~ranked_surv).any() else len(ranked_surv)
        assert ranked_surv[:first_nonsurv].all()


@pytest.mark.slow
def test_server_with_neural_final_stage(trained):
    params, cfg, lcfg, tr, te = trained
    ncfg = dataclasses.replace(CFG.get_smoke("starcoder2-3b"),
                               dtype=jnp.float32)
    neural = NeuralScorer.create(ncfg, jax.random.PRNGKey(3))
    srv = CascadeServer(params, cfg, lcfg, neural_stage=neural)
    srv.submit(RankRequest(request_id=0, q_feat=te.q[0].astype(np.float32),
                           item_feats=te.x[0, :16].astype(np.float32),
                           m_q=int(te.m_q[0])))
    (resp,) = srv.serve()
    # neural stage only scores survivors; filtered stay -inf
    assert np.isfinite(resp.scores[resp.survivors]).all()
    assert np.isneginf(resp.scores[~resp.survivors]).all()


def test_fused_kernel_path_matches_xla_path(trained):
    """The fused score+filter pipeline must reproduce the unfused XLA
    path EXACTLY: same survivor sets at every stage, same orderings."""
    params, cfg, lcfg, tr, te = trained
    batch = alloc_batch(4, te.x.shape[1], cfg.d_x, cfg.d_q)
    for k in ("x", "q", "mask", "m_q"):
        batch[k][...] = getattr(te, k)[:4]
    a = CascadeServer(params, cfg, lcfg, fused="filter").rank_batch(batch)
    b = CascadeServer(params, cfg, lcfg, fused="none").rank_batch(batch)
    # identical survivor sets — final AND per-stage (the served result
    # carries per-stage counts; the masks come from the pipeline itself)
    np.testing.assert_array_equal(a["survivors"], b["survivors"])
    np.testing.assert_array_equal(a["stage_counts"], b["stage_counts"])
    args = [jnp.asarray(batch[k]) for k in ("x", "q", "mask", "m_q")]
    stage_a, stage_b = (
        np.asarray(P.run_cascade(params, cfg, *args, fused=plan)["survivors"])
        for plan in ("filter", "none"))
    np.testing.assert_array_equal(stage_a, stage_b)
    np.testing.assert_array_equal(a["stage_counts"], stage_a.sum(1))
    sa, sb = np.asarray(a["scores"]), np.asarray(b["scores"])
    finite = np.isfinite(sa)
    np.testing.assert_array_equal(finite, np.isfinite(sb))
    np.testing.assert_allclose(sa[finite], sb[finite], rtol=1e-4, atol=1e-5)
    # identical orderings (stable argsort over each path's own scores)
    np.testing.assert_array_equal(np.argsort(-sa, axis=-1, kind="stable"),
                                  np.argsort(-sb, axis=-1, kind="stable"))
    la, lb = a["lat"], b["lat"]
    np.testing.assert_allclose(la, lb, rtol=1e-4, atol=1e-5)


def test_served_responses_identical_across_paths(trained):
    """Full submit->serve loop: fused and unfused servers return the same
    orders, survivor sets, and stage counts for the same requests."""
    params, cfg, lcfg, tr, te = trained
    n = te.x.shape[0]

    def responses(use_fused):
        srv = CascadeServer(params, cfg, lcfg,
                            fused="filter" if use_fused else "none")
        r2 = np.random.default_rng(7)
        for i in range(6):
            qi, k = int(r2.integers(0, n)), int(r2.integers(4, 48))
            srv.submit(RankRequest(request_id=i,
                                   q_feat=te.q[qi].astype(np.float32),
                                   item_feats=te.x[qi, :k].astype(np.float32),
                                   m_q=int(te.m_q[qi])))
        return {r.request_id: r for r in srv.serve()}

    fused, plain = responses(True), responses(False)
    assert fused.keys() == plain.keys()
    for rid in fused:
        np.testing.assert_array_equal(fused[rid].order, plain[rid].order)
        np.testing.assert_array_equal(fused[rid].survivors,
                                      plain[rid].survivors)
        assert fused[rid].stage_counts == plain[rid].stage_counts


@pytest.mark.slow
def test_ux_penalties_improve_tail_counts(trained):
    """The system-level UX claim on a small log (Fig 4 bottom)."""
    _, cfg, _, tr, te = trained
    lcfg_no = L.LossConfig(beta=2.0, delta=0.0, eps_latency=0.0)
    p_no, cfg_no = B.fit_cloes(tr, lcfg=lcfg_no,
                               tcfg=T.TrainConfig(loss="l3", epochs=4, lr=0.01))
    lcfg_ux = L.LossConfig(beta=2.0)
    p_ux, cfg_ux = B.fit_cloes(tr, lcfg=lcfg_ux,
                               tcfg=T.TrainConfig(loss="l3", epochs=4, lr=0.01))
    x, q = jnp.asarray(te.x, jnp.float32), jnp.asarray(te.q, jnp.float32)
    mask, m_q = jnp.asarray(te.mask, jnp.float32), jnp.asarray(te.m_q, jnp.float32)
    tail = te.m_q < np.percentile(te.m_q, 50)
    c_no = np.asarray(C.expected_counts_per_query(p_no, cfg_no, x, q, mask, m_q))[:, -1]
    c_ux = np.asarray(C.expected_counts_per_query(p_ux, cfg_ux, x, q, mask, m_q))[:, -1]
    assert c_ux[tail].mean() > c_no[tail].mean()


def test_checkpoint_roundtrip(trained, tmp_path):
    params, cfg, lcfg, tr, te = trained
    from repro.checkpoint import save_pytree, load_pytree
    path = tmp_path / "ckpt"
    save_pytree(path, {"params": params})
    loaded = load_pytree(path)
    for k in params:
        np.testing.assert_allclose(np.asarray(params[k]),
                                   loaded["params"][k], rtol=1e-6)
