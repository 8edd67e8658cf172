"""RequestBatcher contract tests: exact (B, G) bucket padding, submit-order
responses, and one-compilation-per-shape warmup (no recompiles in serve).
"""

import jax
import numpy as np
import pytest

from repro.core import cascade as C
from repro.core import losses as L
from repro.data import features as F
from repro.serving.batching import (RankRequest, RequestBatcher,
                                    TransferBufferPool, alloc_batch,
                                    pack_into, staging_width)
from repro.serving.cascade_server import CascadeServer


def _req(i, n_items, d_x=24, d_q=16, seed=None):
    rng = np.random.default_rng(n_items if seed is None else seed)
    return RankRequest(request_id=i,
                       q_feat=np.eye(d_q)[i % d_q].astype(np.float32),
                       item_feats=rng.normal(size=(n_items, d_x))
                       .astype(np.float32),
                       m_q=10 * n_items + 1)


def _server(buckets=(8, 16), batch_groups=4, fused="filter"):
    masks = F.default_stage_masks(3)
    cfg = C.CascadeConfig(3, F.N_FEATURES, F.N_QUERY_BUCKETS, masks,
                          F.stage_costs(masks))
    params = C.init_params(cfg, jax.random.PRNGKey(0), scale=0.3)
    batcher = RequestBatcher(batch_groups=batch_groups,
                             group_buckets=buckets)
    return CascadeServer(params, cfg, L.LossConfig(), fused=fused,
                         batcher=batcher)


# ---------------------------------------------------------------------------
# drain(): exact declared (B, G) shapes, and nothing else.
# ---------------------------------------------------------------------------

def test_drain_pads_exactly_to_declared_buckets():
    b = RequestBatcher(batch_groups=8, group_buckets=(16, 64, 256))
    sizes = [1, 3, 15, 16, 17, 63, 64, 65, 255, 256, 300, 4, 40, 200]
    for i, n in enumerate(sizes):
        b.submit(_req(i, n, d_x=6, d_q=4))
    warm_b = {1, 2, 4, 8}                     # the pow2 batch-axis shapes
    seen = []
    for seqs, reqs, batch in b.drain():
        bb, g = batch["x"].shape[:2]
        assert g in (16, 64, 256)             # G is EXACTLY a bucket
        assert bb in warm_b                   # B is EXACTLY a warm pow2
        assert bb == min(8, 1 << (len(reqs) - 1).bit_length())
        assert batch["q"].shape == (bb, 4)
        assert batch["mask"].shape == (bb, g)
        assert batch["m_q"].shape == (bb,)
        for i, r in enumerate(reqs):
            n = min(len(r.item_feats), g)     # > largest bucket: truncated
            assert batch["mask"][i, :n].all()
            assert not batch["mask"][i, n:].any()
            np.testing.assert_array_equal(batch["x"][i, :n],
                                          r.item_feats[:n])
        assert not batch["mask"][len(reqs):].any()   # padded rows inert
        assert (batch["x"][len(reqs):] == 0).all()
        # every request landed in its smallest fitting bucket
        for r in reqs:
            assert g >= min(len(r.item_feats), 256)
            smaller = [bk for bk in (16, 64) if bk < g]
            assert all(len(r.item_feats) > bk for bk in smaller)
        seen.extend(seqs)
    assert sorted(seen) == list(range(len(sizes)))
    assert len(b) == 0


def test_drain_seqs_track_submit_positions():
    b = RequestBatcher(batch_groups=4, group_buckets=(8, 32))
    order = [30, 2, 8, 1, 32, 7, 20, 3]       # interleave the two buckets
    for i, n in enumerate(order):
        b.submit(_req(i, n, d_x=4, d_q=4))
    for seqs, reqs, _ in b.drain():
        # seqs are exactly each request's position in the submit stream
        assert [order[s] for s in seqs] == [len(r.item_feats) for r in reqs]
        assert seqs == sorted(seqs)           # stable within a bucket


# ---------------------------------------------------------------------------
# The staging layout: one (b, W) buffer per batch, the four arrays views
# into it, rows laid out [x (g * d_x) | mask (g) | q (d_q) | m_q (1)].
# ---------------------------------------------------------------------------

LAYOUT_D_X, LAYOUT_D_Q = 24, 8


@pytest.mark.parametrize("g", [16, 256, 4096])
def test_staging_batch_is_one_buffer_in_the_documented_row_layout(g):
    d_x, d_q, b = LAYOUT_D_X, LAYOUT_D_Q, 2
    batch = alloc_batch(b, g, d_x, d_q)
    buf = batch["buf"]
    w = staging_width(g, d_x, d_q)
    assert w == g * (d_x + 1) + d_q + 1
    assert buf.shape == (b, w) and buf.dtype == np.float32
    assert buf.flags["C_CONTIGUOUS"] and not buf.any()
    views = {"x": (b, g, d_x), "mask": (b, g), "q": (b, d_q), "m_q": (b,)}
    assert set(batch) == {"buf", *views}
    for k, shape in views.items():
        assert batch[k].shape == shape and batch[k].dtype == np.float32
        assert np.shares_memory(batch[k], buf), k
    # pack_into's writes land in the one buffer, row by row
    reqs = [_req(0, g - 3, d_x=d_x, d_q=d_q), _req(1, g + 5, d_x=d_x,
                                                 d_q=d_q)]
    pack_into(batch, reqs, g)
    for i, r in enumerate(reqs):
        n = min(len(r.item_feats), g)
        row = buf[i]
        x = np.zeros((g, d_x), np.float32)
        x[:n] = r.item_feats[:n]
        np.testing.assert_array_equal(row[:g * d_x], x.ravel())
        np.testing.assert_array_equal(row[g * d_x:g * (d_x + 1)],
                                      np.arange(g) < n)
        np.testing.assert_array_equal(row[g * (d_x + 1):-1], r.q_feat)
        assert row[-1] == r.m_q
    # the pool hands the same buffer back fully zeroed, keyed by (b, g)
    pool = TransferBufferPool(d_x, d_q)
    got = pool.acquire(b, g)
    got["buf"][...] = 5.0
    pool.release(got)
    assert pool.acquire(b, 2 * g) is not got     # another (b, g): fresh
    again = pool.acquire(b, g)
    assert again is got and not again["buf"].any()
    for k in views:
        assert np.shares_memory(again[k], again["buf"])
    assert pool.allocated == 2 and pool.reused == 1


# ---------------------------------------------------------------------------
# serve(): responses come back in submit order even though the batcher
# drains bucket by bucket.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fused", ["filter", "none"])
def test_serve_returns_responses_in_submit_order(fused):
    srv = _server(fused=fused)
    rng = np.random.default_rng(3)
    # sizes straddling both buckets, shuffled, so drain order != submit
    # order bucket-wise
    sizes = [12, 3, 16, 2, 9, 5, 11, 4, 7]
    for i, n in enumerate(sizes):
        srv.submit(_req(i, n, d_x=srv.cfg.d_x, d_q=srv.cfg.d_q,
                        seed=int(rng.integers(1 << 20))))
    resps = srv.serve()
    assert [r.request_id for r in resps] == list(range(len(sizes)))
    for r, n in zip(resps, sizes):
        assert len(r.scores) == n


def test_server_rejects_unknown_fused_mode_at_construction():
    # the pipeline's ONE error — identical across run_cascade, the server
    # and the session
    with pytest.raises(ValueError, match="unknown pipeline plan: 'scores'"):
        _server(fused="scores")


# ---------------------------------------------------------------------------
# use_fused_kernel deprecation: one release of aliasing onto the plans.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("legacy, plan", [(True, "filter"), (False, "none")])
def test_use_fused_kernel_is_deprecated_but_aliases_the_plan(legacy, plan):
    masks = F.default_stage_masks(3)
    cfg = C.CascadeConfig(3, F.N_FEATURES, F.N_QUERY_BUCKETS, masks,
                          F.stage_costs(masks))
    params = C.init_params(cfg, jax.random.PRNGKey(0), scale=0.3)
    with pytest.warns(DeprecationWarning, match="use_fused_kernel"):
        srv = CascadeServer(params, cfg, use_fused_kernel=legacy)
    assert srv.fused == plan
    assert srv.session.scfg.plan == plan
    # an explicit fused= wins over the legacy bool (still warns)
    other = "none" if legacy else "filter"
    with pytest.warns(DeprecationWarning):
        srv2 = CascadeServer(params, cfg, use_fused_kernel=legacy,
                             fused=other)
    assert srv2.fused == other
    # the modern spelling is warning-free
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        CascadeServer(params, cfg, fused=plan)


# ---------------------------------------------------------------------------
# warmup(): every shape compiled exactly once, up front.
# ---------------------------------------------------------------------------

def test_warmup_compiles_each_bucket_exactly_once_no_serve_recompile():
    srv = _server(buckets=(8, 16), batch_groups=4)
    assert srv._rank._cache_size() == 0
    shapes = srv.warmup()
    # (b, g) for b in pow2 up to batch_groups, per bucket — each EXACTLY one
    # jit cache entry
    assert sorted(shapes) == sorted((b, g) for g in (8, 16)
                                    for b in (1, 2, 4))
    assert len(set(shapes)) == len(shapes)
    n_compiled = srv._rank._cache_size()
    assert n_compiled == len(shapes)
    # a second warmup hits the warm cache — zero new compilations
    srv.warmup()
    assert srv._rank._cache_size() == n_compiled
    # live traffic across all buckets and drain-tail batch sizes: no
    # recompiles on first OR second serve()
    for round_ in range(2):
        for i, n in enumerate([2, 8, 13, 16, 5]):
            srv.submit(_req(i, n, d_x=srv.cfg.d_x, d_q=srv.cfg.d_q))
        resps = srv.serve()
        assert len(resps) == 5
        assert srv._rank._cache_size() == n_compiled, (
            f"serve() round {round_} recompiled the pipeline")
