"""Unit tests for the launch layer: sharding rules, roofline HLO parser,
input specs, and the request batcher. Single-device safe (no mesh state)."""

import numpy as np
import pytest

import repro.configs as CFG
from repro.configs import shapes as SH
from repro.launch import roofline
from repro.serving.batching import RankRequest, RequestBatcher


# ---------------------------------------------------------------------------
# roofline HLO parser
# ---------------------------------------------------------------------------

_HLO = """HloModule test, is_scheduled=true
%body (arg: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p.1 = f32[8,8]{1,0} parameter(0)
  %q.1 = f32[8,8]{1,0} parameter(1)
  %dot.1 = f32[8,8]{1,0} dot(%p.1, %q.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
ENTRY %main (x: f32[8,8]) -> f32[8,8] {
  %p = f32[8,8]{1,0} parameter(0)
  %ar = f32[8,8]{1,0} all-reduce(%p), replica_groups={}
  %w = (s32[], f32[8,8]) while(%t), condition=%c, body=%body, backend_config={"known_trip_count":{"n":"5"}}
}
"""


def test_hlo_parser_trip_counts_and_collectives():
    hc = roofline.HloCost(_HLO)
    # dot inside the while body: 2*8*8*8 = 1024 flops x 5 trips
    assert hc.flops() == pytest.approx(1024 * 5)
    coll = hc.collectives()
    assert coll["all-reduce_bytes"] == 8 * 8 * 4
    assert coll["all-reduce_count"] == 1


def test_roofline_terms_dominance():
    rec = {"hlo_dot_flops_per_device": 197e12,       # exactly 1 s of compute
           "bytes_per_device": 819e9 * 2,            # 2 s of HBM
           "collectives": {"total_bytes": 50e9 * 0.5},  # 0.5 s of links
           "step": "train", "active_params": 0, "tokens": 0}
    t = roofline.terms(rec, n_chips=256)
    assert t["t_compute_s"] == pytest.approx(1.0)
    assert t["t_memory_s"] == pytest.approx(2.0)
    assert t["t_collective_s"] == pytest.approx(0.5)
    assert t["dominant"] == "memory"


def test_streaming_floor_decode_moe_expert_coverage():
    """A 1-token decode should not charge every expert's weights."""
    base = {"params": 1000, "active_params": 100, "cache_bytes": 0,
            "tokens": 1, "n_layers": 1, "d_model": 1, "step": "decode",
            "n_experts": 100, "top_k": 2}
    few = roofline.streaming_floor_bytes(base, n_chips=1)
    many = roofline.streaming_floor_bytes(dict(base, tokens=1000), n_chips=1)
    assert few < many <= 2 * base["params"]


# ---------------------------------------------------------------------------
# input specs / applicability
# ---------------------------------------------------------------------------

def test_applicability_matrix():
    runs = 0
    for arch in CFG.all_archs():
        cfg = CFG.get(arch)
        for shape in SH.SHAPES:
            ok, why = SH.applicable(cfg, shape)
            if shape != "long_500k":
                assert ok
            runs += ok
    assert runs == 33          # 10*3 + 3 sub-quadratic long_500k


@pytest.mark.parametrize("arch", CFG.all_archs())
@pytest.mark.parametrize("shape", list(SH.SHAPES))
def test_input_specs_shapes(arch, shape):
    cfg = CFG.get(arch)
    ok, _ = SH.applicable(cfg, shape)
    if not ok:
        pytest.skip("inapplicable")
    specs = SH.input_specs(cfg, shape)
    sh = SH.SHAPES[shape]
    if sh.step == "decode":
        assert specs["batch"]["tokens"].shape == (sh.global_batch, 1)
        assert "cache" in specs and "cache_len" in specs
    elif sh.step == "train":
        toks = specs["batch"]["tokens"].shape
        assert toks[0] == sh.global_batch
        if cfg.arch_type not in ("encdec",) and not cfg.frontend_positions:
            assert toks[1] == sh.seq_len


def test_decode_cache_total_positions():
    """decode_32k cache must hold seq_len positions (ring caches excepted
    for local layers)."""
    cfg = CFG.get("yi-34b")
    cache = SH.cache_specs(cfg, "decode_32k")
    assert cache["k"].shape == (60, 128, 32768, 8, 128)


def test_gemma_ring_cache_bounded():
    """gemma3 long_500k: local layers keep only window-sized rings."""
    cfg = CFG.get("gemma3-27b")
    cache = SH.cache_specs(cfg, "long_500k")
    assert cache["gk"].shape[2] == 524288          # globals: full
    assert cache["lk"].shape[3] == 1024            # locals: ring = window
    total = sum(np.prod(s.shape) * 2 for s in cache.values())
    full = 62 * 1 * 524288 * 16 * 128 * 2 * 2
    assert total < 0.25 * full                     # >4x memory saving


# ---------------------------------------------------------------------------
# batcher
# ---------------------------------------------------------------------------

def test_batcher_buckets_and_padding():
    b = RequestBatcher(batch_groups=4, group_buckets=(16, 64))
    rng = np.random.default_rng(0)
    for i in range(10):
        n = int(rng.integers(4, 60))
        b.submit(RankRequest(request_id=i, q_feat=np.zeros(8, np.float32),
                             item_feats=np.zeros((n, 24), np.float32),
                             m_q=100 + n))
    seen = set()
    for _seqs, reqs, batch in b.drain():
        assert batch["x"].shape[1] in (16, 64)
        # batch axis is padded to the next power of two (capped at
        # batch_groups) so batch shapes come from a small warm set
        assert len(reqs) <= 4
        assert batch["x"].shape[0] == min(4, 1 << (len(reqs) - 1).bit_length())
        for i, r in enumerate(reqs):
            assert batch["mask"][i].sum() == min(len(r.item_feats),
                                                 batch["x"].shape[1])
            seen.add(r.request_id)
        assert batch["mask"][len(reqs):].sum() == 0   # padded rows all-masked
    assert seen == set(range(10))
    assert len(b) == 0


# ---------------------------------------------------------------------------
# serve launcher: --spans
# ---------------------------------------------------------------------------

def test_serve_spans_report(monkeypatch, tmp_path, capsys):
    """--spans adds each flush step's p50/p99 and the pump's flush and
    item fill to the printed and written report, beside every pump
    counter."""
    import json
    import sys

    from repro.core import baselines as B
    from repro.core import trainer as T
    from repro.launch import serve

    real_fit = B.fit_cloes
    monkeypatch.setattr(serve.B, "fit_cloes", lambda tr, **kw: real_fit(
        tr, lcfg=kw["lcfg"], tcfg=T.TrainConfig(epochs=0)))
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    report = tmp_path / "serve.json"
    monkeypatch.setattr(sys, "argv", [
        "serve", "--requests", "48", "--qps", "2000", "--pump",
        "--threads", "2", "--plan", "none", "--spans",
        "--report", str(report)])
    serve.main()
    out = json.loads(report.read_text())
    spans = out["spans"]
    assert set(spans["spans"]) == {"serve.cycle", "serve.claim",
                                   "serve.pack", "serve.dispatch",
                                   "serve.fetch", "serve.resolve"}
    cycles = out["pump_stats"]["cycles"]
    for name, row in spans["spans"].items():
        assert row["flushes"] == cycles, name
        assert 0.0 <= row["p50_ms"] <= row["p99_ms"], name
    assert spans["dropped"] == 0
    stats = out["pump_stats"]
    assert spans["flush_fill"] == pytest.approx(
        100.0 * stats["served"] / stats["rows_padded"])
    assert 0.0 < spans["flush_fill"] <= 100.0
    assert spans["item_fill"] == pytest.approx(
        100.0 * stats["items_real"] / stats["items_padded"])
    assert 0.0 < spans["item_fill"] <= 100.0
    assert "[serve] item fill: " in capsys.readouterr().out
