"""The serve-wide cell: CLOES at its published recall widths.

Its files load through the harness as they stand, its schedule draws
requests of 2,049 to 4,096 items from the paper-scale log, it warms the
4,096 bucket and the 1,024 bucket a degraded flush shrinks to, and the
plain reference's select at G = 4,096 is the program's stable
double-argsort stage chain. A tiny copy of the cell runs whole on the
CPU and is correct."""

from __future__ import annotations

import numpy as np

import benchtest_support as bts

WIDE_LO, WIDE_HI = 2049, 4096


def test_serve_wide_loads():
    import benchlib
    cell = benchlib.load_cell("serve-wide")
    assert cell.chips == 1
    assert cell.config["name"] == "cloes3_wide"
    assert cell.config["serving"]["group_buckets"][-1] == WIDE_HI
    assert cell.traffic["items"] == {"dist": "uniform", "lo": WIDE_LO,
                                     "hi": WIDE_HI}
    assert {m["name"] for m in cell.end_to_end} == {"p50_ms", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "gen_late_p99_ms", "queue_wait_p99_ms", "service_p50_ms",
        "filter_roofline", "step_mfu.serve", "idle_share.serve"}


def test_serve_wide_schedule_spans_the_log_hot_and_mid_queries():
    import benchlib
    import cell_serve
    cell = benchlib.load_cell("serve-wide")
    log = cell_serve.make_log(cell.config, 2**31 + 11)
    sch = cell_serve.schedule(cell.traffic, 10.0, 2**31 + 11, log)
    assert sch.sizes.min() == WIDE_LO and sch.sizes.max() == WIDE_HI
    assert (log.n_valid[sch.rows] >= sch.sizes).all()
    assert len(np.unique(sch.rows)) >= 200


def test_serve_wide_warms_its_bucket_and_the_shrunk_one():
    import benchlib
    import cell_serve
    cell = benchlib.load_cell("serve-wide")
    assert cell_serve.warm_buckets(cell.config, cell.traffic) == [1024, 4096]


def test_reference_select_at_4096_is_the_stable_stage_chain():
    """reference.cascade_rank's survivors at G = 4,096 equal
    core.pipeline.filter_chain on the reference's own scores and keep
    counts, bit for bit: a sample with a masked tail and tied items."""
    import jax.numpy as jnp
    import reference
    from repro.core.pipeline import filter_chain
    rng = np.random.default_rng(4096)
    r, g, d, d_q, t = 2, WIDE_HI, 24, 8, 3
    x = rng.normal(size=(r, g, d)).astype(np.float32)
    x[:, 1::4] = x[:, ::4]                          # tied twins
    valid = np.ones((r, g), np.float32)
    valid[1, 3000:] = 0.0
    out = reference.cascade_rank(
        *(jnp.asarray(a) for a in (
            0.5 * rng.normal(size=(t, d)).astype(np.float32),
            0.5 * rng.normal(size=(t, d_q)).astype(np.float32),
            0.5 * rng.normal(size=(t,)).astype(np.float32),
            (rng.random((t, d)) < 0.5).astype(np.float32), x,
            rng.normal(size=(r, d_q)).astype(np.float32), valid,
            np.array([8000.0, 120.0], np.float32))))
    want = filter_chain(out["lp"], jnp.asarray(valid), out["n_keep"])
    np.testing.assert_array_equal(np.asarray(out["survivors"]),
                                  np.asarray(want))
    kept = np.asarray(out["survivors"])[..., -1].sum(1)
    assert (kept > 0).all() and (kept < valid.sum(1)).all()


def test_tiny_serve_wide_runs_correct_on_the_cpu(tmp_path, monkeypatch):
    bts.cpu_devices(monkeypatch)
    root = bts.tiny_root(tmp_path)
    out = bts.run_cell(root, "serve-wide")
    assert out["correct"], out["compared"]
    assert out["failed"] == 0
    assert out["_notes"]["compared"] > 0
