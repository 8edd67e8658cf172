"""Whole benchmark runs on the CPU at a tiny size.

The look for a chip is steered round (benchtest_support.cpu_devices); the
rest of a run is the harness's own: the generator, the pumps, the window,
the comparison with the plain reference. Each fault planted underneath
the timed path must turn `correct` false, and so must the control."""

from __future__ import annotations

import numpy as np
import pytest

import benchtest_support as bts


@pytest.fixture
def root(tmp_path, monkeypatch):
    bts.cpu_devices(monkeypatch)
    return bts.tiny_root(tmp_path)


def _e2e(root, cell):
    import benchlib
    return {m["name"] for m in benchlib.load_cell(cell, root).end_to_end}


def test_serve_cell_is_correct_and_reports_its_metrics(root):
    out = bts.run_cell(root, "serve-small")
    assert out["correct"], out["compared"]
    assert out["attempted"] == 60 and out["failed"] == 0
    assert set(out["metrics"]) == _e2e(root, "serve-small")
    assert 0 < out["_notes"]["goodput"] <= 120.0
    assert 0 < out["metrics"]["p50_ms"]["value"] <= out["_notes"]["p99_ms"]
    assert list(out["compared"]) == ["score_err", "mismatches", "unresolved"]


def test_traced_serve_run_reports_per_layer_metrics(root):
    out = bts.run_cell(root, "serve-large", trace=True)
    assert out["correct"]
    # the CPU trace has no device plane: the device's metrics stay silent
    assert set(out["metrics"]) == {"gen_late_p99_ms", "queue_wait_p99_ms",
                                   "service_p50_ms"}
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_train_cell_is_correct(root):
    out = bts.run_cell(root, "train-l3", seconds=0.3)
    assert out["correct"], out["compared"]
    assert set(out["metrics"]) == {"groups_per_s", "setup_s"}
    assert list(out["compared"]) == ["loss_gap", "state_gap", "change_gap"]


def _serve_control(root):
    import time
    import benchlib
    import cell_serve
    cell = benchlib.load_cell("serve-small", root)
    devs = benchlib.require_devices(1)
    out = cell_serve.run(cell, 7, 0.5, False, time.monotonic(), devs,
                         benchlib.peaks_for("x"), control=True)
    return cell, out


def test_serving_control_fails(root):
    """The reference in bfloat16 in the program's place is not correct."""
    import checks
    cell, out = _serve_control(root)
    assert out["correct"]
    ctl = dict(out["control_readings"], unresolved=0)
    ok, _ = checks.judge(ctl, cell.config["limits"]["serve"])
    assert not ok, ctl


def test_training_control_fails(root):
    """The engine's own bfloat16 storage path is not correct."""
    import time
    import benchlib
    import cell_train
    cell = benchlib.load_cell("train-l3", root)
    devs = benchlib.require_devices(1)
    out = cell_train.run(cell, 8, 0.2, False, time.monotonic(), devs,
                         benchlib.peaks_for("x"), precision="bf16")
    assert not out["correct"], out["readings"]


def test_answer_altered_where_produced_fails(root, monkeypatch):
    """The filter's scores nudged by 1e-3 inside the pipeline."""
    from repro.core import pipeline as P
    real = P.K.cascade_filter

    def nudged(*a, **k):
        out = dict(real(*a, **k))
        out["lp"] = out["lp"] + 1e-3
        return out

    monkeypatch.setattr(P.K, "cascade_filter", nudged)
    out = bts.run_cell(root, "serve-small")
    assert not out["correct"]
    assert out["compared"]["score_err"][0] > out["compared"]["score_err"][1]


def test_items_dropped_unannounced_fails(root, monkeypatch):
    """The session serves each request on the first half of its items and
    says nothing of it (no shrink_bucket degradation)."""
    import dataclasses
    from repro.serving.session import CascadeSession
    real = CascadeSession.submit

    def truncating(self, req, *a, **k):
        n = max(1, len(req.item_feats) // 2)
        return real(self, dataclasses.replace(
            req, item_feats=req.item_feats[:n]), *a, **k)

    monkeypatch.setattr(CascadeSession, "submit", truncating)
    out = bts.run_cell(root, "serve-small")
    assert not out["correct"]
    assert out["compared"]["mismatches"][0] > 0


def test_half_the_batch_left_out_fails(tmp_path, monkeypatch):
    """The pipeline ranks only the first half of each batch's rows (at a
    rate that fills batches of several rows)."""
    bts.cpu_devices(monkeypatch)
    root = bts.tiny_root(tmp_path, rate=800.0)
    from repro.core import pipeline as P
    real = P.run_cascade

    def half(params, cfg, x, q, mask, m_q, **k):
        keep = (np.arange(mask.shape[0]) < max(1, mask.shape[0] // 2))
        return real(params, cfg, x, q, mask * keep[:, None], m_q, **k)

    monkeypatch.setattr(P, "run_cascade", half)
    out = bts.run_cell(root, "serve-small")
    assert not out["correct"]
    assert out["compared"]["mismatches"][0] > 0


def test_training_step_that_keeps_its_state_fails(root, monkeypatch):
    from repro.core import trainer as T
    real = T._make_epoch_fn

    def frozen(*a, **k):
        epoch = real(*a, **k)

        def run(theta, opt_state, item, group, idx):
            keep = (theta.copy(), {k: v.copy() for k, v in opt_state.items()})
            _, _, losses = epoch(theta, opt_state, item, group, idx)
            return keep[0], keep[1], losses
        return run

    monkeypatch.setattr(T, "_make_epoch_fn", frozen)
    out = bts.run_cell(root, "train-l3", seconds=0.2)
    assert not out["correct"]
    assert out["compared"]["change_gap"][0] == pytest.approx(1.0)


def test_training_that_stalls_after_its_first_call_fails(root, monkeypatch):
    """Right on the first call, then every later call returns the state
    it was fed: only the check of the window's last epoch can see it."""
    from repro.core import trainer as T
    real = T._make_epoch_fn

    def stalling(*a, **k):
        epoch = real(*a, **k)
        calls = []

        def run(theta, opt_state, item, group, idx):
            calls.append(1)
            if len(calls) == 1:
                return epoch(theta, opt_state, item, group, idx)
            keep = (theta.copy(), {k: v.copy() for k, v in opt_state.items()})
            _, _, losses = epoch(theta, opt_state, item, group, idx)
            return keep[0], keep[1], losses
        return run

    monkeypatch.setattr(T, "_make_epoch_fn", stalling)
    out = bts.run_cell(root, "train-l3", seconds=0.2)
    assert not out["correct"]
    assert out["compared"]["change_gap"][0] == pytest.approx(1.0)


def test_training_on_half_the_batch_fails(root, monkeypatch):
    """Half of each minibatch's groups left out, the mean over the rest."""
    from repro.core import losses as L
    real = L.LOSSES["l3"]

    def half(params, cfg, lcfg, batch):
        b = batch["mask"].shape[0] // 2
        return real(params, cfg, lcfg, {k: v[:b] for k, v in batch.items()})

    monkeypatch.setitem(L.LOSSES, "l3", half)
    out = bts.run_cell(root, "train-l3", seconds=0.2)
    assert not out["correct"]
    assert out["compared"]["loss_gap"][0] > out["compared"]["loss_gap"][1]


def test_new_cell_is_a_traffic_file_and_a_workloads_entry(root):
    """A later PR adds a cell by adding files and one entry, editing no
    file of the harness."""
    bts.add_cell(root, "serve-mid", "cloes3_normal", "midsize",
                 {"kind": "serve", "rate_per_s": 80.0, "sample": 16,
                  "items": {"dist": "uniform", "lo": 17, "hi": 64}})
    out = bts.run_cell(root, "serve-mid")
    assert out["correct"], out["compared"]
    assert out["attempted"] == 40


def test_no_tpu_exits_non_zero_without_a_result(capsys):
    run = bts.load_run_module()
    rc = run.main(["--workload", "serve-large", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "no TPU" in out.err
