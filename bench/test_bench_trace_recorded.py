"""The recorded traces in bench/testdata/ reduce to pinned numbers: the
window, the busy time, the programs' times, the breakdown's first
entries and the per-layer readers' values. A change to the reduction or
to a reader that moves any of them shows here."""

from __future__ import annotations

import json

import pytest

import benchtest_support  # noqa: F401  (puts bench/ and src/ on the path)
import benchlib
import workcount
import xtrace

DATA = benchlib.BENCH_DIR / "testdata"
PEAKS = json.loads((benchlib.BENCH_DIR / "peaks.json").read_text())[
    "devices"]["TPU v5 lite"]

PINNED = {
    "serve": {
        "window_s": 0.204376325, "busy_s": 0.000417704,
        "module_s": {"jit_impl": 0.000411299, "jit__reduce_sum": 1.5905e-05},
        "device_ops": [["cascade_filter", 0.000305796],
                       ["copy", 3.8544e-05],
                       ["slice_reduce_fusion", 2.2902e-05]],
        "idle_gaps": [["pump0: np.asarray(jax.Array)", 0.141375214],
                      ["pump0: shard_args", 0.059917265],
                      ["pump0: PjitFunction(impl)", 0.002663741],
                      ["shorter idle gaps, not attributed", 2.401e-06]],
        "readers": {"filter_roofline": 0.5549439289579776,
                    "step_mfu.serve": 0.3972332508582169,
                    "idle_share.serve": 99.79562016295185},
    },
    "train": {
        "window_s": 0.006904799, "busy_s": 0.005730098,
        "module_s": {"jit_epoch": 0.005732466},
        "device_ops": [["transpose_jvp_jit_cascade_loss_bwd___", 0.003643834],
                       ["jvp_jit_cascade_loss__", 0.001568104],
                       ["copy", 0.000159497]],
        "idle_gaps": [["bench-main: PjitFunction(epoch)", 0.000638127],
                      ["no host event", 0.000534083],
                      ["shorter idle gaps, not attributed", 2.491e-06]],
        "readers": {"loss_roofline": 0.4012548235445745,
                    "step_mfu.train": None,
                    "idle_share.train": 17.012819634575884},
    },
}


def _facts(kind: str):
    s = xtrace.summarize(xtrace.load_json(DATA / f"{kind}_trace.json"))
    items = json.loads((DATA / f"{kind}_items.json").read_text())
    if kind == "serve":
        work = workcount.serve_work(items["items"], 24, 8, 3)
    else:
        work = workcount.train_work(items["real_items"], items["groups"],
                                    24, 8, 3)
    return {"trace": s, "work": work, "peaks": PEAKS}


def _approx(rows):
    return [[name, pytest.approx(v, rel=1e-9, abs=1e-15)] for name, v in rows]


@pytest.mark.parametrize("kind", ["serve", "train"])
def test_recorded_trace_reduces_to_its_pinned_numbers(kind):
    pin = PINNED[kind]
    facts = _facts(kind)
    s = facts["trace"]
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(pin["window_s"], rel=1e-12)
    assert s.busy_s == pytest.approx(pin["busy_s"], rel=1e-12)
    assert s.module_s == pytest.approx(pin["module_s"], rel=1e-12)
    b = s.breakdown()
    assert b["device_ops"][:3] == _approx(pin["device_ops"])
    assert b["idle_gaps"] == _approx(pin["idle_gaps"])
    for name, value in pin["readers"].items():
        got = benchlib.load_reader(name)(facts)
        if value is None:
            assert got is None, name
        else:
            assert got == pytest.approx(value, rel=1e-12), name
