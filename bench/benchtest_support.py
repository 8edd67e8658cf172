"""Helpers for the benchmark's CPU tests (bench/test_bench_*.py).

A tiny copy of the benchmark under a temporary root: BENCHMARK.json and
the configuration, traffic, metric and peak files, with the log, the
batches and the rates cut so that a whole run takes seconds on the CPU.
The look for a chip is steered round inside the test only.
"""

from __future__ import annotations

import importlib.util
import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def load_run_module():
    """bench/run.py under a module name of its own."""
    spec = importlib.util.spec_from_file_location("bench_run_entry",
                                                  BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def add_cell(root: Path, name: str, config: str, traffic: str,
             traffic_spec: dict | None = None, chips: int = 1,
             metrics: dict | None = None) -> None:
    """A new cell the way a later change adds one: a traffic file (when
    given) and entries in BENCHMARK.json, nothing edited. Without
    `metrics` the cell reports what serve-large reports; with it, the
    end-to-end and per-layer metric entries it brings (their
    `workloads` name the cell)."""
    if traffic_spec is not None:
        (root / "bench" / "traffic" / f"{traffic}.json").write_text(
            json.dumps(traffic_spec))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": name, "config": config,
                              "traffic": traffic, "chips": chips,
                              "why": "a cell the tests add"})
    if metrics is None:
        for m in spec["end_to_end"] + spec["per_layer"]:
            if "serve-large" in m.get("workloads", ()):
                m["workloads"].append(name)
    else:
        for key in ("end_to_end", "per_layer"):
            for m in metrics[key]:
                m = dict(m)
                m["workloads"] = [name]
                spec[key].append(m)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


# The entries that bring back the training cell (left out of BENCHMARK.json
# while the program's training step departs from float32): its traffic
# file bench/traffic/l3.json, its end-to-end metric and per-layer metrics
# with readers in bench/metrics/.
TRAIN_L3_METRICS = {
    "end_to_end": [
        {"name": "groups_per_s", "unit": "groups/s", "better": "higher",
         "bound": 0.01, "source": "host_clock"}],
    "per_layer": [
        {"name": "loss_roofline", "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "kernels",
         "moves": "groups_per_s"},
        {"name": "step_mfu.train", "unit": "%", "better": "higher",
         "source": "host_clock", "layer": "training step",
         "moves": "groups_per_s"},
        {"name": "idle_share.train", "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "device",
         "moves": "groups_per_s"}],
}


def tiny_root(tmp: Path, *, n_queries: int = 48, serve_batch: int = 4,
              train_batch: int = 8, rate: float = 120.0) -> Path:
    """A copy of the benchmark's files under tmp, cut to CPU size, with
    the training cell train-l3 added (the L3 fit, as a later change would
    add it); serve-small (8-16 items, the 16 bucket only) is the quick
    one to warm."""
    (tmp / "bench").mkdir(parents=True)
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    for d in ("configs", "traffic", "metrics"):
        shutil.copytree(BENCH / d, tmp / "bench" / d)
    shutil.copy(BENCH / "peaks.json", tmp / "bench" / "peaks.json")
    for p in (tmp / "bench" / "configs").glob("*.json"):
        c = json.loads(p.read_text())
        c["n_queries"] = n_queries
        c["serving"]["batch_groups"] = serve_batch
        c["training"]["batch_groups"] = train_batch
        p.write_text(json.dumps(c))
    add_cell(tmp, "train-l3", "cloes3_normal", "l3",
             metrics=TRAIN_L3_METRICS)
    for p in (tmp / "bench" / "traffic").glob("*.json"):
        t = json.loads(p.read_text())
        if t["kind"] == "serve":
            t["rate_per_s"] = rate
            t["sample"] = 48
        p.write_text(json.dumps(t))
    return tmp


def cpu_devices(monkeypatch):
    """Steer round the harness's look for a TPU: the CPU device, as many
    times as a cell asks for chips, with the v5e's peaks."""
    import jax
    import benchlib
    peaks = json.loads((BENCH / "peaks.json").read_text())
    monkeypatch.setattr(benchlib, "require_devices",
                        lambda chips: (jax.devices() * chips)[:chips])
    monkeypatch.setattr(benchlib, "peaks_for",
                        lambda kind, root=None:
                        peaks["devices"]["TPU v5 lite"])


def run_cell(root: Path, name: str, seconds: float = 0.5,
             seed: int = 2**31 + 5, trace: bool = False) -> dict:
    import time
    import benchlib
    run = load_run_module()
    cell = benchlib.load_cell(name, root)
    devs = benchlib.require_devices(cell.chips)
    return run.run_cell(cell, seed, seconds, trace, time.monotonic(), devs)
