"""Run one benchmark cell once.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of BENCHMARK.json's workloads; its configuration file,
its traffic file (bench/traffic/<mix>.json) and its per-layer metrics'
readers (bench/metrics/<metric>.py) are found by name, so a new cell, mix
or metric is a new file and a new entry. The traffic's "kind" picks the
runner: "serve" (cell_serve.py) or "train" (cell_train.py).

The last line of standard output is one JSON object: correct, attempted,
failed, metrics (the cell's end-to-end metrics with --trace 0, its
per-layer metrics with --trace 1), device, with --trace 1 the breakdown
of the trace, and last the numbers compared for `correct`, each with its
limit. Those numbers are also the last lines of standard error. Without a
TPU, or with fewer chips than the cell asks for, the run exits non-zero
and prints no result.
"""

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parent / "src"))

import benchlib  # noqa: E402

RUNNERS = {"serve": "cell_serve", "train": "cell_train"}


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run_cell(cell: benchlib.Cell, seed: int, seconds: float, trace: bool,
             t0: float, devs) -> dict:
    """Run the cell and assemble the result line's object."""
    import importlib
    kind = cell.traffic.get("kind")
    if kind not in RUNNERS:
        raise benchlib.BenchError(f"traffic {cell.traffic_name!r} has unknown "
                                  f"kind {kind!r}")
    if not (benchlib.ROOT / "src" / "repro").is_dir():
        raise benchlib.BenchError("the program (src/repro) is not in this "
                                  "checkout")
    runner = importlib.import_module(RUNNERS[kind])
    device = benchlib.device_record(devs)
    peaks = benchlib.peaks_for(device["kind"], cell.root)
    out = runner.run(cell, seed, seconds, trace, t0, devs, peaks)
    device["memory_peak_bytes"] = out["memory"]
    if trace:
        metrics = benchlib.read_per_layer(cell.per_layer, out["facts"],
                                          cell.root)
        summary = out["facts"]["trace"]
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {k: {"value": float(out["e2e"][k]), "unit": units[k]}
                   for k in units}
    result = {"correct": bool(out["correct"]),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "device": device}
    if trace:
        result["breakdown"] = out["facts"]["trace"].breakdown()
    result["compared"] = out["checks"]
    result["_notes"] = out["notes"]
    return result


def main(argv=None) -> int:
    benchlib.name_this_thread("bench-main")
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = benchlib.load_cell(args.workload)
        benchlib.split_seed(args.seed)
        devs = benchlib.require_devices(cell.chips)
        benchlib.configure_jax_cache()
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          T0, devs)
    except benchlib.BenchError as e:
        log(f"FAIL: {e}")
        return 2
    notes = result.pop("_notes")
    log("notes " + json.dumps(notes))
    log(f"correct = {result['correct']}")
    for name, (value, limit) in result["compared"].items():
        log(f"compared {name} = {value!r} (limit {limit!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
