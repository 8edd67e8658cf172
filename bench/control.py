"""Readings of the program and of its control, on several seeds.

  python bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s> \
      [--control-seeds 1,2]

In one process, for each seed: one run of the cell as bench/run.py makes
it, with the numbers that decide `correct` read for the program and for
the control, the step below the configuration's precision that has to
fail them:

  serving   the plain reference in bfloat16 (reference.py, precision
            "bf16") put in the program's place on the same sample;
  training  the engine's own bfloat16 storage path
            (TrainConfig.precision "bf16"), run as a second program.

One JSON line per seed. The limits in the configuration file are set
from these readings: above the largest the program gives over a dozen
seeds or more, below the smallest the control gives.
"""

import time

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parent / "src"))

import benchlib  # noqa: E402


def readings(cell, seed: int, seconds: float, devs, peaks,
             control: bool) -> dict:
    kind = cell.traffic["kind"]
    if kind == "serve":
        import cell_serve
        out = cell_serve.run(cell, seed, seconds, False, time.monotonic(),
                             devs, peaks, control=control)
        row = {"seed": seed, "correct": out["correct"],
               "program": out["readings"], "notes": out["notes"]}
        if control:
            row["control"] = out["control_readings"]
        return row
    import cell_train
    out = cell_train.run(cell, seed, seconds, False, time.monotonic(), devs,
                         peaks)
    row = {"seed": seed, "correct": out["correct"],
           "program": out["readings"], "notes": out["notes"]}
    if control:
        ctl = cell_train.run(cell, seed, seconds, False, time.monotonic(),
                             devs, peaks, precision="bf16")
        row["control"] = ctl["readings"]
        row["control_correct"] = ctl["correct"]
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="",
                    help="the seeds on which the control runs too "
                         "(default: all)")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    controlled = ({int(s) for s in args.control_seeds.split(",")}
                  if args.control_seeds else set(seeds))
    cell = benchlib.load_cell(args.workload)
    devs = benchlib.require_devices(cell.chips)
    benchlib.configure_jax_cache()
    peaks = benchlib.peaks_for(devs[0].device_kind)
    for seed in seeds:
        print(json.dumps(readings(cell, seed, args.seconds, devs, peaks,
                                  seed in controlled)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
