"""Find a serving cell's knee: the highest offered rate it sustains.

  python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
      --rates 1000,2000,...

One process warms the cell once and offers each rate for one window, in
the order given, through the same generator and pump as bench/run.py.
A rate is sustained when no request is shed, none is served degraded
(the queue never reached the degradation watermark) and p99 latency
stays under the configuration's limit. One JSON line per rate goes to
standard output. The cell's traffic file then fixes its rate at about
four fifths of the knee.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = benchlib.load_cell(args.workload)
    import cell_serve
    import system
    devs = benchlib.require_devices(cell.chips)
    benchlib.configure_jax_cache()
    config, traffic = cell.config, cell.traffic
    log = cell_serve.make_log(config, args.seed)
    params = system.make_weights(config, args.seed,
                                 config["serve_weight_std"], devs[0])
    server = cell_serve.Server(config, params, devs)
    server.warm(cell_serve.warm_buckets(config, traffic), log)
    server.start()
    limit = float(config["latency_limit_ms"])
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            sch = cell_serve.schedule({**traffic, "rate_per_s": rate},
                                      args.seconds, args.seed, log)
            reqs = cell_serve.build_requests(log, sch)
            win = cell_serve.run_window(server, reqs, sch, (),
                                        annotate=False)
            res = cell_serve.outcome(win, limit, args.seconds)
            row = {
                "rate": rate, "requests": len(reqs), "ok": int(res["ok"].sum()),
                "shed": res["shed"],
                "degraded": int(win.record.degraded[res["ok"]].sum()),
                "p50_ms": res["p50_ms"], "p99_ms": res["p99_ms"],
                "goodput": res["goodput"],
                "gen_late_p99_ms": benchlib.percentile(
                    (win.submitted - win.due) * 1e3, 99),
                "compiles_in_window": win.compiles,
                **win.gc,
                **cell_serve.stall_notes(win),
            }
            row["sustained"] = (row["shed"] == 0 and row["degraded"] == 0
                                and row["p99_ms"] <= limit)
            print(json.dumps(row), flush=True)
    finally:
        server.close()
    print(json.dumps({"setup_and_sweep_s": time.monotonic() - T0}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
