"""The paper's tables and figures, reproduced on the synthetic log — one
function each.

Prints ``name,us_per_call,derived`` CSV rows. Run with:
    PYTHONPATH=src python -m benchmarks.run
"""

from __future__ import annotations

import sys
import time
import traceback


def main() -> None:
    from benchmarks import (fig3_uninstall, fig4_user_experience,
                            fig5_peak_load, table3_offline, table4_importance)
    suites = [
        ("table3", table3_offline.run),
        ("table4", table4_importance.run),
        ("fig3", fig3_uninstall.run),
        ("fig4", fig4_user_experience.run),
        ("fig5", fig5_peak_load.run),
    ]
    print("name,us_per_call,derived")
    failures = []
    for name, fn in suites:
        t0 = time.perf_counter()
        try:
            fn()
            print(f"suite/{name},{(time.perf_counter()-t0)*1e6:.0f},status=ok")
        except Exception as ex:                       # noqa: BLE001
            failures.append(name)
            traceback.print_exc()
            print(f"suite/{name},{(time.perf_counter()-t0)*1e6:.0f},"
                  f"status=FAIL:{type(ex).__name__}")
    if failures:
        sys.exit(f"benchmark suites failed: {failures}")


if __name__ == "__main__":
    main()
