"""Measure statement coverage of src/repro/serving/ with the stdlib only.

The CI coverage gate (scripts/ci.sh, COV_FLOOR) runs under pytest-cov,
which is not installed in every dev container. This script reproduces the
same executed-statements / executable-statements ratio with sys.settrace
plus code-object linetables, so the floor can be (re-)grounded anywhere:

    PYTHONPATH=src python scripts/measure_serving_cov.py [pytest args...]

Defaults to the serving-focused fast-loop test files — the same selection
the CI gate measures. Prints per-file and total coverage and writes
COVERAGE_serving.json; exits nonzero if the run's pytest leg fails.
"""

from __future__ import annotations

import dis
import json
import os
import sys
import threading
import types

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.abspath(os.path.join(_HERE, ".."))
TARGET = os.path.join(_ROOT, "src", "repro", "serving") + os.sep
# fast containment probe for the trace hot path: co_filename may be a
# relative or un-normalized path depending on how the module was imported
_NEEDLE = os.path.join("repro", "serving") + os.sep

# the serving surface's tests, fast loop only — mirror scripts/ci.sh
# (test_engine is the fast prefill/decode leg for serving/engine.py, whose
# full numerical sweep in test_arch_smoke is slow-marked; test_checkpoint
# covers the warm-restart seam and the fs-fault injector)
DEFAULT_ARGS = ["-q", "-m", "not slow",
                "tests/test_serving_batching.py", "tests/test_session.py",
                "tests/test_faults.py", "tests/test_pump.py",
                "tests/test_router.py", "tests/test_determinism.py",
                "tests/test_arch_smoke.py", "tests/test_checkpoint.py",
                "tests/test_engine.py"]

_executed: dict[str, set[int]] = {}


def _tracer(frame, event, arg):
    fn = frame.f_code.co_filename
    if _NEEDLE not in fn:
        return None
    lines = _executed.setdefault(fn, set())

    def local(frame, event, arg):
        if event == "line":
            lines.add(frame.f_lineno)
        return local

    if event == "call":
        lines.add(frame.f_lineno)
        return local
    return None


def executable_lines(path: str) -> set[int]:
    """Line numbers carrying bytecode — the linetable union over every
    code object in the file, the same denominator coverage.py uses."""
    with open(path) as f:
        code = compile(f.read(), path, "exec")
    out: set[int] = set()
    stack = [code]
    while stack:
        c = stack.pop()
        for _, ln in dis.findlinestarts(c):
            if ln is not None and ln > 0:
                out.add(ln)
        stack.extend(k for k in c.co_consts
                     if isinstance(k, types.CodeType))
    return out


def main() -> int:
    # run from the repo root with the root importable, exactly like the CI
    # pytest invocation
    os.chdir(_ROOT)
    if _ROOT not in sys.path:
        sys.path.insert(0, _ROOT)
    import pytest
    args = sys.argv[1:] or DEFAULT_ARGS
    # tracing must be live BEFORE collection imports repro.serving, or the
    # module-level lines (defs, dataclass fields) count as never executed
    assert not any(m.startswith("repro.serving") for m in sys.modules), \
        "repro.serving imported before tracing started"
    threading.settrace(_tracer)
    sys.settrace(_tracer)
    rc = pytest.main(args)
    sys.settrace(None)
    threading.settrace(None)

    hits_by_path: dict[str, set[int]] = {}
    for fn, lines in _executed.items():
        hits_by_path.setdefault(os.path.abspath(fn), set()).update(lines)
    rows, tot_exec, tot_lines = [], 0, 0
    for fn in sorted(os.listdir(TARGET)):
        if not fn.endswith(".py"):
            continue
        path = os.path.join(TARGET, fn)
        lines = executable_lines(path)
        hit = hits_by_path.get(path, set()) & lines
        rows.append({"file": f"repro/serving/{fn}", "lines": len(lines),
                     "covered": len(hit),
                     "percent": round(100.0 * len(hit) / max(len(lines), 1),
                                      1)})
        tot_exec += len(hit)
        tot_lines += len(lines)
    total = round(100.0 * tot_exec / max(tot_lines, 1), 1)
    for r in rows:
        print(f"{r['file']:44s} {r['covered']:4d}/{r['lines']:4d}"
              f"  {r['percent']:5.1f}%")
    print(f"{'TOTAL src/repro/serving':44s} {tot_exec:4d}/{tot_lines:4d}"
          f"  {total:5.1f}%")
    with open("COVERAGE_serving.json", "w") as f:
        json.dump({"total_percent": total, "files": rows}, f, indent=1)
    floor = float(os.environ.get("COV_FLOOR", "0"))
    if total < floor:
        print(f"FAIL: serving coverage {total:.1f}% < floor {floor:.1f}% "
              "(COV_FLOOR)", file=sys.stderr)
        return 1
    return int(rc)


if __name__ == "__main__":
    raise SystemExit(main())
