"""Reduce a profiler trace to the numbers the per-layer metrics read.

A trace is a list of planes, each a list of named lines of events
(name, start_ns, duration_ns), as jax.profiler writes them to an
`.xplane.pb` file and as `load_json` reads them back from a recorded,
trimmed copy (bench/testdata/). On a TPU:

  /device:TPU:<n>  "XLA Ops"      one event per operation; a loop or call
                                  event contains the events of its body
                   "XLA Modules"  one event per program execution
  /host:CPU        one line per host thread, named by the thread's OS name

Host and device events share one clock. The traced window is the span of
the harness's own host annotation WINDOW_SPAN; everything is cut to it.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import json
import re
from pathlib import Path

from benchlib import WINDOW_SPAN

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass
class Line:
    name: str
    events: list[tuple[str, float, float]]    # (name, start_ns, dur_ns)


@dataclasses.dataclass
class Plane:
    name: str
    lines: list[Line]


def load_xplane(log_dir: str | Path) -> list[Plane]:
    """The planes of the one `.xplane.pb` that jax.profiler wrote under
    log_dir."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(str(Path(log_dir) / "plugins" / "profile" / "*"
                                 / "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    planes = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        planes.append(Plane(plane.name, [
            Line(line.name, [(e.name, float(e.start_ns), float(e.duration_ns))
                             for e in line.events])
            for line in plane.lines]))
    return planes


def load_json(path: str | Path) -> list[Plane]:
    """Planes from the JSON form `dump_json` writes."""
    data = json.loads(Path(path).read_text())
    return [Plane(p["name"], [Line(ln["name"], [tuple(e) for e in ln["events"]])
                              for ln in p["lines"]])
            for p in data["planes"]]


def dump_json(planes: list[Plane], path: str | Path) -> None:
    Path(path).write_text(json.dumps({"planes": [
        {"name": p.name, "lines": [{"name": ln.name, "events": ln.events}
                                   for ln in p.lines]}
        for p in planes]}))


def op_name(event_name: str) -> str:
    """The HLO instruction's name without its '%' and '.<n>' suffix:
    '%cascade_filter.1 = (f32[...]) custom-call(...)' -> 'cascade_filter'."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def module_name(event_name: str) -> str:
    """'jit_impl(8496952077487217284)' -> 'jit_impl'."""
    return event_name.split("(", 1)[0]


def merged(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(events) -> list[tuple[str, float]]:
    """(name, self time) of each event of a line whose events nest: an
    event's self time is its duration less the time of the events
    directly inside it (a loop's body ops are events of their own)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    stack: list[list] = []          # [name, end, child_ns, dur]
    for name, s, d in evs:
        while stack and s >= stack[-1][1]:
            top = stack.pop()
            out.append((top[0], top[3] - top[2]))
        if stack:
            stack[-1][2] += min(d, stack[-1][1] - s)
        stack.append([name, s + d, 0.0, d])
    while stack:
        top = stack.pop()
        out.append((top[0], top[3] - top[2]))
    return out


def _clip(events, t0, t1):
    for name, s, d in events:
        e = s + d
        if e <= t0 or s >= t1:
            continue
        yield name, max(s, t0), min(e, t1) - max(s, t0)


@dataclasses.dataclass
class Summary:
    """What the per-layer readers take from a trace."""
    window_s: float                      # length of the traced window
    n_devices: int
    busy_s: float                        # union of op intervals, per chip
    op_s: dict[str, float]               # op base name -> self time, all chips
    module_s: dict[str, float]           # program name -> time, all chips
    idle_gaps: list[tuple[str, float]]   # (host activity, seconds), all chips
    device_ops: list[tuple[str, float]]  # top ops by self time, per chip

    def ops_matching(self, token: str) -> float:
        """Seconds, summed over chips, of every op whose name holds
        `token` (a kernel's function name)."""
        return sum(v for k, v in self.op_s.items() if token in k)

    def breakdown(self, top: int = 10) -> dict:
        return {"device_ops": [[k, v] for k, v in self.device_ops[:top]],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps[:top]]}


def window_of(planes: list[Plane]) -> tuple[float, float]:
    for p in planes:
        if p.name != HOST_PLANE:
            continue
        for ln in p.lines:
            for name, s, d in ln.events:
                if name == WINDOW_SPAN:
                    return s, s + d
    raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")


class _HostIndex:
    """Host events as arrays, per thread line, for attributing gaps."""

    def __init__(self, host_lines):
        import numpy as np
        self.lines = []
        for ln in host_lines:
            evs = [e for e in ln.events if e[0] != WINDOW_SPAN]
            if evs:
                starts = np.array([e[1] for e in evs])
                self.lines.append((ln.name, [e[0] for e in evs], starts,
                                   starts + np.array([e[2] for e in evs])))

    def activity(self, s: float, e: float) -> str:
        """What the host did during device idle time [s, e): the thread,
        and its event, that overlapped the gap longest."""
        import numpy as np
        best, best_ns = "no host event", 0.0
        for thread, names, starts, ends in self.lines:
            ov = np.minimum(ends, e) - np.maximum(starts, s)
            i = int(np.argmax(ov))
            if ov[i] > best_ns:
                best, best_ns = f"{thread}: {names[i]}", float(ov[i])
        return best


def summarize(planes: list[Plane], min_gap_ns: float = 50_000.0,
              max_attributed: int = 400) -> Summary:
    """Cut the trace to the traced window and reduce it. The longest
    max_attributed device idle gaps of at least min_gap_ns are attributed
    to what the host was doing and summed by that activity; the rest of
    the idle time is summed as one entry."""
    t0, t1 = window_of(planes)
    devices = [p for p in planes if p.name.startswith(DEVICE_PREFIX)
               and any(ln.name == OPS_LINE for ln in p.lines)]
    host_lines = [ln for p in planes if p.name == HOST_PLANE
                  for ln in p.lines if ln.events]
    busy = 0.0
    op_s: dict[str, float] = collections.Counter()
    module_s: dict[str, float] = collections.Counter()
    spans_idle: list[tuple[float, float]] = []
    for dev in devices:
        lines = {ln.name: ln for ln in dev.lines}
        ops = list(_clip(lines[OPS_LINE].events, t0, t1))
        spans = merged((s, s + d) for _, s, d in ops)
        busy += sum(e - s for s, e in spans)
        for name, st in self_times(ops):
            op_s[op_name(name)] += st / 1e9
        if MODULES_LINE in lines:
            for name, _, d in _clip(lines[MODULES_LINE].events, t0, t1):
                module_s[module_name(name)] += d / 1e9
        edges = [t0] + [x for sp in spans for x in sp] + [t1]
        spans_idle += [(s, e) for s, e in zip(edges[0::2], edges[1::2])
                       if e > s]
    spans_idle.sort(key=lambda g: g[0] - g[1])
    index = _HostIndex(host_lines)
    gaps: dict[str, float] = collections.Counter()
    for k, (s, e) in enumerate(spans_idle):
        if k < max_attributed and e - s >= min_gap_ns:
            gaps[index.activity(s, e)] += (e - s) / 1e9
        else:
            gaps["shorter idle gaps, not attributed"] += (e - s) / 1e9
    n = max(len(devices), 1)
    per_chip_ops = sorted(((k, v / n) for k, v in op_s.items()),
                          key=lambda kv: -kv[1])
    return Summary(window_s=(t1 - t0) / 1e9, n_devices=len(devices),
                   busy_s=busy / 1e9 / n, op_s=dict(op_s),
                   module_s=dict(module_s),
                   idle_gaps=sorted(gaps.items(), key=lambda kv: -kv[1]),
                   device_ops=per_chip_ops)
