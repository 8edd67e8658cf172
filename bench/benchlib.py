"""Shared pieces of the benchmark harness.

Everything here is independent of any one cell: finding a cell and its
files by name, the compile cache, the device check, the compile clock,
the table of peaks, thread naming for the trace, and small statistics.
The program under test is imported only by the cell runners
(cell_serve.py, cell_train.py); the plain references (reference.py)
import nothing of it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import shutil
import tempfile
import threading
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
# The persistent compilation cache lives at a fixed path inside the
# checkout: the path is part of the cache key, and only the checkout
# outlasts a run. It is set whatever JAX_COMPILATION_CACHE_DIR says.
CACHE_DIR = ROOT / ".bench_cache" / "jax"


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, bad cell, bad file)."""


@dataclasses.dataclass
class Cell:
    """One entry of BENCHMARK.json's workloads with the files it names."""
    name: str
    chips: int
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    root: Path


def _applies(metric: dict, cell: str, e2e_names: set[str]) -> bool:
    """A metric is reported in a cell that its `workloads` lists, or, with
    no list, in every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Find a workload by name and load its configuration file and its
    traffic file (bench/traffic/<traffic>.json)."""
    try:
        spec = json.loads((root / "BENCHMARK.json").read_text())
    except FileNotFoundError as e:
        raise BenchError(f"no BENCHMARK.json under {root}") from e
    wl = {w["name"]: w for w in spec["workloads"]}.get(name)
    if wl is None:
        raise BenchError(f"unknown workload {name!r}; known: "
                         f"{sorted(w['name'] for w in spec['workloads'])}")
    cfg_entry = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic_file = root / "bench" / "traffic" / f"{wl['traffic']}.json"
    traffic = json.loads(traffic_file.read_text())
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if _applies(m, name, e2e_names)]
    return Cell(name=name, chips=int(wl["chips"]), config=config,
                traffic_name=wl["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=per_layer, root=root)


def load_reader(metric: str, root: Path = ROOT):
    """The reader of a per-layer metric: bench/metrics/<metric>.py's
    `read(facts)`. Metric names may hold dots, so the file is loaded by
    path, not imported by module name."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise BenchError(f"per-layer metric {metric!r} has no reader {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_per_layer(metrics: list[dict], facts: dict,
                   root: Path = ROOT) -> dict:
    """Run each metric's reader; a reader that finds nothing to read
    returns None, and the metric is left out of the line."""
    out = {}
    for m in metrics:
        value = load_reader(m["name"], root)(facts)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def configure_jax_cache() -> None:
    """Point JAX's persistent compilation cache at CACHE_DIR, whatever
    JAX_COMPILATION_CACHE_DIR says, and cache every compile however short
    (one serving shape compiles in 0.2 to 3 s). Call it after the device
    check and before the first compile."""
    import jax
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def require_devices(chips: int) -> list:
    """The first `chips` TPU devices. No TPU, or fewer chips than the
    cell asks for, is an error: the benchmark never falls back to the
    CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def device_record(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest chip (0 where not reported)."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def peaks_for(device_kind: str, root: Path = ROOT) -> dict:
    """Published peaks of one chip, from bench/peaks.json. A device that
    is not in the table is an error, not a default."""
    table = json.loads((root / "bench" / "peaks.json").read_text())
    row = table["devices"].get(device_kind)
    if row is None:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         "bench/peaks.json")
    return row


class CompileClock:
    """Counts backend compilations and persistent-cache hits through
    jax.monitoring (a cache hit takes the place of a compile)."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self._event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == self._event:
            self.seconds += duration
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


class GcClock:
    """Times the garbage collector's passes through gc.callbacks: how many
    ran, how many of them over the oldest generation, and the longest
    pause, while it is open."""

    def __init__(self):
        import gc
        import time
        self._clock = time.monotonic
        self._began = 0.0
        self.passes = 0
        self.full = 0
        self.max_ms = 0.0
        gc.callbacks.append(self._on_pass)

    def _on_pass(self, phase, info):
        if phase == "start":
            self._began = self._clock()
            return
        self.passes += 1
        self.full += info["generation"] == 2
        self.max_ms = max(self.max_ms, (self._clock() - self._began) * 1e3)

    def close(self):
        import gc
        gc.callbacks.remove(self._on_pass)

    def notes(self) -> dict:
        return {"gc_passes": self.passes, "gc_full": self.full,
                "gc_max_ms": self.max_ms}


def name_thread(native_id: int | None, name: str) -> None:
    """Give a thread an OS name, which the profiler shows as the name of
    its host line. It takes effect only before the thread's first traced
    event. Linux only; elsewhere the line keeps the process name."""
    if native_id is None:
        return
    try:
        with open(f"/proc/self/task/{native_id}/comm", "w") as f:
            f.write(name[:15])
    except OSError:
        pass


def name_this_thread(name: str) -> None:
    name_thread(threading.get_native_id(), name)


class Phases:
    """Seconds spent in each named phase of set-up, from t0 on the
    monotonic clock."""

    def __init__(self, t0: float):
        import time
        self._clock = time.monotonic
        self._last = t0
        self.laps: dict[str, float] = {}

    def mark(self, name: str) -> None:
        now = self._clock()
        self.laps[name] = round(now - self._last, 3)
        self._last = now


def percentile(values, p: float) -> float:
    """numpy's linear-interpolation percentile; NaN for no values."""
    import numpy as np
    if len(values) == 0:
        return math.nan
    return float(np.percentile(np.asarray(values, np.float64), p))


def split_seed(seed: int) -> tuple[int, int]:
    """Two 32-bit words of a seed that may exceed 32 bits."""
    if seed < 0:
        raise BenchError(f"--seed must be a non-negative whole number, "
                         f"got {seed}")
    return seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF


def device_key(seed: int):
    """A jax PRNG key from a seed of any size."""
    import jax
    lo, hi = split_seed(seed)
    return jax.random.fold_in(jax.random.key(lo), hi)


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

WINDOW_SPAN = "bench.window"


def span(name: str):
    """A host span in the profiler's trace."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def no_span(name: str):
    return contextlib.nullcontext()


class Tracer:
    """The profiler around the window of a traced run (--trace 1), with
    the Python tracer off; the trace goes to a temporary directory under
    TMPDIR, is reduced by xtrace.summarize, and is deleted."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self._dir = None
        self._running = False

    def start(self) -> None:
        if not self.enabled:
            return
        import jax
        self._dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._running = True

    def stop(self):
        """Stop and reduce: an xtrace.Summary, or None when not tracing."""
        if not self._running:
            return None
        import jax
        import xtrace
        jax.profiler.stop_trace()
        self._running = False
        return xtrace.summarize(xtrace.load_xplane(self._dir))

    def close(self) -> None:
        if self._running:
            import jax
            jax.profiler.stop_trace()
            self._running = False
        if self._dir is not None:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None
