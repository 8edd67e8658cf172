"""Spans of the serving flush: where one flush's host time goes.

A flush runs claim -> pack -> dispatch -> fetch -> resolve (serving/
session.py), on the pump thread under a SessionPump (serving/pump.py).
SpanRecorder times each step under the flush's id on time.monotonic_ns,
the clock of the session's wait_ms/service_ms stamps, and a response
carries its flush's id (RankResponse.flush_id), so a request's stamps
join its flush's spans.

  serve.cycle     the pump's service cycle: from the read that starts
                  service_ms to the end of resolve (never annotated)
  serve.claim     claim_due / claim_bucket, lock wait included
  serve.pack      each pack_chunk that stages rows
  serve.dispatch  rank_batch: the one host-to-device copy of the staging
                  buffer and the call into the jitted pipeline, up to its
                  asynchronous return
  serve.fetch     the wait for the device and the one device-to-host copy
                  of the packed result
  serve.resolve   resolve_chunk / fail_chunk

Retries and bisection give one flush several dispatch and fetch spans;
they sum per flush (flush_ms).

Off (the default), span() hands back one shared no-op context: no clock
read, no allocation. On, each span appends (name, flush_id, start_ns,
end_ns) to a list of at most MAX_SPANS (later spans are dropped and
counted in `dropped`). With annotate=True each span() also opens a
jax.profiler.TraceAnnotation of its name, so it lands on the profiler's
host plane, on the device trace's clock; record() never annotates, which
keeps serve.cycle out of the trace: a gap attributed by longest overlap
would otherwise name the cycle instead of the step inside it.
"""

from __future__ import annotations

import threading
import time

import jax
import numpy as np

MAX_SPANS = 1 << 20

CYCLE = "serve.cycle"
CLAIM = "serve.claim"
PACK = "serve.pack"
DISPATCH = "serve.dispatch"
FETCH = "serve.fetch"
RESOLVE = "serve.resolve"
STEPS = (CLAIM, PACK, DISPATCH, FETCH, RESOLVE)


class _Off:
    """The disabled recorder's one span: enters and exits doing nothing,
    and drops a flush id set on it."""

    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    @property
    def flush_id(self) -> None:
        return None

    @flush_id.setter
    def flush_id(self, value) -> None:
        pass


OFF = _Off()


class _Span:
    """One timed step. flush_id may be set inside the block (a claim
    learns its flush's id only once it has claimed); a span that ends
    with no id belongs to no flush and is not recorded."""

    __slots__ = ("_rec", "name", "flush_id", "_start", "_ann")

    def __init__(self, rec: "SpanRecorder", name: str, flush_id):
        self._rec = rec
        self.name = name
        self.flush_id = flush_id
        self._ann = None

    def __enter__(self) -> "_Span":
        if self._rec.annotate:
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        self._start = time.monotonic_ns()
        return self

    def __exit__(self, *exc) -> bool:
        end = time.monotonic_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self.flush_id is not None:
            self._rec.record(self.name, self.flush_id, self._start, end)
        return False


class SpanRecorder:
    """In-memory spans of the serving flush (module docstring). One per
    session (CascadeSession(spans=...)); its pump records into it too."""

    def __init__(self, enabled: bool = False, annotate: bool = False):
        self.enabled = enabled
        self.annotate = enabled and annotate
        self.dropped = 0
        self._spans: list[tuple[str, int, int, int]] = []
        self._lock = threading.Lock()

    def span(self, name: str, flush_id: int | None = None):
        """A context that times its block as `name` under flush_id."""
        if not self.enabled:
            return OFF
        return _Span(self, name, flush_id)

    def record(self, name: str, flush_id: int, start_ns: int,
               end_ns: int | None = None) -> None:
        """Append a span timed by the caller's own clock reads (end_ns
        None: now). Never annotated."""
        if not self.enabled:
            return
        if end_ns is None:
            end_ns = time.monotonic_ns()
        with self._lock:
            if len(self._spans) < MAX_SPANS:
                self._spans.append((name, flush_id, start_ns, end_ns))
            else:
                self.dropped += 1

    def spans(self) -> list[tuple[str, int, int, int]]:
        """A copy of the recorded (name, flush_id, start_ns, end_ns)."""
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self.dropped = 0

    def flush_ms(self) -> dict[str, dict[int, float]]:
        """Per span name, each flush's summed time in milliseconds."""
        out: dict[str, dict[int, float]] = {}
        for name, fid, s, e in self.spans():
            per = out.setdefault(name, {})
            per[fid] = per.get(fid, 0.0) + (e - s) / 1e6
        return out


def report(recorders) -> dict:
    """Per span name over the recorders' flushes: how many flushes, and
    the p50 and p99 of a flush's summed time (ms), under "spans";
    "dropped" counts the spans past the recorders' caps."""
    per: dict[str, list[float]] = {}
    for rec in recorders:
        for name, times in rec.flush_ms().items():
            per.setdefault(name, []).extend(times.values())
    return {"spans": {name: {"flushes": len(v),
                             "p50_ms": float(np.percentile(v, 50)),
                             "p99_ms": float(np.percentile(v, 99))}
                      for name, v in sorted(per.items())},
            "dropped": sum(rec.dropped for rec in recorders)}
