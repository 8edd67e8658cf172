"""Whole serving step: the least time the window's required serving work
takes at the chip's peaks (bench/workcount.py), as a share of the device
time of every program the flushes ran (the trace's XLA Modules)."""

import workcount


def read(facts):
    trace = facts.get("trace")
    if trace is None or facts.get("work") is None:
        return None
    seconds = sum(trace.module_s.values())
    if seconds <= 0:
        return None
    return 100.0 * workcount.least_seconds(facts["work"], facts["peaks"]) / seconds
