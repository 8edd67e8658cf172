"""Kernel cascade_filter: the least time the window's required serving work
takes at the chip's peaks (bench/workcount.py), as a share of the filter
kernel's device time in the trace. Nothing when the kernel is absent."""

import workcount

KERNEL = "cascade_filter"


def read(facts):
    trace = facts.get("trace")
    if trace is None or facts.get("work") is None:
        return None
    seconds = trace.ops_matching(KERNEL)
    if seconds <= 0:
        return None
    return 100.0 * workcount.least_seconds(facts["work"], facts["peaks"]) / seconds
