"""Admission and queues (serving/session.py): 99th percentile of the time
a completed request waited in its bucket's queue before its flush began,
as the session stamps it (RankResponse.wait_ms)."""

import numpy as np


def read(facts):
    wait = facts.get("wait_ms")
    if wait is None or len(wait) == 0:
        return None
    return float(np.percentile(wait, 99))
