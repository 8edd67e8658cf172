"""Pack, transfer and pipeline (serving/batching.py, serving/pump.py,
core/pipeline.py): median time from a completed request's flush start to
the end of its chunk's service, as the session stamps it
(RankResponse.service_ms)."""

import numpy as np


def read(facts):
    service = facts.get("service_ms")
    if service is None or len(service) == 0:
        return None
    return float(np.percentile(service, 50))
