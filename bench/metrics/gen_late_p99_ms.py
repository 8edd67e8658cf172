"""Load generator: 99th percentile of how late the generator submitted
each request after its due time (host clock). A late generator, not a
slow server, would show here first."""

import numpy as np


def read(facts):
    late = facts.get("gen_late_ms")
    if late is None or len(late) == 0:
        return None
    return float(np.percentile(late, 99))
