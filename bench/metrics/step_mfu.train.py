"""Whole training step: the least time the window's required training work
takes at the chip's peaks (bench/workcount.py), as a share of the window's
seconds on the host clock."""

import workcount


def read(facts):
    if facts.get("kind") != "train" or facts.get("window_s", 0) <= 0:
        return None
    return (100.0 * workcount.least_seconds(facts["work"], facts["peaks"])
            / facts["window_s"])
