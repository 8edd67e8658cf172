"""Seeded CL004: hand-rolled staging batches outside the bucket/warmup
code: the one-buffer layout {"buf","x","q","mask","m_q"} built by hand,
and the four-array {"x","q","mask","m_q"} form beside it."""
import numpy as np


def handmade_batch(b, g, d_x, d_q):
    buf = np.zeros((b, g * (d_x + 1) + d_q + 1), np.float32)
    return {"buf": buf,                                 # CL004
            "x": buf[:, :g * d_x].reshape(b, g, d_x),
            "mask": buf[:, g * d_x:g * (d_x + 1)],
            "q": buf[:, g * (d_x + 1):-1],
            "m_q": buf[:, -1]}


def four_array_batch(b, g, d_x, d_q):
    return {"x": np.zeros((b, g, d_x), np.float32),    # CL004
            "q": np.zeros((b, d_q), np.float32),
            "mask": np.zeros((b, g), np.float32),
            "m_q": np.ones((b,), np.float32)}
