"""Closed loop: the next batch goes when the last one's answers are back
on the host (ann-benchmarks' batch mode). Mix keys: ``batch``."""

from __future__ import annotations

import numpy as np

from harness.traffic import Served, WallClock, Window

KEYS = ("batch",)


def shapes(mix: dict) -> list[int]:
    return [mix["batch"]]


def drive(serve, mix: dict, order: np.ndarray, seconds: float, seed: int, root=None,
          clock=None) -> Window:
    """Back-to-back batches of the queries ``order`` lists, cycling, until
    ``seconds`` have passed; the batch in flight at the close finishes and
    counts."""
    clock = clock or WallClock()
    batch = mix["batch"]
    t0 = end = clock.now()
    out = []
    i = 0
    while end - t0 < seconds:
        rows = order[(i * batch + np.arange(batch)) % len(order)]
        start = clock.now()
        dists, ids, counts = serve(rows)
        end = clock.now()
        out.append(Served(rows, batch, start, end, dists, ids, counts))
        i += 1
    return Window(t0, end, out)
