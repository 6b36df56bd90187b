"""Open loop: requests come at their due times whatever the server does.
The loop takes every request that is due, up to ``max_batch``, pads the
batch to the next power of two, and serves it; each request is timed from
its due time to its answer on the host. It sleeps when nothing is due; how
late it wakes is the generator's lateness. Mix keys: ``max_batch`` and
``arrivals`` (the process that gives the due times)."""

from __future__ import annotations

import numpy as np

from harness import registry
from harness.traffic import Served, WallClock, Window, bucket

KEYS = ("max_batch", "arrivals")


def shapes(mix: dict) -> list[int]:
    return [1 << i for i in range(mix["max_batch"].bit_length())]


def due_times(mix: dict, seconds: float, seed: int, root=registry.ROOT) -> np.ndarray:
    """The arrival times inside ``[0, seconds)`` of the mix's process."""
    params = mix["arrivals"]
    t = registry.plugin("arrivals", params["process"], root).times(params, seconds, seed)
    return t[t < seconds]


def drive(serve, mix: dict, order: np.ndarray, seconds: float, seed: int,
          root=registry.ROOT, clock=None) -> Window:
    return serve_due(serve, due_times(mix, seconds, seed, root), order, mix["max_batch"], clock)


def serve_due(serve, due: np.ndarray, order: np.ndarray, max_batch: int, clock=None) -> Window:
    """Serve request ``i``, query ``order[i % len(order)]``, due at
    ``due[i]`` seconds after the start: every request due before the
    close."""
    clock = clock or WallClock()
    t0 = clock.now()
    n = len(due)
    done = np.empty(n)
    late = []
    out = []
    i = 0
    while i < n:
        now = clock.now() - t0
        if due[i] > now:
            clock.sleep_until(t0 + due[i])
            now = clock.now() - t0
            late.append(now - due[i])
        j = min(n, i + max_batch, int(np.searchsorted(due, now, side="right")))
        slots = bucket(j - i, max_batch)
        rows = order[np.arange(i, j) % len(order)]
        padded = np.concatenate([rows, np.full(slots - len(rows), rows[-1])])
        start = clock.now()
        dists, ids, counts = serve(padded)
        end = clock.now()
        done[i:j] = end - t0
        m = j - i
        out.append(Served(rows, slots, start, end, dists[:m], ids[:m], counts[:m]))
        i = j
    return Window(t0, t0 + float(done.max()), out, latency_s=done - due,
                  late_s=np.asarray(late))
