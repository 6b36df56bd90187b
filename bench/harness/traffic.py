"""What the client loops (``bench/loops/``) share: the wall clock, a
served batch, and a window with the arithmetic of the end-to-end metrics
it gives.

Every loop takes a ``clock`` (``now()`` and ``sleep_until(t)``), so the
arithmetic can be checked on a scripted timeline.
"""

from __future__ import annotations

import dataclasses
import math
import time

import numpy as np


def bucket(b: int, max_batch: int) -> int:
    """The power-of-two batch a group of ``b`` requests is padded to."""
    return min(max_batch, 1 << max(0, math.ceil(math.log2(b))))


class WallClock:
    def now(self) -> float:
        return time.perf_counter()

    def sleep_until(self, t: float) -> None:
        left = t - time.perf_counter()
        if left > 0:
            time.sleep(left)


@dataclasses.dataclass
class Served:
    """One served batch: the queries it carried (indices into the pool), how
    many slots it took, and the host's answer (dists, ids, candidate counts)
    to each real query."""

    rows: np.ndarray
    slots: int
    start: float
    end: float
    dists: np.ndarray
    ids: np.ndarray
    counts: np.ndarray


@dataclasses.dataclass
class Window:
    start: float
    end: float
    batches: list
    latency_s: np.ndarray | None = None  # open loop: per request
    late_s: np.ndarray | None = None  # open loop: generator lateness per wake-up

    @property
    def queries(self) -> int:
        return int(sum(len(b.rows) for b in self.batches))

    @property
    def slots(self) -> int:
        return int(sum(b.slots for b in self.batches))

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def qps(self) -> float:
        """Queries answered over the whole window's time."""
        return self.queries / self.seconds

    def p99_ms(self) -> float:
        return tail_ms(self.latency_s, 99)

    def batch_fill(self) -> float:
        """Real queries over padded slots."""
        return self.queries / self.slots


def tail_ms(latency_s: np.ndarray, pct: float) -> float:
    """The ``pct`` percentile of the latencies, in ms (linear
    interpolation between order statistics)."""
    return float(np.percentile(np.asarray(latency_s), pct) * 1e3)
