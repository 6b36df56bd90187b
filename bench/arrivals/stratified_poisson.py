"""Poisson arrivals with the same gaps for every seed. Keys: ``rate_hz``.

The gaps are the ``rate_hz * seconds`` quantiles of the exponential law,
in an order drawn from the seed. Every seed sends the same number of
requests with the same gaps, so seeds differ only in how the gaps are
ordered, and the trace still ends near ``seconds``."""

from __future__ import annotations

import numpy as np

KEYS = ("rate_hz",)


def times(params: dict, seconds: float, seed: int) -> np.ndarray:
    rate_hz = params["rate_hz"]
    if rate_hz <= 0:
        raise ValueError(f"rate_hz must be positive, got {rate_hz}")
    n = max(1, int(round(rate_hz * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate_hz
    return np.cumsum(np.random.default_rng(seed).permutation(gaps))
