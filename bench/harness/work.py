"""Peaks of each device, and the work each kernel's call needs.

A roofline share is the least time the chip could take for the work, at
its peak, over the time the kernel took. The gather/rerank and scan
kernels run on the vector unit, for which no peak is published, so their
least time is set by bytes over HBM bandwidth alone.
"""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (per chip).
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud TPU v5e documentation",
    },
}

ITEMSIZE = {"f32": 4, "bf16": 2, "int8": 1}


def peaks(device_kind: str) -> dict:
    """The peak table of a device; an unknown device is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}") from None


def gather_rerank_bytes(
    sum_candidates: int, queries: int, d: int, storage: str, keep: int, k: int
) -> int:
    """Bytes the gather/rerank/top-k calls of a set of batches must move:
    each distinct candidate row once at its stored width, each query's
    vector and weights, the decode scales and the answers. With a screen
    (``keep`` > 0) the screen reads every candidate and the rerank reads
    the ``keep`` survivors of each query again."""
    row = d * ITEMSIZE[storage]
    vectors = queries * d * 4 * 2
    answers = queries * k * 8
    total = sum_candidates * row + vectors + answers
    if keep:
        total += queries * keep * row + vectors + d * 4 + queries * keep * 8
    return int(total)


def scan_bytes(n: int, d: int, queries: int, batches: int, k: int) -> int:
    """Bytes an exact scan must move: the whole table once per batch, plus
    each query's vector, weights and answer."""
    return int(batches * n * d * 4 + queries * (d * 4 * 2 + k * 8))


def scan_ops(n: int, d: int, queries: int) -> int:
    """Vector operations of an exact scan: subtract, absolute value and
    multiply-add per coordinate of each (query, row) pair."""
    return int(3 * queries * n * d)


def roofline_pct(bytes_: float, kernel_s: float, device_kind: str) -> float | None:
    """Share of the bytes bound, in %; nothing when the kernel never ran."""
    if kernel_s <= 0:
        return None
    return 100.0 * bytes_ / peaks(device_kind)["hbm_bytes_per_s"] / kernel_s
