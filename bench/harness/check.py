"""How ``correct`` is decided: the window's own answers against the plain
reference, recomputed in float64 on the host.

Two numbers are compared, each with its limit from the configuration:

* ``dist_err``: the largest relative gap between a distance the program
  returned and the float64 distance of the row it named;
* ``miss_share``: the share of checked answers whose k rows are not the
  reference's k rows. Two rows at exactly the same float64 distance are
  interchangeable, so the lists are compared as multisets of float64
  distances. A missing slot (id -1) where the reference has a row is a
  miss.

The checked queries are those of the pool's first ``check_queries`` that
the window answered; every answer the window gave them is compared, so a
query answered several times is checked each time.
"""

from __future__ import annotations

import numpy as np

NUMBERS = ("dist_err", "miss_share")


def answers_for(window, n_check: int):
    """(queries, dists, ids) of every answer in the window to a query of
    the pool below ``n_check``."""
    rows, dists, ids = [], [], []
    for b in window.batches:
        sel = b.rows < n_check
        rows.append(b.rows[sel])
        dists.append(b.dists[sel])
        ids.append(b.ids[sel])
    return np.concatenate(rows), np.concatenate(dists), np.concatenate(ids)


def f64_dist(rows64: np.ndarray, q64: np.ndarray, w64: np.ndarray) -> np.ndarray:
    """rows (..., k, d), q and w (..., d) -> (..., k) sum w |row - q|."""
    return np.einsum("...kd,...d->...k", np.abs(rows64 - q64[..., None, :]), w64)


def compare(prog_d, prog_i, ref_i, q64, w64, rows_of) -> dict:
    """Both numbers over A answers.

    prog_d, prog_i: (A, k) the program's answers; ref_i: (A, k) the
    reference's ids for the same queries; q64, w64: (A, d) the queries;
    ``rows_of(ids) -> (m, d) float64`` gives the stored rows the reference
    reads (decoded where the storage is quantized)."""
    prog_i = np.asarray(prog_i)
    ref_i = np.asarray(ref_i)
    if prog_i.shape[0] == 0:
        return {"dist_err": float("inf"), "miss_share": 1.0}
    uniq, inv = np.unique(np.concatenate([prog_i.ravel(), ref_i.ravel()]), return_inverse=True)
    table = rows_of(np.maximum(uniq, 0))
    inv = inv.reshape(2, *prog_i.shape)
    d_prog = f64_dist(table[inv[0]], q64, w64)
    d_ref = f64_dist(table[inv[1]], q64, w64)
    d_prog = np.where(prog_i >= 0, d_prog, np.inf)
    d_ref = np.where(ref_i >= 0, d_ref, np.inf)
    ok = prog_i >= 0
    gap = np.asarray(prog_d, np.float64) - np.where(ok, d_prog, 0.0)
    rel = np.abs(np.where(ok, gap, 0.0)) / np.maximum(np.where(ok, d_prog, 1.0), 1e-30)
    dist_err = float(np.max(np.where(ok, rel, 0.0)))
    miss = np.any(np.sort(d_prog, axis=1) != np.sort(d_ref, axis=1), axis=1)
    return {"dist_err": dist_err, "miss_share": float(np.mean(miss))}


def recall(prog_i, truth_i) -> float:
    """Mean share of each answer's true k nearest that it returned."""
    prog_i, truth_i = np.asarray(prog_i), np.asarray(truth_i)
    k = truth_i.shape[1]
    hits = [len(set(p[p >= 0].tolist()) & set(t.tolist())) for p, t in zip(prog_i, truth_i)]
    return float(np.sum(hits) / (k * len(hits)))


def verdict(numbers: dict, limits: dict) -> bool:
    """Every compared number at or under its limit."""
    return all(numbers[name] <= limits[name] for name in NUMBERS)


def lines(numbers: dict, limits: dict) -> list[str]:
    return [f"check {name} {numbers[name]!r} limit {limits[name]!r}" for name in NUMBERS]
