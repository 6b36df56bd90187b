"""Systems a run can drive: the program under test, and the control.

Each is made from a configuration and the ``QuerySpec`` fields of its
queries, and has ``build(key, rows)`` (returns the build's run time, compile
excluded), ``warm(batches, q, w)``, ``serve(q, w)`` (host answers of one
batch: dists, ids, candidate counts) and ``free()``.
"""

from __future__ import annotations

import time

import numpy as np

from harness import reference


def index_config(cfg: dict):
    """The program's ``IndexConfig`` of a configuration file."""
    from repro.core.index import IndexConfig
    from repro.core.transforms import BoundedSpace

    ix = cfg["index"]
    space = ix["space"]
    return IndexConfig(
        d=ix["d"], M=ix["M"], K=ix["K"], L=ix["L"], family=ix["family"], W=ix["W"],
        max_candidates=ix["max_candidates"],
        space=BoundedSpace(space["lo"], space["hi"], space["t"]), storage=ix["storage"],
    )


class Program:
    """``repro.api.Index``: built by one compiled call of ``Index.build``,
    queried through ``Index.query`` with host arrays, as a client would."""

    def __init__(self, cfg: dict, spec: dict):
        from repro.api import QuerySpec

        self.icfg = index_config(cfg)
        self.spec = QuerySpec(**spec)
        self.index = None

    def build(self, key, rows) -> float:
        import jax

        from repro.api import Index

        icfg = self.icfg
        compiled = jax.jit(lambda k, x: Index.build(k, x, icfg)).lower(key, rows).compile()
        t0 = time.perf_counter()
        self.index = jax.block_until_ready(compiled(key, rows))
        return time.perf_counter() - t0

    def serve(self, q: np.ndarray, w: np.ndarray):
        res = self.index.query(q, w, self.spec)
        return np.asarray(res.dists), np.asarray(res.ids), np.asarray(res.n_candidates)

    def warm(self, batches, q: np.ndarray, w: np.ndarray) -> None:
        for b in batches:
            self.serve(q[:b], w[:b])

    def free(self) -> None:
        self.index = None


class Control:
    """The reference in the program's place, one precision step down:
    bfloat16 distances for float32 storage, 4-bit codes for int8 storage,
    a bfloat16 scan for the exact mode."""

    def __init__(self, cfg: dict, spec: dict):
        self.g = reference.Geometry.from_config(cfg, spec)
        self.mode = spec["mode"]
        self.ref = None

    def build(self, key, rows) -> float:
        import jax

        t0 = time.perf_counter()
        if self.mode == "exact":
            self.rows = rows
        else:
            low = self.g.storage != "f32"
            self.ref = reference.RefIndex.build(
                key, rows, self.g, precision="f32" if low else "bf16",
                codec="int4" if low else None)
            jax.block_until_ready(self.ref.sorted_keys)
        return time.perf_counter() - t0

    def serve(self, q: np.ndarray, w: np.ndarray):
        if self.mode == "exact":
            d_, i_, _ = reference.brute_force(self.rows, q, w, self.g.k, precision="bf16")
            counts = np.full(len(q), self.rows.shape[0], np.int32)
        else:
            d_, i_, counts = self.ref.query(q, w)
        return np.asarray(d_), np.asarray(i_), np.asarray(counts)

    def warm(self, batches, q, w) -> None:
        for b in batches:
            self.serve(q[:b], w[:b])

    def free(self) -> None:
        self.ref = None

