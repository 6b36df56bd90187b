"""The plain reference: the index's semantics written out in jnp and NumPy.

It imports nothing of the program. What it shares with the program is the
definition of an index, as the configuration states it:

* rows are cut to the lattice ``floor((x - lo) * t)``, clipped to
  ``[0, M]``;
* the ``K * L`` hashes are sign bits of Gaussian projections of the
  asymmetric transforms (Hu & Li 2021, Eq 5 with the O(d) table of
  section 4.2.3). The Gaussians are the index's own: drawn from its build
  key as ``normal(split(split(key)[0])[0], (K * L, 2 d, M))``;
* a table's key packs its K bits, bit k worth ``2**k``;
* a query's candidates in a table are the first ``max_candidates`` rows,
  in ascending row id, whose key equals the query's; the candidates of all
  tables are merged without duplicates;
* ``int8`` storage keeps ``clip(round(x / s), -127, 127)`` with
  ``s = max |x| / 127`` per dimension and decodes ``code * s``. With a
  screen factor a, the ``ceil(k * a)`` candidates nearest by the distance
  between codes (weights ``w * s``, query ``clip(round(q / s))``) go on to
  the rerank;
* the answer is the k candidates nearest by the weighted l1 distance
  ``sum w |x - q|`` of the decoded rows.

Projections are taken in float32 at ``Precision.HIGHEST``, distances in
float32. The check recomputes every distance it compares in
float64 on the host.

``precision="bf16"`` computes the rerank over bfloat16 rows, queries and
weights (float32 sums), and ``codec="int4"`` stores 15 levels in place of
255: these are the controls, the reference one precision step down.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
CODEC_MAX = {"int8": 127.0, "int4": 7.0}


@dataclasses.dataclass(frozen=True)
class Geometry:
    """The index as a configuration file states it (its ``index`` group),
    and the queries' ``k`` and screen factor."""

    d: int
    M: int
    K: int
    L: int
    lo: float
    hi: float
    t: float
    max_candidates: int
    storage: str
    k: int
    screen_alpha: float = 0.0

    @classmethod
    def from_config(cls, cfg: dict, spec: dict) -> "Geometry":
        """The configuration's index, queried with ``spec`` (its
        ``QuerySpec`` fields)."""
        ix = cfg["index"]
        return cls(
            d=ix["d"], M=ix["M"], K=ix["K"], L=ix["L"], lo=ix["space"]["lo"],
            hi=ix["space"]["hi"], t=ix["space"]["t"], max_candidates=ix["max_candidates"],
            storage=ix["storage"], k=spec["k"], screen_alpha=spec.get("screen_alpha", 0.0),
        )

    @property
    def keep(self) -> int:
        """Survivors of the screen; 0 where no screen runs."""
        if self.storage == "f32" or not self.screen_alpha:
            return 0
        keep = max(self.k, math.ceil(self.k * self.screen_alpha))
        return 0 if keep >= self.L * self.max_candidates else keep


@partial(jax.jit, static_argnames=("g",))
def folded_tables(build_key, g: Geometry) -> jax.Array:
    """(K*L, d, M+1) float32: entry [h, i, m] is the projection of hash h
    onto the part of the transform that coordinate i at level m sets: the
    suffix sum of the first half of the Gaussian row plus the prefix sum of
    the second, in float32."""
    k_tab, _ = jax.random.split(jnp.asarray(build_key, jnp.uint32))
    k_a, _ = jax.random.split(k_tab)
    a = jax.random.normal(k_a, (g.K * g.L, 2 * g.d, g.M), jnp.float32)
    first, second = a[:, : g.d], a[:, g.d:]
    zeros = jnp.zeros((*first.shape[:-1], 1), jnp.float32)
    suffix = jnp.concatenate([jnp.cumsum(first[..., ::-1], axis=-1)[..., ::-1], zeros], axis=-1)
    prefix = jnp.concatenate([zeros, jnp.cumsum(second, axis=-1)], axis=-1)
    return suffix + prefix


def lattice(x, g: Geometry):
    return jnp.clip(jnp.floor((x - g.lo) * g.t).astype(jnp.int32), 0, g.M)


@partial(jax.jit, static_argnames=("g",))
def bucket_keys(x, w, tables, g: Geometry):
    """(B, d) points [with (B, d) weights] -> (B, L) int32 keys."""
    lv = lattice(x, g)
    onehot = (lv[..., None] == jnp.arange(g.M + 1)).astype(jnp.float32)
    if w is not None:
        onehot = onehot * w[..., None]
    proj = jnp.einsum("bim,him->bh", onehot, tables, precision=HIGHEST)
    bits = (proj >= 0).astype(jnp.int32).reshape(x.shape[0], g.L, g.K)
    return jnp.sum(bits << jnp.arange(g.K, dtype=jnp.int32), axis=-1)


ROW_BLOCK = 8192


@partial(jax.jit, static_argnames=("g",))
def _table_order(rows, tables, g: Geometry):
    n = rows.shape[0]
    pad = -n % ROW_BLOCK
    blocks = jnp.pad(rows, ((0, pad), (0, 0))).reshape(-1, ROW_BLOCK, g.d)
    keys = jax.lax.map(lambda r: bucket_keys(r, None, tables, g), blocks)
    keys = keys.reshape(-1, g.L)[:n].T  # (L, n)
    ids = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), keys.shape)
    # by key, then by row id
    return jax.lax.sort((keys, ids), dimension=1, num_keys=2)


def encode(rows, codec: str):
    """Symmetric per-dimension codes and scales of a row block."""
    top = CODEC_MAX[codec]
    amax = jnp.max(jnp.abs(rows), axis=0)
    scales = jnp.where(amax > 0, amax / top, 1.0)
    codes = jnp.clip(jnp.round(rows / scales), -top, top)
    return codes, scales


@dataclasses.dataclass
class RefIndex:
    """The reference index over one corpus: per-table (key, id) order plus
    the rows as the configuration stores them."""

    g: Geometry
    tables: jax.Array
    sorted_keys: jax.Array  # (L, n)
    sorted_ids: jax.Array  # (L, n)
    stored: jax.Array  # (n, d) decoded rows the rerank reads
    codes: jax.Array | None  # (n, d) codes the screen reads
    scales: jax.Array | None
    precision: str = "f32"
    codec_top: float = 0.0

    @classmethod
    def build(cls, build_key, rows, g: Geometry, precision: str = "f32", codec: str | None = None):
        tables = folded_tables(build_key, g)
        sorted_keys, sorted_ids = _table_order(rows, tables, g)
        codec = codec or (g.storage if g.storage != "f32" else None)
        codes = scales = None
        stored = rows
        if codec:
            codes, scales = encode(rows, codec)
            stored = codes * scales
        return cls(g, tables, sorted_keys, sorted_ids, stored, codes, scales, precision,
                   CODEC_MAX.get(codec, 0.0))

    def candidates(self, q, w):
        """(S, L*C) candidate ids, duplicates and empty slots set to n,
        and the (S,) count of distinct candidates."""
        return _candidates(self.sorted_keys, self.sorted_ids, self.tables, q, w, self.g)

    def query(self, q, w, block: int = 64):
        """(S, k) distances and ids, and (S,) candidate counts."""
        q = jnp.asarray(q, jnp.float32)
        w = jnp.asarray(w, jnp.float32)
        out = []
        for s in range(0, q.shape[0], block):
            qs, ws = q[s:s + block], w[s:s + block]
            cand, count = self.candidates(qs, ws)
            d_, i_ = _rerank(self.stored, self.codes, self.scales, cand, qs, ws, self.g,
                             self.precision, self.codec_top)
            out.append((d_, i_, count))
        return tuple(jnp.concatenate(parts) for parts in zip(*out))


@partial(jax.jit, static_argnames=("g",))
def _candidates(sorted_keys, sorted_ids, tables, q, w, g: Geometry):
    n = sorted_keys.shape[1]
    qk = bucket_keys(q, w, tables, g)  # (S, L)
    C = g.max_candidates

    def one_table(sk, sid, key):  # (n,), (n,), (S,)
        lo = jnp.searchsorted(sk, key, side="left")
        hi = jnp.searchsorted(sk, key, side="right")
        pos = lo[:, None] + jnp.arange(C)
        ids = sid[jnp.minimum(pos, n - 1)]
        return jnp.where(pos < hi[:, None], ids, n)

    cand = jax.vmap(one_table, in_axes=(0, 0, 1), out_axes=1)(sorted_keys, sorted_ids, qk)
    cand = jnp.sort(cand.reshape(q.shape[0], -1), axis=1)
    first = jnp.concatenate(
        [jnp.ones((cand.shape[0], 1), bool), cand[:, 1:] != cand[:, :-1]], axis=1)
    cand = jnp.where(first, cand, n)
    return cand, jnp.sum(cand < n, axis=1)


def wl1(rows, q, w, precision: str = "f32"):
    """sum w |rows - q| over the last axis; rows (..., m, d), q and w (..., d)."""
    if precision == "bf16":
        rows, q, w = (x.astype(jnp.bfloat16).astype(jnp.float32) for x in (rows, q, w))
    return jnp.sum(w[..., None, :] * jnp.abs(rows - q[..., None, :]), axis=-1)


def _smallest(dist, ids, k):
    neg, pos = jax.lax.top_k(-dist, k)
    return -neg, jnp.take_along_axis(ids, pos, axis=-1)


@partial(jax.jit, static_argnames=("g", "precision", "top"))
def _rerank(stored, codes, scales, cand, q, w, g: Geometry, precision: str, top: float):
    n = stored.shape[0]
    safe = jnp.minimum(cand, n - 1)
    valid = cand < n
    if g.keep and codes is not None:
        qc = jnp.clip(jnp.round(q / scales), -top, top)
        proxy = wl1(codes[safe], qc, w * scales)
        proxy = jnp.where(valid, proxy, jnp.inf)
        _, cand = _smallest(proxy, cand, g.keep)
        safe = jnp.minimum(cand, n - 1)
        valid = cand < n
    dist = jnp.where(valid, wl1(stored[safe], q, w, precision), jnp.inf)
    d_, i_ = _smallest(dist, cand, g.k)
    return d_, jnp.where(jnp.isfinite(d_), i_, -1)


SCAN_ROWS = 2048


@partial(jax.jit, static_argnames=("k", "precision"))
def _scan_block(rows, q, w, k: int, precision: str):
    """Exact top-k of one query block over all rows, by row chunks, with
    the sum of all distances (for the relative contrast)."""
    n = rows.shape[0]
    pad = -n % SCAN_ROWS
    chunks = jnp.pad(rows, ((0, pad), (0, 0))).reshape(-1, SCAN_ROWS, rows.shape[1])
    S = q.shape[0]

    def step(carry, xs):
        best_d, best_i, total = carry
        c, chunk = xs
        ids = c * SCAN_ROWS + jnp.arange(SCAN_ROWS, dtype=jnp.int32)
        dist = wl1(chunk, q, w, precision)
        dist = jnp.where(ids[None, :] < n, dist, jnp.inf)
        total = total + jnp.sum(jnp.where(jnp.isfinite(dist), dist, 0.0), axis=1)
        d_, i_ = _smallest(
            jnp.concatenate([best_d, dist], axis=1),
            jnp.concatenate([best_i, jnp.broadcast_to(ids, dist.shape)], axis=1), k)
        return (d_, i_, total), None

    init = (jnp.full((S, k), jnp.inf), jnp.full((S, k), -1, jnp.int32), jnp.zeros((S,)))
    (d_, i_, total), _ = jax.lax.scan(
        step, init, (jnp.arange(chunks.shape[0], dtype=jnp.int32), chunks))
    return d_, i_, total / n


def brute_force(rows, q, w, k: int, precision: str = "f32", block: int = 256):
    """Exact (S, k) distances and ids of every query over all rows, and
    (S,) mean distances."""
    q = jnp.asarray(q, jnp.float32)
    w = jnp.asarray(w, jnp.float32)
    out = [_scan_block(rows, q[s:s + block], w[s:s + block], k, precision)
           for s in range(0, q.shape[0], block)]
    return tuple(jnp.concatenate(parts) for parts in zip(*out))
