"""Seeded corpus, query and weight generators.

Rows lie near a low-rank latent: ``clusters`` centres in a ``latent_dim``
space, each row a centre plus ``cluster_std`` latent jitter, mapped to
``d`` dimensions by a fixed random basis, plus ``noise_std`` ambient noise,
then scaled by ``scale`` around 0.5 and clipped into the box [0, 1]^d.
With ``levels`` set, every value is rounded to that many levels, as the
uint8 descriptors of BIGANN are. Queries are held-out draws of the same
generator: the same basis and centres, fresh rows. Weights are uniform in
[``w_lo``, ``w_hi``].

Everything is a pure function of the seed. Large seeds (beyond 32 bits)
are folded into the key through NumPy's ``SeedSequence``, so two seeds
never share a key by truncation.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# Streams of one seed: each draw has its own key, so adding a stream never
# changes another.
WORLD, CORPUS, QUERIES, WEIGHTS, BUILD = range(5)


def seed_key(seed: int) -> jax.Array:
    """Raw uint32 key data (2,) of any non-negative whole number."""
    state = np.random.SeedSequence(int(seed)).generate_state(2, dtype=np.uint32)
    return jnp.asarray(state, dtype=jnp.uint32)


def stream_key(seed: int, stream: int) -> jax.Array:
    return jax.random.fold_in(seed_key(seed), stream)


def _world(key, d: int, gen: dict):
    k_basis, k_centres = jax.random.split(key)
    r = gen["latent_dim"]
    basis = jax.random.normal(k_basis, (r, d), jnp.float32) / np.sqrt(r)
    centres = jax.random.normal(k_centres, (gen["clusters"], r), jnp.float32)
    return basis, centres


@partial(jax.jit, static_argnames=("n", "d", "gen_items"))
def _draw(world_key, key, n: int, d: int, gen_items: tuple):
    gen = dict(gen_items)
    basis, centres = _world(world_key, d, gen)
    k_assign, k_latent, k_noise = jax.random.split(key, 3)
    which = jax.random.randint(k_assign, (n,), 0, gen["clusters"])
    latent = centres[which] + gen["cluster_std"] * jax.random.normal(
        k_latent, (n, basis.shape[0]), jnp.float32
    )
    x = latent @ basis + gen["noise_std"] * jax.random.normal(k_noise, (n, d), jnp.float32)
    x = jnp.clip(0.5 + gen["scale"] * x, 0.0, 1.0)
    if gen.get("levels"):
        top = gen["levels"] - 1
        x = jnp.round(x * top) / top
    return x


def _items(gen: dict) -> tuple:
    return tuple(sorted(gen.items()))


def corpus(seed: int, n: int, d: int, gen: dict) -> jax.Array:
    """(n, d) f32 rows on the default device."""
    return _draw(stream_key(seed, WORLD), stream_key(seed, CORPUS), n, d, _items(gen))


def queries(seed: int, n: int, d: int, gen: dict) -> jax.Array:
    """(n, d) f32 held-out queries: same basis and centres as the corpus."""
    return _draw(stream_key(seed, WORLD), stream_key(seed, QUERIES), n, d, _items(gen))


@partial(jax.jit, static_argnames=("n", "d", "lo", "hi"))
def _weights(key, n: int, d: int, lo: float, hi: float):
    return jax.random.uniform(key, (n, d), jnp.float32, minval=lo, maxval=hi)


def weights(seed: int, n: int, d: int, gen: dict) -> jax.Array:
    """(n, d) f32 per-query weights, uniform in [w_lo, w_hi]."""
    return _weights(stream_key(seed, WEIGHTS), n, d, float(gen["w_lo"]), float(gen["w_hi"]))


def relative_contrast(rows: np.ndarray, q: np.ndarray, w: np.ndarray, k: int = 10) -> float:
    """Mean over queries of (mean distance / k-th nearest distance), under
    the weighted l1 distance, in float64. Uniform data in high dimension
    reads near 1; data with neighbour structure reads well above."""
    out = []
    for qi, wi in zip(np.asarray(q, np.float64), np.asarray(w, np.float64)):
        dist = np.abs(np.asarray(rows, np.float64) - qi) @ wi
        out.append(dist.mean() / np.partition(dist, k - 1)[k - 1])
    return float(np.mean(out))
