"""The seeded generators: pure functions of the seed, with neighbour
structure that uniform rows lack."""

import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import data  # noqa: E402

GEN = dict(clusters=100, latent_dim=16, cluster_std=0.5, noise_std=0.1, scale=0.15,
           w_lo=0.1, w_hi=1.0)


def test_same_seed_same_inputs_and_streams_differ():
    a = np.asarray(data.corpus(2**33 + 5, 512, 128, GEN))
    b = np.asarray(data.corpus(2**33 + 5, 512, 128, GEN))
    c = np.asarray(data.corpus(5, 512, 128, GEN))
    q = np.asarray(data.queries(2**33 + 5, 512, 128, GEN))
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)  # seeds beyond 32 bits are not truncated
    assert not np.array_equal(a, q)  # queries are held out, not corpus rows
    w1 = np.asarray(data.weights(9, 64, 128, GEN))
    np.testing.assert_array_equal(w1, np.asarray(data.weights(9, 64, 128, GEN)))
    assert w1.min() >= 0.1 and w1.max() <= 1.0


def test_rows_in_box_and_on_levels():
    rows = np.asarray(data.corpus(3, 2048, 128, dict(GEN, levels=256)))
    assert rows.min() >= 0.0 and rows.max() <= 1.0
    np.testing.assert_allclose(rows * 255, np.round(rows * 255), atol=1e-4)


def test_relative_contrast_well_above_uniform():
    n, d = 20000, 128
    rows = np.asarray(data.corpus(11, n, d, GEN))
    q = np.asarray(data.queries(11, 32, d, GEN))
    w = np.asarray(data.weights(11, 32, d, GEN))
    rng = np.random.default_rng(0)
    uniform = data.relative_contrast(rng.random((n, d)), rng.random((32, d)), w)
    clustered = data.relative_contrast(rows, q, w)
    assert uniform < 1.5
    assert clustered > 1.5 * uniform
