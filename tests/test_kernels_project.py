"""Pallas alsh_project kernel vs ref oracle: shape/dtype sweeps (interpret=True)."""

import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.alsh_project import alsh_project_pallas

SHAPES = [
    (1, 1, 1, 1),  # degenerate minimum
    (7, 5, 3, 4),  # everything sub-block
    (128, 64, 128, 32),  # exact block multiples
    (130, 65, 129, 32),  # off-by-one over blocks
    (64, 200, 17, 9),  # d > BD (multi-step reduction)
    (256, 33, 1024, 5),  # many hashes
]


@pytest.mark.parametrize("n,d,H,M", SHAPES)
@pytest.mark.parametrize("weighted", [False, True])
def test_project_matches_ref(n, d, H, M, weighted):
    key = jax.random.PRNGKey(n * 1000 + d * 100 + H + M)
    k1, k2, k3 = jax.random.split(key, 3)
    levels = jax.random.randint(k1, (n, d), 0, M + 1)
    folded = jax.random.normal(k2, (H, d, M + 1), jnp.float32)
    weights = jax.random.normal(k3, (n, d), jnp.float32) if weighted else None
    got = alsh_project_pallas(levels, folded, weights, interpret=True)
    want = ref.alsh_project(levels, folded, weights)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("table_dtype", [jnp.float32, jnp.bfloat16])
def test_project_dtypes(table_dtype):
    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    levels = jax.random.randint(k1, (32, 16), 0, 9)
    folded = jax.random.normal(k2, (8, 16, 9), jnp.float32).astype(table_dtype)
    got = alsh_project_pallas(levels, folded, None, interpret=True)
    want = ref.alsh_project(levels, folded.astype(jnp.float32), None)
    tol = 1e-4 if table_dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)
    assert got.dtype == jnp.float32  # accumulation stays f32


@hypothesis.settings(max_examples=15, deadline=None)
@hypothesis.given(
    n=st.integers(1, 40),
    d=st.integers(1, 48),
    H=st.integers(1, 24),
    M=st.integers(1, 12),
    seed=st.integers(0, 2**31 - 1),
)
def test_project_property_random_shapes(n, d, H, M, seed):
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    levels = jax.random.randint(k1, (n, d), 0, M + 1)
    folded = jax.random.normal(k2, (H, d, M + 1), jnp.float32)
    weights = jax.random.normal(k3, (n, d), jnp.float32)
    got = alsh_project_pallas(levels, folded, weights, interpret=True)
    want = ref.alsh_project(levels, folded, weights)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-4, atol=3e-4)


def _dot_precisions(jaxpr) -> list:
    """The ``precision`` of every dot_general in ``jaxpr`` and the jaxprs
    nested in its equations (the pallas_call's kernel body among them)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn.params["precision"])
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                found += _dot_precisions(inner)
    return found


@pytest.mark.parametrize("weighted", [False, True])
def test_kernel_contracts_at_highest_precision(weighted):
    """The kernel's matmul asks for float32 on the MXU: at the default
    precision a TPU rounds the float32 tables (and the weighted one-hot) to
    bfloat16, and the keys drift from a float32 hash of the same rows."""
    levels = jnp.zeros((8, 16), jnp.int32)
    folded = jnp.zeros((4, 16, 5), jnp.float32)
    weights = jnp.ones((8, 16), jnp.float32) if weighted else None
    jaxpr = jax.make_jaxpr(
        lambda lv, f, w: alsh_project_pallas(lv, f, w, interpret=True))(levels, folded, weights)
    precisions = _dot_precisions(jaxpr.jaxpr)
    assert precisions, "no dot_general in the kernel"
    highest = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
    assert all(p == highest for p in precisions), precisions


# The paper's service widths: d=128, M+1=33 levels, H = K·L = 12·32 hashes.
SERVICE_D, SERVICE_M, SERVICE_H = 128, 32, 384
# Bound on |kernel - exact| as a share of sum_i |w_i folded[h, i, l_i]|: a
# float32 sum of 128 terms errs by at most about 128 · 2^-24 ≈ 7.6e-6 of
# that total (about 8e-8 here). Operands rounded to bfloat16 (2^-8 apart)
# err by up to 2^-9 per term: about 1e-4 of the total in the median hash
# and 1e-3 in the worst at these widths. So 1e-5, far under bfloat16's
# 2^-8, tells the two apart.
PROJECT_RTOL = 1e-5


def _exact_projection(levels, folded, weights):
    """float64 ``sum_i w_i folded[h, i, levels_i]`` and the sum of the
    terms' magnitudes, both (n, H)."""
    lv, f = np.asarray(levels), np.asarray(folded, np.float64)
    picked = f[:, np.arange(lv.shape[1])[None, :], lv].transpose(1, 0, 2)  # (n, H, d)
    if weights is not None:
        picked = picked * np.asarray(weights, np.float64)[:, None, :]
    return picked.sum(-1), np.abs(picked).sum(-1)


@pytest.mark.parametrize("weighted", [False, True])
def test_project_is_float32_faithful_at_service_widths(weighted):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2**33 + 5), 3)
    n = 300
    levels = jax.random.randint(k1, (n, SERVICE_D), 0, SERVICE_M + 1)
    folded = jax.random.normal(k2, (SERVICE_H, SERVICE_D, SERVICE_M + 1), jnp.float32)
    weights = (jax.random.uniform(k3, (n, SERVICE_D), jnp.float32, 0.1, 1.0)
               if weighted else None)
    got = np.asarray(alsh_project_pallas(levels, folded, weights, interpret=True), np.float64)
    exact, scale = _exact_projection(levels, folded, weights)
    assert np.max(np.abs(got - exact) / scale) < PROJECT_RTOL
    # the bound is tight enough to catch bfloat16 operands
    w16 = None if weights is None else weights.astype(jnp.bfloat16).astype(jnp.float32)
    rounded = folded.astype(jnp.bfloat16).astype(jnp.float32)
    lossy, _ = _exact_projection(levels, rounded, w16)
    assert np.max(np.abs(lossy - exact) / scale) > PROJECT_RTOL
