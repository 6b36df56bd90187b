"""Unit tests: optimizer math, data pipeline determinism, sharding sanitizer."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_bundle
from repro.configs.base import TrainConfig
from repro.data.pipeline import DataConfig, SyntheticStream
from repro.launch.mesh import make_mesh
from repro.models.sharding import sanitize_spec
from repro.optim import (
    adamw_update,
    clip_by_global_norm,
    compress_grads,
    decompress_accumulate,
    init_opt_state,
    lr_schedule,
)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------


def test_adamw_decreases_quadratic():
    tcfg = TrainConfig(learning_rate=0.1, warmup_steps=0, total_steps=100,
                       weight_decay=0.0, grad_clip=1e9)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = init_opt_state(params, tcfg)
    for _ in range(60):
        grads = {"w": 2 * params["w"]}  # d/dw ||w||^2
        params, state, _ = adamw_update(params, grads, state, tcfg)
    assert float(jnp.linalg.norm(params["w"])) < 0.3


def test_weight_decay_shrinks_params():
    tcfg = TrainConfig(learning_rate=0.1, warmup_steps=0, weight_decay=0.5,
                       grad_clip=1e9)
    params = {"w": jnp.asarray([1.0])}
    state = init_opt_state(params, tcfg)
    zero_grads = {"w": jnp.zeros(1)}
    new_params, *_ = adamw_update(params, zero_grads, state, tcfg)
    assert float(new_params["w"][0]) < 1.0


def test_clip_by_global_norm():
    grads = {"a": jnp.asarray([3.0, 4.0])}  # norm 5
    clipped, gn = clip_by_global_norm(grads, 1.0)
    np.testing.assert_allclose(float(gn), 5.0, rtol=1e-5)
    np.testing.assert_allclose(
        float(jnp.linalg.norm(clipped["a"])), 1.0, rtol=1e-5
    )


def test_lr_schedule_shape():
    tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=10, total_steps=100)
    lrs = [float(lr_schedule(jnp.asarray(s), tcfg)) for s in range(0, 101, 10)]
    assert lrs[0] < lrs[1]  # warmup rises
    assert lrs[-1] < lrs[2]  # cosine decays
    assert all(l >= 0 for l in lrs)


def test_int8_ef_compression_error_feedback_converges():
    """With error feedback, quantization error doesn't accumulate: the sum of
    decompressed grads over steps tracks the true sum."""
    g = jnp.asarray([0.001, -0.003, 0.5])
    ef = jnp.zeros(3)
    acc = jnp.zeros(3)
    for step in range(50):
        comp, ef = compress_grads(g, "int8_ef", ef)
        acc = decompress_accumulate(acc, comp, "int8_ef")
    # EF keeps the residual bounded (error does NOT grow with steps): the
    # accumulated sum tracks the true sum within one quantum per element.
    np.testing.assert_allclose(np.asarray(acc), np.asarray(g) * 50, rtol=0.05)
    assert float(jnp.max(jnp.abs(ef))) < 0.5 / 127.0 + 1e-6  # one quantum


def test_bf16_compression_halves_bytes():
    g = {"w": jnp.ones((128,), jnp.float32)}
    comp, _ = compress_grads(g, "bf16", None)
    assert comp["w"].dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------


def test_stream_deterministic_and_step_pure():
    mcfg = get_bundle("qwen3-8b").model
    dcfg = DataConfig(seq_len=64, global_batch=4, seed=9)
    s1 = SyntheticStream(dcfg, mcfg)
    s2 = SyntheticStream(dcfg, mcfg)
    for step in (0, 5, 1000):
        np.testing.assert_array_equal(s1.batch(step)["tokens"], s2.batch(step)["tokens"])
    assert not np.array_equal(s1.batch(0)["tokens"], s1.batch(1)["tokens"])


def test_stream_shards_disjoint_rng():
    mcfg = get_bundle("qwen3-8b").model
    a = SyntheticStream(DataConfig(seq_len=64, global_batch=8, n_shards=2, shard_id=0), mcfg)
    b = SyntheticStream(DataConfig(seq_len=64, global_batch=8, n_shards=2, shard_id=1), mcfg)
    assert a.local_batch == 4
    assert not np.array_equal(a.batch(3)["tokens"], b.batch(3)["tokens"])


def test_stream_modalities():
    audio = get_bundle("hubert-xlarge").model
    vlm = get_bundle("qwen2-vl-2b").model
    sa = SyntheticStream(DataConfig(seq_len=32, global_batch=2), audio).batch(0)
    assert sa["frames"].shape == (2, 32, audio.frontend_dim)
    assert sa["targets"].max() < audio.vocab_size
    sv = SyntheticStream(DataConfig(seq_len=32, global_batch=2), vlm).batch(0)
    nv = min(vlm.n_vision_tokens, 16)
    assert sv["tokens"].shape == (2, 32 - nv)
    assert sv["positions"].shape == (3, 2, 32)


# ---------------------------------------------------------------------------
# sharding sanitizer
# ---------------------------------------------------------------------------


def test_sanitize_spec_divisibility():
    mesh = make_mesh((1, 1), ("data", "model"))  # single device: sizes 1
    s = sanitize_spec(P("data", "model"), (8, 8), mesh)
    assert s == P("data", "model")  # size-1 axes always divide


def test_sanitize_spec_drops_nondivisible():
    import subprocess, sys, os, textwrap
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(repo, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent("""
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.models.sharding import sanitize_spec
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        # dim 8 % 4 == 0 keeps "model"; dim 3 % 2 != 0 drops "data"
        assert sanitize_spec(P("data", "model"), (3, 8), mesh) == P(None, "model")
        # tuple degrades greedily: ("pod","data") -> prefix that divides
        mesh2 = make_mesh((2, 4), ("pod", "data"))
        assert sanitize_spec(P(("pod", "data")), (2,), mesh2) == P(("pod",))
        assert sanitize_spec(P(("pod", "data")), (8,), mesh2) == P(("pod", "data"))
        # unknown axis names dropped
        assert sanitize_spec(P("nope"), (8,), mesh2) == P(None)
        print("OK")
    """)], capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
