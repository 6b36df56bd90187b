"""Pipeline parallelism: pipelined forward/backward == sequential reference
(subprocess with 4 fake CPU devices)."""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-3000:]}"
    return out.stdout


def test_pipeline_forward_and_grads_match_sequential():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.runtime.pipeline import pipeline_apply, pipeline_loss

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("pod",))
        P_stages, n_micro, mb, dim = 4, 8, 2, 16
        key = jax.random.PRNGKey(0)
        Ws = jax.random.normal(key, (P_stages, dim, dim)) / dim**0.5
        bs = jax.random.normal(jax.random.fold_in(key, 1), (P_stages, dim)) * 0.1
        params = {"W": Ws, "b": bs}
        x = jax.random.normal(jax.random.fold_in(key, 2), (n_micro, mb, dim))
        tgt = jax.random.normal(jax.random.fold_in(key, 3), (n_micro, mb, dim))

        def stage_fn(p, h):
            return jnp.tanh(h @ p["W"] + p["b"])

        def loss_fn(y, t):
            return jnp.mean((y - t) ** 2)

        # sequential reference
        def seq_forward(params, x):
            h = x
            for s in range(P_stages):
                h = stage_fn(jax.tree.map(lambda q: q[s], params), h)
            return h
        y_ref = jax.vmap(lambda xm: seq_forward(params, xm))(x)
        y_pipe = pipeline_apply(stage_fn, params, x, mesh, "pod")
        np.testing.assert_allclose(np.asarray(y_pipe), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-5)

        # gradients through the pipeline == sequential gradients
        def seq_loss(params):
            y = jax.vmap(lambda xm: seq_forward(params, xm))(x)
            return jnp.mean(jax.vmap(loss_fn)(y, tgt))
        def pipe_loss(params):
            return pipeline_loss(stage_fn, loss_fn, params, x, tgt, mesh, "pod")
        g_ref = jax.grad(seq_loss)(params)
        g_pipe = jax.grad(pipe_loss)(params)
        for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_pipe)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)
        print("OK")
    """)
    assert "OK" in out
