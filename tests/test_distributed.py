"""Multi-device CPU tests (8 fake host devices via subprocess — the main
pytest process must keep seeing 1 device).

Covers: shard_map distributed ALSH query + hierarchical top-k merge matching
the global brute force, and train-step sharding on a real (2,2,2) mesh.
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-3000:]}"
    return out.stdout


def test_sharded_alsh_matches_global_bruteforce():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import IndexConfig, BoundedSpace
        from repro.core.distributed import sharded_query
        from repro.distance import brute_force_nn

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        n, d, M, k = 4096, 12, 16, 10
        key = jax.random.PRNGKey(0)
        data = jax.random.uniform(key, (n, d))
        cfg = IndexConfig(d=d, M=M, K=10, L=24, family="theta",
                          max_candidates=128, space=BoundedSpace(0., 1., float(M)))
        q = jax.random.uniform(jax.random.fold_in(key, 1), (8, d))
        w = jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), (8, d))) + 0.2
        res = sharded_query(jax.random.fold_in(key, 3), data, q, w, cfg, mesh, k=k)
        bf_d, bf_i = brute_force_nn(data, q, w, k=k)
        recall = np.mean([len(set(np.asarray(res.ids[i])) & set(np.asarray(bf_i[i]))) / k
                          for i in range(8)])
        # distances of returned ids must be exact
        for i in range(8):
            for j in range(k):
                pid = int(res.ids[i, j])
                if pid < 0: continue
                want = float(jnp.sum(w[i] * jnp.abs(data[pid] - q[i])))
                got = float(res.dists[i, j])
                assert abs(got - want) < 1e-3, (got, want)
        print("RECALL", recall)
        assert recall >= 0.5, recall

        # hierarchical merge == flat merge (same answer, fewer cross-pod bytes)
        res_flat = sharded_query(jax.random.fold_in(key, 3), data, q, w, cfg, mesh,
                                 k=k, merge_hierarchical=False)
        np.testing.assert_allclose(np.sort(np.asarray(res.dists), -1),
                                   np.sort(np.asarray(res_flat.dists), -1), atol=1e-4)
        print("OK")
    """)
    assert "OK" in out


def test_facade_shard_prebuilt_matches_oneshot():
    """Index.shard builds shard-local indexes ONCE; its query() must be
    bit-identical to the one-shot sharded_query path (same key/cfg) and its
    exact mode must reproduce the global brute force."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.api import Index, IndexConfig, QuerySpec, BoundedSpace
        from repro.core.distributed import sharded_query
        from repro.distance import brute_force_nn
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        n, d, M, k = 2048, 12, 16, 5
        key = jax.random.PRNGKey(0)
        data = jax.random.uniform(key, (n, d))
        cfg = IndexConfig(d=d, M=M, K=10, L=16, family="theta",
                          max_candidates=128, space=BoundedSpace(0., 1., float(M)))
        q = jax.random.uniform(jax.random.fold_in(key, 1), (8, d))
        w = jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), (8, d))) + 0.2
        bkey = jax.random.fold_in(key, 3)

        sharded = Index.build(bkey, data, cfg).shard(mesh)
        res = sharded.query(q, w, QuerySpec(k=k))

        ds = jax.device_put(data, NamedSharding(mesh, P(tuple(mesh.axis_names), None)))
        ref = sharded_query(bkey, ds, q, w, cfg, mesh, k=k)
        np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ref.ids))
        np.testing.assert_array_equal(np.asarray(res.dists), np.asarray(ref.dists))

        rex = sharded.query(q, w, QuerySpec(k=k, mode="exact"))
        bf_d, _ = brute_force_nn(data, q, w, k=k)
        np.testing.assert_allclose(np.asarray(rex.dists), np.asarray(bf_d), atol=1e-4)
        print("OK")
    """)
    assert "OK" in out


def test_mutable_lifecycle_save_load_shard_parity():
    """The full lifecycle × persistence × distribution matrix: a mutated
    index (non-empty delta + tombstones) round-trips through save/load,
    re-shards from the persisted build_key (which must reproduce the DELTA
    hashes too), serves bit-identical queries sharded vs single-host with
    the same global ids, and keeps serving inserts/deletes sharded."""
    out = _run("""
        import tempfile
        import jax, jax.numpy as jnp, numpy as np
        from repro.api import Index, IndexConfig, QuerySpec, UpdateSpec, BoundedSpace

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        n, d, k = 512, 8, 7
        key = jax.random.PRNGKey(0)
        data = jax.random.uniform(jax.random.fold_in(key, 0), (n, d))
        extra = jax.random.uniform(jax.random.fold_in(key, 1), (37, d))
        q = jax.random.uniform(jax.random.fold_in(key, 2), (5, d))
        w = jnp.abs(jax.random.normal(jax.random.fold_in(key, 3), (5, d))) + 0.2
        cfg = IndexConfig(d=d, M=8, K=6, L=10, family="theta",
                          max_candidates=n + 64, space=BoundedSpace(0., 1., 8.))

        local = Index.build(jax.random.fold_in(key, 9), data, cfg,
                            update=UpdateSpec(delta_capacity=64))
        local, ids = local.insert(extra)
        local = local.delete(jnp.asarray([3, 77, int(ids[4])], jnp.int32))

        with tempfile.TemporaryDirectory() as td:
            local.save(td)
            restored = Index.load(td)
        sharded = restored.shard(mesh)  # replays the delta through the
                                        # re-derived tables (same build_key)
        r_l = local.query(q, w, QuerySpec(k=k))
        r_s = sharded.query(q, w, QuerySpec(k=k))
        np.testing.assert_array_equal(np.asarray(r_l.ids), np.asarray(r_s.ids))
        np.testing.assert_array_equal(np.asarray(r_l.dists), np.asarray(r_s.dists))
        np.testing.assert_array_equal(np.asarray(r_l.n_candidates),
                                      np.asarray(r_s.n_candidates))

        # lifecycle continues sharded, in lockstep with single-host
        local2, ids_l = local.insert(extra[:11])
        sharded2, ids_s = sharded.insert(extra[:11])
        np.testing.assert_array_equal(np.asarray(ids_l), np.asarray(ids_s))
        dels = jnp.asarray([int(ids_l[0]), 42], jnp.int32)
        local2, sharded2 = local2.delete(dels), sharded2.delete(dels)
        for mode in ("probe", "multiprobe", "exact"):
            a = local2.query(q, w, QuerySpec(k=k, mode=mode))
            b = sharded2.query(q, w, QuerySpec(k=k, mode=mode))
            np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
            np.testing.assert_array_equal(np.asarray(a.dists), np.asarray(b.dists))
        assert not np.isin(np.asarray(dels), np.asarray(b.ids)).any()

        # sharded compact == single-host compact, bit for bit
        ca, cb = local2.compact(), sharded2.compact()
        for la, lb in zip(jax.tree_util.tree_leaves(ca.state),
                          jax.tree_util.tree_leaves(cb.state)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        print("OK")
    """)
    assert "OK" in out


def test_train_step_on_small_production_mesh():
    out = _run("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_bundle, reduced_model
        from repro.launch import specs
        from repro.models.sharding import use_mesh, sanitize_spec_tree
        from repro.runtime.train_step import (init_train_state, make_train_step,
                                              train_state_specs, batch_pytree_specs)

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        bundle = get_bundle("qwen3-8b")
        mcfg = dataclasses.replace(reduced_model(bundle.model), n_units=2, n_layers=2,
                                   n_heads=4, n_kv_heads=2, d_model=64)
        tcfg = bundle.train
        with use_mesh(mesh):
            state = init_train_state(jax.random.PRNGKey(0), mcfg, tcfg)
            batch = specs.train_batch(mcfg, 8, 32, concrete=True)
            sspec = sanitize_spec_tree(train_state_specs(mcfg, tcfg), state, mesh)
            bspec = sanitize_spec_tree(batch_pytree_specs(batch), batch, mesh)
            to_sh = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                           is_leaf=lambda s: isinstance(s, P))
            state = jax.device_put(state, to_sh(sspec))
            batch = jax.device_put(batch, to_sh(bspec))
            step = jax.jit(make_train_step(mcfg, tcfg),
                           in_shardings=(to_sh(sspec), to_sh(bspec)),
                           out_shardings=(to_sh(sspec), None))
            new_state, metrics = step(state, batch)
            loss1 = float(metrics["loss"])
            assert np.isfinite(loss1)

            # distributed result == single-device result
        state1 = init_train_state(jax.random.PRNGKey(0), mcfg, tcfg)
        batch1 = specs.train_batch(mcfg, 8, 32, concrete=True)
        step1 = jax.jit(make_train_step(mcfg, tcfg))
        _, metrics1 = step1(state1, batch1)
        loss_single = float(metrics1["loss"])
        print("LOSSES", loss1, loss_single)
        assert abs(loss1 - loss_single) < 5e-3, (loss1, loss_single)
        print("OK")
    """)
    assert "OK" in out


def test_decode_step_on_small_mesh():
    out = _run("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import models
        from repro.configs import get_bundle, reduced_model
        from repro.models.sharding import use_mesh, sanitize_spec_tree
        from repro.runtime.serve_step import make_decode_step

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        mcfg = reduced_model(get_bundle("gemma3-1b").model)
        with use_mesh(mesh):
            params = models.init_params(jax.random.PRNGKey(0), mcfg)
            caches = models.init_caches(8, 64, mcfg)
            pspec = sanitize_spec_tree(models.param_specs(mcfg), params, mesh)
            cspec = sanitize_spec_tree(models.cache_specs(mcfg), caches, mesh)
            to_sh = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                           is_leaf=lambda s: isinstance(s, P))
            params_d = jax.device_put(params, to_sh(pspec))
            caches_d = jax.device_put(caches, to_sh(cspec))
            batch = {"token": jnp.zeros((8,), jnp.int32),
                     "pos": jnp.zeros((8,), jnp.int32)}
            step = jax.jit(make_decode_step(mcfg),
                           in_shardings=(to_sh(pspec), None, to_sh(cspec)),
                           out_shardings=(None, None, to_sh(cspec)))
            logits, tok, new_caches = step(params_d, batch, caches_d)
            assert np.all(np.isfinite(np.asarray(logits)))

        # matches single-device decode
        step1 = jax.jit(make_decode_step(mcfg))
        logits1, _, _ = step1(params, batch, caches)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(logits1),
                                   rtol=2e-3, atol=2e-3)
        print("OK")
    """)
    assert "OK" in out


def test_moe_ep_shardmap_matches_gspmd():
    out = _run("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_bundle, reduced_model
        from repro.models import moe
        from repro.models.sharding import use_mesh, set_policy

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        bundle = get_bundle("llama4-scout-17b-16e")
        mcfg = reduced_model(bundle.model)  # 4 experts, capacity >= T
        key = jax.random.PRNGKey(0)
        params = moe.init_moe(key, mcfg, mcfg.moe, jnp.float32)
        x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, mcfg.d_model))

        ref = moe.moe_ffn_gspmd(params, x, mcfg, mcfg.moe)  # no mesh: plain
        with use_mesh(mesh):
            mcfg_ep = dataclasses.replace(mcfg, moe_impl="ep_shardmap")
            got = moe.moe_ffn_ep_shardmap(params, x, mcfg_ep, mcfg.moe)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

        # grads flow through the shard_map path
        with use_mesh(mesh):
            g = jax.grad(lambda p: jnp.sum(
                moe.moe_ffn_ep_shardmap(p, x, mcfg_ep, mcfg.moe) ** 2))(params)
        for leaf in jax.tree.leaves(g):
            assert np.all(np.isfinite(np.asarray(leaf)))
        print("OK")
    """)
    assert "OK" in out


def test_moe_a2a_shardmap_matches_gspmd():
    out = _run("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_bundle, reduced_model
        from repro.models import moe
        from repro.models.sharding import use_mesh, set_policy

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        bundle = get_bundle("llama4-scout-17b-16e")
        mcfg = reduced_model(bundle.model)  # 4 experts, capacity >= T (no drops)
        key = jax.random.PRNGKey(0)
        params = moe.init_moe(key, mcfg, mcfg.moe, jnp.float32)
        x = jax.random.normal(jax.random.fold_in(key, 1), (8, 16, mcfg.d_model))

        ref = moe.moe_ffn_gspmd(params, x, mcfg, mcfg.moe)
        mcfg_a2a = dataclasses.replace(mcfg, moe_impl="a2a_shardmap",
                                       dp_over_model=True)
        try:
            set_policy(dp_over_model=True)
            with use_mesh(mesh):
                got = moe.moe_ffn_a2a_shardmap(params, x, mcfg_a2a, mcfg.moe)
                g = jax.grad(lambda p: jnp.sum(
                    moe.moe_ffn_a2a_shardmap(p, x, mcfg_a2a, mcfg.moe) ** 2))(params)
        finally:
            set_policy()
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        for leaf in jax.tree.leaves(g):
            assert np.all(np.isfinite(np.asarray(leaf)))
        print("OK")
    """)
    assert "OK" in out
