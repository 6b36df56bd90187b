"""Loop-aware HLO collective parsing: totals must scale with scan trip count."""

import re

import pytest

from repro.launch.dryrun import parse_collectives, _split_computations, _trip_count

FAKE_HLO = """
HloModule test

%cond.1 (arg: (s32[], f32[8])) -> pred[] {
  %iv = s32[] get-tuple-element(%arg), index=0
  %bound = s32[] constant(12)
  ROOT %lt = pred[] compare(%iv, %bound), direction=LT
}

%body.1 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %x = f32[8]{0} get-tuple-element(%arg), index=1
  %ag = f32[128]{0} all-gather(%x), replica_groups=[16,16]<=[256], dimensions={0}
  %ar = f32[8]{0} all-reduce(%x), replica_groups=[16,16]<=[256], to_apply=%add
  ROOT %t = (s32[], f32[8]) tuple(%iv2, %x)
}

ENTRY %main (p0: f32[8]) -> f32[8] {
  %w = (s32[], f32[8]) while(%init), condition=%cond.1, body=%body.1
  %ag2 = f32[64]{0} all-gather(%p0), replica_groups=[1,256]<=[256], dimensions={0}
  ROOT %out = f32[8] get-tuple-element(%w), index=1
}
"""


def test_split_and_tripcount():
    comps = _split_computations(FAKE_HLO)
    assert "cond.1" in comps and "body.1" in comps and "main" in comps
    assert _trip_count(comps["cond.1"]) == 12


def test_loop_scaled_collectives():
    res = parse_collectives(FAKE_HLO)
    # body: all-gather 128*4 = 512 B * 12 trips; all-reduce 8*4*2 = 64 B * 12
    # entry: all-gather 64*4 = 256 B
    assert res["per_type_bytes"]["all-gather"] == 512 * 12 + 256
    assert res["per_type_bytes"]["all-reduce"] == 64 * 12
    assert res["counts"]["all-gather"] == 13
    assert res["total_bytes"] == 512 * 12 + 256 + 64 * 12


def test_real_module_scales_with_layers():
    """Compile tiny 1-unit vs 4-unit models: parsed collective bytes must
    scale ~4x (each unit all-gathers its FSDP-sharded weights)."""
    import subprocess
    import sys
    import os
    import textwrap

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(repo, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent("""
        import dataclasses, jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_bundle, reduced_model
        from repro.launch import specs
        from repro.launch.dryrun import parse_collectives
        from repro.models.sharding import use_mesh, sanitize_spec_tree
        from repro.runtime.train_step import (init_train_state, make_train_step,
                                              train_state_specs, batch_pytree_specs)

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        vals = {}
        for n_units in (1, 4):
            bundle = get_bundle("qwen3-8b")
            mcfg = dataclasses.replace(reduced_model(bundle.model), n_units=n_units,
                                       n_layers=n_units)
            tcfg = bundle.train
            with use_mesh(mesh):
                state = jax.eval_shape(lambda: init_train_state(
                    jax.random.PRNGKey(0), mcfg, tcfg))
                batch = specs.train_batch(mcfg, 8, 64)
                sspec = sanitize_spec_tree(train_state_specs(mcfg, tcfg), state, mesh)
                bspec = sanitize_spec_tree(batch_pytree_specs(batch), batch, mesh)
                to_sh = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                               is_leaf=lambda s: isinstance(s, P))
                comp = jax.jit(make_train_step(mcfg, tcfg),
                               in_shardings=(to_sh(sspec), to_sh(bspec)),
                               out_shardings=(to_sh(sspec), None)).lower(
                                   state, batch).compile()
            vals[n_units] = parse_collectives(comp.as_text())["total_bytes"]
        ratio = vals[4] / max(vals[1], 1.0)
        print("RATIO", ratio, vals)
        assert 2.0 < ratio < 8.0, (ratio, vals)
        print("OK")
    """)], capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-2500:]}"
    assert "OK" in out.stdout
