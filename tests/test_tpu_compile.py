"""Compile the main-path Pallas kernels for a TPU v5e, at real widths.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology, and refuses what a v5e would refuse — block shapes off the (8, 128)
tiling, scalar prefetch beyond SMEM, more VMEM than a kernel may use. Sizes are
the paper's service config over SIFT1M's shape: n = 1,000,000 rows, d = 128,
b = 1024 queries, P = L·C = 32·128 = 4096 candidate slots, H = K·L = 384.

The topology is described inside the module fixture (never at import): only
one process at a time may load the TPU library, and the test workers all
import this file.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import alsh_project, gather_rerank, wl1_distance, wl1_topk

N, D, B, P, K = 1_000_000, 128, 1024, 4096, 10
H, M1 = 12 * 32, 33  # K·L hash functions; M+1 levels
CAP = 8192  # delta slots of the two-segment view
HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES, used
    return compiled


CASES = {
    # the f32 probe tail at the config's query batch: ids outgrow SMEM, so
    # the wrapper splits them into bounded calls
    "gather_f32": (
        lambda x, i, q, w: gather_rerank.gather_rerank_topk_pallas(x, i, q, w, K),
        [((N, D), jnp.float32), ((B, P), jnp.int32), ((B, D), jnp.float32),
         ((B, D), jnp.float32)],
    ),
    # two-segment view: sealed table + delta, ids over both
    "gather_two_segment": (
        lambda x, dl, i, q, w: gather_rerank.gather_rerank_topk_pallas(
            x, i, q, w, K, delta=dl),
        [((N, D), jnp.float32), ((CAP, D), jnp.float32), ((B, P + CAP), jnp.int32),
         ((B, D), jnp.float32), ((B, D), jnp.float32)],
    ),
    # int8 rows: the block-coalesced schedule with in-register decode
    "gather_int8_blocked": (
        lambda x, s, i, q, w: gather_rerank.gather_rerank_topk_pallas(
            x, i, q, w, K, scales=s),
        [((N, D), jnp.int8), ((D,), jnp.float32), ((B, P), jnp.int32),
         ((B, D), jnp.float32), ((B, D), jnp.float32)],
    ),
    # the build's hash projection over every row
    "alsh_project": (
        lambda lv, f: alsh_project.alsh_project_pallas(lv, f),
        [((N, D), jnp.int32), ((H, D, M1), jnp.float32)],
    ),
    # a query batch's weighted hash projection
    "alsh_project_query": (
        lambda lv, f, w: alsh_project.alsh_project_pallas(lv, f, w),
        [((B, D), jnp.int32), ((H, D, M1), jnp.float32), ((B, D), jnp.float32)],
    ),
    # the exact scan
    "wl1_scan_topk": (
        lambda x, q, w: wl1_topk.wl1_scan_topk_pallas(x, q, w, K),
        [((N, D), jnp.float32), ((64, D), jnp.float32), ((64, D), jnp.float32)],
    ),
    # the exact scan with its merge counter, as ops.wl1_scan_topk_merge_share runs it
    "wl1_scan_topk_count_merges": (
        lambda x, q, w: wl1_topk.wl1_scan_topk_pallas(x, q, w, K, count_merges=True),
        [((N, D), jnp.float32), ((64, D), jnp.float32), ((64, D), jnp.float32)],
    ),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = CASES[case]
    _compile(fn, *(jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes))


def test_gather_splits_ids_past_the_smem_bound():
    """At b=1024, P=4096 the (b, P) ids are 16 MiB, and v5e has 1 MiB of
    SMEM: each call prefetches at most MAX_PREFETCH_IDS of them."""
    bq, pc = gather_rerank._id_blocks(B, P, gather_rerank.MAX_PREFETCH_IDS, 1)
    assert bq * pc <= gather_rerank.MAX_PREFETCH_IDS
    assert bq % 8 == 0 and pc == P
    bq, pc = gather_rerank._id_blocks(B, P, gather_rerank.MAX_PREFETCH_IDS,
                                      gather_rerank.CBLK)
    assert bq * pc <= gather_rerank.MAX_PREFETCH_IDS and pc % gather_rerank.CBLK == 0


# the name each kernel states with ``pallas_call(name=...)``: the trace
# reduction of the benchmark finds the kernels by these names
PINNED = {
    "gather_f32": "gather_rerank_topk_pallas",
    "gather_two_segment": "gather_rerank_topk_pallas",
    "gather_int8_blocked": "gather_rerank_topk_pallas_blocked",
    "alsh_project": "alsh_project_pallas",
    "alsh_project_query": "alsh_project_pallas",
    "wl1_scan_topk": "wl1_scan_topk_pallas",
    "wl1_scan_topk_count_merges": "wl1_scan_topk_pallas",
    "wl1_distance_scan": "wl1_distance_scan_pallas",
    "wl1_distance_rerank": "wl1_distance_rerank_pallas",
}
NAMED_CASES = {
    **CASES,
    "wl1_distance_scan": (
        lambda x, q, w: wl1_distance.wl1_scan_pallas(x, q, w),
        [((65536, D), jnp.float32), ((64, D), jnp.float32), ((64, D), jnp.float32)],
    ),
    "wl1_distance_rerank": (
        lambda p, q, w: wl1_distance.wl1_rerank_pallas(p, q, w),
        [((64, 512, D), jnp.float32), ((64, D), jnp.float32), ((64, D), jnp.float32)],
    ),
}


@pytest.mark.parametrize("case", sorted(PINNED))
def test_kernel_carries_its_pinned_name(one_chip, case):
    fn, shapes = NAMED_CASES[case]
    text = _compile(fn, *(jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                          for s, dt in shapes)).as_text()
    calls = re.findall(r"%(\S+?)(?:\.\d+)? = .*custom-call\(.*tpu_custom_call", text)
    assert calls and set(calls) == {PINNED[case]}, calls
