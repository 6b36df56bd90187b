"""The probe cells of BENCHMARK.json: each reaches the probe path through
a closed loop, on one chip, and reports what its layers are read by."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import reference, registry  # noqa: E402

# cell -> (storage, survivors of the screen; 0 where none runs)
PROBE_CELLS = {"sift1m-f32.batch": ("f32", 0), "bigann4m-int8.batch": ("int8", 20)}


@pytest.mark.parametrize("workload", sorted(PROBE_CELLS))
def test_probe_cell_reports_its_metrics(workload):
    cell = registry.cell(workload)
    assert {m["name"] for m in cell.end_to_end} == {"qps", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "gather_rerank_roofline", "xla_ops_ms", "idle_share.batch"}
    assert all(callable(registry.reader(m["name"])) for m in cell.per_layer)
    assert cell.chips == 1 and cell.mix["loop"] == "closed"
    assert cell.spec["mode"] == "probe" and cell.mix["batch"] == cell.config["query_batch"]


@pytest.mark.parametrize("workload", sorted(PROBE_CELLS))
def test_probe_cell_runs_its_tail(workload):
    """f32 storage reranks every candidate; int8 screens by code distance
    and reranks ceil(k * alpha) survivors."""
    cell = registry.cell(workload)
    storage, keep = PROBE_CELLS[workload]
    g = reference.Geometry.from_config(cell.config, cell.spec)
    assert (g.storage, g.keep) == (storage, keep)
    assert "probe" in cell.config["limits"]


def test_cut_deployment_names_its_cut():
    """A configuration cut in scale lists each cut key, with the source's
    value under ``published``."""
    for entry in registry.load()["configs"]:
        cfg = json.loads((registry.ROOT / entry["file"]).read_text())
        assert cfg["name"] == entry["name"]
        assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
        for key in entry["reduced"]:
            assert cfg["published"][key] != cfg[key], key
