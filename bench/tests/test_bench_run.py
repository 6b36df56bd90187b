"""Whole runs of the harness on the CPU at a tiny size: the sound program
comes out correct; the control and a broken timed path do not; without a
TPU a run prints nothing and fails. Also: the harness finds each cell's
files by name."""

import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
from harness import registry, systems  # noqa: E402

SEED = 2**33 + 17


# The cells the harness drives at a tiny size: the committed one and the
# probe cells kept out of BENCHMARK.json until the program's projection is
# reproducible (PERF.md, Open questions), so their harness paths stay tested.
CELLS = [("sift1m-f32.batch", "sift1m-f32", "batch"), ("sift1m-f32.served", "sift1m-f32", "served"),
         ("bigann4m-int8.batch", "bigann4m-int8", "batch"), ("sift1m-f32.exact", "sift1m-f32", "exact")]
E2E = {"qps": ["sift1m-f32.batch", "bigann4m-int8.batch", "sift1m-f32.exact"],
       "p99_ms": ["sift1m-f32.served"],
       "recall_at_10": ["sift1m-f32.batch", "bigann4m-int8.batch"],
       "build_s": ["sift1m-f32.batch", "bigann4m-int8.batch"]}
PER_LAYER = {"idle_share.batch": "qps", "idle_share.served": "p99_ms",
             "gather_rerank_roofline": "qps", "xla_ops_ms": "qps",
             "exact_scan_roofline": "qps", "batch_fill.served": "p99_ms"}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A checkout whose cells keep the configurations' widths and index but
    hold 4096 rows, 16 candidates a table and small batches."""
    root = tmp_path_factory.mktemp("tiny")
    for kind in ("metrics", "loops", "arrivals"):
        shutil.copytree(BENCH / kind, root / "bench" / kind)
    (root / "bench" / "configs").mkdir()
    (root / "bench" / "mixes").mkdir()
    bench = registry.load()
    configs = sorted({c for _, c, _ in CELLS})
    for name in configs:
        cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
        cfg["n"] = 4096
        cfg["index"]["max_candidates"] = 16
        cfg["generator"]["clusters"] = 64
        cfg["check_queries"] = 64
        (root / "bench" / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    small = {"batch": dict(batch=32, pool=128), "exact": dict(batch=16, pool=64),
             "served": dict(max_batch=8, pool=64,
                            arrivals={"process": "stratified_poisson", "rate_hz": 200.0})}
    for mix_name, upd in small.items():
        mix = json.loads((BENCH / "mixes" / f"{mix_name}.json").read_text())
        (root / "bench" / "mixes" / f"{mix_name}.json").write_text(json.dumps(dict(mix, **upd)))
    bench["configs"] = [{"name": c, "source": "test", "file": f"bench/configs/{c}.json",
                         "reduced": [], "why": "test"} for c in configs]
    bench["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": 1, "why": "test"}
                          for n, c, t in CELLS]
    bench["end_to_end"] = [{"name": m, "unit": "u", "better": "higher", "bound": 0.01,
                            "source": "host_clock", "workloads": w} for m, w in E2E.items()]
    bench["end_to_end"].append({"name": "setup_s", "unit": "s", "better": "lower",
                                "bound": 0.25, "source": "host_clock"})
    bench["per_layer"] = [{"name": m, "unit": "fraction", "better": "higher",
                           "source": "device_trace", "layer": "x", "moves": moves,
                           "workloads": E2E[moves]} for m, moves in PER_LAYER.items()]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def one_run(root, capsys, workload, trace=0, system=None):
    rc = run.main(["--workload", workload, "--seed", str(SEED), "--seconds", "0.5",
                   "--trace", str(trace)], system=system, require_tpu=False, root=root,
                  cache=None)
    out, err = capsys.readouterr()
    assert rc == 0, err
    result = json.loads(out.strip().splitlines()[-1])
    assert list(result)[-1] == "checks"
    for name, c in result["checks"].items():
        assert f"check {name} {c['value']!r} limit {c['limit']!r}" in err
    return result


@pytest.mark.parametrize("workload", ["sift1m-f32.batch", "sift1m-f32.served",
                                      "bigann4m-int8.batch", "sift1m-f32.exact"])
def test_sound_program_is_correct(tiny, capsys, workload):
    r = one_run(tiny, capsys, workload)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    names = {m["name"] for m in registry.cell(workload, tiny).end_to_end}
    assert set(r["metrics"]) == names and "setup_s" in names
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(r["device"])


def test_seed_makes_the_dataset(tiny, capsys):
    """The corpus, queries and weights come from --seed: the same seed
    gives the same answers, another seed other data."""
    a = one_run(tiny, capsys, "sift1m-f32.batch")
    b = one_run(tiny, capsys, "sift1m-f32.batch")
    rc = run.main(["--workload", "sift1m-f32.batch", "--seed", "3", "--seconds", "0.5"],
                  require_tpu=False, root=tiny, cache=None)
    out, _ = capsys.readouterr()
    c = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and c["correct"] is True
    assert a["checks"] == b["checks"]
    assert a["checks"]["dist_err"] != c["checks"]["dist_err"]


def test_traced_run_reports_per_layer_metrics(tiny, capsys):
    r = one_run(tiny, capsys, "sift1m-f32.served", trace=1)
    assert r["correct"] is True
    assert set(r["metrics"]) == {"batch_fill.served", "idle_share.served"}
    assert 0 < r["metrics"]["batch_fill.served"]["value"] <= 1
    assert {"busy_s", "window_s"} <= set(r["device"])
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("workload", ["sift1m-f32.batch", "bigann4m-int8.batch",
                                      "sift1m-f32.exact"])
def test_control_is_not_correct(tiny, capsys, workload):
    r = one_run(tiny, capsys, workload, system=systems.Control)
    assert r["correct"] is False
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


class AlteredAnswer(systems.Program):
    """Each batch's first answer names another row at the same distance."""

    def serve(self, q, w):
        d, i, c = super().serve(q, w)
        i = i.copy()
        i[0, 0] = (i[0, 0] + 1) % self.index.n
        return d, i, c


class HalfBatch(systems.Program):
    """Answers only the first half of each batch."""

    def serve(self, q, w):
        h = max(1, len(q) // 2)
        d, i, c = super().serve(q[:h], w[:h])
        k = d.shape[1]
        pad = len(q) - h
        return (np.concatenate([d, np.full((pad, k), np.inf, d.dtype)]),
                np.concatenate([i, np.full((pad, k), -1, i.dtype)]),
                np.concatenate([c, np.zeros(pad, c.dtype)]))


@pytest.mark.parametrize("fault", [AlteredAnswer, HalfBatch])
@pytest.mark.parametrize("workload", ["sift1m-f32.batch", "sift1m-f32.exact"])
def test_broken_timed_path_is_not_correct(tiny, capsys, fault, workload):
    r = one_run(tiny, capsys, workload, system=fault)
    assert r["correct"] is False


def test_no_tpu_no_result(tiny, capsys):
    rc = run.main(["--workload", "sift1m-f32.batch", "--seed", "1", "--seconds", "1"],
                  root=tiny, cache=None)
    out, err = capsys.readouterr()
    assert rc != 0 and out == "" and "no TPU" in err


def test_every_cell_found_by_name():
    bench = registry.load()
    for w in bench["workloads"]:
        cell = registry.cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert {"loop", "spec", "pool"} <= set(cell.mix)
        assert cell.config["check_queries"] > 0
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        moved = {m["name"] for m in cell.end_to_end}
        assert all(m["moves"] in moved for m in cell.per_layer)
        for m in cell.per_layer:
            assert callable(registry.reader(m["name"]))
    for c in bench["configs"]:
        cfg = json.loads((registry.ROOT / c["file"]).read_text())
        assert set(c["reduced"]) <= set(cfg)


def test_new_files_are_found_without_code(tiny):
    """A mix and a per-layer metric added as files and entries."""
    root = tiny
    bench = json.loads((root / "BENCHMARK.json").read_text())
    mix = json.loads((root / "bench" / "mixes" / "batch.json").read_text())
    (root / "bench" / "mixes" / "batch64.json").write_text(json.dumps(dict(mix, batch=64)))
    (root / "bench" / "metrics" / "answers_per_batch.py").write_text(
        "def read(ctx):\n    return ctx['window'].queries / len(ctx['window'].batches)\n")
    bench["workloads"].append({"name": "sift1m-f32.batch64", "config": "sift1m-f32",
                               "traffic": "batch64", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "answers_per_batch", "unit": "queries", "better": "higher",
                               "source": "program_counter", "layer": "x", "moves": "qps",
                               "workloads": ["sift1m-f32.batch64"]})
    bench["end_to_end"][0]["workloads"].append("sift1m-f32.batch64")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = registry.cell("sift1m-f32.batch64", root)
    assert cell.mix["batch"] == 64
    assert [m["name"] for m in cell.per_layer] == ["answers_per_batch"]
    window = type("W", (), {"queries": 128, "batches": [0, 1]})()
    assert registry.reader("answers_per_batch", root)({"window": window}) == 64


def test_mix_spec_reaches_index_query(tiny, capsys, monkeypatch):
    """A QuerySpec field that a mix file states is the one the timed path
    queries with."""
    from repro.api import Index

    root = tiny
    mix = json.loads((root / "bench" / "mixes" / "batch.json").read_text())
    mix["spec"] = dict(mix["spec"], early_exit=True, exit_group=64)
    (root / "bench" / "mixes" / "batch-early.json").write_text(json.dumps(mix))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "sift1m-f32.batch-early", "config": "sift1m-f32",
                               "traffic": "batch-early", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    specs = []
    query = Index.query

    def spy(self, q, w, spec=None, *a, **kw):
        specs.append(spec)
        return query(self, q, w, spec, *a, **kw)

    monkeypatch.setattr(Index, "query", spy)
    r = one_run(root, capsys, "sift1m-f32.batch-early")
    assert r["attempted"] > 0 and specs
    assert all(s.early_exit and s.exit_group == 64 and s.k == 10 for s in specs)


@pytest.mark.parametrize("bad", [{"batchsize": 64}, {"spec": {"mode": "probe", "kk": 3}},
                                 {"arrivals": {"process": "stratified_poisson",
                                               "rate_hz": 1.0, "burst": 4}}])
def test_mix_key_nothing_reads_is_an_error(tiny, capsys, bad):
    root = tiny
    base = "served" if "arrivals" in bad else "batch"
    mix = dict(json.loads((root / "bench" / "mixes" / f"{base}.json").read_text()), **bad)
    (root / "bench" / "mixes" / "bad.json").write_text(json.dumps(mix))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "sift1m-f32.bad", "config": "sift1m-f32",
                               "traffic": "bad", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises((ValueError, TypeError)):
        run.main(["--workload", "sift1m-f32.bad", "--seed", "1", "--seconds", "0.5"],
                 require_tpu=False, root=root, cache=None)


def test_new_arrival_process_is_found_without_code(tiny, capsys):
    """An arrival process and a mix that names it, added as files, drive an
    open-loop run."""
    root = tiny
    (root / "bench" / "arrivals" / "even.py").write_text(
        "import numpy as np\nKEYS = ('rate_hz',)\n\n\ndef times(params, seconds, seed):\n"
        "    return np.arange(1, int(params['rate_hz'] * seconds) + 1) / params['rate_hz']\n")
    mix = json.loads((root / "bench" / "mixes" / "served.json").read_text())
    mix["arrivals"] = {"process": "even", "rate_hz": 40.0}
    (root / "bench" / "mixes" / "served-even.json").write_text(json.dumps(mix))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "sift1m-f32.served-even", "config": "sift1m-f32",
                               "traffic": "served-even", "chips": 1, "why": "test"})
    bench["end_to_end"][1]["workloads"].append("sift1m-f32.served-even")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    r = one_run(root, capsys, "sift1m-f32.served-even")
    assert r["correct"] is True and r["attempted"] == 19
