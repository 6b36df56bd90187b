"""Find a cell's configuration, traffic mix and the code they name, by name.

``BENCHMARK.json`` at the root of the checkout names them. A configuration
is the JSON file its entry names; a traffic mix ``<mix>`` is
``bench/mixes/<mix>.json``. The mix names the rest by name, and each name
is a file:

* ``"loop": <loop>`` is ``bench/loops/<loop>.py``: how requests reach
  the system (``KEYS``, the mix keys it reads; ``shapes(mix)``, every batch
  size it sends; ``drive(...)``, the window);
* ``"arrivals": {"process": <process>, ...}`` is
  ``bench/arrivals/<process>.py`` (``KEYS``; ``times(params, seconds,
  seed)``);
* ``"spec"`` is the query's ``QuerySpec`` fields, over the
  configuration's ``query`` group;
* a per-layer metric ``<metric>`` is read by ``bench/metrics/<metric>.py``,
  whose ``read(ctx)`` returns a number or None where it finds nothing to
  read.

A mix key that nothing reads is an error, so a mix cannot claim what the
run does not do. Adding any of these is adding files and entries: nothing
here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
MIX_KEYS = {"loop", "pool", "spec", "why"}


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list  # metric entries of BENCHMARK.json that this cell reports
    per_layer: list

    @property
    def spec(self) -> dict:
        """The ``QuerySpec`` fields of the cell's queries."""
        return {**self.config["query"], **self.mix["spec"]}


def load(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def plugin(kind: str, name: str, root: Path = ROOT):
    """The module ``bench/<kind>/<name>.py``."""
    path = root / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {kind} file {path.relative_to(root)}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def check_mix(mix: dict, root: Path = ROOT) -> None:
    """Raise on a mix key that no part of the run reads."""
    if "mode" not in mix.get("spec", {}):
        raise ValueError("a mix states its spec's mode")
    unknown = set(mix) - MIX_KEYS - set(plugin("loops", mix["loop"], root).KEYS)
    if "arrivals" in mix:
        params = mix["arrivals"]
        proc = plugin("arrivals", params["process"], root)
        unknown |= {f"arrivals.{k}" for k in set(params) - {"process"} - set(proc.KEYS)}
    if unknown:
        raise ValueError(f"mix keys that nothing reads: {sorted(unknown)}")


def cell(name: str, root: Path = ROOT) -> Cell:
    bench = load(root)
    entry = {w["name"]: w for w in bench["workloads"]}.get(name)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    mix = json.loads((root / "bench" / "mixes" / f"{entry['traffic']}.json").read_text())
    check_mix(mix, root)
    return Cell(
        name=name,
        chips=entry["chips"],
        config=config,
        mix=mix,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
    )


def reader(metric: str, root: Path = ROOT):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    return plugin("metrics", metric, root).read
