"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload sift1m-f32.batch --seed 7 --seconds 20 --trace 0

A cell ``<config>.<mix>`` of ``BENCHMARK.json`` names a configuration (a
corpus shape, the index and its storage) and a traffic mix. The run makes
the corpus, the queries and their weights on the device from ``--seed``,
builds the index with one compiled call, warms every batch shape the mix
sends, and then drives ``Index.query`` for ``--seconds``. It reads the
device's peak memory, frees the index, and checks the window's answers
against the plain reference (``harness/reference.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device`` and,
last, ``checks``: each compared number with its limit. The same numbers
end standard error. Without a TPU, or with fewer chips than the cell asks
for, it prints no result and exits non-zero.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

NO_DEVICE = 3


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def say(*parts, file=None):
    """A line of the run's account, on standard error unless ``file``."""
    print(*parts, file=file or sys.stderr, flush=True)


class CompileCounter:
    """Counts, in the phase it is in (``setup`` or ``window``), the programs
    JAX traces and builds, and how many of those builds the persistent
    cache served: a build it did not serve is a compile."""

    def __init__(self):
        import jax

        self.phase = "setup"
        self.counts = {}

        def count(what):
            key = (self.phase, what)
            self.counts[key] = self.counts.get(key, 0) + 1

        def on_duration(event, duration, **_):
            if event.endswith("jaxpr_trace_duration"):
                count("traces")
            elif event.endswith("backend_compile_duration"):
                count("builds")

        def on_event(event, **_):
            if event.endswith("compilation_cache/cache_hits"):
                count("cache_hits")

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def line(self, phase: str) -> str:
        n = {what: self.counts.get((phase, what), 0) for what in ("builds", "cache_hits", "traces")}
        return (f"in {phase}: compiles {n['builds'] - n['cache_hits']}  programs from the "
                f"persistent cache {n['cache_hits']}  traces {n['traces']}")


class SpanClock:
    """The wall clock, with its sleeps marked in the trace."""

    def __init__(self):
        from harness import traffic

        self.wall = traffic.WallClock()
        self.now = self.wall.now

    def sleep_until(self, t: float) -> None:
        from harness import trace

        with trace.span("wait_for_arrival"):
            self.wall.sleep_until(t)


def use_cache(path: Path) -> None:
    """Keep every compiled program in ``path``: a fixed path inside the
    checkout, so that only a cell's first run compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def peak_memory(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


@dataclasses.dataclass
class Deployment:
    """A cell's system, built and warmed, with the data it serves."""

    rows: object  # (n, d) corpus on the device
    pool_q: np.ndarray  # (pool, d) queries
    pool_w: np.ndarray  # (pool, d) their weights
    sut: object
    build_s: float


def prepare(cell, seed: int, system=None, root: Path = ROOT) -> Deployment:
    """Make the cell's corpus, query pool and weights from ``seed`` on the
    device, build the index with one compiled call, and warm every batch
    shape the cell's loop sends."""
    from harness import data, registry, systems

    cfg, mix, spec = cell.config, cell.mix, cell.spec
    sut = (system or systems.Program)(cfg, spec)  # rejects unknown spec fields
    d, gen = cfg["index"]["d"], cfg["generator"]
    rows = data.corpus(seed, cfg["n"], d, gen)
    pool_q = np.asarray(data.queries(seed, mix["pool"], d, gen))
    pool_w = np.asarray(data.weights(seed, mix["pool"], d, gen))
    build_s = sut.build(data.stream_key(seed, data.BUILD), rows)
    sut.warm(registry.plugin("loops", mix["loop"], root).shapes(mix), pool_q, pool_w)
    return Deployment(rows, pool_q, pool_w, sut, build_s)


def main(argv=None, system=None, require_tpu: bool = True, root: Path = ROOT,
         cache: Path | None = ROOT / ".jax_cache") -> int:
    """One run. ``system(cfg, spec)`` replaces the program under test (the
    control, or a broken program in the tests); ``require_tpu=False`` lets
    the tests drive a run on the CPU, from another ``root`` and with no
    persistent compile ``cache``."""
    t_start = time.perf_counter()
    args = parse(argv)
    from harness import registry

    cell = registry.cell(args.workload, root)
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        say(f"no TPU: JAX's device 0 is {devices[0].platform}")
        return NO_DEVICE
    if len(devices) < cell.chips:
        say(f"{cell.name} needs {cell.chips} chips, JAX finds {len(devices)}")
        return NO_DEVICE
    if cache is not None:
        use_cache(cache)

    from harness import check, data, reference
    from harness import trace as tracing

    cfg, mix, spec = cell.config, cell.mix, cell.spec
    mode = spec["mode"]
    loop = registry.plugin("loops", mix["loop"], root)
    counter = CompileCounter()

    # -- set-up: the data from the seed, the build, every batch shape -----------
    dep = prepare(cell, args.seed, system, root)
    sut, pool_q, pool_w = dep.sut, dep.pool_q, dep.pool_w

    def serve(queries):
        with tracing.span("prepare"):
            q, w = pool_q[queries], pool_w[queries]
        with tracing.span("query"):
            return sut.serve(q, w)

    setup_s = time.perf_counter() - t_start
    say(f"setup {setup_s:.3f} s  build {dep.build_s:.4f} s  cell {cell.name}  "
        f"n {cfg['n']}  device {devices[0].device_kind}")
    say(counter.line("setup"))

    # -- the measured window --------------------------------------------------
    counter.phase = "window"
    with tracing.Capture(bool(args.trace)) as cap:
        window = loop.drive(serve, mix, np.arange(mix["pool"]), args.seconds, args.seed,
                            root=root, clock=SpanClock())
    counter.phase = "after"
    memory = peak_memory(devices[: cell.chips])
    took = np.array([b.end - b.start for b in window.batches])
    say(f"window {window.seconds:.3f} s  batches {len(window.batches)}  queries "
        f"{window.queries}  batch s min {took.min():.4f} median {np.median(took):.4f} "
        f"max {took.max():.4f}")
    say(counter.line("window"))
    if window.late_s is not None and len(window.late_s):
        say(f"generator late: p99 {np.percentile(window.late_s, 99) * 1e3:.3f} ms  max "
            f"{window.late_s.max() * 1e3:.3f} ms over {len(window.late_s)} wake-ups")
    sut.free()
    dep.sut = sut = None

    # -- the check against the plain reference ---------------------------------
    t_ref = time.perf_counter()
    rows = dep.rows
    n_check = min(cfg["check_queries"], mix["pool"])
    asked, got_d, got_i = check.answers_for(window, n_check)
    checked, first = np.unique(asked, return_index=True)  # the queries answered
    at = np.searchsorted(checked, asked)
    g = reference.Geometry.from_config(cfg, spec)
    qs, ws = pool_q[checked], pool_w[checked]
    truth_d, truth_i, mean_d = reference.brute_force(rows, qs, ws, g.k)
    contrast = float(np.mean(np.asarray(mean_d) / np.asarray(truth_d)[:, -1]))
    if mode == "exact":
        ref_i = np.asarray(truth_i)
        stored = rows
    else:
        ref = reference.RefIndex.build(data.stream_key(args.seed, data.BUILD), rows, g)
        ref_i = np.asarray(ref.query(qs, ws)[1])
        stored = ref.stored

    def rows_of(ids):
        return np.asarray(stored[ids], np.float64)

    numbers = check.compare(got_d, got_i, ref_i[at], qs[at].astype(np.float64),
                            ws[at].astype(np.float64), rows_of)
    limits = cfg["limits"][mode]
    failed = int(sum(int(np.any(b.ids < 0, axis=1).sum()) for b in window.batches))
    correct = check.verdict(numbers, limits) and len(asked) > 0
    recall = check.recall(got_i[first], np.asarray(truth_i))
    say(f"reference {time.perf_counter() - t_ref:.3f} s  checked answers {len(asked)} "
        f"to {len(checked)} queries  recall@{g.k} {recall:.4f}  relative contrast "
        f"{contrast:.4f}  mean candidates "
        f"{np.mean(np.concatenate([b.counts for b in window.batches])):.1f}")

    # -- metrics ----------------------------------------------------------------
    kind = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": memory}
    result = {"correct": bool(correct), "attempted": window.queries, "failed": failed}
    if args.trace:
        reduced = cap.reduce()
        ctx = {"trace": reduced, "window": window, "config": cfg, "mix": mix, "spec": spec,
               "device_kind": kind, "geometry": g}
        metrics = {}
        for m in cell.per_layer:
            value = registry.reader(m["name"], root)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        result.update(metrics=metrics, device=device, breakdown=reduced.breakdown())
    else:
        e2e = {
            "qps": window.qps,
            "p99_ms": window.p99_ms,
            "recall_at_10": lambda: recall,
            "build_s": lambda: dep.build_s,
            "setup_s": lambda: setup_s,
        }
        metrics = {m["name"]: {"value": e2e[m["name"]](), "unit": m["unit"]}
                   for m in cell.end_to_end}
        result.update(metrics=metrics, device=device)
    result["checks"] = {name: {"value": numbers[name], "limit": limits[name]}
                        for name in check.NUMBERS}
    for line in check.lines(numbers, limits):
        say(line)
    say(json.dumps(result), file=sys.stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
