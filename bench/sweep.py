"""Sweep the arrival rate of an open-loop cell, to find its knee.

    python3 bench/sweep.py --workload sift1m-f32.served --seed 1 --seconds 10 \
        --rates 300 500 700 900

One process on one chip: the cell's set-up once, then one open-loop window
per rate. For each rate it prints p50 and p99 latency, the batch fill, and
the mean latency of the last tenth of the requests over the first tenth: a
ratio well above 1 means the backlog grew through the window. The served
cell's fixed rate is set at about four fifths of the highest rate whose
backlog stays flat.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run  # noqa: E402
from harness import registry, traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return run.NO_DEVICE
    run.use_cache(run.ROOT / ".jax_cache")
    cell = registry.cell(args.workload)
    dep = run.prepare(cell, args.seed)
    loop = registry.plugin("loops", cell.mix["loop"])
    order = np.arange(cell.mix["pool"])

    def serve(r):
        return dep.sut.serve(dep.pool_q[r], dep.pool_w[r])

    for rate in args.rates:
        mix = dict(cell.mix, arrivals=dict(cell.mix["arrivals"], rate_hz=rate))
        w = loop.drive(serve, mix, order, args.seconds, args.seed)
        lat = w.latency_s
        tenth = max(1, len(lat) // 10)
        print(json.dumps({
            "rate_hz": rate, "requests": len(lat), "p50_ms": traffic.tail_ms(lat, 50),
            "p99_ms": w.p99_ms(), "batch_fill": w.batch_fill(), "batches": len(w.batches),
            "growth": float(lat[-tenth:].mean() / lat[:tenth].mean()),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
