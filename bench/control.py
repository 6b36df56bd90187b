"""Read a cell's compared numbers over many seeds in one process, for the
program or for the control (the reference one precision step down, in the
program's place). The limits in the configuration files are set from
these readings; the benchmark's own runs never run the control.

    python3 bench/control.py --workload sift1m-f32.batch --seconds 5 --seeds 1 2 3
    python3 bench/control.py --workload sift1m-f32.batch --seconds 5 --seeds 1 2 3 --program

Each seed is one whole run of ``run.py`` (set-up, window, check); its last
line is printed as the run prints it.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402
from harness import systems  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--program", action="store_true", help="read the program, not the control")
    args = ap.parse_args(argv)
    system = None if args.program else systems.Control
    rc = 0
    for seed in args.seeds:
        rc |= run.main(["--workload", args.workload, "--seed", str(seed),
                        "--seconds", str(args.seconds), "--trace", str(args.trace)],
                       system=system)
    return rc


if __name__ == "__main__":
    sys.exit(main())
