#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip: runs of
the cell on many seeds, each with the program's widest gap and, on the
first ``--control`` seeds, the float8 control's.

    python3 benchmarks/chip/calibrate.py --workload qwen3-4b.prompt_heavy \\
        --seeds 101 102 103 --control 3 --seconds 50

Each seed is a whole run of the cell in a process of its own (weights
from its seed, the primer, warm-up, a window of ``--seconds`` at the
cell's own load, the check): a run does not give its engine's device
memory back, so a second run in one process would not fit. This parent
never touches JAX. One JSON line per seed. The limit goes into the
cell's file by hand, between the program's largest reading and the
control's smallest.
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def one(workload: str, seed: int, seconds: float, control: bool) -> int:
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(HERE.parents[1] / "src"))
    import harness
    out = harness.run(workload, seed, seconds, False, control=control)
    row = {"seed": seed, "correct": out["correct"],
           "attempted": out["attempted"], "failed": out["failed"],
           **{k: v["value"] for k, v in out["check"].items()},
           "memory_peak_bytes": out["device"]["memory_peak_bytes"]}
    print(json.dumps(row), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3,
                    help="how many of the seeds also read the control")
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--one", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.one:
        return one(args.workload, args.seeds[0], args.seconds,
                   args.control > 0)
    rc = 0
    for i, seed in enumerate(args.seeds):
        r = subprocess.run(
            [sys.executable, __file__, "--one", "--workload", args.workload,
             "--seeds", str(seed), "--control", str(int(i < args.control)),
             "--seconds", str(args.seconds)], timeout=600)
        rc = rc or r.returncode
    return rc


if __name__ == "__main__":
    sys.exit(main())
