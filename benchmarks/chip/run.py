#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 benchmarks/chip/run.py --workload qwen3-4b.prompt_heavy \\
        --seed 12345 --seconds 50 --trace 0

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``; under ``check`` last, every number the correctness check
compared beside its limit. The same numbers are the last lines of
standard error. Without a TPU, or with fewer chips than the cell asks
for, or without the system under test beside the benchmark, the run
exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's .xplane.pb into this "
                         "directory")
    args = ap.parse_args(argv)

    import harness
    if not (ROOT / "src" / "repro").is_dir():
        print("run.py: the system under test (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return 2
    errors = []

    def hook(exc):
        errors.append(exc.exc_value)
        threading.__excepthook__(exc)

    threading.excepthook = hook
    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), keep_trace=args.keep_trace)
    except harness.NoChip as e:
        print(f"run.py: {e}; this benchmark runs only on the chip",
              file=sys.stderr)
        return 3
    except harness.RunFailed as e:
        print(f"run.py: FAILED: {e}", file=sys.stderr)
        return 1
    if errors:
        print(f"run.py: FAILED: a thread raised {errors[0]!r}",
              file=sys.stderr)
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
