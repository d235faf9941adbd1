#!/usr/bin/env python3
"""Find an open-loop cell's knee once, on the chip: the highest offered
rate whose backlog does not grow through the window.

    python3 benchmarks/chip/sweep.py --workload qwen3-4b.prompt_heavy \\
        --rates 1.0 1.5 2.0 2.5 --seconds 30 --seed 7

One process and one engine: the cell's weights and sizing, primed as a
run primes it, then for each rate the mix's warm-up and a window of
``--seconds``, then a drain before the next rate. Prints one JSON line
per rate: offered and completed requests per second, the backlog
(requests sent and not finished) at the window's open and close, TTFT
p50/p90 and the inter-token gap p95. The knee goes into the cell's
file by hand; the benchmark's runs never search for it.
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import harness  # noqa: E402
import traffic  # noqa: E402


def backlog(sent, t):
    return sum(s.sent and s.sent <= t and (s.finished is None
                                           or s.finished > t) for s in sent)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    files = harness.cell_files(args.workload)
    mix, c = files.mix, files.config
    devices = harness.check_platform(files.workload["chips"])
    harness.enable_compile_cache()
    cfg = harness.model_config(c)
    params, session, driver = harness.build(files, cfg, devices, args.seed)
    vocab, max_len = c["vocab_size"], c["serve"]["max_len"]
    driver.start()
    for rate in args.rates:
        reqs = traffic.open_loop(mix, rate, args.seconds, vocab, args.seed)
        s0 = harness.Sent(traffic.primer(reqs, max_len, vocab, args.seed),
                          time.perf_counter())
        harness.consume(driver, s0)
        origin = time.perf_counter() + float(mix["warmup_s"])
        gen = harness.OpenLoop(driver, reqs, origin)
        gen.start()
        t_close = origin + args.seconds
        time.sleep(max(0.0, t_close - time.perf_counter()))
        gen.stop()
        gen.join(float(mix["drain_s"]) + 60.0)
        win = [s for s in gen.sent if s.req.in_window]
        done = sum(s.done and origin <= s.finished < t_close
                   for s in gen.sent)
        ttfts = [harness.ttft(s, t_close + float(mix["drain_s"]), origin)
                 for s in win]
        gaps = harness.window_gaps(gen.sent, origin, t_close)
        row = {"rate_rps": rate, "window_s": args.seconds,
               "offered": len(win),
               "completed_rps": done / args.seconds,
               "backlog_open": backlog(gen.sent, origin),
               "backlog_close": backlog(gen.sent, t_close),
               "ttft_p50_ms": 1e3 * traffic.percentile(ttfts, 0.5),
               "ttft_p90_ms": 1e3 * traffic.percentile(ttfts, 0.9),
               "itl_p95_ms": 1e3 * traffic.percentile(gaps, 0.95)
               if gaps else None,
               "unfinished": sum(not s.done for s in win)}
        print(json.dumps(row), flush=True)
    driver.stop(drain=False)
    return 0


if __name__ == "__main__":
    sys.exit(main())
