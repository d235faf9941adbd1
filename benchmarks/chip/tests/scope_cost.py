#!/usr/bin/env python3
"""What the engine tracer's step record costs on the host, with no
profiler session running: one step cycle as the mixed step makes it
(six phase entries, five child spans), with its ``serve.*`` profiler
scopes, with the scopes swapped for a no-op, and at trace level 0; and
one scope entered and left.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/tests/scope_cost.py

Prints one JSON object of microseconds, each the median of three
passes of 20000 cycles. Not part of the benchmark's runs.
"""
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[3] / "src"))

import repro.serve.tracing as tracing  # noqa: E402

N = 20000


def cycle(tr):
    t0 = time.perf_counter()
    for i in range(N):
        st = tr.begin_step(i)
        st.enter("draft")
        st.enter("pack")
        st.sub("inputs")
        st.enter("dispatch")
        st.sub("inputs")
        st.sub("launch")
        st.enter("sync")
        st.sub("wait")
        st.sub("readback")
        st.enter("bookkeeping")
        tr.end_step(st, 24)
    return (time.perf_counter() - t0) / N * 1e6


class _Nop:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def one_scope():
    t0 = time.perf_counter()
    for _ in range(N):
        with tracing.scope("serve.loop"):
            pass
    return (time.perf_counter() - t0) / N * 1e6


def main():
    real = tracing.scope
    runs = {"step_us": [], "step_no_scopes_us": [], "level0_us": [],
            "one_scope_us": []}
    for _ in range(3):
        runs["step_us"].append(cycle(tracing.Tracer()))
        tracing.scope = lambda name, step_num=None: _Nop()
        try:
            runs["step_no_scopes_us"].append(cycle(tracing.Tracer()))
        finally:
            tracing.scope = real
        runs["level0_us"].append(cycle(tracing.Tracer(level=0)))
        runs["one_scope_us"].append(one_scope())
    out = {k: sorted(v)[1] for k, v in runs.items()}
    out["scopes_us"] = out["step_us"] - out["step_no_scopes_us"]
    print(json.dumps(out))


if __name__ == "__main__":
    main()
