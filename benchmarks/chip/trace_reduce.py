"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: each device's busy time (the union of the intervals in which
an operation ran on it) over the traced window, its operations' total
times, how many times its step program ran, and the longest idle gaps, each labelled with the host span that covers
most of it (what the host was doing).

The window is the host span ``bench.trace_window`` that the harness
opens right after the profiler starts and closes right before it stops;
device events are clipped to it. The host's labels are the harness's
``bench.*`` spans (``jax.profiler.TraceAnnotation``).
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

DEVICE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
WINDOW_SPAN = "bench.trace_window"


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(paths, key=os.path.getmtime)


def op_name(name: str) -> str:
    """An op's name without the HLO text the trace appends to it:
    ``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted, merged intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, t0, t1):
    return [(max(a, t0), min(b, t1)) for a, b in intervals
            if b > t0 and a < t1]


def load(path: str) -> dict:
    """The events the reduction needs, times in ns: per device its ops
    ``[(name, start, end)]`` and modules, and the host's ``bench.*``
    spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[int, dict] = {}
    host: List[tuple] = []
    for plane in pd.planes:
        m = DEVICE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)),
                                     {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is None:
                    continue
                dev[key].extend((op_name(e.name), e.start_ns, e.end_ns)
                                for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events
                            if e.name.startswith("bench."))
    return {"devices": devices, "host": host}


def reduce_events(ev: dict, devices: int | None = None, top: int = 10
                  ) -> dict:
    """The summary of :func:`load`'s events (see the module's text)."""
    win = [(a, b) for n, a, b in ev["host"] if n == WINDOW_SPAN]
    devs = ev["devices"]
    if devices is not None:
        devs = {d: v for d, v in devs.items() if d < devices}
    if not devs:
        raise ValueError("the trace holds no TPU device plane")
    if win:
        t0, t1 = win[0]
    else:
        spans = [(a, b) for v in devs.values() for _, a, b in v["ops"]]
        t0, t1 = min(a for a, _ in spans), max(b for _, b in spans)
    window = (t1 - t0) * 1e-9
    per = {}
    op_tot: Dict[str, float] = defaultdict(float)
    for d, v in sorted(devs.items()):
        ops = [(n, a, b) for n, a, b in v["ops"] if b > t0 and a < t1]
        busy = union(clip([(a, b) for _, a, b in ops], t0, t1))
        busy_s = sum(b - a for a, b in busy) * 1e-9
        for n, a, b in ops:
            op_tot[n] += (min(b, t1) - max(a, t0)) * 1e-9
        mods: Dict[str, list] = defaultdict(list)
        for n, a, b in v["modules"]:
            if t0 <= a < t1:
                mods[n].append(b - a)
        step_mod = max(mods, key=lambda n: sum(mods[n])) if mods else None
        gaps = []
        edges = [(t0, t0)] + busy + [(t1, t1)]
        for (_, b), (a, _) in zip(edges, edges[1:]):
            if a > b:
                gaps.append((b, a))
        per[d] = {"busy_s": busy_s, "idle_share": 1.0 - busy_s / window,
                  "step_module": step_mod,
                  "steps": len(mods[step_mod]) if step_mod else 0,
                  "gaps": gaps}
    n = len(per)
    top_ops = sorted(((name, t / n) for name, t in op_tot.items()),
                     key=lambda x: -x[1])[:top]
    first = per[min(per)]
    spans = [(name, a, b) for name, a, b in ev["host"]
             if name != WINDOW_SPAN]

    def label(a, b):
        """The host span that covers most of the gap."""
        best = max(((min(b, e) - max(a, s), name) for name, s, e in spans),
                   default=(0, ""))
        return best[1] if best[0] > 0 else "no bench span"

    longest = sorted(first["gaps"], key=lambda g: g[0] - g[1])[:top]
    idle = [[label(a, b), (b - a) * 1e-9] for a, b in longest]
    for v in per.values():
        del v["gaps"]
    return {"window_s": window,
            "busy_s": sum(v["busy_s"] for v in per.values()) / n,
            "devices": per, "top_ops": [[k, v] for k, v in top_ops],
            "idle_gaps": idle}


def reduce(path: str, devices: int | None = None) -> dict:
    return reduce_events(load(path), devices)
