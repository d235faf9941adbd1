#!/usr/bin/env python3
"""Read a kept chip trace (``run.py --trace 1 --keep-trace DIR``) by the
program's own names: the step program's device time by named scope,
and each idle gap of the device by the ``serve.*`` host spans over it.

    python3 benchmarks/chip/tests/trace_scopes.py TRACE.xplane.pb

Prints one JSON object:

``scopes``  the step module's device time (each op's own time, less
            the ops nested in it) summed by the innermost named scope
            of the op's ``op_name`` metadata, read from the HLO the
            trace embeds; ``mapped_share`` is the part of that time
            under one of ``SCOPES``.
``gaps``    every idle gap of at least ``--min-gap-ms`` on device 0
            inside the ``bench.trace_window`` span: its length, the
            innermost ``serve.*`` span over its middle, the share of
            the gap that ``serve.*`` spans cover, and whether it lies at
            an edge of the recorded steps (see :func:`gaps`);
            ``gaps_covered_80`` counts the other gaps that are covered
            for at least 80% of their length, out of how many.

Reads the trace with ``jax.profiler.ProfileData`` and decodes the
embedded HLO with a small reader of the protobuf wire format, so it
needs nothing beyond JAX. Not part of the benchmark's runs.
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import trace_reduce  # noqa: E402

#: the named scopes of the serve decode path; an op counts under the
#: innermost of them in its ``op_name``
SCOPES = ("layers", "qkv", "kv_write", "attention", "attn_out", "mlp",
          "head", "sample")
HOST_PREFIX = "serve."


# ------------------------------------------------ protobuf wire format
def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf):
    """``(field number, value)`` of one message: ints for varints and
    fixed-width values, ``memoryview`` slices for length-delimited."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield num, val


def _sub(buf, num):
    return [v for k, v in fields(buf) if k == num]


def hlo_op_names(path: str) -> dict:
    """``{module name: {instruction name: op_name}}`` from the HLO
    protos that the trace's ``/host:metadata`` plane embeds (XSpace
    planes 1; XPlane name 2, event_metadata 4; XEventMetadata name 2,
    stats 5; XStat bytes 6; HloProto module 1; HloModuleProto
    computations 3; HloComputationProto instructions 2;
    HloInstructionProto name 1, metadata 7; OpMetadata op_name 2)."""
    space = memoryview(Path(path).read_bytes())
    out = {}
    for plane in _sub(space, 1):
        names = _sub(plane, 2)
        if not names or bytes(names[0]) != b"/host:metadata":
            continue
        for entry in _sub(plane, 4):
            for meta in _sub(entry, 2):
                mod_names = _sub(meta, 2)
                mod = bytes(mod_names[0]).decode() if mod_names else ""
                table = out.setdefault(mod, {})
                for stat in _sub(meta, 5):
                    for proto in _sub(stat, 6):
                        for module in _sub(proto, 1):
                            for comp in _sub(module, 3):
                                for inst in _sub(comp, 2):
                                    name = bytes(_sub(inst, 1)[0]).decode()
                                    md = _sub(inst, 7)
                                    on = _sub(md[0], 2) if md else []
                                    table[name] = (bytes(on[0]).decode()
                                                   if on else "")
    return out


def scope_of(op_name: str) -> str:
    """The innermost of ``SCOPES`` in an ``op_name`` path, or
    ``(none)``."""
    parts = op_name.split("/")
    for p in reversed(parts):
        if p in SCOPES:
            return p
    return "(none)"


# ------------------------------------------------------------- events
def load(path: str) -> dict:
    """Device 0's ops and modules, and the host's ``bench.*`` and
    ``serve.*`` spans, each ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, host = [], [], []
    for plane in pd.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    ops += [(trace_reduce.op_name(e.name), e.start_ns,
                             e.end_ns) for e in line.events]
                elif line.name == trace_reduce.MODULES_LINE:
                    modules += [(e.name, e.start_ns, e.end_ns)
                                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.end_ns) for e in line.events
                         if e.name.startswith(("bench.", HOST_PREFIX))]
    return {"ops": ops, "modules": modules, "host": host}


def self_times(ops):
    """Each op's own time: its length less the ops nested inside it on
    the same line (a ``while`` holds its body's ops)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [b - a for _, a, b in ops]
    stack = []
    for i in order:
        _, a, b = ops[i]
        while stack and ops[stack[-1]][2] <= a:
            stack.pop()
        if stack and b <= ops[stack[-1]][2]:
            own[stack[-1]] -= b - a
        stack.append(i)
    return own


def device_time_by_scope(ev, names, module_prefix="jit__mixed_fn"):
    """Seconds of the step module's op time by named scope, with the
    mapped share; ops outside any run of that module do not count."""
    runs = [(a, b) for n, a, b in ev["modules"]
            if n.startswith(module_prefix)]
    table = next((t for m, t in names.items()
                  if m.startswith(module_prefix)), {})
    own = self_times(ev["ops"])
    by = defaultdict(float)
    unnamed = defaultdict(float)
    for (name, a, b), t in zip(ev["ops"], own):
        if not any(s <= a and b <= e for s, e in runs):
            continue
        sc = scope_of(table.get(name, ""))
        by[sc] += t * 1e-9
        if sc == "(none)":
            unnamed[f"{name} [{table.get(name, '?')}]"] += t * 1e-9
    total = sum(by.values())
    mapped = total - by.get("(none)", 0.0)
    top_unnamed = sorted(unnamed.items(), key=lambda kv: -kv[1])[:8]
    return {"total_s": total,
            "mapped_share": mapped / total if total else None,
            "by_scope": dict(sorted(by.items(), key=lambda kv: -kv[1])),
            "unnamed_top": top_unnamed, "steps": len(runs)}


def gaps(ev, min_gap_ms=1.0):
    """Idle gaps of at least ``min_gap_ms`` inside the trace window,
    each with the innermost ``serve.*`` span over its middle, the share
    of it covered by ``serve.*`` spans, and each span's own time under
    it (less the spans nested in it). The profiler records a host scope only if it opens
    after the trace starts and closes before it stops, so a gap that
    begins before the first recorded ``serve.step`` or ends after the
    last lies partly under scopes that went unrecorded: it is marked
    ``edge``."""
    win = [(a, b) for n, a, b in ev["host"]
           if n == trace_reduce.WINDOW_SPAN]
    t0, t1 = win[0] if win else (min(a for _, a, _ in ev["ops"]),
                                 max(b for _, _, b in ev["ops"]))
    busy = trace_reduce.union(trace_reduce.clip(
        [(a, b) for _, a, b in ev["ops"]], t0, t1))
    serve = [(n, a, b) for n, a, b in ev["host"]
             if n.startswith(HOST_PREFIX)]
    cover = trace_reduce.union([(a, b) for _, a, b in serve])
    steps = [(a, b) for n, a, b in serve if n == "serve.step"]
    lo = min((a for a, _ in steps), default=t1)
    hi = max((b for _, b in steps), default=t0)
    out = []
    edges = [(t0, t0)] + busy + [(t1, t1)]
    for (_, a), (b, _) in zip(edges, edges[1:]):
        if (b - a) * 1e-6 < min_gap_ms:
            continue
        mid = (a + b) / 2
        over = [(e - s, n) for n, s, e in serve if s <= mid <= e]
        inner = min(over)[1] if over else "(none)"
        covered = sum(max(0, min(b, e) - max(a, s)) for s, e in cover)
        # each serve.* span's own time under the gap: its overlap less
        # the part the spans nested in it cover
        under = [(n, max(a, s), min(b, e)) for n, s, e in serve
                 if s < b and e > a]
        own = defaultdict(float)
        for n, s, e in under:
            kids = trace_reduce.union(
                [(s2, e2) for _, s2, e2 in under
                 if s <= s2 and e2 <= e and (s2, e2) != (s, e)])
            own[n] += (e - s - sum(y - x for x, y in kids)) * 1e-6
        out.append({"start_ms": (a - t0) * 1e-6, "ms": (b - a) * 1e-6,
                    "innermost": inner, "covered": covered / (b - a),
                    "edge": a < lo or b > hi,
                    "own_ms": dict(sorted(own.items(),
                                          key=lambda kv: -kv[1]))})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--min-gap-ms", type=float, default=1.0)
    args = ap.parse_args(argv)
    ev = load(args.trace)
    g = gaps(ev, args.min_gap_ms)
    inner = [x for x in g if not x["edge"]]
    out = {"scopes": device_time_by_scope(ev, hlo_op_names(args.trace)),
           "gaps": g, "edge_gaps": len(g) - len(inner),
           "gaps_covered_80": (sum(x["covered"] >= 0.8 for x in inner),
                               len(inner))}
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
