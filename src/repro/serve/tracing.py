"""Request-level tracing, step flight recorder, Perfetto export.

The survey's §4 loop (search → materialize → run) leaves the RUN half a
black box once the engine layers chunked prefill, speculation,
preemption and sharding on top of one traced program: aggregate
Prometheus summaries (serve/metrics.py) say THAT TTFT regressed, not
WHICH phase of WHICH step a given request spent its time in. This
module is the missing visibility layer, woven through the serve path by
PR 10 and deliberately dependency-free (stdlib only, like metrics.py):

  * **Per-request span trees** — every request accumulates typed events
    (``submitted``, ``admitted``, ``prefill_chunk``, ``decode``,
    ``first_token``, ``preempted``, ``expired``, ``completed``) stamped
    from the engine's existing lifecycle hooks, plus O(1) counters
    (generated tokens, prefill-chunk tokens, preemptions) that are
    default-on — the acceptance check "span tree matches the streamed
    token count" reads ``tokens`` straight off the trace.
  * **Per-step phase records** — :meth:`Tracer.begin_step` hands the
    engine a :class:`StepTrace`; ``enter(phase)`` starts a phase (draft
    / pack / dispatch / sync / bookkeeping...) and ends the one before,
    so the phases partition the step's host wall time, and ``sub(name)``
    starts a child span inside the current phase (the device inputs,
    the launch, the wait, the readback). The closed record also carries
    the step's work items (which slot decoded/prefilled what), so a step
    is attributable request by request, and for a mixed step its live
    rows, the paged attention's row width and the token budget
    (``rows``, ``attn_rows``, ``budget``). The driver reads the newest
    records' phases into the ``serve_step_phase_seconds{phase=...}``
    histograms.
  * **Flight recorder** — bounded ring buffers (``deque(maxlen=N)``) of
    the last N step records and recently finished request traces.
    :meth:`Tracer.flight` snapshots them on demand; the AsyncDriver's
    watchdog dumps the snapshot when a step overruns (replacing the
    PR 6 ad-hoc log dump), and ``GET /debug/flight`` serves it over
    HTTP — readable even while a stalled step holds the engine lock,
    because the tracer has its own tiny lock and the stalled thread is
    inside a device call, not inside the tracer.
  * **Chrome/Perfetto export** — :func:`chrome_trace` renders one or
    more tracers (one per DP replica) into the ``trace_event`` JSON
    object format: pid = replica, tid 0 = the engine-step lane (step
    spans with nested phase spans), tid 1+s = slot ``s``'s lane (one
    decode/prefill span per step, labeled with the rid and token
    counts). Load the file in https://ui.perfetto.dev or
    chrome://tracing. Request span trees ride in ``otherData``.

Overhead is bounded by construction: every hook is O(1) (append to a
ring or increment a counter), records live in fixed-size deques, and
``level`` gates the detail — 0 disables every hook (begin_step returns
the shared no-op :data:`NULL_STEP`), 1 (default) keeps lifecycle events
+ step records + counters, 2 adds a per-chunk / per-decode-step event to
the request span tree. Timestamps are ``time.perf_counter()`` — one
monotonic clock shared by every replica in the process, so merged lanes
line up. At level 1 and above the step, its phases and their child
spans are also ``jax.profiler`` host scopes named ``serve.<name>``
(:func:`scope`, the one place this module touches jax), so a profiler
trace shows them on the clock of the device ops.

Composition: a TP engine is still ONE engine → one tracer; a DP
``ReplicaRouter`` gives each replica its own tracer (``tracer.replica``
is stamped after construction) and merges them at export time —
per-lane ids never collide because the replica index is the pid.
"""
from __future__ import annotations

import json
import threading
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence

#: trace levels: OFF disables every hook, BASIC (default) records
#: lifecycle events + step records + per-request counters, DETAIL adds
#: per-chunk / per-decode-step events to the request span trees.
LEVEL_OFF, LEVEL_BASIC, LEVEL_DETAIL = 0, 1, 2


class _NullStep:
    """Shared no-op StepTrace stand-in for ``level=0`` — the engine's
    instrumentation calls land here branch-free and enter no profiler
    scope."""

    __slots__ = ()

    def enter(self, phase: str):
        pass

    def sub(self, name: str):
        pass

    def note_decode(self, slot, rid, tokens, drafted=0, accepted=0):
        pass

    def note_chunk(self, slot, rid, start, count):
        pass

    def note_rows(self, rows, attn_rows, budget):
        pass


#: the singleton every disabled begin_step returns
NULL_STEP = _NullStep()


class StepTrace:
    """One engine step's record under construction (engine-thread local
    until :meth:`Tracer.end_step` publishes it into the ring).

    The step starts in ``phase``. ``enter(phase)`` ends the current
    phase and starts ``phase``; ``phases`` sums each phase's time over
    its repeats, so the phases partition the step wall time with no
    gaps — which is what makes the exported phase spans cover ~100% of
    the step span (the acceptance bound is >= 95%). ``sub(name)`` starts
    a child span inside the current phase, which ends at the next
    ``sub`` or ``enter``. ``spans`` holds every phase and child span in
    the order it ended, as ``(name, start, end, parent)``: the parent of
    a phase is None, that of a child its phase. Each is also a profiler
    scope (:func:`scope`): ``serve.step`` around the whole step,
    ``serve.<phase>`` and ``serve.<child>`` inside it."""

    __slots__ = ("step_id", "t0", "dur", "produced", "phases", "spans",
                 "work", "rows", "attn_rows", "budget", "_phase", "_t",
                 "_child", "_tc", "_scopes")

    def __init__(self, step_id: int, phase: str):
        self.step_id = step_id
        self.dur = 0.0
        self.produced = 0
        self.phases: Dict[str, float] = {}    # insertion-ordered totals
        self.spans: List[tuple] = []          # phase and child spans
        self.work: List[dict] = []            # per-slot items this step
        # the mixed step's live rows, the attention width that ran and
        # the token budget (note_rows); None where no program ran
        self.rows = self.attn_rows = self.budget = None
        self._child: Optional[str] = None
        self._tc = 0.0
        # profiler scopes: the step's, the phase's, the last child's
        self._scopes = [_open(scope("serve.step", step_num=step_id)),
                        _open(scope("serve." + phase)), None]
        self.t0 = self._t = time.perf_counter()
        self._phase = phase

    def enter(self, phase: str):
        t = time.perf_counter()
        if self._child is not None:
            self._end_child(t)
        if phase == self._phase:
            return
        self._end_phase(t)
        self._phase, self._t = phase, t
        _close(self._scopes[1])
        self._scopes[1] = _open(scope("serve." + phase))

    def sub(self, name: str):
        t = time.perf_counter()
        if self._child is not None:
            self._end_child(t)
        self._child, self._tc = name, t
        self._scopes[2] = _open(scope("serve." + name))

    def _end_child(self, t: float):
        self.spans.append((self._child, self._tc, t, self._phase))
        self._child = None
        _close(self._scopes[2])

    def _end_phase(self, t: float):
        ph = self._phase
        self.phases[ph] = self.phases.get(ph, 0.0) + (t - self._t)
        self.spans.append((ph, self._t, t, None))

    def close(self):
        """End the open child span, the current phase and the step."""
        t = time.perf_counter()
        if self._child is not None:
            self._end_child(t)
        self._end_phase(t)
        self.dur = t - self.t0
        _close(self._scopes[1])
        _close(self._scopes[0])

    def note_decode(self, slot: int, rid: int, tokens: int,
                    drafted: int = 0, accepted: int = 0):
        item = {"kind": "decode", "slot": int(slot), "rid": int(rid),
                "tokens": int(tokens)}
        if drafted:
            item["drafted"] = int(drafted)
            item["accepted_drafts"] = int(accepted)
        self.work.append(item)

    def note_chunk(self, slot: int, rid: int, start: int, count: int):
        self.work.append({"kind": "prefill", "slot": int(slot),
                          "rid": int(rid), "start": int(start),
                          "count": int(count)})

    def note_rows(self, rows: int, attn_rows: int, budget: int):
        self.rows, self.attn_rows, self.budget = rows, attn_rows, budget

    def to_dict(self) -> dict:
        return {"step_id": self.step_id, "t0": self.t0, "dur": self.dur,
                "produced": self.produced,
                "phases": dict(self.phases),
                "spans": [{"name": n, "start": a, "end": b, "parent": p}
                          for n, a, b, p in self.spans],
                "work": list(self.work), "rows": self.rows,
                "attn_rows": self.attn_rows, "budget": self.budget}


class RequestTrace:
    """One request's span tree: typed events plus O(1) counters.

    ``tokens`` counts every generated token the engine appended to the
    request (prefill-sampled firsts included) — by construction it
    equals ``len(request.out)``, the streamed token count, which the
    tracing tests pin. ``events`` is bounded; overflow increments
    ``dropped`` instead of growing."""

    __slots__ = ("rid", "events", "tokens", "chunk_tokens",
                 "preemptions", "dropped", "max_events", "done",
                 "outcome")

    def __init__(self, rid: int, max_events: int = 256):
        self.rid = rid
        self.events: List[tuple] = []     # (t, kind, fields|None)
        self.tokens = 0
        self.chunk_tokens = 0
        self.preemptions = 0
        self.dropped = 0
        self.max_events = max_events
        self.done = False
        self.outcome: Optional[str] = None

    def add(self, kind: str, fields: Optional[dict]):
        if len(self.events) >= self.max_events:
            self.dropped += 1
            return
        self.events.append((time.perf_counter(), kind, fields))

    def to_dict(self) -> dict:
        return {
            "rid": self.rid, "tokens": self.tokens,
            "chunk_tokens": self.chunk_tokens,
            "preemptions": self.preemptions, "done": self.done,
            "outcome": self.outcome, "dropped_events": self.dropped,
            "events": [
                {"t": t, "kind": kind, **(fields or {})}
                for t, kind, fields in self.events]}


class Tracer:
    """The engine-side recorder: request span trees + step flight ring.

    One tracer per :class:`~repro.serve.engine.ServeEngine` (a TP engine
    is still one engine); a DP router stamps each replica's
    ``tracer.replica`` after construction so merged exports get distinct
    pid lanes. Thread-safety: every mutation of the shared rings/maps
    happens under one small lock; a StepTrace is engine-thread-local
    until published. The HTTP/watchdog threads only ever read through
    :meth:`flight` / :func:`chrome_trace`, which snapshot under the same
    lock — safe to call while a stalled step holds the DRIVER lock,
    since the stalled thread is inside a device call, not in here."""

    def __init__(self, level: int = LEVEL_BASIC, *, max_steps: int = 256,
                 max_requests: int = 64, max_events: int = 256,
                 replica: int = 0):
        if max_steps < 1 or max_requests < 1 or max_events < 1:
            raise ValueError("tracer ring sizes must be >= 1")
        self.level = int(level)
        self.replica = int(replica)
        self.max_events = int(max_events)
        self._lock = threading.Lock()
        self.steps: Deque[StepTrace] = deque(maxlen=max_steps)
        self._live: Dict[int, RequestTrace] = {}
        self._done: Deque[RequestTrace] = deque(maxlen=max_requests)
        self.published = 0      # step records ever published
        self.dropped_requests = 0

    @property
    def enabled(self) -> bool:
        return self.level >= LEVEL_BASIC

    # -------------------------------------------------------- step hooks
    def begin_step(self, step_id: int):
        """A fresh :class:`StepTrace` in ``bookkeeping`` (or
        :data:`NULL_STEP` when disabled) — the engine enters phases on it
        and hands it back to :meth:`end_step`."""
        if self.level < LEVEL_BASIC:
            return NULL_STEP
        return StepTrace(step_id, "bookkeeping")

    def end_step(self, st, produced: int):
        """Close a StepTrace and publish it into the flight ring."""
        if st is NULL_STEP:
            return
        st.close()
        if self.level < LEVEL_BASIC:
            return
        st.produced = int(produced)
        with self._lock:
            self.steps.append(st)
            self.published += 1

    def steps_since(self, seen: int):
        """``(published, records)``: how many step records were ever
        published, and the ring's records among those published after
        the first ``seen`` (fewer when the ring has overwritten some) —
        the driver's phase histograms read each step once this way."""
        with self._lock:
            n = self.published
            k = min(n - seen, len(self.steps))
            recs = [self.steps[-i] for i in range(k, 0, -1)]
        return n, recs

    # ----------------------------------------------------- request hooks
    def _req(self, rid: int) -> RequestTrace:
        # caller holds the lock
        rt = self._live.get(rid)
        if rt is None:
            if len(self._live) >= 4096:      # runaway guard, not a limit
                self.dropped_requests += 1
                return RequestTrace(rid, max_events=1)
            rt = RequestTrace(rid, max_events=self.max_events)
            self._live[rid] = rt
        return rt

    def req_event(self, rid: int, kind: str, **fields):
        """Append a lifecycle event to ``rid``'s span tree (level >= 1)."""
        if self.level < LEVEL_BASIC:
            return
        with self._lock:
            self._req(rid).add(kind, fields or None)

    def req_detail(self, rid: int, kind: str, **fields):
        """Append a per-chunk / per-decode-step event (level >= 2 only —
        the O(step) detail the default level keeps out of the tree)."""
        if self.level < LEVEL_DETAIL:
            return
        with self._lock:
            self._req(rid).add(kind, fields or None)

    def req_amend(self, rid: int, kind: str, **fields):
        """Add ``fields`` to the newest ``kind`` event of live request
        ``rid`` (level >= 1) — for a number known only once the event
        is recorded, such as the driver's lock wait on ``submitted``."""
        if self.level < LEVEL_BASIC:
            return
        with self._lock:
            rt = self._live.get(rid)
            if rt is None:
                return
            for i in range(len(rt.events) - 1, -1, -1):
                t, k, old = rt.events[i]
                if k == kind:
                    rt.events[i] = (t, k, {**(old or {}), **fields})
                    return

    def req_tokens(self, rid: int, n: int):
        """Count ``n`` freshly generated tokens against ``rid``."""
        if self.level < LEVEL_BASIC:
            return
        with self._lock:
            self._req(rid).tokens += int(n)

    def req_chunk_tokens(self, rid: int, n: int):
        if self.level < LEVEL_BASIC:
            return
        with self._lock:
            self._req(rid).chunk_tokens += int(n)

    def req_preempted(self, rid: int, **fields):
        if self.level < LEVEL_BASIC:
            return
        with self._lock:
            rt = self._req(rid)
            rt.preemptions += 1
            rt.add("preempted", fields or None)

    def finish_request(self, rid: int, outcome: str, **fields):
        """Close ``rid``'s span tree (``completed`` or ``expired``) and
        move it from the live map to the finished ring."""
        if self.level < LEVEL_BASIC:
            return
        with self._lock:
            rt = self._live.pop(rid, None)
            if rt is None:
                rt = RequestTrace(rid, max_events=self.max_events)
            rt.add(outcome, fields or None)
            rt.done = True
            rt.outcome = outcome
            self._done.append(rt)

    def request_trace(self, rid: int) -> Optional[dict]:
        """The span tree for ``rid`` (live or recently finished)."""
        with self._lock:
            rt = self._live.get(rid)
            if rt is None:
                for cand in self._done:
                    if cand.rid == rid:
                        rt = cand
                        break
            return rt.to_dict() if rt is not None else None

    # ---------------------------------------------------- flight recorder
    def flight(self, last: Optional[int] = None) -> dict:
        """Snapshot of the ring buffers: the most recent ``last`` step
        records (all when None) plus live and recently finished request
        traces — the watchdog's dump and ``GET /debug/flight``."""
        with self._lock:
            steps = list(self.steps)
            live = [rt.to_dict() for rt in self._live.values()]
            done = [rt.to_dict() for rt in self._done]
        if last is not None:
            steps = steps[-last:]
        return {"replica": self.replica, "level": self.level,
                "steps": [st.to_dict() for st in steps],
                "live_requests": live, "finished_requests": done,
                "dropped_requests": self.dropped_requests}

    # ------------------------------------------------------------ export
    def export(self, path: str) -> dict:
        """Write this tracer's Chrome ``trace_event`` JSON to ``path``
        and return the object (see :func:`export_chrome_trace` for the
        multi-replica merge)."""
        return export_chrome_trace(path, [self])


# ------------------------------------------------------- profiler bridge
# The one place this module touches jax, imported on first use so the
# recorder stays stdlib-only. With no profiler session running a scope
# costs about a microsecond to enter and leave.
_SCOPE_TYPES: Optional[tuple] = None


def scope(name: str, step_num: Optional[int] = None):
    """A ``jax.profiler`` host scope named ``name``, not yet entered: a
    ``StepTraceAnnotation`` when ``step_num`` is given, else a
    ``TraceAnnotation``. Use it as a context manager."""
    global _SCOPE_TYPES
    if _SCOPE_TYPES is None:
        from jax.profiler import StepTraceAnnotation, TraceAnnotation
        _SCOPE_TYPES = (TraceAnnotation, StepTraceAnnotation)
    if step_num is None:
        return _SCOPE_TYPES[0](name)
    return _SCOPE_TYPES[1](name, step_num=step_num)


def _open(sc):
    sc.__enter__()
    return sc


def _close(sc):
    sc.__exit__(None, None, None)


# -------------------------------------------------- Chrome trace assembly
def _us(t: float) -> float:
    """perf_counter seconds -> trace_event microseconds."""
    return t * 1e6


def chrome_trace(tracers: Sequence[Tracer]) -> dict:
    """Merge one tracer per replica into one Chrome ``trace_event``
    object: ``{"traceEvents": [...], "otherData": {...}}``.

    Lanes: pid = the tracer's replica index (process rows in Perfetto),
    tid 0 = the engine-step lane — one complete ("X") span per step with
    its phase spans and their child spans nested inside — and tid
    ``1 + slot`` = that slot's
    lane, one span per step describing the decode run or prefill chunk
    the slot performed (rid + token counts in ``args``). Per-lane
    timestamps are non-decreasing (step records are ring-ordered and a
    slot does at most one work item per step), which the CI trace-smoke
    job asserts. Request span trees ride in
    ``otherData["requests"]`` keyed by replica."""
    events: List[dict] = []
    requests: Dict[str, list] = {}
    for tr in tracers:
        snap = tr.flight()
        pid = snap["replica"]
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "tid": 0,
                       "args": {"name": f"replica {pid}"}})
        events.append({"name": "thread_name", "ph": "M", "pid": pid,
                       "tid": 0, "args": {"name": "engine steps"}})
        slots_seen = set()
        for rec in snap["steps"]:
            ts0 = _us(rec["t0"])
            events.append({
                "name": f"step {rec['step_id']}", "cat": "step",
                "ph": "X", "ts": ts0, "dur": _us(rec["dur"]),
                "pid": pid, "tid": 0,
                "args": {"produced": rec["produced"],
                         "phases_s": rec["phases"]}})
            for sp in sorted(rec["spans"], key=lambda sp: sp["start"]):
                child = sp["parent"] is not None
                events.append({
                    "name": sp["name"], "cat": "span" if child else "phase",
                    "ph": "X", "ts": _us(sp["start"]),
                    "dur": _us(sp["end"] - sp["start"]), "pid": pid,
                    "tid": 0,
                    "args": {"parent": sp["parent"]} if child else {}})
            for item in rec["work"]:
                s = item["slot"]
                slots_seen.add(s)
                if item["kind"] == "decode":
                    name = f"decode r{item['rid']}"
                    args = {k: item[k] for k in
                            ("rid", "tokens", "drafted",
                             "accepted_drafts") if k in item}
                else:
                    name = (f"prefill r{item['rid']} "
                            f"[{item['start']},"
                            f"{item['start'] + item['count']})")
                    args = {"rid": item["rid"], "start": item["start"],
                            "count": item["count"]}
                events.append({
                    "name": name, "cat": item["kind"], "ph": "X",
                    "ts": ts0, "dur": _us(rec["dur"]), "pid": pid,
                    "tid": 1 + s, "args": args})
        for s in sorted(slots_seen):
            events.append({"name": "thread_name", "ph": "M", "pid": pid,
                           "tid": 1 + s,
                           "args": {"name": f"slot {s}"}})
        requests[str(pid)] = (snap["live_requests"]
                              + snap["finished_requests"])
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"clock": "perf_counter_us",
                          "requests": requests}}


def export_chrome_trace(path: str, tracers: Sequence[Tracer]) -> dict:
    """Serialize :func:`chrome_trace` of ``tracers`` to ``path``
    (Perfetto/chrome://tracing-loadable JSON); returns the object."""
    obj = chrome_trace(tracers)
    with open(path, "w") as f:
        json.dump(obj, f)
    return obj


def phase_coverage(tracers: Sequence[Tracer]) -> float:
    """Fraction of recorded step wall time the phases account for —
    1.0 when every section between begin_step and end_step was in one
    (the acceptance bound is >= 0.95). NaN-free: 1.0 with no steps."""
    tot = cov = 0.0
    for tr in tracers:
        for rec in tr.flight()["steps"]:
            tot += rec["dur"]
            cov += sum(rec["phases"].values())
    return cov / tot if tot > 0 else 1.0
