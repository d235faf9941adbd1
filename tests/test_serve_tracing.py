"""PR 10 observability layer (serve/tracing.py + its weave): request
span trees whose token counters match the streamed output exactly, step
phase laps covering >= 95% of step wall time, bounded flight-recorder
rings, Chrome/Perfetto trace_event export (monotonic per-lane
timestamps, dp2 merge with one pid lane per replica and no id
collisions), the ``serve_step_phase_seconds{phase=...}`` histogram fed
by the driver drain, render-vs-observe hammer on every metric class, and
the lock-free ``/healthz`` + ``/debug/*`` endpoints answering while a
stalled step holds the driver lock.
"""
import json
import re
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import get_model
from repro.serve.driver import AsyncDriver
from repro.serve.engine import ServeEngine
from repro.serve.metrics import (Histogram, LabeledHistogram,
                                 MetricsRegistry, ServeMetrics)
from repro.serve.parallel import ReplicaRouter
from repro.serve.server import ServeHTTPServer
from repro.serve.tracing import (LEVEL_DETAIL, LEVEL_OFF, NULL_STEP,
                                 StepTrace, Tracer, chrome_trace,
                                 phase_coverage)

CFG = ModelConfig(name="trace-dense", arch_type="dense", num_layers=2,
                  d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                  vocab_size=128, dtype="float32")


def _params(cfg, seed=0):
    return get_model(cfg).init(jax.random.key(seed), cfg)


def _prompts(rng, cfg, lens):
    return [rng.integers(0, cfg.vocab_size, size=(int(n),)).astype(np.int32)
            for n in lens]


def _run_engine(eng, prompts, max_new=6):
    for i, p in enumerate(prompts):
        eng.submit(i, p, max_new=max_new)
    return eng.run()


# ------------------------------------------------------------- unit level

def test_step_trace_laps_partition_wall_time():
    st = StepTrace(7, "pack")
    time.sleep(0.01)
    st.enter("dispatch")
    st.sub("launch")
    time.sleep(0.01)
    st.enter("pack")                    # repeats accumulate
    st.enter("pack")                    # re-entering the current phase
    tr = Tracer()
    tr.end_step(st, produced=3)
    [rec] = tr.flight()["steps"]
    assert rec["step_id"] == 7 and rec["produced"] == 3
    assert list(rec["phases"]) == ["pack", "dispatch"]
    assert rec["phases"]["pack"] >= 0.01
    assert rec["phases"]["dispatch"] >= 0.01
    # phases partition [t0, end_step): coverage is ~100% of dur
    assert sum(rec["phases"].values()) <= rec["dur"]
    assert sum(rec["phases"].values()) >= 0.95 * rec["dur"]
    # one span per phase entry, and the child inside its phase
    names = [(sp["name"], sp["parent"]) for sp in rec["spans"]]
    assert names == [("pack", None), ("launch", "dispatch"),
                     ("dispatch", None), ("pack", None)]
    [launch] = [sp for sp in rec["spans"] if sp["name"] == "launch"]
    [disp] = [sp for sp in rec["spans"] if sp["name"] == "dispatch"]
    assert disp["start"] <= launch["start"] <= launch["end"] \
        <= disp["end"]


def test_tracer_rings_are_bounded():
    tr = Tracer(max_steps=4, max_requests=3, max_events=5)
    for i in range(10):
        tr.end_step(tr.begin_step(i), produced=0)
    assert [r["step_id"] for r in tr.flight()["steps"]] == [6, 7, 8, 9]
    for rid in range(9):
        tr.req_event(rid, "submitted")
        for _ in range(10):             # overflow the event cap
            tr.req_event(rid, "noise")
        tr.finish_request(rid, "completed")
    snap = tr.flight()
    assert len(snap["finished_requests"]) == 3     # ring, newest kept
    assert snap["finished_requests"][-1]["rid"] == 8
    assert all(r["dropped_events"] > 0 for r in snap["finished_requests"])
    # every step is published once; the ring keeps the newest 4
    assert tr.published == 10
    n, recs = tr.steps_since(7)
    assert n == 10 and [r.step_id for r in recs] == [7, 8, 9]
    n, recs = tr.steps_since(0)
    assert [r.step_id for r in recs] == [6, 7, 8, 9]
    assert tr.steps_since(10) == (10, [])


def test_level0_is_off_and_null_step_is_shared():
    tr = Tracer(level=LEVEL_OFF)
    assert not tr.enabled
    assert tr.begin_step(0) is NULL_STEP
    NULL_STEP.enter("x")
    NULL_STEP.sub("y")
    NULL_STEP.note_decode(0, 0, 1)
    NULL_STEP.note_chunk(0, 0, 0, 4)   # all no-ops
    tr.end_step(NULL_STEP, produced=5)
    tr.req_event(0, "submitted")
    tr.req_tokens(0, 3)
    tr.finish_request(0, "completed")
    snap = tr.flight()
    assert snap["steps"] == [] and snap["live_requests"] == [] \
        and snap["finished_requests"] == []


# -------------------------------------------------------- engine weaving

def test_span_tree_matches_streamed_token_count():
    """The acceptance pin: RequestTrace.tokens == len(request.out) for
    every request, across chunked prefill AND speculative decode."""
    from repro.serve.speculative import SpecConfig
    params = _params(CFG)
    rng = np.random.default_rng(0)
    # long/short mix forces multi-step chunked prefill; a repeated motif
    # makes the ngram drafter land multi-token accepts
    motif = rng.integers(0, CFG.vocab_size, size=(5,))
    prompts = _prompts(rng, CFG, (40, 6, 23)) + \
        [np.tile(motif, 6).astype(np.int32)]
    eng = ServeEngine(CFG, params, slots=2, max_len=96, paged=True,
                      mixed=True, chunk_tokens=16,
                      spec=SpecConfig(k=4, drafter="ngram"))
    results = _run_engine(eng, prompts, max_new=8)
    assert len(results) == len(prompts)
    for rid, req in results.items():
        tree = eng.tracer.request_trace(rid)
        assert tree is not None and tree["done"]
        assert tree["outcome"] == "completed"
        assert tree["tokens"] == len(req.out)
        kinds = [e["kind"] for e in tree["events"]]
        assert kinds[0] == "submitted"
        assert "admitted" in kinds and "first_token" in kinds
        assert kinds[-1] == "completed"
        assert kinds.index("admitted") < kinds.index("first_token")
    # chunked prefill is accounted token-exactly too (no prefix cache:
    # every prompt position goes through exactly one chunk)
    for rid, p in enumerate(prompts):
        assert eng.tracer.request_trace(rid)["chunk_tokens"] == len(p)


def test_phase_coverage_and_step_accounting():
    params = _params(CFG)
    prompts = _prompts(np.random.default_rng(1), CFG, (30, 5, 12))
    eng = ServeEngine(CFG, params, slots=2, max_len=64, paged=True,
                      mixed=True, chunk_tokens=16)
    _run_engine(eng, prompts, max_new=6)
    cov = phase_coverage([eng.tracer])
    assert cov >= 0.95, cov             # the acceptance bound
    steps = eng.tracer.flight()["steps"]
    assert steps, "no step records"
    # every step record's phases sit inside its duration and the mixed
    # phase vocabulary is what the engine laps
    for rec in steps:
        assert sum(rec["phases"].values()) <= rec["dur"] + 1e-9
        assert set(rec["phases"]) <= {"bookkeeping", "draft", "pack",
                                      "dispatch", "sync"}
    # produced totals across the ring match the engine counter
    assert sum(r["produced"] for r in steps) == eng.stats["decode_tokens"]


def test_legacy_path_is_traced_too():
    params = _params(CFG)
    prompts = _prompts(np.random.default_rng(2), CFG, (9, 5))
    eng = ServeEngine(CFG, params, slots=2, max_len=64, paged=True,
                      mixed=False)
    results = _run_engine(eng, prompts, max_new=4)
    for rid, req in results.items():
        tree = eng.tracer.request_trace(rid)
        assert tree["tokens"] == len(req.out) and tree["done"]
    recs = eng.tracer.flight()["steps"]
    assert recs and all("dispatch" in r["phases"] for r in recs)
    assert phase_coverage([eng.tracer]) >= 0.95


def test_trace_level_2_adds_detail_and_level_0_adds_nothing():
    params = _params(CFG)
    prompts = _prompts(np.random.default_rng(3), CFG, (25, 6))
    eng2 = ServeEngine(CFG, params, slots=2, max_len=64, paged=True,
                       mixed=True, chunk_tokens=16,
                       trace_level=LEVEL_DETAIL)
    res2 = _run_engine(eng2, prompts, max_new=4)
    kinds = [e["kind"]
             for e in eng2.tracer.request_trace(0)["events"]]
    assert "prefill_chunk" in kinds and "decode" in kinds
    eng0 = ServeEngine(CFG, params, slots=2, max_len=64, paged=True,
                       mixed=True, chunk_tokens=16, trace_level=0)
    res0 = _run_engine(eng0, prompts, max_new=4)
    assert eng0.tracer.flight()["steps"] == []
    assert eng0.tracer.request_trace(0) is None
    # tracing level never changes the tokens
    assert {r: list(v.out) for r, v in res0.items()} \
        == {r: list(v.out) for r, v in res2.items()}


# ------------------------------------------------------------ export shape

def _lane_ts_monotonic(events):
    lanes = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        lane = (ev["pid"], ev["tid"], ev.get("cat"))
        assert ev["ts"] >= lanes.get(lane, float("-inf")), lane
        lanes[lane] = ev["ts"]
    return lanes


def test_chrome_export_is_valid_trace_event_json(tmp_path):
    params = _params(CFG)
    prompts = _prompts(np.random.default_rng(4), CFG, (30, 5, 14))
    eng = ServeEngine(CFG, params, slots=2, max_len=64, paged=True,
                      mixed=True, chunk_tokens=16)
    _run_engine(eng, prompts, max_new=5)
    path = tmp_path / "trace.json"
    obj = eng.export_trace(str(path))
    disk = json.loads(path.read_text())
    assert disk == obj
    evs = disk["traceEvents"]
    assert isinstance(evs, list) and evs
    for ev in evs:
        assert {"name", "ph", "pid", "tid"} <= set(ev)
        if ev["ph"] == "X":
            assert "ts" in ev and "dur" in ev and ev["dur"] >= 0
    lanes = _lane_ts_monotonic(evs)
    assert lanes, "no complete events"
    # the step lane exists and slot lanes carry named work spans
    names = {e["name"] for e in evs}
    assert any(n.startswith("step ") for n in names)
    assert any(n.startswith(("decode r", "prefill r")) for n in names)
    # metadata rows label every lane that has spans
    assert {e["args"]["name"] for e in evs if e["name"] == "thread_name"} \
        >= {"engine steps", "slot 0"}
    # request span trees ride in otherData
    assert set(disk["otherData"]["requests"]) == {"0"}
    assert {r["rid"] for r in disk["otherData"]["requests"]["0"]} \
        == set(range(len(prompts)))


def test_dp2_trace_merge_has_both_replica_lanes(tmp_path):
    params = _params(CFG)
    rng = np.random.default_rng(5)
    prompts = _prompts(rng, CFG, (18, 7, 22, 9, 13, 6))
    router = ReplicaRouter(CFG, params, dp=2, tp=1, slots=2, max_len=64,
                           paged=True, mixed=True, chunk_tokens=16)
    assert [t.replica for t in router.tracers] == [0, 1]
    for i, p in enumerate(prompts):
        router.submit(i, p, max_new=4)
    router.run()
    path = tmp_path / "dp2.json"
    obj = router.export_trace(str(path))
    evs = json.loads(path.read_text())["traceEvents"]
    pids = {e["pid"] for e in evs if e["ph"] == "X"}
    assert pids == {0, 1}, "both replica lanes must appear"
    _lane_ts_monotonic(evs)
    # no rid collisions ACROSS lanes: each request's spans live only in
    # its home replica's pid
    for rid in range(len(prompts)):
        home = router.replica_of(rid)
        owning = {e["pid"] for e in evs
                  if e["ph"] == "X" and e.get("args", {}).get("rid") == rid}
        assert owning == {home}
    # per-replica step ids overlap (both start at 0) but stay in
    # distinct pid lanes — that is the collision-avoidance contract
    assert set(obj["otherData"]["requests"]) == {"0", "1"}
    flight = router.flight()
    assert [f["replica"] for f in flight["replicas"]] == [0, 1]


# ------------------------------------------------- driver + metrics drain

def test_driver_feeds_phase_histogram_and_render():
    params = _params(CFG)
    prompts = _prompts(np.random.default_rng(6), CFG, (20, 6))
    eng = ServeEngine(CFG, params, slots=2, max_len=64, paged=True,
                      mixed=True, chunk_tokens=16)
    drv = AsyncDriver(eng, start=False)
    streams = [drv.submit(p, max_new=4, rid=i)
               for i, p in enumerate(prompts)]
    drv.start()
    assert drv.join(timeout=120.0)
    drv.stop(drain=False)
    for s in streams:
        assert s.result(timeout=0.0).done
    hist = drv.metrics.step_phase
    assert {"dispatch", "pack", "sync"} <= set(hist.labels())
    assert hist.child("dispatch").count >= eng.stats["step_count"] > 0
    text = drv.render_metrics()
    assert text.count("# TYPE serve_step_phase_seconds summary") == 1
    assert 'serve_step_phase_seconds{phase="dispatch",quantile="0.5"}' \
        in text
    assert 'serve_step_phase_seconds_sum{phase="dispatch"}' in text
    assert 'serve_step_phase_seconds_count{phase="dispatch"}' in text
    # driver-side health surface agrees with the engine
    h = drv.health()
    assert h["queue_depth"] == 0 and h["step_count"] > 0
    assert h["last_step_age_s"] is not None
    # flight + trace surfaces exist on the driver too
    assert drv.flight()["replicas"][0]["steps"]
    assert drv.trace()["traceEvents"]


def test_metrics_render_hammer_under_concurrent_observes():
    """Satellite: every metric class renders consistently while another
    thread observes — the single-lock snapshot must never produce a
    quantile/_sum/_count tear or crash."""
    reg = MetricsRegistry()
    h = reg.histogram("h_seconds", "plain", window=512)
    lh = reg.labeled_histogram("lh_seconds", "labeled", label="phase",
                               window=512)
    c = reg.counter("c_total")
    g = reg.gauge("g_now")
    stop = threading.Event()

    def pound():
        i = 0
        while not stop.is_set():
            h.observe(i % 7)
            lh.observe("a" if i % 2 else "b", i % 5)
            c.inc()
            g.set(i)
            i += 1

    threads = [threading.Thread(target=pound, daemon=True)
               for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(300):
            text = reg.render()
            for line in text.splitlines():
                if line.startswith("#") or not line:
                    continue
                val = line.rsplit(" ", 1)[1]
                assert val == "NaN" or float(val) >= 0
        # snapshot consistency: sum/count/window from ONE lock hold
        for _ in range(200):
            window, total, count = h.snapshot()
            assert len(window) <= 512
            assert count >= len(window)
            assert all(window[i] <= window[i + 1]
                       for i in range(len(window) - 1))
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5.0)


# ------------------------------------------------------ HTTP observability

def test_healthz_and_debug_endpoints_respond_while_step_stalled():
    """Satellite: a wedged-but-alive engine still answers /healthz —
    lock-free — with a growing last_step_age_s and the real queue depth;
    /debug/flight and /debug/trace answer too."""
    params = _params(CFG)
    prompts = _prompts(np.random.default_rng(8), CFG, (6, 5, 7))
    eng = ServeEngine(CFG, params, slots=2, max_len=64, paged=True,
                      mixed=True, chunk_tokens=16)
    eng.submit(100, prompts[0], max_new=2)
    eng.run()                           # warm traces

    calls = {"n": 0}
    stalled = threading.Event()

    def step_fn(drv):
        calls["n"] += 1
        if calls["n"] >= 2:             # wedge from the second step on
            stalled.set()
            while not drv.abort_step.is_set():
                time.sleep(0.005)
            return
        drv.engine.step()

    drv = AsyncDriver(eng, step_fn=step_fn, start=False)
    server = ServeHTTPServer(drv, port=0)
    try:
        for i, p in enumerate(prompts):
            drv.submit(p, max_new=8, rid=i)
        drv.start()
        assert stalled.wait(timeout=30.0)
        time.sleep(0.05)                # let the stall age a little

        def get(path):
            with urllib.request.urlopen(server.url + path,
                                        timeout=10) as r:
                return json.loads(r.read().decode())

        # the driver lock is HELD by the wedged step right now; these
        # must all answer anyway
        health = get("/healthz")
        assert health["status"] == "ok"
        assert health["step_in_flight_s"] > 0.0
        assert health["last_step_age_s"] > 0.0
        assert health["queue_depth"] >= 1      # slots=2, 3 requests
        assert health["step_count"] >= 1
        flight = get("/debug/flight")
        [rep] = flight["replicas"]
        assert rep["steps"], "flight ring must hold the warm steps"
        assert flight["snapshot"]["active"]
        trace = get("/debug/trace")
        assert trace["traceEvents"]
    finally:
        drv.abort_step.set()
        server.close(drain=False)


def test_scheduler_explain_lands_on_submitted_event():
    from repro.serve.scheduler import Priority
    params = _params(CFG)
    eng = ServeEngine(CFG, params, slots=1, max_len=64, paged=True,
                      mixed=True, chunk_tokens=8, scheduler=Priority())
    p = np.arange(5, dtype=np.int32) % CFG.vocab_size
    eng.submit(0, p, max_new=2, priority=3)
    eng.run()
    tree = eng.tracer.request_trace(0)
    sub = next(e for e in tree["events"] if e["kind"] == "submitted")
    assert sub["policy"] == "priority" and sub["priority"] == 3
    assert sub["prompt_tokens"] == 5


def test_tracing_overhead_within_bounds():
    """Enabled-vs-disabled throughput on the bench smoke stays within
    5% — here we assert the cheap proxy: identical outputs and a wide
    sanity margin on wall time (CI's trace-smoke pins the real bench)."""
    params = _params(CFG)
    prompts = _prompts(np.random.default_rng(9), CFG, (16, 8, 12, 6))

    def run(level):
        eng = ServeEngine(CFG, params, slots=2, max_len=64, paged=True,
                          mixed=True, chunk_tokens=16,
                          trace_level=level)
        for i, p in enumerate(prompts):
            eng.submit(i, p, max_new=6)
        t0 = time.perf_counter()
        res = eng.run()
        return time.perf_counter() - t0, \
            {r: list(v.out) for r, v in res.items()}

    run(1)                      # warm compile caches for both paths
    run(0)
    t_on, out_on = run(1)
    t_off, out_off = run(0)
    assert out_on == out_off
    # generous CI-safe envelope; the real 5% bound rides on the bench
    assert t_on < 3.0 * t_off + 0.25, (t_on, t_off)


# ------------------------------------- spans on the profiler's clock

def _children(rec, name):
    return [sp for sp in rec["spans"] if sp["name"] == name]


def _assert_nested(rec, child, parent):
    """Every ``child`` span of the step names ``parent`` and lies inside
    one ``parent`` phase span of the same step."""
    phases = [sp for sp in rec["spans"]
              if sp["name"] == parent and sp["parent"] is None]
    kids = _children(rec, child)
    assert kids, (child, rec["spans"])
    for sp in kids:
        if sp["parent"] != parent:
            continue
        assert any(ph["start"] <= sp["start"] <= sp["end"] <= ph["end"]
                   for ph in phases), (sp, phases)
    return [sp for sp in kids if sp["parent"] == parent]


@pytest.mark.parametrize("mixed", [True, False])
def test_step_child_spans_nest_in_dispatch_and_sync(mixed):
    params = _params(CFG)
    prompts = _prompts(np.random.default_rng(10), CFG, (30, 5, 12))
    eng = ServeEngine(CFG, params, slots=2, max_len=64, paged=True,
                      mixed=mixed, chunk_tokens=16)
    _run_engine(eng, prompts, max_new=6)
    assert phase_coverage([eng.tracer]) >= 0.95
    vocab = {"bookkeeping", "draft", "pack", "dispatch", "sync"} if mixed \
        else {"bookkeeping", "admit", "pack", "dispatch", "sync"}
    ran = 0
    for rec in eng.tracer.flight()["steps"]:
        assert set(rec["phases"]) <= vocab
        # the phase spans are the phases dict, entry by entry
        tops = [sp for sp in rec["spans"] if sp["parent"] is None]
        for ph, sec in rec["phases"].items():
            got = sum(sp["end"] - sp["start"] for sp in tops
                      if sp["name"] == ph)
            assert got == pytest.approx(sec, rel=1e-9, abs=1e-12)
        for sp in rec["spans"]:
            assert rec["t0"] <= sp["start"] <= sp["end"] \
                <= rec["t0"] + rec["dur"] + 1e-9
        if "dispatch" not in rec["phases"]:
            continue
        ran += 1
        assert len(_assert_nested(rec, "inputs", "dispatch")) == 1
        assert len(_assert_nested(rec, "launch", "dispatch")) == 1
        assert len(_assert_nested(rec, "wait", "sync")) == 1
        assert len(_assert_nested(rec, "readback", "sync")) == 1
        # the page-table push is an input too, inside pack
        for sp in _children(rec, "inputs"):
            assert sp["parent"] in ("pack", "dispatch")
            if sp["parent"] == "pack":
                _assert_nested(rec, "inputs", "pack")
        kids = sum(sp["end"] - sp["start"] for sp in rec["spans"]
                   if sp["parent"] == "dispatch")
        assert kids <= rec["phases"]["dispatch"] + 1e-9
    assert ran >= 3


def test_profiler_trace_holds_serve_spans(tmp_path):
    """Two engine steps under a jax.profiler trace on the CPU: the
    step, its phases and child spans are host events named serve.*."""
    from jax.profiler import ProfileData
    params = _params(CFG)
    prompts = _prompts(np.random.default_rng(11), CFG, (20, 6))
    eng = ServeEngine(CFG, params, slots=2, max_len=64, paged=True,
                      mixed=True, chunk_tokens=16)
    for i, p in enumerate(prompts):
        eng.submit(i, p, max_new=4)
    eng.step()                          # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.step()
        eng.step()
    finally:
        jax.profiler.stop_trace()
    [path] = list(tmp_path.rglob("*.xplane.pb"))
    pd = ProfileData.from_file(str(path))
    names = {e.name for plane in pd.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert {"serve.step", "serve.pack", "serve.dispatch", "serve.inputs",
            "serve.launch", "serve.sync", "serve.wait",
            "serve.readback"} <= names, sorted(
                n for n in names if n.startswith("serve"))


def test_mixed_fn_carries_named_scopes():
    """Each named scope of the serve decode path reaches the op_name
    metadata of the lowered step program."""
    params = _params(CFG)
    eng = ServeEngine(CFG, params, slots=2, max_len=64, paged=True,
                      mixed=True, chunk_tokens=16)
    eng.submit(0, np.arange(5, dtype=np.int32), max_new=2)
    eng.run()
    T = eng.chunk_tokens
    zeros = np.zeros(T, np.int32)
    lowered = eng._mixed.lower(
        eng.params, eng._cache, np.zeros((T, 1), np.int32), zeros, zeros,
        np.ones(T, bool), np.ones(T, bool), None)
    op_names = set(re.findall(r'op_name="([^"]*)"',
                              lowered.compile().as_text()))
    for name in ("layers", "qkv", "kv_write", "attention", "attn_out", "mlp",
                 "head", "sample"):
        assert any(f"/{name}/" in n or n.endswith(f"/{name}")
                   for n in op_names), name


def test_driver_records_lock_wait_on_submitted_event():
    params = _params(CFG)
    eng = ServeEngine(CFG, params, slots=2, max_len=64, paged=True,
                      mixed=True, chunk_tokens=16)
    drv = AsyncDriver(eng, start=False)
    held, done = threading.Event(), threading.Event()

    def hold():
        with drv._lock:
            held.set()
            time.sleep(0.2)
        done.set()

    t = threading.Thread(target=hold, daemon=True)
    t.start()
    assert held.wait(timeout=10.0)
    drv.submit(np.arange(5, dtype=np.int32), max_new=2, rid=0)
    assert done.is_set()
    t.join(timeout=10.0)
    assert not t.is_alive()
    drv.submit(np.arange(4, dtype=np.int32), max_new=2, rid=1)
    waits = {}
    for rid in (0, 1):
        sub = next(e for e in eng.tracer.request_trace(rid)["events"]
                   if e["kind"] == "submitted")
        assert sub["prompt_tokens"] == 5 - rid   # the engine's fields stay
        waits[rid] = sub["lock_wait_s"]
    assert waits[0] >= 0.15, waits
    assert 0.0 <= waits[1] < 0.15, waits
    drv.stop(drain=False)


def test_trace_levels_give_identical_tokens():
    from repro.serve.speculative import SpecConfig
    params = _params(CFG)
    rng = np.random.default_rng(12)
    motif = rng.integers(0, CFG.vocab_size, size=(4,))
    prompts = _prompts(rng, CFG, (26, 7)) + \
        [np.tile(motif, 5).astype(np.int32)]
    outs = {}
    for level in (0, 1, 2):
        for kind, kw in (("mixed", {"mixed": True, "chunk_tokens": 16}),
                         ("spec", {"mixed": True, "chunk_tokens": 16,
                                   "spec": SpecConfig(k=3)}),
                         ("legacy", {"mixed": False})):
            eng = ServeEngine(CFG, params, slots=2, max_len=64, paged=True,
                              trace_level=level, **kw)
            res = _run_engine(eng, prompts, max_new=5)
            outs[level, kind] = {r: list(v.out) for r, v in res.items()}
    for kind in ("mixed", "spec", "legacy"):
        assert outs[0, kind] == outs[1, kind] == outs[2, kind], kind
    assert outs[0, "mixed"] == outs[0, "spec"] == outs[0, "legacy"]
