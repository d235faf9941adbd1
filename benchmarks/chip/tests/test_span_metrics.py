"""The readers of the engine tracer's lock wait and child spans
(``submit_wait_p90_ms``, ``input_ms_per_step``,
``readback_ms_per_step``) on run objects built by hand and on the
flight record of a tiny engine on the CPU; and the offline trace
reader ``trace_scopes.py``."""
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import harness
import trace_scopes

DATA = Path(__file__).resolve().parent / "data"


def _step(t0, spans):
    return {"step_id": int(t0), "t0": t0, "dur": 1.0,
            "phases": {"dispatch": 0.5, "sync": 0.5}, "work": [],
            "spans": [{"name": n, "start": t0 + a, "end": t0 + b,
                       "parent": p} for n, a, b, p in spans]}


def _run(steps, traces=None, rids=()):
    return SimpleNamespace(
        steps=steps, t_open=0.0, t_close=10.0, req_traces=traces or {},
        window_requests=[SimpleNamespace(rid=r) for r in rids])


def test_input_and_readback_means_over_the_window():
    steps = [
        _step(1.0, [("inputs", 0.0, 0.001, "pack"),
                    ("inputs", 0.1, 0.103, "dispatch"),
                    ("launch", 0.103, 0.2, "dispatch"),
                    ("wait", 0.5, 0.9, "sync"),
                    ("readback", 0.9, 0.902, "sync")]),
        _step(2.0, [("inputs", 0.1, 0.102, "dispatch"),
                    ("readback", 0.9, 0.904, "sync")]),
        _step(3.0, []),                          # no dispatch: counts 0
        _step(12.0, [("inputs", 0.0, 0.5, "dispatch")]),  # after close
    ]
    run = _run(steps)
    assert harness.layer_metric("input_ms_per_step", run) == \
        pytest.approx((4.0 + 2.0 + 0.0) / 3)
    assert harness.layer_metric("readback_ms_per_step", run) == \
        pytest.approx((2.0 + 4.0 + 0.0) / 3)


def test_span_readers_read_nothing_without_spans():
    steps = [{"step_id": 0, "t0": 1.0, "dur": 1.0, "phases": {},
              "work": []}]
    for name in ("input_ms_per_step", "readback_ms_per_step"):
        assert harness.layer_metric(name, _run(steps)) is None
        assert harness.layer_metric(name, _run([])) is None


def test_submit_wait_p90_over_the_window_requests():
    traces = {r: {"events": [{"t": 0.0, "kind": "submitted",
                              "lock_wait_s": 0.1 * r},
                             {"t": 1.0, "kind": "admitted"}]}
              for r in range(1, 11)}
    run = _run([], traces, rids=range(1, 11))
    # nearest rank: the 9th of the 10 waits 0.1 .. 1.0 s
    assert harness.layer_metric("submit_wait_p90_ms", run) == \
        pytest.approx(900.0)
    # a program that records no wait: nothing, and no error
    bare = {r: {"events": [{"t": 0.0, "kind": "submitted"}]}
            for r in range(3)}
    assert harness.layer_metric(
        "submit_wait_p90_ms", _run([], bare, rids=range(3))) is None
    assert harness.layer_metric("submit_wait_p90_ms", _run([])) is None


def test_readers_on_a_tiny_engine_behind_the_driver():
    """The readers find what the program records: a tiny engine on the
    CPU behind the async driver, its flight record read as the harness
    reads it."""
    import jax

    from repro.configs.base import ModelConfig
    from repro.models import get_model
    from repro.serve.driver import AsyncDriver
    from repro.serve.engine import ServeEngine

    cfg = ModelConfig(name="spans", arch_type="dense", num_layers=2,
                      d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=128, dtype="float32")
    params = get_model(cfg).init(jax.random.key(0), cfg)
    eng = ServeEngine(cfg, params, slots=2, max_len=64, chunk_tokens=16)
    drv = AsyncDriver(eng)
    rng = np.random.default_rng(0)
    streams = [drv.submit(rng.integers(0, 128, size=(n,)).astype(np.int32),
                          max_new=4, rid=i) for i, n in enumerate((20, 6))]
    for s in streams:
        assert s.result(timeout=120.0).done
    drv.stop()
    flight = eng.tracer.flight()
    run = SimpleNamespace(
        steps=flight["steps"], t_open=0.0, t_close=float("inf"),
        req_traces={r["rid"]: r for r in flight["finished_requests"]},
        window_requests=[SimpleNamespace(rid=i) for i in range(2)])
    for name in ("input_ms_per_step", "readback_ms_per_step",
                 "submit_wait_p90_ms"):
        v = harness.layer_metric(name, run)
        assert v is not None and 0.0 <= v < 10_000.0, (name, v)


def test_trace_scopes_helpers():
    assert trace_scopes.scope_of(
        "jit(_mixed_fn)/while/body/closed_call/attention/gather") == \
        "attention"
    assert trace_scopes.scope_of("jit(_mixed_fn)/head/dot_general") == \
        "head"
    assert trace_scopes.scope_of("jit(_mixed_fn)/while") == "(none)"
    # a while of 10 holding ops of 3 and 2, then a lone op of 4
    ops = [("while.1", 0, 10), ("a", 1, 4), ("b", 5, 7), ("c", 12, 16)]
    assert trace_scopes.self_times(ops) == [5, 3, 2, 4]


def test_trace_scopes_reads_the_committed_chip_trace():
    """The committed trace predates the named scopes and serve.* spans:
    its HLO names every op, none maps to a scope, no gap is covered."""
    path = str(DATA / "qwen3-4b.decode_heavy.xplane.pb")
    names = trace_scopes.hlo_op_names(path)
    [(mod, table)] = names.items()
    assert mod.startswith("jit__mixed_fn")
    assert table["copy.71"].startswith("jit(_mixed_fn)/while/body")
    ev = trace_scopes.load(path)
    sc = trace_scopes.device_time_by_scope(ev, names)
    assert sc["steps"] >= 3 and sc["total_s"] > 0.5
    assert sc["mapped_share"] == 0.0
    gaps = trace_scopes.gaps(ev, 1.0)
    assert gaps and all(g["covered"] == 0.0 for g in gaps)
    # three inter-step gaps of 4-5 ms, and the window's tail
    assert sum(4.0 < g["ms"] < 6.0 for g in gaps) == 3
