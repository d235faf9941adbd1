"""The reader of the mixed step's attention row width
(``attn_row_share``) on run objects built by hand and on the flight
record of a tiny engine behind the async driver on the CPU."""
from types import SimpleNamespace

import numpy as np
import pytest

import harness


def _step(t0, attn_rows=None, budget=None):
    rec = {"step_id": int(t0), "t0": t0, "dur": 1.0, "phases": {},
           "work": []}
    if attn_rows is not None:
        rec.update(rows=attn_rows // 2, attn_rows=attn_rows, budget=budget)
    return rec


def _run(steps):
    return SimpleNamespace(steps=steps, t_open=0.0, t_close=10.0)


def test_share_of_the_budget_over_the_window():
    steps = [_step(1.0, 32, 256), _step(2.0, 8, 256), _step(3.0, 256, 256),
             dict(_step(4.0), rows=None, attn_rows=None,
                  budget=None),                 # no program launched
             _step(12.0, 256, 256)]             # after the window closed
    assert harness.layer_metric("attn_row_share", _run(steps)) == \
        pytest.approx(100.0 * (32 + 8 + 256) / (3 * 256))


def test_share_reads_nothing_without_row_records():
    # a program that records no row width (the records lack the field)
    assert harness.layer_metric("attn_row_share", _run([_step(1.0)])) is None
    # ...or records it as None on every step, or has no steps at all
    bare = [dict(_step(1.0), rows=None, attn_rows=None, budget=None)]
    assert harness.layer_metric("attn_row_share", _run(bare)) is None
    assert harness.layer_metric("attn_row_share", _run([])) is None


def test_share_on_a_tiny_engine_behind_the_driver():
    import jax

    from repro.configs.base import ModelConfig
    from repro.models import get_model
    from repro.serve.driver import AsyncDriver
    from repro.serve.engine import ServeEngine

    cfg = ModelConfig(name="rows", arch_type="dense", num_layers=2,
                      d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=128, dtype="float32")
    params = get_model(cfg).init(jax.random.key(0), cfg)
    eng = ServeEngine(cfg, params, slots=2, max_len=64, chunk_tokens=32)
    drv = AsyncDriver(eng)
    rng = np.random.default_rng(0)
    streams = [drv.submit(rng.integers(0, 128, size=(n,)).astype(np.int32),
                          max_new=6, rid=i) for i, n in enumerate((20, 6))]
    for s in streams:
        assert s.result(timeout=120.0).done
    drv.stop()
    run = SimpleNamespace(steps=eng.tracer.flight()["steps"], t_open=0.0,
                          t_close=float("inf"))
    v = harness.layer_metric("attn_row_share", run)
    # the prefill step runs the full budget, the decode steps 8 rows
    assert v is not None and 25.0 < v < 100.0, v
