"""Each cell end to end on the CPU at smoke size, through the same
entry as on the chip, and the faults its check must catch."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import harness
import smoke

CELLS = smoke.cell_names()
ROOT = Path(harness.ROOT)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_at_smoke_size(monkeypatch, cell):
    smoke.steer(monkeypatch, cell)
    out = harness.run(cell, 2**33 + 17, 3.0, trace=False)
    assert out["correct"], out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in harness.benchmark()["end_to_end"]
            if harness.applies(m, cell)}
    assert set(out["metrics"]) == want
    assert out["metrics"]["setup_s"]["value"] > 0
    assert list(out)[-1] == "check"
    assert out["device"]["count"] == smoke.smoke_files(cell).workload[
        "chips"]


def test_closed_loop_counts_requests_that_outlast_the_window(monkeypatch):
    """Answers longer than the window: every client's request is in
    flight in it, cut short at the close, and still counted and
    checked."""
    cell = "qwen3-4b.decode_heavy"
    f = smoke.steer(monkeypatch, cell)[0]
    f.mix["output"].update({"min": 200, "max": 240, "median": 220})
    out = harness.run(cell, 2**31 + 5, 1.0, trace=False)
    assert out["attempted"] == f.load["clients"], out
    assert out["failed"] == 0
    assert out["correct"], out["check"]
    assert out["metrics"]["output_tok_per_s"]["value"] > 0


def test_traced_run_reads_every_metric(monkeypatch):
    """A traced run: the program-span readers read the run, the trace
    readers a summary as trace_reduce gives it (a CPU trace has no TPU
    plane to reduce)."""
    import trace_reduce
    cell = "qwen3-4b.prompt_heavy"
    smoke.steer(monkeypatch, cell)
    summary = {"window_s": 2.0, "busy_s": 1.5,
               "devices": {0: {"busy_s": 1.5, "idle_share": 0.25,
                               "steps": 10,
                               "step_module": "jit__mixed_fn"}},
               "top_ops": [["fusion.1", 1.0]],
               "idle_gaps": [["bench.engine_step", 0.01]]}
    monkeypatch.setattr(trace_reduce, "reduce", lambda path, devices: summary)
    out = harness.run(cell, 5, 3.0, trace=True)
    m = out["metrics"]
    want = {x["name"] for x in harness.benchmark()["per_layer"]
            if harness.applies(x, cell)}
    assert set(m) == want
    assert 0 < m["step_mfu"]["value"] < 100
    assert m["device_idle"]["value"] == 25.0
    assert out["device"]["busy_s"] == 1.5
    assert out["breakdown"]["device_ops"] == [["fusion.1", 1.0]]


def _alter_tokens(monkeypatch):
    """A token altered where it is produced: every sampled token moves
    half the vocabulary away."""
    import repro.serve.engine as eng
    real = eng.sample_tokens

    def bad(logits, **kw):
        tok = real(logits, **kw)
        return (tok + logits.shape[-1] // 2) % logits.shape[-1]

    monkeypatch.setattr(eng, "sample_tokens", bad)


def _drop_cache_writes(monkeypatch):
    """A step that leaves its state unchanged: every row of the mixed
    step writes its K/V to the null page, so the cache never changes."""
    import jax.numpy as jnp
    import repro.serve.engine as eng
    real = eng.ServeEngine._mixed_fn

    def bad(self, params, cache, tokens, pos, slot, active, wnull, rng):
        return real(self, params, cache, tokens, pos, slot, active,
                    jnp.ones_like(wnull), rng)

    monkeypatch.setattr(eng.ServeEngine, "_mixed_fn", bad)


FAULTS = [(c, f) for c in CELLS
          for f in ("token_altered", "state_unchanged")]


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_fault_makes_correct_false(monkeypatch, cell, fault):
    smoke.steer(monkeypatch, cell)
    {"token_altered": _alter_tokens,
     "state_unchanged": _drop_cache_writes}[fault](monkeypatch)
    out = harness.run(cell, 11, 3.0, trace=False)
    assert not out["correct"], out["check"]
    chk = out["check"]["max_gap_logits"]
    assert chk["value"] > chk["limit"]


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "qwen3-4b.prompt_heavy", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    r = _run_py(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_benchmark_alone_exits_nonzero_without_result(tmp_path):
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmarks" / "chip",
                    tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_py(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
