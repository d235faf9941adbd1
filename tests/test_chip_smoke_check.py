"""chip_smoke.py's own checks, exercised on the CPU at smoke size.

The script only runs on a TPU, so what it relies on is pinned here: its
teacher-forced check passes on a real engine's output and fails once one
token is corrupted; it refuses the CPU without printing a result; the
compile-cache helper leaves a set ``JAX_COMPILATION_CACHE_DIR`` alone and
otherwise picks the fixed directory inside the checkout; and kernels are
never interpreted on a backend other than the CPU.
"""
import importlib.util
import os
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.kernels import ops
from repro.launch import compile_cache
from repro.models import get_model
from repro.serve.engine import ServeEngine

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served(chip_smoke):
    """A bf16 smoke model (the chip's dtype) and a real engine's greedy
    outputs for two of the script's prompts."""
    cfg = get_smoke("minitron-4b")
    assert cfg.dtype == "bfloat16"
    params = get_model(cfg).init(jax.random.key(0), cfg)
    prompts = [p[:n] for p, n in
               zip(chip_smoke.make_prompts(cfg.vocab_size), (40, 16))]
    eng = ServeEngine(cfg, params, slots=2, max_len=128)
    for i, p in enumerate(prompts):
        eng.submit(i, p, max_new=8)
    res = eng.run()
    return cfg, params, prompts, [res[i].out for i in range(len(prompts))]


def test_teacher_forced_check_passes_on_engine_output(chip_smoke, served):
    cfg, params, prompts, outs = served
    reference = chip_smoke.make_reference(cfg)
    for p, o in zip(prompts, outs):
        res = chip_smoke.teacher_forced_check(cfg, params, p, o, reference)
        assert res["ok"], res
        assert res["n"] == 8 and res["exact"] >= 7


def test_teacher_forced_check_fails_on_one_corrupted_token(chip_smoke,
                                                          served):
    cfg, params, prompts, outs = served
    reference = chip_smoke.make_reference(cfg)
    bad = list(outs[0])
    bad[3] = (bad[3] + cfg.vocab_size // 2) % cfg.vocab_size
    res = chip_smoke.teacher_forced_check(cfg, params, prompts[0], bad,
                                          reference)
    assert not res["ok"]
    # the corrupted token is flagged first (later ones may follow: the
    # reference is fed the corrupted context)
    assert res["bad"][0] == 3


def test_chip_smoke_refuses_the_cpu(chip_smoke, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_compile_cache_follows_the_environment(monkeypatch):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.compile_cache_dir() is None
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert updates == []

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.compile_cache_dir()
    assert path == str(ROOT / ".jax_cache")
    assert compile_cache.enable_compile_cache() == path
    assert updates == [("jax_compilation_cache_dir", path)]
    assert compile_cache.compile_cache_dir() == path    # fixed, not fresh


@pytest.mark.parametrize("backend,interpret", [("cpu", True),
                                               ("tpu", False)])
def test_interpret_mode_only_on_the_cpu(monkeypatch, backend, interpret):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ops.interpret_mode() is interpret


def test_interpret_mode_refuses_other_backends(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops.interpret_mode()
