"""Steer a harness run to a smoke-size model on the CPU, inside a test:
the cell's files as committed, with the model, sizing and lengths cut
to what the CPU runs in seconds, and the chip look skipped."""
from __future__ import annotations

import copy

import harness
import peaks

SMOKE_SERVE = {"slots": 4, "max_len": 256, "kv_pages": 64, "page_size": 16}
SMOKE_LENGTHS = {"prompt_heavy": ({"min": 8, "max": 120, "median": 48},
                                  {"min": 2, "max": 12, "median": 6}),
                 "decode_heavy": ({"min": 4, "max": 16},
                                  {"min": 16, "max": 48, "median": 24})}


#: the model a test runs: the configuration's block at smoke widths
SMOKE_MODEL = {"num_hidden_layers": 2, "hidden_size": 256,
               "num_attention_heads": 8, "num_key_value_heads": 2,
               "head_dim": 32, "intermediate_size": 512, "vocab_size": 512,
               "max_position_embeddings": 256}


def cell_names():
    return [w["name"] for w in harness.benchmark()["workloads"]]


def smoke_files(cell: str):
    f = harness_cell_files(cell)
    c = copy.deepcopy(f.config)
    c.update(SMOKE_MODEL, serve=dict(SMOKE_SERVE))
    f.config = c
    mix = copy.deepcopy(f.mix)
    p, o = SMOKE_LENGTHS[f.workload["traffic"]]
    mix["prompt"].update(p)
    mix["output"].update(o)
    mix["warmup_s"] = 1.0
    mix["drain_s"] = 20.0
    f.mix = mix
    load = dict(f.load)
    if "clients" in load:
        load["clients"] = SMOKE_SERVE["slots"]
    else:
        load["rate_rps"] = 3.0
    f.load = load
    return f


harness_cell_files = harness.cell_files


def steer(monkeypatch, cell: str):
    """Patch the harness so ``harness.run(cell, ...)`` runs the smoke
    model on the CPU devices."""
    import jax
    f = smoke_files(cell)
    chips = f.workload["chips"]
    monkeypatch.setattr(harness, "cell_files", lambda name: f)
    monkeypatch.setattr(harness, "check_platform",
                        lambda n: jax.devices()[:n])
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")
    monkeypatch.setattr(harness.peaks_table, "peaks",
                        lambda kind: peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(harness, "CHECK_TOKENS", 60)
    monkeypatch.setattr(harness, "CHECK_MIN_TOKENS", 20)
    return f, chips
