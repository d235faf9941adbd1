"""The yardstick's pieces on the CPU: traffic, FLOPs, peaks."""
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

import flops
import harness
import peaks
import traffic
import weights

CHIP = Path(harness.HERE)
MIXES = sorted(p.stem for p in (CHIP / "mixes").glob("*.json"))


def _mix(name):
    return json.loads((CHIP / "mixes" / f"{name}.json").read_text())


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_offers_the_same_work_in_another_order(mix):
    m = _mix(mix)

    def draw(seed):
        if m["loop"] == "open":
            reqs = traffic.open_loop(m, 1.7, 50, 1000, seed)
            gaps = np.diff([r.due for r in reqs if r.in_window])
        else:
            reqs = [r for lst in traffic.closed_loop(m, 16, 1000, seed)
                    for r in lst]
            gaps = np.array([])
        return ([len(r.prompt) for r in reqs], [r.max_new for r in reqs],
                sorted(np.round(gaps, 9)), reqs)

    a, b = draw(1), draw(2**33 + 5)
    assert Counter(a[0]) == Counter(b[0])
    assert Counter(a[1]) == Counter(b[1])
    assert a[0] != b[0] or a[1] != b[1]
    if m["loop"] == "open":
        # the last gap of a window closes it, so one gap differs
        assert np.allclose(sorted(a[2])[:-1], sorted(b[2])[:-1], atol=0.6)
        win = [r for r in a[3] if r.in_window]
        assert len(win) == round(1.7 * 50)
        assert all(0 <= r.due < 50 for r in win)
    lo, hi = m["prompt"]["min"], m["prompt"]["max"]
    assert min(a[0]) >= lo and max(a[0]) <= hi


def test_same_seed_same_traffic():
    m = _mix("prompt_heavy")
    x = traffic.open_loop(m, 1.5, 20, 5000, 2**40 + 3)
    y = traffic.open_loop(m, 1.5, 20, 5000, 2**40 + 3)
    assert all(np.array_equal(p.prompt, q.prompt) and p.due == q.due
               for p, q in zip(x, y))


def test_stratified_lognormal_quantiles():
    q = traffic.quantiles({"dist": "lognormal", "median": 384,
                           "sigma": 0.6, "min": 64, "max": 960}, 101)
    assert q[50] == 384 and q.min() >= 64 and q.max() == 960
    assert list(q) == sorted(q)


def test_onoff_keeps_the_mean_rate():
    m = dict(_mix("prompt_heavy"),
             arrivals={"kind": "onoff", "burst": 4, "period_s": 8})
    reqs = [r for r in traffic.open_loop(m, 2.0, 48, 100, 1) if r.in_window]
    due = np.array([r.due for r in reqs])
    assert len(reqs) == 96 and due.max() < 48
    assert np.all(np.mod(due, 8) < 2 + 1e-9)     # sent in the first quarter


def test_primer_books_the_widest_bucket():
    m = _mix("prompt_heavy")
    reqs = traffic.open_loop(m, 1.7, 50, 1000, 9)
    p = traffic.primer(reqs, 1024, 1000, 9)
    longest = max(len(r.prompt) + r.max_new - 1 for r in reqs)
    assert len(p.prompt) + p.max_new - 1 >= min(longest, 1023)
    assert len(p.prompt) < 1024


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert traffic.percentile(v, 0.9) == 90
    assert traffic.percentile(v, 0.95) == 95
    assert traffic.percentile([5.0], 0.9) == 5.0


CONFIGS = sorted(p.stem for p in (CHIP / "configs").glob("*.json"))


@pytest.mark.parametrize("name", CONFIGS)
def test_flops_param_count_matches_the_program_and_the_weights(name):
    import harness
    c = json.loads((CHIP / "configs" / f"{name}.json").read_text())
    s = flops.Shape.from_config(c)
    cfg = harness.model_config(c)
    # ModelConfig.param_count leaves out the final norm and qk-norm scales
    extra = s.d_model + (2 * s.head_dim * s.layers if s.qk_norm else 0)
    assert flops.param_count(s) == cfg.param_count() + extra
    tree = weights.shapes(s, "bfloat16")
    import jax
    assert flops.param_count(s) == sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def test_qwen3_4b_has_its_published_parameter_count():
    c = json.loads((CHIP / "configs" / "qwen3-4b.json").read_text())
    assert flops.param_count(flops.Shape.from_config(c)) == 4_022_468_096


def test_flops_count_no_pad_rows_and_head_only_when_sampled():
    s = flops.Shape(layers=2, d_model=8, heads=2, kv_heads=1, head_dim=4,
                    d_ff=16, vocab=32)
    base = 2 * 2 * s.layer_matmul_params
    assert flops.row_flops(s, 0, False) == base + 4 * 2 * 2 * 4 * 1
    assert flops.row_flops(s, 9, True) == \
        base + 4 * 2 * 2 * 4 * 10 + 2 * 8 * 32
    assert flops.step_flops(s, []) == 0


def test_step_mfu_replays_positions_and_skips_pads():
    """A prompt of 5 tokens in two chunks, then two decode steps: rows
    at positions 0..4 (the last sampled), then 5 and 6."""
    from types import SimpleNamespace
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "m", CHIP / "layer_metrics" / "step_mfu.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    s = flops.Shape(layers=1, d_model=4, heads=1, kv_heads=1, head_dim=4,
                    d_ff=4, vocab=10)
    req = SimpleNamespace(rid=7, req=SimpleNamespace(prompt=[1] * 5))
    steps = [
        {"step_id": 0, "t0": 0.0, "dur": 1.0, "phases": {},
         "work": [{"kind": "prefill", "rid": 7, "start": 0, "count": 3}]},
        {"step_id": 1, "t0": 1.0, "dur": 1.0, "phases": {},
         "work": [{"kind": "prefill", "rid": 7, "start": 3, "count": 2}]},
        {"step_id": 2, "t0": 2.0, "dur": 1.0, "phases": {},
         "work": [{"kind": "decode", "rid": 7, "tokens": 1}]},
        {"step_id": 3, "t0": 3.0, "dur": 1.0, "phases": {},
         "work": [{"kind": "decode", "rid": 7, "tokens": 1}]}]
    run = SimpleNamespace(sent=[req], primer=SimpleNamespace(rid=None),
                          steps=steps, t_open=0.0, t_close=4.0, chips=1,
                          shape=s, peaks={"bf16_flops": 1.0})
    rows = mod.rows_by_step(run)
    assert rows == {0: [(0, False), (1, False), (2, False)],
                    1: [(3, False), (4, True)], 2: [(5, True)],
                    3: [(6, True)]}
    want = sum(flops.step_flops(s, r) for r in rows.values())
    assert mod.read(run) == pytest.approx(100 * want / 4.0)


def test_peaks_refuse_an_unknown_device_kind():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_big_seeds_give_distinct_keys():
    assert not np.array_equal(weights.key_words(5),
                              weights.key_words(2**32 + 5))
