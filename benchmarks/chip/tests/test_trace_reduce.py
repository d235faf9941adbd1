"""trace_reduce on events built by hand and on a trace recorded on the
chip (a short traced run of qwen3-4b.prompt_heavy)."""
from pathlib import Path

import pytest

import trace_reduce

DATA = Path(__file__).resolve().parent / "data"


def _events():
    ms = 1_000_000
    return {
        "host": [("bench.trace_window", 0, 100 * ms),
                 ("bench.engine_step", 0, 60 * ms),
                 ("bench.submit", 70 * ms, 75 * ms)],
        "devices": {
            0: {"ops": [("fusion.1", 10 * ms, 30 * ms),
                        ("fusion.2", 20 * ms, 40 * ms),   # overlaps
                        ("all-reduce.3", 40 * ms, 50 * ms),
                        ("fusion.1", 90 * ms, 120 * ms)],  # clipped
                "modules": [("jit__mixed_fn(1)", 10 * ms, 50 * ms),
                            ("jit__mixed_fn(1)", 90 * ms, 120 * ms),
                            ("jit_small(2)", 50 * ms, 51 * ms)]},
            1: {"ops": [("fusion.1", 0, 100 * ms)], "modules": []},
        },
    }


def test_busy_union_and_idle_share():
    s = trace_reduce.reduce_events(_events())
    d0 = s["devices"][0]
    assert s["window_s"] == pytest.approx(0.1)
    assert d0["busy_s"] == pytest.approx(0.050)     # 10-50 and 90-100
    assert d0["idle_share"] == pytest.approx(0.5)
    assert d0["step_module"] == "jit__mixed_fn(1)" and d0["steps"] == 2
    assert s["devices"][1]["idle_share"] == pytest.approx(0.0)
    assert s["busy_s"] == pytest.approx(0.075)       # mean over chips


def test_idle_gaps_are_labelled_by_the_host_span():
    s = trace_reduce.reduce_events(_events())
    gaps = s["idle_gaps"]
    # 50-90: the step covers 10 ms of it, the submit 5
    assert gaps[0] == ["bench.engine_step", pytest.approx(0.040)]
    assert gaps[1] == ["bench.engine_step", pytest.approx(0.010)]
    ev = _events()
    ev["host"] = ev["host"][:1]
    assert trace_reduce.reduce_events(ev)["idle_gaps"][0][0] == \
        "no bench span"


def test_top_ops_average_over_chips():
    s = trace_reduce.reduce_events(_events())
    top = dict(s["top_ops"])
    assert top["fusion.1"] == pytest.approx((0.020 + 0.010 + 0.100) / 2)


def test_op_name_drops_the_hlo_text():
    long = "%while.13 = (s32[]{:T(128)}, bf16[256,1,2560]) while(...)"
    assert trace_reduce.op_name(long) == "while.13"
    assert trace_reduce.op_name("fusion.1") == "fusion.1"


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce_events({"host": [], "devices": {}})


RECORDED = sorted(DATA.glob("*.xplane.pb"))


@pytest.mark.skipif(not RECORDED, reason="no recorded chip trace")
@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.name)
def test_recorded_chip_trace(path):
    s = trace_reduce.reduce(str(path))
    assert 0 < s["busy_s"] <= s["window_s"]
    for d in s["devices"].values():
        assert 0.0 <= d["idle_share"] < 1.0
        assert d["steps"] >= 1
    assert s["top_ops"] and s["idle_gaps"]
