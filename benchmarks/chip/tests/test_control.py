"""The control of the correctness check, at a size a test run holds:
the reference computed one precision step below the configuration's
bfloat16 (float8 e4m3 weights and activations) stands in the program's
place and fails the cell's committed limit, which the program passes."""
import pytest

import harness
import smoke

CELLS = smoke.cell_names()


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_committed_limit(monkeypatch, cell):
    f = smoke.steer(monkeypatch, cell)[0]
    limit = f.load["check"]["max_gap_logits"]
    for seed in (2**31 + 7, 3):
        out = harness.run(cell, seed, 3.0, False, control=True)
        chk = out["check"]
        assert chk["control_max_gap_logits"]["limit"] == limit
        assert chk["control_max_gap_logits"]["value"] > limit, chk
        assert chk["max_gap_logits"]["value"] <= limit, chk
        assert not out["correct"], chk
