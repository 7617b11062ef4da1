"""``correct`` comes out false when the timed path is broken underneath:
answers altered where the program produces them, and the bfloat16 control
put in the program's place."""

import pytest
from _chipbench_tiny import CPU_PEAKS, tiny

import repro.serve.estimator as estimator
from chipbench import bench


@pytest.mark.parametrize("cell", ["synth-initial"])
def test_an_altered_answer_is_caught(monkeypatch, cell):
    split = estimator._split_votes

    def altered(raw, stacked):
        out = split(raw, stacked)
        out["latency_p"] = out["latency_p"].copy()
        out["latency_p"][0] *= 1.2  # one answer per forward, a fifth too high
        return out

    monkeypatch.setattr(estimator, "_split_votes", altered)
    r = bench.run_cell(cell, 11, 1.5, False, overrides=tiny(cell), peaks=CPU_PEAKS,
                       log=lambda *a, **k: None)
    assert not r["correct"]
    assert r["compared"]["answer_gap"]["value"] > r["compared"]["answer_gap"]["limit"]


@pytest.mark.parametrize("cell", ["synth-initial"])
def test_the_bfloat16_control_fails_the_limit(cell):
    # the control in the program's place, judged by the cell's own limits
    r = bench.run_cell(cell, 2**31 + 9, 1.5, False, overrides=tiny(cell), peaks=CPU_PEAKS, control=True,
                       log=lambda *a, **k: None)
    assert not r["correct"], r["compared"]
    program, control = r["calibration"]["program"], r["calibration"]["control"]
    assert control["n_regression"] == program["n_regression"] > 0
    # the median gap is the number that the control has to fail (PERF.md)
    assert r["compared"]["answer_gap_p50"]["value"] == control["gap_p50"]
    assert control["gap_p50"] > r["compared"]["answer_gap_p50"]["limit"] > program["gap_p50"]
