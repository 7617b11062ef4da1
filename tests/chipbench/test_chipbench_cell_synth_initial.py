"""The ``synth-initial`` cell end to end through the harness's inner entry, on
the CPU at a tiny size: every metric the cell names, the device block, and
answers that agree with the reference."""

import pytest
from _chipbench_tiny import CPU_PEAKS, tiny

from chipbench import bench

CELL = "synth-initial"


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_is_correct(trace):
    # the traced run is longer: its sub-window must hold drains and answers on a loaded CPU
    r = bench.run_cell(CELL, 2**31 + 77, 3.0 if trace else 1.5, bool(trace), overrides=tiny(CELL), peaks=CPU_PEAKS,
                       log=lambda *a, **k: None)
    assert r["correct"], r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "compared"
    spec = bench.Spec()
    want = {m["name"] for m in spec.metrics(CELL, "per_layer" if trace else "end_to_end")}
    # a reader that finds nothing returns nothing; on the CPU every one finds something
    assert set(r["metrics"]) == want, want ^ set(r["metrics"])
    units = {m["name"]: m["unit"] for m in spec.data["end_to_end"] + spec.data["per_layer"]}
    assert all(v["unit"] == units[k] for k, v in r["metrics"].items())
    dev = r["device"]
    assert dev["platform"] == "cpu" and dev["kind"] and dev["count"] >= 1
    if trace:
        assert 0 < dev["busy_s"] <= dev["window_s"]
        assert r["breakdown"]["device_ops"] and r["breakdown"]["idle_gaps"]
