"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

The smoke's phases are functions with size arguments, so the same code that
runs on the chip runs here: a few hundred traces, one training step per
metric, a handful of requests.  The "chip" and the reference are then both
the CPU, so every answer must agree to float32 rounding.  ``main()`` itself
must refuse the CPU.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

import pytest

from repro.serve.policy import DispatchPolicy, use_policy

ROOT = Path(__file__).resolve().parents[1]

TINY = dict(
    corpus=200,
    hidden=16,
    train_steps=1,
    batch=16,
    init_structures=1,
    init_cands=4,
    refine_structures=2,
    estimate_graphs=8,
    control_ticks=10,
)


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load_smoke()


@pytest.fixture(scope="module")
def tiny_run(smoke):
    """Run every phase once at the tiny size; keep the printed lines."""
    import contextlib
    import io

    out = io.StringIO()
    with contextlib.redirect_stdout(out), smoke.Meter(None) as meter:
        with use_policy(DispatchPolicy()):
            summary = smoke.run(meter, smoke.Sizes(**TINY))
    lines = [json.loads(line) for line in out.getvalue().splitlines()]
    return {line["phase"]: line for line in lines}, summary


def test_main_refuses_the_cpu(smoke, capsys):
    rc = smoke.main([])
    out = capsys.readouterr()
    assert rc != 0
    assert '"ok": true' not in out.out
    assert "no TPU" in out.err


def test_every_phase_reports(tiny_run):
    lines, _ = tiny_run
    for phase in ("corpus", "train", "train_check", "load", "serve", "search", "control"):
        line = lines[phase]
        assert line["seconds"] >= 0 and line["backend_compile_s"] >= 0, phase
        assert line["device_kind"] == "cpu", phase
    assert lines["corpus"]["n_traces"] == TINY["corpus"]
    assert lines["train"]["hidden"] == TINY["hidden"]
    assert set(lines["train"]["metrics"]) == set(lines["load"]["metrics"])


def test_training_is_finite_and_matches_reference(tiny_run):
    lines, _ = tiny_run
    for metric, r in lines["train"]["metrics"].items():
        assert r["steps"] == TINY["train_steps"], metric
    for metric, loss in lines["train_check"]["first_batch_loss"].items():
        assert loss["rel_err"] < 1e-5, metric


def test_service_answers_without_fallback(tiny_run):
    lines, _ = tiny_run
    stats = lines["serve"]["stats"]
    for field in ("n_degraded", "n_retries", "n_nonfinite", "n_timeouts", "n_failed"):
        assert stats[field] == 0, field
    assert stats["n_cross_query"] == TINY["refine_structures"]
    assert lines["serve"]["requests"]["init"] == 2 * TINY["init_structures"]
    assert lines["serve"]["merged_tpu_custom_calls"] == 0  # no Mosaic on the CPU


def test_answers_agree_with_reference(tiny_run):
    lines, summary = tiny_run
    assert summary["violation"] is None
    assert summary["max_abs_err"] < 1e-4  # CPU against CPU: float32 rounding
    assert summary["n_regression_compared"] > 0 and summary["n_votes_compared"] > 0
    for pick in lines["search"]["queries"]:
        assert pick["gap"] <= 1e-4


def test_controller_ran(tiny_run):
    lines, _ = tiny_run
    assert lines["control"]["ticks"] == TINY["control_ticks"]
    assert lines["control"]["replans"] > 0


def test_a_violation_fails_the_run(smoke):
    """An answer off by more than the tolerance is recorded and fails the
    final check; a near-zero logit is not compared."""
    import numpy as np
    from repro.core.model import CostModelConfig

    cmp = smoke.Comparison(0.05)
    cfgs = {"latency_p": CostModelConfig("latency_p"), "success": CostModelConfig("success")}
    raw = {"latency_p": np.zeros((3, 2)), "success": np.array([[1.0, 0.01]] * 3)}
    cmp.answers("ok", {"latency_p": np.zeros(2), "success": np.array([1, 0])}, raw, cfgs)
    assert cmp.violation is None and cmp.n_votes == 1 and cmp.n_votes_skipped == 1
    cmp.answers("bad", {"latency_p": np.array([0.0, 0.2]), "success": np.array([1, 0])}, raw, cfgs)
    assert cmp.violation.startswith("bad/latency_p")
    assert cmp.max_err == pytest.approx(np.log1p(0.2))
