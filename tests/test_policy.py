"""DispatchPolicy: validation, profile persistence, resolution, routing, and
the policy-invariance property (policies move performance knobs, never
predictions).  Methodology reference: docs/dispatch.md."""

import json
import os
import warnings

import jax
import numpy as np
import pytest
from _propcheck import given, settings, strategies as st

from repro.core import CostModelConfig, GNNConfig
from repro.core.model import init_cost_model
from repro.dsps import WorkloadGenerator
from repro.placement import sample_assignment_matrix
from repro.serve import CostEstimator, PlacementService
from repro.serve.policy import (
    PROFILE_ENV,
    PROFILE_SCHEMA_VERSION,
    DispatchPolicy,
    autotune,
    host_fingerprint,
    load_profile,
    resolve_policy,
    save_profile,
    use_policy,
)

GEN = WorkloadGenerator(seed=11)


def _models(metrics=("latency_p", "success"), hidden=16, n_ensemble=2):
    models = {}
    for i, m in enumerate(metrics):
        cfg = CostModelConfig(metric=m, n_ensemble=n_ensemble, gnn=GNNConfig(hidden=hidden))
        models[m] = (init_cost_model(jax.random.PRNGKey(40 + i), cfg), cfg)
    return models


def _mixed_requests(n_structures=4, rows=4, seed=23):
    kinds = ("linear", "two_way", "three_way")
    out = []
    for i in range(n_structures):
        q = GEN.query(kind=kinds[i % len(kinds)], name=f"pol{seed}-{i}")
        c = GEN.cluster(4 + i % 3)
        a = sample_assignment_matrix(q, c, rows, np.random.default_rng(seed + i))
        out.append((q, c, a))
    return out


# -- validation / serialization ---------------------------------------------------


def test_policy_roundtrips_through_json():
    p = DispatchPolicy(cross_query_row_limit=None, score_chunk=0, double_buffer=True)
    d = json.loads(json.dumps(p.to_dict()))
    assert DispatchPolicy.from_dict(d) == p


def test_policy_validate_rejects_bad_fields():
    with pytest.raises(ValueError, match="max_batch"):
        DispatchPolicy(max_batch=0).validate()
    with pytest.raises(ValueError, match="trace_cache_size"):
        DispatchPolicy(trace_cache_size=-1).validate()
    with pytest.raises(ValueError, match="score_chunk"):
        DispatchPolicy(score_chunk=None).validate()  # None only where meaningful
    with pytest.raises(ValueError, match="double_buffer"):
        DispatchPolicy(double_buffer="yes").validate()
    with pytest.raises(ValueError, match="unknown"):
        DispatchPolicy.from_dict({"not_a_knob": 1})


# -- profile persistence ----------------------------------------------------------


def test_profile_save_load_roundtrip(tmp_path):
    path = tmp_path / "prof.json"
    tuned = DispatchPolicy(cross_query_row_limit=4, score_chunk=64)
    save_profile(path, tuned, measurements={"note": "test"})
    payload = load_profile(path)
    assert payload is not None
    assert payload["schema_version"] == PROFILE_SCHEMA_VERSION
    assert payload["policy_obj"] == tuned
    assert payload["measurements"] == {"note": "test"}
    assert payload["host_fingerprint"] == host_fingerprint()


def test_foreign_host_profile_falls_back_to_defaults(tmp_path, monkeypatch):
    """A profile stamped by another machine must be ignored (None), not
    mis-applied — resolve_policy then lands on the built-in defaults."""
    path = tmp_path / "prof.json"
    save_profile(
        path,
        DispatchPolicy(cross_query_row_limit=1),
        descriptor={"node": "other-host", "machine": "never", "cpu_count": 1,
                    "backend": "cpu", "device_count": 1},
    )
    assert load_profile(path, require_host_match=True) is None
    # but an explicit env pin skips the host check (CI containers)
    assert load_profile(path, require_host_match=False)["policy_obj"].cross_query_row_limit == 1
    monkeypatch.setenv(PROFILE_ENV, str(path))
    assert resolve_policy().cross_query_row_limit == 1


def test_corrupt_or_stale_profiles_return_none_with_warning(tmp_path):
    """Unusable profiles fall back to builtins (None) AND warn once with the
    path + reason — an operator must be able to tell a tuned host from a
    silently-defaulted one.  A missing profile is the normal un-tuned state
    and stays silent."""
    from repro.serve import DispatchProfileWarning

    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    with pytest.warns(DispatchProfileWarning, match=str(bad)):
        assert load_profile(bad) is None
    stale = tmp_path / "stale.json"
    save_profile(stale, DispatchPolicy())
    payload = json.loads(stale.read_text())
    payload["schema_version"] = PROFILE_SCHEMA_VERSION + 1
    stale.write_text(json.dumps(payload))
    with pytest.warns(DispatchProfileWarning, match="schema"):
        assert load_profile(stale) is None
    invalid = tmp_path / "invalid.json"
    save_profile(invalid, DispatchPolicy())
    payload = json.loads(invalid.read_text())
    payload["policy"]["max_batch"] = -1
    invalid.write_text(json.dumps(payload))
    with pytest.warns(DispatchProfileWarning, match=str(invalid)):
        assert load_profile(invalid) is None
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # missing file: silent None
        assert load_profile(tmp_path / "missing.json") is None


def test_env_override_semantics(tmp_path, monkeypatch):
    monkeypatch.setenv(PROFILE_ENV, "default")
    assert resolve_policy() == DispatchPolicy()
    monkeypatch.setenv(PROFILE_ENV, "none")
    assert resolve_policy() == DispatchPolicy()
    monkeypatch.setenv(PROFILE_ENV, str(tmp_path / "nope.json"))
    with pytest.raises(ValueError, match="dispatch"):
        resolve_policy()  # an explicit pin must never silently degrade


# -- routing determinism ----------------------------------------------------------


def _drain_stats(est, requests):
    svc = PlacementService(est, auto_start=False)
    futs = [svc.submit_score(q, c, a) for q, c, a in requests]
    svc.start()
    answers = [f.result(timeout=120) for f in futs]
    svc.close()
    return svc.stats, answers


def test_recorded_profile_deterministically_routes_drains(tmp_path):
    """The same profile yields the same merged-vs-per-structure decision on
    every run: row_limit >= drain rows merges, a tuned row_limit below them
    pins the per-structure path."""
    models = _models(hidden=20)
    requests = _mixed_requests(rows=4)

    merge_prof = tmp_path / "merge.json"
    save_profile(merge_prof, DispatchPolicy(cross_query_row_limit=16))
    split_prof = tmp_path / "split.json"
    save_profile(split_prof, DispatchPolicy(cross_query_row_limit=2))

    merged_counts, split_counts, baseline = [], [], None
    for _ in range(2):  # determinism: identical routing on repeat runs
        pm = load_profile(merge_prof)["policy_obj"]
        stats, answers = _drain_stats(CostEstimator(models, policy=pm), requests)
        assert stats.n_cross_query == len(requests), "4 rows/structure <= 16 must merge"
        merged_counts.append(stats.n_forwards)

        ps = load_profile(split_prof)["policy_obj"]
        stats2, answers2 = _drain_stats(CostEstimator(models, policy=ps), requests)
        assert stats2.n_cross_query == 0, "4 rows/structure > 2 must split"
        split_counts.append(stats2.n_forwards)

        # routing changes dispatch only, never the numbers
        for a, b in zip(answers, answers2):
            for m in a:
                np.testing.assert_allclose(a[m], b[m], rtol=1e-5, atol=1e-6)
        if baseline is None:
            baseline = answers
        else:
            for a, b in zip(baseline, answers):
                for m in a:
                    np.testing.assert_array_equal(a[m], b[m])
    assert merged_counts[0] == merged_counts[1]
    assert split_counts[0] == split_counts[1]
    assert merged_counts[0] < split_counts[0], "merged drain must use fewer forwards"


# -- the policy-invariance property ----------------------------------------------


@settings(max_examples=6, deadline=None)
@given(
    st.sampled_from([1, 2, 8, 64, None]),  # cross_query_row_limit
    st.sampled_from([0, 2, 64, 256]),  # score_chunk
    st.integers(1, 4),  # tiny cache capacities stress eviction
)
def test_any_valid_policy_changes_only_performance(row_limit, chunk, caches):
    """ANY valid policy yields float-identical score_many/estimate_many
    results: the policy moves batching, chunking, and cache knobs — never
    the math."""
    models = _models(hidden=12)
    requests = _mixed_requests(n_structures=3, rows=5, seed=31)
    graphs = [GEN.corpus(2), GEN.corpus(3)]

    def run(policy):
        est = CostEstimator(models, policy=policy)
        with use_policy(policy):
            scores = est.score_many([(q, c, a) for q, c, a in requests])
            # the placed per-structure path exercises score_chunk directly
            q0, c0, a0 = requests[0]
            scores.append(est.score(q0, c0, a0))
            ests = est.estimate_many(graphs)
        return scores, ests

    base_scores, base_ests = run(DispatchPolicy())
    policy = DispatchPolicy(
        cross_query_row_limit=row_limit,
        score_chunk=chunk,
        max_batch=8,
        trace_cache_size=caches,
        banding_cache_size=caches,
        skeleton_cache_size=caches,
        merged_group_cache_size=caches,
    ).validate()
    got_scores, got_ests = run(policy)
    for want, have in zip(base_scores, got_scores):
        for m in want:
            np.testing.assert_array_equal(have[m], want[m], err_msg=f"score {m} {policy}")
    for want, have in zip(base_ests, got_ests):
        for m in want:
            np.testing.assert_array_equal(have[m], want[m], err_msg=f"estimate {m} {policy}")


# -- autotune ---------------------------------------------------------------------


def test_autotune_budget_zero_writes_default_profile_and_reuses(tmp_path):
    """budget_s=0: every probe is skipped (budget_exhausted recorded), the
    profile still validates, and the second call is a cached no-op."""
    out = tmp_path / "tuned.json"
    res = autotune(quick=True, budget_s=0, out=out)
    assert not res.reused_cached
    assert res.policy == DispatchPolicy()
    assert "budget_exhausted" in res.measurements
    payload = load_profile(out)
    assert payload is not None and payload["policy_obj"] == res.policy

    res2 = autotune(quick=True, budget_s=0, out=out)
    assert res2.reused_cached and res2.policy == res.policy
    # force re-probes even with a valid cache
    res3 = autotune(quick=True, budget_s=0, out=out, force=True)
    assert not res3.reused_cached


def test_kernel_tile_probe_fails_with_the_compiler_message(monkeypatch):
    """A kernel lowering that cannot compile stops the tile probe with the
    compiler's error; no timing is recorded for it.  Off the TPU the Pallas
    lowering (``interpret=False``) is such a kernel."""
    import repro.kernels as kernels
    from repro.kernels.mp_sweep import ops as sweep_ops
    from repro.kernels.seg_gather import ops as seg_ops
    from repro.serve.policy import _measure_kernel_tiles

    for mod in (kernels, sweep_ops, seg_ops):
        name = "active_lowering" if mod is kernels else "_lowering"
        monkeypatch.setattr(mod, name, lambda: "pallas")
    with pytest.raises(ValueError, match="interpret mode"):
        _measure_kernel_tiles((32,), repeats=1, seed=0)


def test_autotune_cli_validate_and_expect_cached(tmp_path, capsys):
    from repro.serve.policy import main

    out = tmp_path / "cli.json"
    assert main(["--quick", "--budget-s", "0", "--out", str(out)]) == 0
    assert main(["--validate", str(out)]) == 0
    assert main(["--quick", "--budget-s", "0", "--out", str(out), "--expect-cached"]) == 0
    bad = tmp_path / "bad.json"
    bad.write_text("{}")
    assert main(["--validate", str(bad)]) == 1
    fresh = tmp_path / "fresh.json"
    assert main(["--quick", "--budget-s", "0", "--out", str(fresh), "--expect-cached"]) == 1
    capsys.readouterr()


def test_service_explicit_args_override_policy():
    """Constructor args always beat the policy — including explicit None for
    cross_query_row_limit (always merge), which _UNSET must distinguish."""
    est = CostEstimator(_models(), policy=DispatchPolicy(cross_query_row_limit=4, max_batch=32))
    svc = PlacementService(est, auto_start=False)
    assert svc.cross_query_row_limit == 4 and svc.max_batch == 32
    svc.close()
    svc = PlacementService(est, auto_start=False, cross_query_row_limit=None, max_batch=7)
    assert svc.cross_query_row_limit is None and svc.max_batch == 7
    svc.close()


def test_optimizer_search_knobs_come_from_policy():
    models = _models(metrics=("latency_p",))
    q, c = GEN.query(name="polk"), GEN.cluster(6)
    narrow = CostEstimator(models, policy=DispatchPolicy(search_k=4)).optimize(q, c, "latency_p")
    wide = CostEstimator(models, policy=DispatchPolicy(search_k=64)).optimize(q, c, "latency_p")
    assert narrow.n_candidates <= 4 < wide.n_candidates  # pool tracks policy.search_k
    # an explicit k still beats the policy
    explicit = CostEstimator(models, policy=DispatchPolicy(search_k=4)).optimize(
        q, c, "latency_p", k=16
    )
    assert explicit.n_candidates > 4
