"""Serving subsystem: bundle round-trips + versioning, the CostEstimator
facade (parity with the pre-redesign paths, cache/forward counters), the
deprecation shims, and PlacementService micro-batching."""

import json
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
import repro.serve.estimator as estimator_mod
from repro.core import CostModelConfig, GNNConfig, init_cost_model
from repro.core.graph import (
    batch_graphs,
    build_a_place_batch,
    build_graph,
    build_graph_skeleton,
    query_static,
    skeleton_cache_key,
)
from repro.dsps import WorkloadGenerator
from repro.dsps.placement import Placement
from repro.placement import PlacementOptimizer, sample_assignment_matrix
from repro.serve import (
    BUNDLE_SCHEMA_VERSION,
    BundleVersionError,
    CostEstimator,
    CostModelBundle,
    PlacementService,
    bundle_from_checkpoint,
    merge_bundles,
)
from repro.serve.estimator import placed_predict
from repro.training import TrainConfig, dataset_from_traces, split_dataset, train_cost_model

GEN = WorkloadGenerator(seed=33)


def _models(hidden=16, n_ensemble=2, metrics=("latency_p", "success", "backpressure")):
    models = {}
    for i, m in enumerate(metrics):
        cfg = CostModelConfig(metric=m, n_ensemble=n_ensemble, gnn=GNNConfig(hidden=hidden))
        models[m] = (init_cost_model(jax.random.PRNGKey(i), cfg), cfg)
    return models


def _graphs(n=9, seed=3):
    gen = WorkloadGenerator(seed=seed)
    traces = gen.corpus(n)
    g = batch_graphs([build_graph(t.query, t.cluster, t.placement) for t in traces])
    return traces, jax.tree_util.tree_map(jnp.asarray, g)


# -- bundle ---------------------------------------------------------------------


def test_bundle_roundtrip_bit_identical(tmp_path):
    """save -> load must reproduce params exactly and predictions bit-identically."""
    models = _models()
    bundle = CostModelBundle(models, meta={"note": "roundtrip"})
    d = str(tmp_path / "bundle")
    bundle.save(d)
    loaded = CostModelBundle.load(d)
    assert loaded.metrics == bundle.metrics
    assert loaded.meta == {"note": "roundtrip"}
    for m in bundle.metrics:
        assert loaded.config(m) == bundle.config(m)
        for a, b in zip(
            jax.tree_util.tree_leaves(bundle.params(m)),
            jax.tree_util.tree_leaves(loaded.params(m)),
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    _, g = _graphs()
    before = CostEstimator(models).estimate(g)
    after = CostEstimator.from_bundle(loaded).estimate(g)
    for m in before:
        np.testing.assert_array_equal(before[m], after[m], err_msg=m)


def _tamper_manifest(directory, mutate):
    step_dir = os.path.join(directory, "step_0000000000")
    p = os.path.join(step_dir, "manifest.json")
    with open(p) as f:
        manifest = json.load(f)
    mutate(manifest["extra"])
    with open(p, "w") as f:
        json.dump(manifest, f)


def test_bundle_refuses_incompatible_versions(tmp_path):
    """A bumped schema version or a different slot layout must refuse loudly,
    never deserialize into silently mis-predicting models."""
    bundle = CostModelBundle(_models(metrics=("latency_p",)))
    d = str(tmp_path / "schema")
    bundle.save(d)
    _tamper_manifest(d, lambda extra: extra.update(schema_version=BUNDLE_SCHEMA_VERSION + 1))
    with pytest.raises(BundleVersionError, match="schema_version"):
        CostModelBundle.load(d)

    d2 = str(tmp_path / "layout")
    bundle.save(d2)

    def bump_layout(extra):
        extra["layout"]["slot_ranges"][0][2] += 1  # pretend 4 source slots

    _tamper_manifest(d2, bump_layout)
    with pytest.raises(BundleVersionError, match="slot layout"):
        CostModelBundle.load(d2)


def test_bundle_from_training_checkpoint(tmp_path):
    """The train_cost_model checkpoint ((params, opt_state, ef)) exports to a
    bundle whose params are exactly the persisted best params."""
    ds = dataset_from_traces(WorkloadGenerator(seed=5).corpus(24), "latency_p")
    tr, va, _ = split_dataset(ds, seed=0)
    cfg = CostModelConfig(metric="latency_p", n_ensemble=1, gnn=GNNConfig(hidden=8))
    ckpt = str(tmp_path / "ckpt")
    res = train_cost_model(tr, va, cfg, TrainConfig(epochs=1, batch_size=16, ckpt_dir=ckpt))
    bundle = bundle_from_checkpoint(ckpt, cfg)
    assert bundle.metrics == ("latency_p",)
    assert bundle.meta["step"] == res.steps
    for a, b in zip(
        jax.tree_util.tree_leaves(res.params),
        jax.tree_util.tree_leaves(bundle.params("latency_p")),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # wrong config must fail with a shape complaint, not deserialize garbage
    bad = CostModelConfig(metric="latency_p", n_ensemble=2, gnn=GNNConfig(hidden=8))
    with pytest.raises(ValueError, match="shape mismatch"):
        bundle_from_checkpoint(ckpt, bad)


def test_merge_bundles():
    """Disjoint and agreeing meta merge flat; conflicting keys (e.g. each
    export's own checkpoint provenance) are namespaced, never overwritten."""
    a = CostModelBundle(
        _models(metrics=("latency_p",)), meta={"a": 1, "corpus": 100, "step": 7}
    )
    b = CostModelBundle(
        _models(metrics=("success",)), meta={"b": 2, "corpus": 100, "step": 9}
    )
    merged = merge_bundles(a, b)
    assert set(merged.metrics) == {"latency_p", "success"}
    assert merged.meta == {
        "a": 1,
        "b": 2,
        "corpus": 100,
        "latency_p/step": 7,
        "success/step": 9,
    }


# -- estimator ------------------------------------------------------------------


def test_estimator_score_matches_pre_redesign_path():
    """CostEstimator.score on a fixed seed == the per-metric placed forward
    (the pre-facade reference), and estimate == the facade's own score on the
    equivalent broadcast batch."""
    models = _models()
    est = CostEstimator(models)
    q = GEN.query(kind="two_way", name="parity")
    c = GEN.cluster(6)
    a = sample_assignment_matrix(q, c, 13, np.random.default_rng(11))
    got = est.score(q, c, a)
    skel = jax.tree_util.tree_map(jnp.asarray, build_graph_skeleton(q, c))
    static = query_static(q)
    a_place = jnp.asarray(build_a_place_batch(q, c, a))
    for m, (params, cfg) in models.items():
        ref = placed_predict(params, skel, a_place, static, cfg)
        np.testing.assert_allclose(got[m], ref[: len(a)], rtol=1e-5, atol=1e-6, err_msg=m)
    # generic estimate over the broadcast batch agrees with the placed scorer
    g = batch_graphs([build_graph(q, c, Placement.of(r)) for r in a])
    scored = est.estimate(g)
    for m in models:
        np.testing.assert_allclose(got[m], scored[m], rtol=1e-4, atol=1e-4, err_msg=m)


def test_estimator_optimize_matches_optimizer():
    """estimator.optimize is the same search as PlacementOptimizer.optimize
    on a fixed seed: identical placement, predictions, and score vector."""
    models = _models()
    est = CostEstimator(models)
    opt = PlacementOptimizer(_models())  # fresh estimator, same weights
    q = GEN.query(kind="linear", name="optparity")
    c = GEN.cluster(6)
    r1 = est.optimize(q, c, "latency_p", k=16, rng=np.random.default_rng(4), refine_rounds=1)
    r2 = opt.optimize(q, c, "latency_p", k=16, rng=np.random.default_rng(4), refine_rounds=1)
    assert r1.placement.assignment == r2.placement.assignment
    assert r1.predicted == r2.predicted
    assert r1.n_candidates == r2.n_candidates and r1.n_feasible == r2.n_feasible
    np.testing.assert_array_equal(r1.scores, r2.scores)


def test_estimator_estimate_accepts_traces():
    traces, g = _graphs(n=7, seed=9)
    est = CostEstimator(_models(metrics=("latency_p",)))
    np.testing.assert_array_equal(
        est.estimate(traces)["latency_p"], est.estimate(g)["latency_p"]
    )


def test_score_one_skeleton_build_one_stacked_forward(monkeypatch):
    """Counter-asserted serving contract: across repeated score calls on one
    (query, cluster) pair the facade builds the skeleton at most ONCE, and
    each scored batch issues exactly ONE fused stacked forward (traced once),
    never a per-metric loop."""
    calls = {"skel": 0, "fused": 0, "per_metric": 0, "traced": 0}
    orig_skel = estimator_mod.build_graph_skeleton
    orig_fused = estimator_mod.placed_predict_fused
    orig_placed = estimator_mod.placed_predict
    orig_apply = estimator_mod.apply_gnn_placed_stacked

    monkeypatch.setattr(
        estimator_mod,
        "build_graph_skeleton",
        lambda *a, **k: (calls.__setitem__("skel", calls["skel"] + 1), orig_skel(*a, **k))[1],
    )
    monkeypatch.setattr(
        estimator_mod,
        "placed_predict_fused",
        lambda *a, **k: (calls.__setitem__("fused", calls["fused"] + 1), orig_fused(*a, **k))[1],
    )
    monkeypatch.setattr(
        estimator_mod,
        "placed_predict",
        lambda *a, **k: (calls.__setitem__("per_metric", calls["per_metric"] + 1), orig_placed(*a, **k))[1],
    )
    monkeypatch.setattr(
        estimator_mod,
        "apply_gnn_placed_stacked",
        lambda *a, **k: (calls.__setitem__("traced", calls["traced"] + 1), orig_apply(*a, **k))[1],
    )

    # unique hidden size: the jit caches are shared across estimators, so a
    # config no other test uses guarantees the trace happens HERE
    est = CostEstimator(_models(hidden=20))
    q = GEN.query(kind="two_way", name="counters")
    c = GEN.cluster(6)
    rng = np.random.default_rng(2)
    a1 = sample_assignment_matrix(q, c, 9, rng)
    a2 = sample_assignment_matrix(q, c, 9, rng)
    s1 = est.score(q, c, a1)
    s2 = est.score(q, c, a2)
    assert calls["skel"] == 1, "second score on the same pair must hit the LRU"
    assert calls["fused"] == 2, "exactly one fused stacked forward per scored batch"
    assert calls["per_metric"] == 0, "fusable configs must never take the per-metric loop"
    assert calls["traced"] == 1, "the stacked forward must be traced once, then cached"
    assert set(s1) == set(s2) == {"latency_p", "success", "backpressure"}


def _mixed_requests(n=8, cands=5, seed=43):
    """n score requests over n DISTINCT (query, cluster) structures."""
    gen = WorkloadGenerator(seed=seed)
    rng = np.random.default_rng(seed)
    out = []
    kinds = ("linear", "two_way", "three_way")
    for i in range(n):
        q = gen.query(kind=kinds[i % len(kinds)], name=f"mix{i}")
        c = gen.cluster(3 + i % 5)
        out.append((q, c, sample_assignment_matrix(q, c, cands, rng)))
    return out


def test_score_many_matches_serial_score():
    """Cross-query coalescing is invisible: score_many over a mixed stream
    answers each request exactly like a serial per-request score (to float
    tolerance — the merged generic engine and the placement-specialized
    engine are the same math in different sweep orders)."""
    est = CostEstimator(_models())
    requests = _mixed_requests()
    serial = [est.score(q, c, a) for q, c, a in requests]
    merged = est.score_many(requests)
    assert len(merged) == len(requests)
    for want, have in zip(serial, merged):
        for m in want:
            np.testing.assert_allclose(have[m], want[m], rtol=1e-4, atol=1e-5, err_msg=m)
    # chunked (max_rows smaller than the merged stream) stays exact too
    chunked = est.score_many(requests, max_rows=8)
    for want, have in zip(serial, chunked):
        for m in want:
            np.testing.assert_allclose(have[m], want[m], rtol=1e-4, atol=1e-5, err_msg=m)


def test_estimate_many_matches_serial_estimate():
    """Merged estimate batches answer exactly like per-batch estimate."""
    est = CostEstimator(_models(metrics=("latency_p", "success")))
    _, g1 = _graphs(n=6, seed=47)
    _, g2 = _graphs(n=3, seed=53)
    serial = [est.estimate(g1), est.estimate(g2)]
    merged = est.estimate_many([g1, g2])
    for want, have in zip(serial, merged):
        for m in want:
            np.testing.assert_allclose(have[m], want[m], rtol=1e-4, atol=1e-5, err_msg=m)


def test_mixed_drain_is_one_forward_for_eight_structures(monkeypatch):
    """Counter-asserted tentpole contract: 8 score requests over 8 DISTINCT
    query structures, drained together, must issue exactly ONE stacked
    forward (not one per structure), traced once."""
    calls = {"stacked": 0}
    orig = estimator_mod._jitted_merged_forward.__wrapped__

    @estimator_mod.lru_cache(maxsize=128)
    def counting(*a, **k):
        calls["stacked"] += 1
        return orig(*a, **k)

    monkeypatch.setattr(estimator_mod, "_jitted_merged_forward", counting)
    # unique hidden size so the trace cannot come from another test's cache
    est = CostEstimator(_models(hidden=28))
    requests = _mixed_requests()
    assert len({skeleton_cache_key(q, c) for q, c, _ in requests}) == 8
    svc = PlacementService(est, auto_start=False)
    futs = [svc.submit_score(q, c, a) for q, c, a in requests]
    svc.start()
    answers = [f.result(timeout=120) for f in futs]
    svc.close()
    assert all(set(ans) == set(est.models) for ans in answers)
    assert svc.stats.n_batches == 1, "pre-queued requests must drain in one wake-up"
    assert svc.stats.n_forwards == 1, "8 distinct structures must share ONE forward"
    assert svc.stats.n_cross_query == 8
    assert calls["stacked"] == 1, "the merged forward must be traced exactly once"


def test_lazy_bundle_loads_metrics_on_first_use(tmp_path):
    """load() defers each metric's params to first access; an estimator over
    a lazy bundle only ever touches the metrics it serves."""
    from repro.serve import LazyModels

    bundle = CostModelBundle(_models(), meta={"note": "lazy"})
    d = str(tmp_path / "lazy")
    bundle.save(d)
    loaded = CostModelBundle.load(d)
    assert isinstance(loaded.models, LazyModels)
    assert loaded.metrics == bundle.metrics  # manifest-only, nothing loaded
    assert not loaded.models._loaded
    est = CostEstimator.from_bundle(loaded)
    _, g = _graphs(n=4, seed=59)
    est.estimate(g, ["latency_p"])
    assert set(loaded.models._loaded) == {"latency_p"}, "untouched metrics must stay on disk"
    # the loaded params equal the eager load bit-for-bit
    eager = CostModelBundle.load(d, lazy=False)
    for a, b in zip(
        jax.tree_util.tree_leaves(loaded.params("latency_p")),
        jax.tree_util.tree_leaves(eager.params("latency_p")),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_from_bundle_warns_on_corpus_fingerprint_mismatch():
    """A recorded corpus_fingerprint that disagrees with the caller's is a
    provenance mismatch: warn (once per call), never silently serve; agreeing
    or absent fingerprints stay silent."""
    from repro.serve import corpus_fingerprint

    traces = WorkloadGenerator(seed=61).corpus(6)
    fp = corpus_fingerprint(traces)
    assert fp == corpus_fingerprint(list(traces)), "fingerprint must be deterministic"
    assert fp != corpus_fingerprint(traces[:5])
    models = _models(metrics=("latency_p",))
    bundle = CostModelBundle(models, meta={"corpus_fingerprint": fp})
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        CostEstimator.from_bundle(bundle)  # no expectation: silent
        CostEstimator.from_bundle(bundle, corpus_fingerprint=fp)  # agreeing: silent
        # no recorded fingerprint: nothing to check against
        CostEstimator.from_bundle(CostModelBundle(models), corpus_fingerprint=fp)
    with pytest.warns(UserWarning, match="provenance mismatch"):
        CostEstimator.from_bundle(bundle, corpus_fingerprint=corpus_fingerprint(traces[:5]))


def test_from_bundle_strict_provenance_raises():
    """strict_provenance=True turns the provenance-mismatch warning into a
    typed BundleVersionError — deployment pipelines opt in to refusing a
    model trained on the wrong corpus instead of serving it with a warning."""
    from repro.serve import BundleVersionError, corpus_fingerprint

    traces = WorkloadGenerator(seed=61).corpus(6)
    fp = corpus_fingerprint(traces)
    bundle = CostModelBundle(_models(metrics=("latency_p",)), meta={"corpus_fingerprint": fp})
    with pytest.raises(BundleVersionError, match="provenance mismatch"):
        CostEstimator.from_bundle(
            bundle,
            corpus_fingerprint=corpus_fingerprint(traces[:5]),
            strict_provenance=True,
        )
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # agreeing fingerprints: strict stays silent
        CostEstimator.from_bundle(bundle, corpus_fingerprint=fp, strict_provenance=True)


def test_bundle_load_verify_rejects_corrupt_arrays(tmp_path):
    """load(verify=True) must read every metric's npz params up front and
    wrap corruption in BundleIntegrityError at load time — not at first use
    mid-drain (the lazy default defers exactly that discovery)."""
    from repro.serve import BundleIntegrityError
    from repro.serve.chaos import corrupt_bundle

    bundle = CostModelBundle(_models(metrics=("latency_p",)), meta={"note": "verify"})
    d = str(tmp_path / "verify")
    bundle.save(d)
    CostModelBundle.load(d, verify=True)  # pristine: verification passes
    corrupt_bundle(d, seed=3)
    loaded = CostModelBundle.load(d)  # lazy default: corruption undetected
    assert loaded.metrics == ("latency_p",)
    with pytest.raises(BundleIntegrityError, match="failed verification"):
        CostModelBundle.load(d, verify=True)


# -- 0.7 shim removal ------------------------------------------------------------


def test_predict_shims_removed_in_0_7():
    """The deprecated ``core.model.predict_*`` surface is GONE at 0.7 (the
    removal horizon pinned in docs/api.md): no shim symbols, no deprecation
    machinery, and the numeric core neither imports ``warnings`` nor mentions
    ``DeprecationWarning``.  The facade is the one inference surface."""
    import inspect

    import repro
    from repro.core import model as model_mod

    assert repro.__version__.split(".")[:2] == ["0", "7"]
    for name in (
        "predict",
        "predict_proba",
        "predict_metrics",
        "predict_placements",
        "predict_placements_fused",
        "_DEPRECATION_WARNED",
        "_warn_deprecated",
    ):
        assert not hasattr(model_mod, name), f"core.model.{name} must be removed"
        assert not hasattr(repro.core, name), f"repro.core.{name} must be removed"
    src = inspect.getsource(model_mod)
    assert "DeprecationWarning" not in src
    assert "import warnings" not in src
    # the facade still answers everything the shims used to
    models = _models(metrics=("latency_p", "success"))
    est = CostEstimator(models)
    _, g = _graphs(n=6, seed=13)
    out = est.estimate(g)
    assert set(out) == {"latency_p", "success"}
    # proba is the mean of per-member sigmoids (not 1/mean(1+e^-x))
    from repro.kernels import active_lowering
    from repro.serve.estimator import _jitted_forward

    sparams, scfg = models["success"]
    raw = np.asarray(_jitted_forward(scfg, active_lowering())(sparams, g))
    np.testing.assert_allclose(
        est.proba(g, "success"), (1.0 / (1.0 + np.exp(-raw))).mean(axis=0), rtol=1e-6
    )


# -- service --------------------------------------------------------------------


def _service_inputs(n_requests=5, cands=6, seed=17):
    q = GEN.query(kind="two_way", name=f"svc{seed}")
    c = GEN.cluster(6)
    pool = sample_assignment_matrix(q, c, n_requests * cands, np.random.default_rng(seed))
    idx = np.arange(n_requests * cands) % len(pool)
    return q, c, [pool[idx[i * cands : (i + 1) * cands]] for i in range(n_requests)]


def test_service_coalesces_score_requests():
    """Requests enqueued before the worker starts drain as ONE batch; every
    answer equals the direct facade answer (coalescing is invisible)."""
    est = CostEstimator(_models())
    q, c, requests = _service_inputs()
    ref = [est.score(q, c, r) for r in requests]
    svc = PlacementService(est, auto_start=False)
    futs = [svc.submit_score(q, c, r) for r in requests]
    svc.start()
    got = [f.result(timeout=60) for f in futs]
    svc.close()
    for want, have in zip(ref, got):
        for m in want:
            np.testing.assert_allclose(have[m], want[m], rtol=1e-5, atol=1e-6, err_msg=m)
    assert svc.stats.n_requests == len(requests)
    assert svc.stats.n_batches == 1, "pre-queued requests must drain in one wake-up"
    assert svc.stats.n_forwards == 1, "same (query, cluster, metrics): one fused forward"
    assert svc.stats.n_coalesced == len(requests)


def test_service_groups_incompatible_requests():
    """Score and estimate requests coalesce only within their own kind, and
    all answers stay exact.  Score requests for *different* (query, cluster)
    structures now share ONE merged cross-query forward (the broadcast-batch
    path); estimates coalesce per metrics tuple as before."""
    est = CostEstimator(_models())
    q1, c1, reqs1 = _service_inputs(n_requests=2, seed=19)
    q2, c2, reqs2 = _service_inputs(n_requests=2, seed=23)
    traces, g = _graphs(n=5, seed=29)
    ref_est = est.estimate(g, ["latency_p"])
    svc = PlacementService(est, auto_start=False)
    f_scores = [svc.submit_score(q1, c1, r) for r in reqs1]
    f_scores += [svc.submit_score(q2, c2, r) for r in reqs2]
    f_est = svc.submit_estimate(g, ["latency_p"])
    f_est2 = svc.submit_estimate(g, ["latency_p"])
    svc.start()
    got = [f.result(timeout=60) for f in f_scores]
    got_est = f_est.result(timeout=60)
    got_est2 = f_est2.result(timeout=60)
    svc.close()
    refs = [est.score(q1, c1, r) for r in reqs1] + [est.score(q2, c2, r) for r in reqs2]
    for want, have in zip(refs, got):
        for m in want:
            # merged cross-query answers run the generic signature-banded
            # engine, not the placement-specialized sweep: same math,
            # different reduction order -> float-level tolerance
            np.testing.assert_allclose(have[m], want[m], rtol=1e-4, atol=1e-5, err_msg=m)
    # coalesced estimates run at the merged batch shape: float-level
    # reduction-order differences are allowed, semantic ones are not
    np.testing.assert_allclose(got_est["latency_p"], ref_est["latency_p"], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_est2["latency_p"], ref_est["latency_p"], rtol=1e-5, atol=1e-6)
    # 2 groups: score (q1 + q2 merged cross-query), estimate -- one drain
    assert svc.stats.n_forwards == 2
    assert svc.stats.n_coalesced == 6
    assert svc.stats.n_cross_query == 4  # the four score requests merged


def test_service_cross_query_off_restores_per_structure_drain():
    """cross_query=False pins the pre-merge semantics: one forward per
    (query structure, cluster, metrics) group, identical answers."""
    est = CostEstimator(_models())
    q1, c1, reqs1 = _service_inputs(n_requests=2, seed=19)
    q2, c2, reqs2 = _service_inputs(n_requests=2, seed=23)
    svc = PlacementService(est, auto_start=False, cross_query=False)
    futs = [svc.submit_score(q1, c1, r) for r in reqs1]
    futs += [svc.submit_score(q2, c2, r) for r in reqs2]
    svc.start()
    got = [f.result(timeout=60) for f in futs]
    svc.close()
    # the drain scores each structure's requests as ONE concatenated
    # candidate matrix (one bucket), so the reference is the facade call on
    # exactly that batch, split back per request
    refs = []
    for q, c, reqs in ((q1, c1, reqs1), (q2, c2, reqs2)):
        joined = est.score(q, c, np.concatenate(reqs))
        off = 0
        for r in reqs:
            refs.append({m: v[off : off + len(r)] for m, v in joined.items()})
            off += len(r)
    for want, have in zip(refs, got):
        for m in want:
            # per-structure groups take the same placement-specialized path
            # on the same batch as the direct facade call: bit-identical
            np.testing.assert_array_equal(have[m], want[m], err_msg=m)
    assert svc.stats.n_forwards == 2  # one per structure
    assert svc.stats.n_cross_query == 0


def test_service_delivers_exceptions():
    est = CostEstimator(_models(metrics=("latency_p",)))
    q, c, requests = _service_inputs(n_requests=1, seed=31)
    with PlacementService(est) as svc:
        bad = svc.submit_score(q, c, np.zeros((0, requests[0].shape[1]), dtype=np.int64))
        with pytest.raises(ValueError, match="no candidates"):
            bad.result(timeout=60)
        # the worker must survive a failed group and keep serving
        ok = svc.score(q, c, requests[0])
    np.testing.assert_allclose(
        ok["latency_p"], est.score(q, c, requests[0])["latency_p"], rtol=1e-5, atol=1e-6
    )
    # after close(): submissions must fail fast, never hang a future
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit_score(q, c, requests[0])
    # close() before start() must fail queued futures, not strand them
    svc2 = PlacementService(est, auto_start=False)
    orphan = svc2.submit_score(q, c, requests[0])
    svc2.close()
    with pytest.raises(RuntimeError, match="closed before start"):
        orphan.result(timeout=60)


def test_bad_request_never_fails_its_batchmates():
    """Metrics-tuple groups span unrelated callers: an empty (invalid) score
    request drained together with valid ones — same or different structures —
    must fail alone while every batchmate gets its exact answer."""
    est = CostEstimator(_models(metrics=("latency_p",)))
    good = _mixed_requests(n=3, cands=4, seed=67)
    q0, c0, a0 = good[0]
    svc = PlacementService(est, auto_start=False)
    futs = [svc.submit_score(q, c, a) for q, c, a in good]
    bad = svc.submit_score(q0, c0, np.zeros((0, a0.shape[1]), dtype=np.int64))
    svc.start()
    with pytest.raises(ValueError, match="no candidates"):
        bad.result(timeout=60)
    got = [f.result(timeout=60) for f in futs]
    svc.close()
    for (q, c, a), have in zip(good, got):
        want = est.score(q, c, a)
        np.testing.assert_allclose(
            have["latency_p"], want["latency_p"], rtol=1e-4, atol=1e-5
        )
    assert svc.stats.n_cross_query == 3  # the valid requests still merged


@pytest.mark.parametrize("profiled", [False, True])
def test_malformed_request_fails_alone_and_the_service_goes_on(profiled, tmp_path):
    """A 0-d assignments array drained with valid requests fails alone, with
    or without a profiler recording the drain's spans, and the worker keeps
    answering later requests."""
    est = CostEstimator(_models(metrics=("latency_p",)))
    good = _mixed_requests(n=3, cands=4, seed=71)
    q0, c0, a0 = good[0]
    svc = PlacementService(est, auto_start=False)
    futs = [svc.submit_score(q, c, a) for q, c, a in good]
    bad = svc.submit_score(q0, c0, np.asarray(3))
    if profiled:
        jax.profiler.start_trace(str(tmp_path))
    try:
        svc.start()
        with pytest.raises(ValueError, match="matrix"):
            bad.result(timeout=60)
        got = [f.result(timeout=60) for f in futs]
    finally:
        if profiled:
            jax.profiler.stop_trace()
    later = svc.submit_score(q0, c0, a0).result(timeout=60)
    svc.close()
    for (q, c, a), have in zip(good, got):
        np.testing.assert_allclose(
            have["latency_p"], est.score(q, c, a)["latency_p"], rtol=1e-4, atol=1e-5
        )
    np.testing.assert_allclose(later["latency_p"], got[0]["latency_p"], rtol=1e-5, atol=1e-6)
    assert svc.stats.n_failed == 0


def test_service_chunks_oversized_groups():
    """A coalesced group larger than max_batch is scored in chunks but still
    answered per request, exactly."""
    est = CostEstimator(_models(metrics=("latency_p",)))
    q, c, requests = _service_inputs(n_requests=6, cands=4, seed=37)
    ref = [est.score(q, c, r) for r in requests]
    svc = PlacementService(est, max_batch=8, auto_start=False)
    futs = [svc.submit_score(q, c, r) for r in requests]
    svc.start()
    got = [f.result(timeout=60) for f in futs]
    svc.close()
    for want, have in zip(ref, got):
        np.testing.assert_allclose(have["latency_p"], want["latency_p"], rtol=1e-5, atol=1e-6)
    assert svc.stats.n_forwards == 3  # 24 rows / max_batch 8

    # the estimate path chunks by max_batch too, splitting WITHIN a request
    _, g = _graphs(n=5, seed=41)
    ref_g = est.estimate(g, ["latency_p"])["latency_p"]
    svc = PlacementService(est, max_batch=4, auto_start=False)
    futs = [svc.submit_estimate(g, ["latency_p"]) for _ in range(2)]
    svc.start()
    answers = [f.result(timeout=60) for f in futs]
    svc.close()
    for have in answers:
        np.testing.assert_allclose(have["latency_p"], ref_g, rtol=1e-4, atol=1e-5)
    assert svc.stats.n_forwards == 3  # 10 graphs / max_batch 4


# -- package surface ------------------------------------------------------------


def test_top_level_package_surface():
    assert repro.__version__
    for name in repro.__all__:
        assert getattr(repro, name, None) is not None, name
    assert repro.CostEstimator is CostEstimator
    assert repro.CostModelBundle is CostModelBundle
