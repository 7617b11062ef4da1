"""Placement enumeration rules (Fig. 5), optimizer (Fig. 4), baselines."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings, strategies as st

from repro.core import CostModelConfig, GNNConfig, init_cost_model
from repro.core.graph import (
    batch_graphs,
    build_a_place_batch,
    build_graph,
    build_graph_skeleton,
    query_static,
    skeleton_cache_key,
    slot_index,
)
from repro.core.gnn import trimmed_columns
from repro.serve.estimator import (
    CostEstimator,
    ensemble_predict,
    placed_predict,
    placed_predict_fused,
)
from repro.serve.stacking import stack_metric_models
from repro.dsps import WorkloadGenerator, simulate
from repro.dsps.placement import (
    Placement,
    is_acyclic_placement,
    respects_increasing_capability,
)
from repro.dsps.simulator import SimulatorConfig
from repro.placement import (
    PlacementOptimizer,
    batch_validity_mask,
    heuristic_placement,
    mutate_assignments,
    online_monitoring_run,
    sample_assignment_matrix,
    sample_assignments,
    valid_candidate,
)

GEN = WorkloadGenerator(seed=21)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 5000))
def test_enumeration_respects_rules(seed):
    gen = WorkloadGenerator(seed=seed)
    q = gen.query(name="e")
    c = gen.cluster(6)
    rng = np.random.default_rng(seed)
    for row in sample_assignment_matrix(q, c, 8, rng):
        p = Placement.of(row)
        assert respects_increasing_capability(q, c, p)
        assert is_acyclic_placement(q, p)
        p.validate(q, c)


def test_heuristic_placement_valid():
    for i in range(10):
        q = GEN.query(name=f"h{i}")
        c = GEN.cluster(6)
        p = heuristic_placement(q, c)
        p.validate(q, c)
        assert valid_candidate(q, c, p)


def _tiny_models():
    models = {}
    for m in ("latency_p", "success", "backpressure"):
        cfg = CostModelConfig(metric=m, n_ensemble=2, gnn=GNNConfig(hidden=16))
        models[m] = (init_cost_model(jax.random.PRNGKey(0), cfg), cfg)
    return models


def test_optimizer_returns_valid_candidate():
    opt = PlacementOptimizer(_tiny_models())
    q = GEN.query(kind="two_way", name="opt")
    c = GEN.cluster(6)
    res = opt.optimize(q, c, "latency_p", k=12, rng=np.random.default_rng(1))
    res.placement.validate(q, c)
    assert valid_candidate(q, c, res.placement)
    assert res.n_candidates > 0
    assert len(res.scores) == res.n_candidates


def test_optimizer_feasibility_filter():
    opt = PlacementOptimizer(_tiny_models())
    q = GEN.query(name="feas")
    c = GEN.cluster(5)
    res = opt.optimize(q, c, "latency_p", k=8, rng=np.random.default_rng(2))
    assert 0 < res.n_feasible <= res.n_candidates


def test_monitoring_baseline_improves_or_stops():
    q = GEN.query(kind="linear", name="mon")
    c = GEN.cluster(6)
    init = heuristic_placement(q, c)
    target = simulate(q, c, init).latency_p * 0.5  # ambitious target
    res = online_monitoring_run(q, c, init, target_latency=target, max_rounds=6)
    assert res.final_latency <= res.initial_latency * 1.5
    assert len(res.steps) >= 1
    assert res.migrations >= 0


# -- vectorized search path (docs/placement_search.md) -------------------------


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 5000))
def test_batch_validity_mask_matches_scalar_rules(seed):
    """The vectorized rule check is exactly the scalar Fig.-5 predicates."""
    gen = WorkloadGenerator(seed=seed)
    q = gen.query(name="vm")
    c = gen.cluster(3 + seed % 6)
    rng = np.random.default_rng(seed)
    a = sample_assignments(q, c, 128, rng)
    mask = batch_validity_mask(q, c, a)
    ref = np.asarray([valid_candidate(q, c, Placement.of(row)) for row in a])
    np.testing.assert_array_equal(mask, ref)


def test_sampler_produces_only_valid_distinct_candidates():
    for seed in range(6):
        gen = WorkloadGenerator(seed=seed)
        q = gen.query(name="sv")
        c = gen.cluster(6)
        a = sample_assignment_matrix(q, c, 32, np.random.default_rng(seed))
        assert 0 < len(a) <= 32
        assert len(np.unique(a, axis=0)) == len(a)
        for row in a:
            assert valid_candidate(q, c, Placement.of(row))


def test_mutations_stay_valid_and_distinct():
    q = GEN.query(kind="two_way", name="mut")
    c = GEN.cluster(6)
    rng = np.random.default_rng(5)
    parents = sample_assignment_matrix(q, c, 8, rng)
    children = mutate_assignments(q, c, parents, 6, rng)
    assert len(children) > 0
    assert len(np.unique(children, axis=0)) == len(children)
    for row in children:
        assert valid_candidate(q, c, Placement.of(row))


def test_batched_scorer_matches_per_candidate_predict():
    """score_assignments (build once, all metrics) == per-candidate predict."""
    opt = PlacementOptimizer(_tiny_models())
    q = GEN.query(kind="linear", name="par")
    c = GEN.cluster(6)
    a = sample_assignment_matrix(q, c, 11, np.random.default_rng(7))
    fast = opt.score_assignments(q, c, a, ["latency_p", "success", "backpressure"])
    for metric in fast:
        params, cfg = opt.models[metric]
        singles = batch_graphs([build_graph(q, c, Placement.of(row)) for row in a])
        ref = ensemble_predict(params, jax.tree_util.tree_map(jnp.asarray, singles), cfg)
        np.testing.assert_allclose(fast[metric], ref, rtol=1e-5, atol=1e-6, err_msg=metric)


def test_padding_bucket_invariance():
    """Scores are identical whether the batch is bucket-padded or not, and do
    not depend on which other candidates share the batch."""
    opt = PlacementOptimizer(_tiny_models())
    q = GEN.query(name="pad")
    c = GEN.cluster(6)
    a = sample_assignment_matrix(q, c, 11, np.random.default_rng(9))
    n = len(a)
    together = opt.score_assignments(q, c, a, ["latency_p"])["latency_p"]
    head = opt.score_assignments(q, c, a[: n // 2], ["latency_p"])["latency_p"]
    np.testing.assert_allclose(together[: n // 2], head, rtol=1e-5, atol=1e-6)
    # power-of-two count: pad_batch is the identity, same scores still
    four = opt.score_assignments(q, c, a[:4], ["latency_p"])["latency_p"]
    np.testing.assert_allclose(together[:4], four, rtol=1e-5, atol=1e-6)


# -- kernel routing + fused ensembles + skeleton cache -------------------------


def _placed_inputs(seed=7, n=11, kind="two_way"):
    q = GEN.query(kind=kind, name=f"pk{seed}")
    c = GEN.cluster(6)
    a = sample_assignment_matrix(q, c, n, np.random.default_rng(seed))
    skel = jax.tree_util.tree_map(jnp.asarray, build_graph_skeleton(q, c))
    static = query_static(q)
    a_place = jnp.asarray(build_a_place_batch(q, c, a))
    return q, c, a, skel, static, a_place


@pytest.mark.parametrize("lowering", ["ref", "interpret"])
def test_placed_path_pallas_matches_jnp(lowering, monkeypatch):
    """apply_gnn_placed with use_pallas=True must be numerically equivalent to
    the jnp banked-MLP path under BOTH off-TPU lowerings of the kernel ops:
    the compiled jnp-oracle lowering (default) and the forced Pallas
    interpreter, which executes the actual kernel bodies."""
    from repro.core.gnn import apply_gnn_placed, init_gnn

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1" if lowering == "interpret" else "0")
    _, _, _, skel, static, a_place = _placed_inputs()
    cfg_j = GNNConfig(hidden=16)
    cfg_p = GNNConfig(hidden=16, use_pallas=True)
    params = init_gnn(jax.random.PRNGKey(3), cfg_j)
    out_j = apply_gnn_placed(params, skel, a_place, static, cfg_j)
    out_p = apply_gnn_placed(params, skel, a_place, static, cfg_p)
    np.testing.assert_allclose(np.asarray(out_j), np.asarray(out_p), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("lowering", ["ref", "interpret"])
def test_stacked_path_pallas_matches_jnp(lowering, monkeypatch):
    """The stacked trimmed forward under use_pallas — including the banded
    per-level row_span mp_update calls — matches its jnp twin under both
    off-TPU lowerings (the interpret case executes the kernel bodies)."""
    from repro.core.gnn import apply_gnn_placed_stacked

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1" if lowering == "interpret" else "0")
    _, _, _, skel, static, a_place = _placed_inputs(seed=12)
    models = _tiny_models()
    stacked = stack_metric_models(models)
    n_hw = int(np.asarray(skel.hw_mask).sum())
    gnn_j = models["latency_p"][1].gnn
    gnn_p = GNNConfig(hidden=gnn_j.hidden, use_pallas=True)
    out_j = apply_gnn_placed_stacked(stacked.params, skel, a_place, static, gnn_j, n_hw)
    out_p = apply_gnn_placed_stacked(stacked.params, skel, a_place, static, gnn_p, n_hw)
    np.testing.assert_allclose(np.asarray(out_j), np.asarray(out_p), atol=1e-4, rtol=1e-4)


def test_placed_predict_pallas_parity():
    """The full placed-predict path (jit + ensemble vmap + vote) agrees
    between the Pallas-routed and jnp scorers on every metric type."""
    _, _, _, skel, static, a_place = _placed_inputs(seed=8)
    for metric in ("latency_p", "success"):
        cfg_j = CostModelConfig(metric=metric, n_ensemble=2, gnn=GNNConfig(hidden=16))
        cfg_p = CostModelConfig(
            metric=metric, n_ensemble=2, gnn=GNNConfig(hidden=16, use_pallas=True)
        )
        params = init_cost_model(jax.random.PRNGKey(0), cfg_j)
        ref = placed_predict(params, skel, a_place, static, cfg_j)
        got = placed_predict(params, skel, a_place, static, cfg_p)
        if metric == "success":  # classification: votes must match exactly
            np.testing.assert_array_equal(got, ref, err_msg=metric)
        else:
            np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4, err_msg=metric)


def test_stacked_ensembles_match_per_metric_loop():
    """One fused stacked forward == the per-(metric, member) loop, to float
    tolerance, for both the placed path and the generic estimate path."""
    q, c, a, skel, static, a_place = _placed_inputs(seed=9)
    models = _tiny_models()
    stacked = stack_metric_models(models)
    assert stacked.sizes == (2, 2, 2)
    cols = trimmed_columns(static, slot_index(q))
    fused = placed_predict_fused(stacked, skel, jnp.asarray(a, dtype=jnp.int32), static, cols)
    for metric, (params, cfg) in models.items():
        ref = placed_predict(params, skel, a_place, static, cfg)
        np.testing.assert_allclose(fused[metric], ref, rtol=1e-5, atol=1e-6, err_msg=metric)
    # generic path: estimate (fused internally) vs per-metric ensemble_predict
    g = jax.tree_util.tree_map(
        jnp.asarray, batch_graphs([build_graph(q, c, Placement.of(r)) for r in a])
    )
    scored = CostEstimator(models).estimate(g)
    for metric, (params, cfg) in models.items():
        np.testing.assert_allclose(
            scored[metric], ensemble_predict(params, g, cfg), rtol=1e-5, atol=1e-6, err_msg=metric
        )


def test_stack_metric_models_rejects_mixed_configs():
    models = _tiny_models()
    cfg = CostModelConfig(metric="latency_e", n_ensemble=2, gnn=GNNConfig(hidden=8))
    models["latency_e"] = (init_cost_model(jax.random.PRNGKey(5), cfg), cfg)
    with pytest.raises(ValueError):
        stack_metric_models(models)
    # the optimizer must still score correctly through the per-metric fallback
    opt = PlacementOptimizer(models)
    q = GEN.query(name="mix")
    c = GEN.cluster(6)
    a = sample_assignment_matrix(q, c, 6, np.random.default_rng(3))
    got = opt.score_assignments(q, c, a, ["latency_p", "latency_e"])
    for metric in ("latency_p", "latency_e"):
        params, cfg = opt.models[metric]
        skel = jax.tree_util.tree_map(jnp.asarray, build_graph_skeleton(q, c))
        ref = placed_predict(
            params, skel, jnp.asarray(build_a_place_batch(q, c, a)), query_static(q), cfg
        )[: len(a)]
        np.testing.assert_allclose(got[metric], ref, rtol=1e-5, atol=1e-6, err_msg=metric)


def test_use_pallas_raises_loudly_on_unfusable_config():
    """use_pallas must never silently fall back to jnp: a config the kernels
    cannot fuse (!= 2 layers) raises instead."""
    from repro.core.gnn import apply_gnn_placed, init_gnn

    _, _, _, skel, static, a_place = _placed_inputs(seed=10)
    cfg = GNNConfig(hidden=16, update_layers=3, use_pallas=True)
    params = init_gnn(jax.random.PRNGKey(0), cfg)
    with pytest.raises(NotImplementedError, match="use_pallas"):
        apply_gnn_placed(params, skel, a_place, static, cfg)


def test_skeleton_cached_across_optimize_calls(monkeypatch):
    """The second optimize() on the same (query, cluster) must perform ZERO
    build_graph_skeleton rebuilds (the online-monitoring amortization).

    The skeleton LRU lives on the CostEstimator facade since the serving
    redesign, so the counter patches repro.serve.estimator."""
    import repro.serve.estimator as estimator_mod

    calls = {"n": 0}
    orig = estimator_mod.build_graph_skeleton

    def counted(*args, **kw):
        calls["n"] += 1
        return orig(*args, **kw)

    monkeypatch.setattr(estimator_mod, "build_graph_skeleton", counted)
    opt = PlacementOptimizer(_tiny_models())
    q = GEN.query(kind="linear", name="cache")
    c = GEN.cluster(6)
    opt.optimize(q, c, "latency_p", k=8, rng=np.random.default_rng(0))
    first = calls["n"]
    assert first == 1
    r1 = opt.optimize(q, c, "latency_p", k=8, rng=np.random.default_rng(1))
    assert calls["n"] == first  # cache hit: zero rebuilds
    # a *different* query must miss the cache, not reuse a stale skeleton
    q2 = GEN.query(kind="two_way", name="cache2")
    assert skeleton_cache_key(q2, c) != skeleton_cache_key(q, c)
    opt.optimize(q2, c, "latency_p", k=8, rng=np.random.default_rng(2))
    assert calls["n"] == first + 1
    r1.placement.validate(q, c)


def test_skeleton_cache_key_structural():
    """Equal-structure (query, cluster) pairs share a key even when they are
    distinct objects; differing clusters do not."""
    gen_a = WorkloadGenerator(seed=55)
    gen_b = WorkloadGenerator(seed=55)
    qa, qb = gen_a.query(name="a"), gen_b.query(name="b")
    ca, cb = gen_a.cluster(5), gen_b.cluster(5)
    assert qa is not qb and ca is not cb
    assert skeleton_cache_key(qa, ca) == skeleton_cache_key(qb, cb)
    assert skeleton_cache_key(qa, ca) != skeleton_cache_key(qa, gen_a.cluster(5))


class _OracleOptimizer(PlacementOptimizer):
    """Scores candidates with the simulator itself (no learned model), which
    isolates the search machinery — sampling, batching, refinement — from
    cost-model accuracy."""

    def __init__(self, sim):
        super().__init__(models={})
        self.sim = sim

    def score_assignments(self, query, cluster, assignments, metrics):
        lat = np.asarray(
            [
                simulate(query, cluster, Placement.of(row), self.sim).latency_p
                for row in np.asarray(assignments)
            ]
        )
        return {m: lat for m in metrics}


def test_refined_search_beats_heuristic_end_to_end():
    """With an oracle scorer, the refined search must find a placement at
    least as good (simulator-measured) as the deterministic heuristic, and
    refinement must never do worse than the unrefined sample."""
    sim = SimulatorConfig(noise_sigma=0.0)
    opt = _OracleOptimizer(sim)
    gen = WorkloadGenerator(seed=31)
    for i in range(4):
        q = gen.query(name=f"e2e{i}")
        c = gen.cluster(6)
        base_lat = simulate(q, c, heuristic_placement(q, c), sim).latency_p
        plain = opt.optimize(q, c, "latency_p", k=16, rng=np.random.default_rng(i), refine_rounds=0)
        refined = opt.optimize(q, c, "latency_p", k=16, rng=np.random.default_rng(i), refine_rounds=3)
        plain_lat = simulate(q, c, plain.placement, sim).latency_p
        refined_lat = simulate(q, c, refined.placement, sim).latency_p
        assert refined.n_candidates >= plain.n_candidates
        assert refined_lat <= plain_lat + 1e-9
        assert refined_lat <= base_lat + 1e-9
