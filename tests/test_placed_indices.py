"""The placed engine's index input: ``CostEstimator.score`` hands the device
the ``(B, n_ops)`` host-index matrix and the jitted forward builds the
trimmed placement adjacency there.  Its answers are bit-identical to the
one-hot ``a_place`` entry's, and a host index outside the cluster is refused."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.serve.estimator as estimator_mod
from repro.core import CostModelConfig, GNNConfig, init_cost_model
from repro.core.gnn import apply_gnn_placed_stacked, apply_gnn_placed_stacked_idx, trimmed_columns
from repro.core.graph import (
    bucket_size,
    build_a_place_batch,
    build_graph_skeleton,
    query_static,
    slot_index,
)
from repro.dsps import WorkloadGenerator
from repro.placement import sample_assignment_matrix
from repro.serve import CostEstimator, DispatchPolicy, PlacementService
from repro.serve.stacking import _split_votes, stack_metric_models

METRICS = ("latency_p", "success", "backpressure")
CHUNK = 256  # the score_chunk: B = 1,024 runs the panel scan


def _models():
    models = {}
    for i, m in enumerate(METRICS):
        cfg = CostModelConfig(metric=m, n_ensemble=2, gnn=GNNConfig(hidden=16))
        models[m] = (init_cost_model(jax.random.PRNGKey(i), cfg), cfg)
    return models


_MODELS = _models()
_STACKED = stack_metric_models(_MODELS)
_GNN = _MODELS["latency_p"][1].gnn


def _structure(kind, hosts, seed=3):
    gen = WorkloadGenerator(seed=seed)
    return gen.query(kind=kind, name=f"{kind}{hosts}"), gen.cluster(hosts)


def _raw_pair(q, c, a):
    """Raw ``(members, B)`` outputs of both stacked entries on one batch."""
    skel = jax.tree_util.tree_map(jnp.asarray, build_graph_skeleton(q, c))
    static = query_static(q)
    cols = trimmed_columns(static, slot_index(q))
    n_hw = c.n_nodes()
    one_hot = jax.jit(
        lambda p, s, ap: apply_gnn_placed_stacked(p, s, ap, static, _GNN, n_hw, CHUNK)
    )(_STACKED.params, skel, jnp.asarray(build_a_place_batch(q, c, a)))
    index = jax.jit(
        lambda p, s, ix: apply_gnn_placed_stacked_idx(p, s, ix, cols, static, _GNN, n_hw, CHUNK)
    )(_STACKED.params, skel, jnp.asarray(a, dtype=jnp.int32))
    return np.asarray(one_hot), np.asarray(index)


@pytest.mark.parametrize("hosts", [3, 6, 8])
@pytest.mark.parametrize("kind", ["linear", "two_way", "three_way"])
def test_index_input_matches_one_hot_input_bit_for_bit(kind, hosts):
    """Both entries, raw and voted, on a padded bucket (11 rows in 16) and on
    1,024 rows (the panel scan), for every served metric."""
    q, c = _structure(kind, hosts)
    rng = np.random.default_rng(hosts)
    est = CostEstimator(_MODELS, policy=DispatchPolicy(score_chunk=CHUNK))
    for n in (11, 1024):
        a = rng.integers(0, hosts, size=(n, q.n_ops()))
        padded = np.concatenate([a, np.repeat(a[-1:], bucket_size(n) - n, axis=0)])
        one_hot, index = _raw_pair(q, c, padded)
        assert index.shape == (sum(_STACKED.sizes), bucket_size(n))
        np.testing.assert_array_equal(index, one_hot)
        want = _split_votes(one_hot, _STACKED)
        got = est.score(q, c, a)
        assert set(got) == set(METRICS)
        for m in METRICS:
            np.testing.assert_array_equal(got[m], want[m][:n], err_msg=f"{m} at {n} rows")


class _Span:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each span's
    name and arguments."""

    opened = []

    def __init__(self, name, **args):
        self.opened.append((name, args))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_score_hands_the_device_one_int32_index_matrix(monkeypatch):
    """A fusable stack builds no one-hot on the host: ``score`` dispatches one
    ``(bucket, n_ops)`` int32 array, and its ``costream.dispatch`` span's
    ``bytes`` is that array's size."""
    q, c = _structure("two_way", 6)
    a = sample_assignment_matrix(q, c, 11, np.random.default_rng(4))
    est = CostEstimator(_MODELS)
    est.score(q, c, a)  # warm: skeleton and trace
    dispatched = []
    orig = estimator_mod._jitted_placed_forward_stacked

    def spy_factory(*key):
        fwd = orig(*key)

        def spy(params, skel, assign):
            dispatched.append(assign)
            return fwd(params, skel, assign)

        return spy

    def no_one_hot(*a, **k):
        raise AssertionError("the fused placed path built a host a_place")

    _Span.opened = []
    monkeypatch.setattr(estimator_mod, "_jitted_placed_forward_stacked", spy_factory)
    monkeypatch.setattr(estimator_mod, "build_a_place_batch", no_one_hot)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Span)
    got = est.score(q, c, a)
    assert len(dispatched) == 1
    (sent,) = dispatched
    assert sent.shape == (bucket_size(len(a)), q.n_ops()) and sent.dtype == jnp.int32
    (span,) = [args for name, args in _Span.opened if name == "costream.dispatch"]
    assert span == {"rows": bucket_size(len(a)), "bytes": bucket_size(len(a)) * q.n_ops() * 4}
    assert set(got) == set(METRICS)


@pytest.mark.parametrize("bad", ["below", "above"])
def test_out_of_range_host_index_is_refused(bad):
    q, c = _structure("linear", 5)
    a = sample_assignment_matrix(q, c, 8, np.random.default_rng(6))
    a[3, 1] = -1 if bad == "below" else c.n_nodes()
    with pytest.raises(ValueError, match="host index"):
        CostEstimator(_MODELS).score(q, c, a)


@pytest.mark.parametrize("bad", ["below", "above"])
def test_out_of_range_request_fails_alone_in_the_service(bad):
    """One request of a drain carries a host index outside its cluster: it
    alone fails with ``ValueError``; its batchmates on the same structure
    (one placed forward) are answered exactly."""
    q, c = _structure("three_way", 6)
    rng = np.random.default_rng(8)
    good = [sample_assignment_matrix(q, c, 12, rng) for _ in range(3)]
    wrong = good[0].copy()
    wrong[5, 2] = -1 if bad == "below" else c.n_nodes()
    est = CostEstimator(_MODELS)
    svc = PlacementService(est, auto_start=False)
    futs = [svc.submit_score(q, c, a) for a in good[:2]]
    refused = svc.submit_score(q, c, wrong)
    futs.append(svc.submit_score(q, c, good[2]))
    svc.start()
    with pytest.raises(ValueError, match="host index"):
        refused.result(timeout=60)
    got = [f.result(timeout=60) for f in futs]
    svc.close()
    want = est.score(q, c, np.concatenate(good))
    for i, have in enumerate(got):
        for m in METRICS:
            np.testing.assert_array_equal(have[m], want[m][12 * i : 12 * (i + 1)], err_msg=m)
    assert svc.stats.n_failed == 0 and svc.stats.n_degraded == 0
