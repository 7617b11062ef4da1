"""The serving path's profiler spans: ``PlacementService`` and
``CostEstimator`` open ``costream.*`` spans on the thread that does each piece
of work, with their counts as arguments, and change no answer."""

import jax
import numpy as np
import pytest

from repro.core import CostModelConfig, GNNConfig, init_cost_model
from repro.core.graph import batch_graphs, bucket_size, build_graph
from repro.dsps import WorkloadGenerator
from repro.placement import sample_assignment_matrix
from repro.serve import CostEstimator, PlacementService

METRICS = ("latency_p", "success", "backpressure")


def _estimator():
    models = {}
    for i, m in enumerate(METRICS):
        cfg = CostModelConfig(metric=m, n_ensemble=2, gnn=GNNConfig(hidden=16))
        models[m] = (init_cost_model(jax.random.PRNGKey(i), cfg), cfg)
    return CostEstimator(models)


_EST = _estimator()
_GEN = WorkloadGenerator(seed=83)
_STRUCTURES = [
    (_GEN.query(kind="linear", name="trace0"), _GEN.cluster(3)),
    (_GEN.query(kind="two_way", name="trace1"), _GEN.cluster(5)),
]
_GRAPHS = batch_graphs([build_graph(t.query, t.cluster, t.placement) for t in WorkloadGenerator(seed=89).corpus(3)])
_RNG = np.random.default_rng(5)
#: a few rows for each structure (one merged drain), then many for one (placed)
_SMALL = [sample_assignment_matrix(q, c, 4, _RNG) for q, c in _STRUCTURES]
_LARGE = sample_assignment_matrix(*_STRUCTURES[0], 24, _RNG)


def _serve():
    """Three drains on a double-buffered service: ``_SMALL`` (merged),
    ``_LARGE`` (placed), one estimate; returns the answers in submit order."""
    svc = PlacementService(_EST, auto_start=False, double_buffer=True)
    first = [svc.submit_score(q, c, a) for (q, c), a in zip(_STRUCTURES, _SMALL)]
    svc.start()
    answers = [f.result(timeout=120) for f in first]
    answers.append(svc.submit_score(*_STRUCTURES[0], _LARGE).result(timeout=120))
    answers.append(svc.submit_estimate(_GRAPHS).result(timeout=120))
    svc.close()
    return answers


def _program_spans(log_dir):
    """(name, start_ns, end_ns, args, line index) of every ``costream.*``
    event on the host plane of the recorded trace."""
    import glob
    import os

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.startswith("costream."):
                        out.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats), i))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    _serve()  # compile outside the trace
    log_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(log_dir)
    try:
        answers = _serve()
    finally:
        jax.profiler.stop_trace()
    return answers, _program_spans(log_dir)


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(child, parents):
    return any(p[4] == child[4] and p[1] <= child[1] and child[2] <= p[2] for p in parents)


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_spans_on_their_threads_with_their_arguments(traced):
    _, spans = traced
    waits = _named(spans, "costream.worker.wait")
    assert waits
    (worker,) = {s[4] for s in waits}
    submits = _named(spans, "costream.submit")
    assert sorted(s[3]["req"] for s in submits) == [0, 1, 2, 3]
    assert all(s[4] != worker for s in submits)  # on the callers' thread
    launches = _named(spans, "costream.launch")
    finalizes = _named(spans, "costream.finalize")
    assert all(s[4] == worker for s in launches + finalizes)
    by_path = {s[3]["path"]: s[3] for s in launches}
    assert set(by_path) == {"merged", "placed", "estimate"}
    assert {k: by_path["merged"][k] for k in ("n_req", "first_req", "last_req", "rows")} == \
        {"n_req": 2, "first_req": 0, "last_req": 1, "rows": sum(len(a) for a in _SMALL)}
    assert {k: by_path["placed"][k] for k in ("n_req", "first_req", "last_req", "rows")} == \
        {"n_req": 1, "first_req": 2, "last_req": 2, "rows": len(_LARGE)}
    assert by_path["estimate"]["rows"] == 3 and by_path["estimate"]["first_req"] == 3
    assert len({s[3]["drain"] for s in launches}) == 3
    for name in ("costream.featurize", "costream.dispatch"):
        found = _named(spans, name)
        assert found and all(s[4] == worker and _inside(s, launches) for s in found)
        assert all(s[3]["rows"] > 0 for s in found)
    fetches = _named(spans, "costream.fetch")
    assert len(fetches) >= 3 and all(_inside(s, finalizes) for s in fetches)
    # bytes copied to the device: the placed path hands over its int32 index matrix
    dispatches = _named(spans, "costream.dispatch")
    assert all(s[3]["bytes"] > 0 for s in dispatches)
    placed = [s for s in launches if s[3]["path"] == "placed"]
    assert [s[3]["bytes"] for s in dispatches if _inside(s, placed)] == \
        [bucket_size(len(_LARGE)) * _STRUCTURES[0][0].n_ops() * 4]


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_every_finalize_follows_its_launch(traced):
    _, spans = traced
    launches = {s[3]["drain"]: s for s in _named(spans, "costream.launch")}
    finalizes = _named(spans, "costream.finalize")
    assert len(finalizes) == len(launches)
    for f in finalizes:
        launch = launches[f[3]["drain"]]
        assert launch[2] <= f[1] and launch[3]["n_req"] == f[3]["n_req"]


def test_answers_bit_identical_with_a_profiler_running(traced):
    answers, _ = traced
    again = _serve()
    assert len(answers) == len(again) == 4
    for a, b in zip(answers, again):
        assert set(a) == set(b) == set(METRICS)
        for m in METRICS:
            assert np.array_equal(np.asarray(a[m]), np.asarray(b[m]))


def test_submit_numbers_are_distinct_under_concurrent_callers():
    """Many caller threads submitting at once, with the interpreter switching
    threads as often as it can: every request gets its own ``req``."""
    import sys
    import threading

    svc = PlacementService(_EST, auto_start=False)
    q, c = _STRUCTURES[0]
    per_thread, n_threads = 50, 16

    def caller():
        for _ in range(per_thread):
            svc.submit_score(q, c, _SMALL[0])

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=caller) for _ in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads)
    assert sorted(r.seq for r in svc._queue) == list(range(per_thread * n_threads))
    svc.close()
