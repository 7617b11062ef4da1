"""``chipbench/spans.py`` and the six program-span readers, on traces recorded
on the CPU in the test and on a hand-made one."""

import threading
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _chipbench_tiny import ROOT  # noqa: F401

from chipbench import bench
from chipbench import reference as R
from chipbench import spans as P
from chipbench import structures as S
from chipbench import trace as T

READERS = {  # metric -> (span names, self time)
    "submit_ms.initial": (["costream.submit"], False),
    "worker_ms.initial": (["costream.launch", "costream.finalize"], False),
    "featurize_ms.initial": (["costream.featurize"], False),
    "dispatch_ms.initial": (["costream.dispatch"], False),
    "fetch_ms.initial": (["costream.fetch"], False),
    "finalize_ms.initial": (["costream.finalize"], True),
}
#: the per-layer readers that ``synth-initial`` reports
TRACE_READERS = ["fwd_device_ms.initial", "fwd_mfu.initial", "device_idle_share.initial",
            "compiles_in_window.initial"]


def _options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A trace whose device gaps lie in known spans: the worker waits
    (also inside the benchmark's ``chipbench.wait``), then fetches with device
    work on both sides of a sleep (inside ``costream.finalize``); later the
    main thread sleeps in ``chipbench.sleep`` alone."""
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    log_dir = str(tmp_path_factory.mktemp("spans"))

    def worker():
        with jax.profiler.TraceAnnotation("costream.worker.wait"):
            time.sleep(0.04)
        with jax.profiler.TraceAnnotation("costream.launch", drain=1, n_req=2):
            with jax.profiler.TraceAnnotation("costream.dispatch", rows=8):
                f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("costream.finalize", drain=1, n_req=2):
            with jax.profiler.TraceAnnotation("costream.fetch"):
                f(x).block_until_ready()
                time.sleep(0.03)
                f(x).block_until_ready()
            time.sleep(0.01)
        f(x).block_until_ready()

    jax.profiler.start_trace(log_dir, profiler_options=_options())
    with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
        f(x).block_until_ready()
        th = threading.Thread(target=worker, name="placement-service")
        with jax.profiler.TraceAnnotation("chipbench.wait"):
            th.start()
            th.join()
        with jax.profiler.TraceAnnotation("chipbench.sleep"):
            time.sleep(0.05)
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    return T.find(log_dir)


def _span(name, start, end, line):
    return P.Span(name, start, end, {}, line)


def _handmade(lo=0.0, hi=10.0):
    spans = [
        _span("costream.launch", 1.0, 3.0, 0),
        _span("costream.featurize", 1.5, 2.0, 0),
        _span("costream.dispatch", 2.0, 2.5, 0),
        _span("costream.finalize", 4.0, 7.0, 0),
        _span("costream.fetch", 4.5, 6.0, 0),
        _span("costream.submit", 0.5, 1.5, 1),
        _span("costream.submit", 2.0, 2.25, 2),
        _span("costream.worker.wait", 8.0, 12.0, 0),
    ]
    return P.SpanTrace(ops={}, modules={}, spans=[], lo=lo, hi=hi, program_spans=spans)


def test_span_seconds_sums_clips_and_takes_self_time():
    tr = _handmade()
    assert tr.span_seconds(["costream.fetch"]) == pytest.approx(1.5)
    assert tr.span_seconds(["costream.launch", "costream.finalize"]) == pytest.approx(5.0)
    # a name nested inside another named span counts once
    assert tr.span_seconds(["costream.finalize", "costream.fetch"]) == pytest.approx(3.0)
    assert tr.span_seconds(["costream.finalize"], self_time=True) == pytest.approx(1.5)
    assert tr.span_seconds(["costream.launch"], self_time=True) == pytest.approx(1.0)
    # threads add: two callers' submits
    assert tr.span_seconds(["costream.submit"]) == pytest.approx(1.25)
    assert tr.span_seconds(["costream.worker.wait"]) == pytest.approx(2.0)  # clipped at hi
    narrow = _handmade(lo=5.0, hi=6.5)
    assert narrow.span_seconds(["costream.finalize"]) == pytest.approx(1.5)
    assert narrow.span_seconds(["costream.finalize"], self_time=True) == pytest.approx(0.5)
    assert tr.span_seconds(["costream.nothing"]) == 0.0


def test_worker_line_shares_sum_to_one():
    tr = _handmade()
    assert tr.worker_line() == 0
    shares = tr.line_shares(0)
    assert shares["costream.launch"] == pytest.approx(0.2)
    assert shares["costream.finalize"] == pytest.approx(0.3)
    assert shares["costream.worker.wait"] == pytest.approx(0.2)
    assert shares["no span"] == pytest.approx(0.3)
    assert "costream.fetch" not in shares  # nested: inside finalize's share


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_read_keeps_program_spans_on_their_lines(recorded):
    tr = P.read(recorded, host_ops=True)
    names = {s.name for s in tr.program_spans}
    assert names == {"costream.worker.wait", "costream.launch", "costream.dispatch", "costream.finalize",
                     "costream.fetch"}
    assert {s.name for s in tr.spans} == {"chipbench.wait", "chipbench.sleep"}
    assert len({s.line for s in tr.program_spans}) == 1 and tr.worker_line() is not None
    (launch,) = [s for s in tr.program_spans if s.name == "costream.launch"]
    assert launch.stats == {"drain": 1, "n_req": 2}
    fetch = tr.span_seconds(["costream.fetch"])
    assert fetch >= 0.03
    finalize = tr.span_seconds(["costream.finalize"])
    assert tr.span_seconds(["costream.finalize"], self_time=True) == pytest.approx(finalize - fetch)


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_idle_gaps_prefer_the_program_span(recorded):
    tr = P.read(recorded, host_ops=True)
    gaps = dict((name, t) for name, t in tr.idle_gaps(10) if t >= 0.02)
    # inside the worker's wait (and the benchmark's wait): the program's span names it
    assert gaps["costream.worker.wait"] >= 0.035
    # inside fetch inside finalize: a tie of overlap goes to the innermost span
    assert gaps["costream.fetch"] >= 0.025
    # covered by no program span: the benchmark's span, as before
    assert gaps["chipbench.sleep"] >= 0.045
    assert "costream.finalize" not in gaps
    base = T.read(recorded, host_ops=True).idle_gaps(10)
    assert {name for name, t in base if t >= 0.02} <= {"chipbench.wait", "chipbench.sleep"}


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
@pytest.mark.parametrize("metric", TRACE_READERS)
def test_trace_readers_read_the_same(recorded, metric):
    old, new = T.read(recorded, host_ops=True), P.read(recorded, host_ops=True)
    assert (old.busy_s(), old.window_s(), old.module_seconds()) == (new.busy_s(), new.window_s(),
                                                                     new.module_seconds())
    read = bench.reader(metric)

    def ctx(tr):
        return SimpleNamespace(trace=tr, traced_stats={"n_drained": 7}, compiles_in_window=0, seconds=1.0,
                               flops_answered=1e9, peaks={"flops_per_s": 1e12})

    value = read(ctx(new))
    assert value is not None and value == read(ctx(old))


@pytest.mark.parametrize("metric", sorted(READERS))
def test_program_span_readers(metric):
    names, self_time = READERS[metric]
    read = bench.reader(metric)
    tr = _handmade()
    ctx = SimpleNamespace(trace=tr, traced_stats={"n_drained": 4})
    assert read(ctx) == pytest.approx(1e3 * tr.span_seconds(names, self_time) / 4)
    assert read(ctx) > 0
    # nothing to read: a plain trace, no trace, no request popped
    plain = T.Trace(ops={}, modules={}, spans=[], lo=0.0, hi=10.0)
    assert read(SimpleNamespace(trace=plain, traced_stats={"n_drained": 4})) is None
    assert read(SimpleNamespace(trace=None, traced_stats=None)) is None
    assert read(SimpleNamespace(trace=tr, traced_stats={"n_drained": 0})) is None


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_readers_find_the_service_spans(tmp_path):
    """A traced window around a tiny closed loop on ``PlacementService``:
    every reader finds its spans, and the worker's line is accounted for."""
    from repro.serve import CostEstimator, PlacementService

    spec = bench.Spec()
    config, traffic = spec.config("costream-synth"), spec.traffic("initial-closed")
    config["model"].update(hidden=16)
    traffic.update(pool=2)
    wl = bench.Workload(config, traffic, 2**31 + 5)
    weights = R.make_weights(config["model"], 2**31 + 5)
    est = CostEstimator(bench.build_models(config["model"], weights))
    sess = PlacementService(est, double_buffer=True)
    rng = np.random.default_rng(3)
    reqs = [(q, c, S.sample_candidates(s, wl.hw, 32, rng)) for (q, c), s in zip(wl.programs, wl.pool)]
    for q, c, a in reqs:  # warm every shape the loop sends
        sess.submit_score(q, c, a, wl.metrics).result(timeout=120)
    stop = time.perf_counter() + 0.6

    def caller(i):
        while time.perf_counter() < stop:
            q, c, a = reqs[i]
            sess.submit_score(q, c, a, wl.metrics).result(timeout=120)

    jax.profiler.start_trace(str(tmp_path), profiler_options=_options())
    stats0 = dict(vars(sess.stats))
    threads = [threading.Thread(target=caller, args=(i,)) for i in range(2)]
    with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    stats1 = dict(vars(sess.stats))
    jax.profiler.stop_trace()
    sess.close()
    tr = P.read(T.find(str(tmp_path)), host_ops=True)
    ctx = SimpleNamespace(trace=tr, traced_stats={"n_drained": stats1["n_drained"] - stats0["n_drained"]})
    values = {m: bench.reader(m)(ctx) for m in READERS}
    assert all(v is not None and v > 0 for v in values.values()), values
    assert values["worker_ms.initial"] >= values["featurize_ms.initial"] + values["dispatch_ms.initial"]
    shares = tr.line_shares(tr.worker_line())
    assert {"costream.launch", "costream.finalize", "costream.worker.wait"} <= set(shares)
    assert min(shares.values()) >= -1e-9  # outermost spans on one thread never overlap
    assert any(name.startswith("costream.") for name, _ in tr.idle_gaps(10))


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_span_readings_reads_a_traced_run():
    """The one-off ``span_readings.py`` gives the six readings of a traced
    run of the cell at test size, and leaves the harness as it found it."""
    from _chipbench_tiny import CPU_PEAKS, tiny

    from chipbench import span_readings

    before = (bench.T, bench.Spec.metrics, bench._delta)
    out = span_readings.readings("synth-initial", 2**31 + 19, 8.0, overrides=tiny("synth-initial"),
                                 peaks=CPU_PEAKS)
    assert (bench.T, bench.Spec.metrics, bench._delta) == before
    assert out["correct"], out
    assert set(out["readings"]) == set(span_readings.READERS)
    assert all(v > 0 for v in out["readings"].values())
    assert out["traced"]["pops_per_s"] > 0
    shares = out["worker_shares"]
    assert {"costream.launch", "costream.finalize", "costream.worker.wait"} <= set(shares)
    assert sum(shares.values()) == pytest.approx(1.0)
