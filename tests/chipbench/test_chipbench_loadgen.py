"""Arrival schedules are fixed by the seed and hold the offered load; the
open-loop driver records how late it fired and what never came back."""

import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest
from _chipbench_tiny import ROOT  # noqa: F401  (puts the checkout on sys.path)

from chipbench import loadgen as L

BURSTY = {"kind": "bursty", "burst_factor": 4.0, "burst_fraction": 0.25, "period_s": 1.0}


@pytest.mark.parametrize("spec", [{"kind": "poisson"}, BURSTY])
def test_arrivals_same_seed_same_schedule_and_same_count_for_any_seed(spec):
    a = L.arrivals(spec, 200.0, 5.0, np.random.default_rng(3))
    b = L.arrivals(spec, 200.0, 5.0, np.random.default_rng(3))
    c = L.arrivals(spec, 200.0, 5.0, np.random.default_rng(2**31 + 5))
    np.testing.assert_array_equal(a, b)
    assert len(a) == len(c) == 1000
    assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < 5.0
    assert not np.array_equal(a, c)


def test_bursty_arrivals_fall_in_the_burst_phase():
    t = L.arrivals(BURSTY, 100.0, 10.0, np.random.default_rng(0))
    # burst_factor * burst_fraction = 1: every arrival lies in the first quarter of its second
    assert len(t) == 1000 and np.all((t % 1.0) < 0.25)
    per_period = np.bincount(t.astype(int), minlength=10)
    assert np.all(per_period == 100)


def test_fixed_multiset_is_a_permutation_of_the_same_counts():
    w = [1.0, 1 / 2, 1 / 3, 1 / 4]
    a = L.fixed_multiset(w, 100, np.random.default_rng(1))
    b = L.fixed_multiset(w, 100, np.random.default_rng(2))
    assert np.array_equal(np.bincount(a), np.bincount(b)) and np.bincount(a).sum() == 100
    assert not np.array_equal(a, b)


def test_open_loop_records_lag_failures_and_unanswered():
    sched = np.linspace(0.0, 0.2, 11)
    never = Future()  # request 3 is accepted and never answered

    def fire(i):
        if i == 5:
            raise RuntimeError("refused")
        if i == 3:
            return never
        f = Future()
        threading.Timer(0.01, f.set_result, args=({"ok": i},)).start()
        return f

    t0 = time.perf_counter()
    out = L.run_open_loop(fire, sched, answer_deadline_s=0.3)
    assert time.perf_counter() - t0 < 2.0
    lag = out.lag_s()
    assert np.all(lag >= 0) and np.all(lag < 0.1)
    assert out.failed[3] and out.failed[5] and out.failed.sum() == 2
    lat = out.latencies_s()
    assert np.isinf(lat[3]) and np.isinf(lat[5])
    ok = np.flatnonzero(~out.failed)
    assert np.all(lat[ok] >= 0.01 - 1e-3) and out.answers[0] == {"ok": 0}


def test_closed_loop_callers_wait_for_each_answer():
    inflight, worst = [0], [0]
    lock = threading.Lock()

    def fire(c, k):
        with lock:
            inflight[0] += 1
            worst[0] = max(worst[0], inflight[0])
        f = Future()

        def done():
            with lock:
                inflight[0] -= 1
            f.set_result(k)

        threading.Timer(0.005, done).start()
        return f

    outs = L.run_closed_loop(fire, callers=3, seconds=0.3, answer_deadline_s=1.0)
    assert len(outs) == 3 and worst[0] <= 3
    for o in outs:
        assert len(o.scheduled_s) > 5 and not o.failed.any()
        assert o.answers == list(range(len(o.answers)))
