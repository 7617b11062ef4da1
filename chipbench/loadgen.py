"""Arrival schedules and the two drivers that offer load to the service.

The open-loop driver follows the program's ``serve/load.py``
(``run_open_loop``): a driver thread sleeps to each scheduled arrival and
fires without waiting for the answer, and a request's latency runs from its
*scheduled* arrival to its answer, so driver lag and queueing both count.
This copy adds what that one lacks: how late each fire was (``lag_s``), and
an answer deadline after the window, past which a request is unanswered.

Schedules hold the number of arrivals fixed: a Poisson process over the
window is drawn as that many uniform times (the process conditioned on its
count), and a periodic on/off process as a fixed count per phase of every
period.  Every seed thus offers the same load in another order.

The closed-loop driver runs ``callers`` threads, each sending its next
request only once the previous one is answered.
"""

from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence

import numpy as np

UNANSWERED = float("inf")


def arrivals(spec: dict, rate: float, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """Arrival offsets in [0, seconds) at ``rate`` req/s on average.

    ``spec["kind"]``: ``poisson``, or ``bursty`` with ``burst_factor``,
    ``burst_fraction`` and ``period_s``: each period opens with a burst
    phase of ``burst_fraction`` of the period at ``burst_factor`` times the
    mean intensity, and the quiet rest carries what is left of the mean."""
    kind = spec.get("kind", "poisson")
    if kind == "poisson":
        return np.sort(rng.uniform(0.0, seconds, int(round(rate * seconds))))
    if kind != "bursty":
        raise ValueError(f"unknown arrival process {kind!r}")
    period, frac, factor = float(spec["period_s"]), float(spec["burst_fraction"]), float(spec["burst_factor"])
    if not (0.0 < frac < 1.0 and factor >= 1.0):
        raise ValueError(f"bad bursty arrivals {spec}")
    per_period = rate * period
    in_burst = min(per_period, rate * factor * frac * period)
    out = []
    n_periods = int(np.ceil(seconds / period))
    # whole counts per period that add up to the mean over the window
    burst_counts = np.diff(np.floor(np.arange(n_periods + 1) * in_burst + 0.5)).astype(int)
    quiet_counts = np.diff(np.floor(np.arange(n_periods + 1) * (per_period - in_burst) + 0.5)).astype(int)
    for p in range(n_periods):
        t0 = p * period
        out.append(t0 + rng.uniform(0.0, frac * period, burst_counts[p]))
        out.append(t0 + rng.uniform(frac * period, period, quiet_counts[p]))
    t = np.sort(np.concatenate(out))
    return t[t < seconds]


@dataclass
class Outcome:
    """Per-request record of one driven window (index = request order)."""

    scheduled_s: np.ndarray  # due time, from the window's start
    fired_s: np.ndarray  # submit time, from the window's start
    done_s: np.ndarray  # answer time, from the window's start (nan: never)
    failed: np.ndarray  # bool: an error, a rejection, or no answer by the deadline
    answers: List[object] = field(default_factory=list)

    def latencies_s(self) -> np.ndarray:
        """Per request, due to answer; a failed request is beyond every limit."""
        lat = self.done_s - self.scheduled_s
        return np.where(self.failed | ~np.isfinite(lat), UNANSWERED, lat)

    def lag_s(self) -> np.ndarray:
        return self.fired_s - self.scheduled_s


def _span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def run_open_loop(
    fire: Callable[[int], object],
    schedule: np.ndarray,
    answer_deadline_s: float,
    spans: bool = False,
    on_start: Optional[Callable[[float], None]] = None,
) -> Outcome:
    """Fire ``fire(i)`` (returns a future) at ``schedule[i]`` from a driver
    thread; wait for every answer until ``answer_deadline_s`` past the end of
    the schedule's window.  ``on_start(t0)`` runs on the calling thread once
    the driver has started (the traced run starts its profiler from there)."""
    n = len(schedule)
    fired = np.full(n, np.nan)
    done = np.full(n, np.nan)
    failed = np.zeros(n, dtype=bool)
    answers: List[object] = [None] * n
    remaining = [n]
    lock = threading.Condition()

    def finish(i: int, t0: float, fut) -> None:
        t = time.perf_counter() - t0
        exc = fut.exception()
        with lock:
            done[i] = t
            if exc is not None:
                failed[i] = True
            else:
                answers[i] = fut.result()
            remaining[0] -= 1
            lock.notify_all()

    def drive(t0: float) -> None:
        for i in range(n):
            wait = schedule[i] - (time.perf_counter() - t0)
            if wait > 0:
                with _span("chipbench.sleep", spans):
                    time.sleep(wait)
            fired[i] = time.perf_counter() - t0
            try:
                with _span("chipbench.submit", spans):
                    fut = fire(i)
            except Exception:  # rejected or refused: the client got no answer
                with lock:
                    failed[i] = True
                    remaining[0] -= 1
                    lock.notify_all()
                continue
            fut.add_done_callback(lambda f, i=i: finish(i, t0, f))

    t0 = time.perf_counter()
    driver = threading.Thread(target=drive, args=(t0,), name="chipbench-driver", daemon=True)
    driver.start()
    if on_start is not None:
        on_start(t0)
    window = float(schedule[-1]) if n else 0.0
    deadline = t0 + window + answer_deadline_s
    driver.join(max(0.0, deadline - time.perf_counter()))
    with lock:
        while remaining[0] > 0 and time.perf_counter() < deadline:
            lock.wait(max(0.0, min(1.0, deadline - time.perf_counter())))
        unanswered = np.isnan(done) & ~failed
        failed |= unanswered
    return Outcome(fired_s=fired, scheduled_s=np.asarray(schedule, dtype=np.float64),
                   done_s=done, failed=failed.copy(), answers=answers)


def run_closed_loop(
    fire: Callable[[int, int], object],
    callers: int,
    seconds: float,
    answer_deadline_s: float,
    spans: bool = False,
    on_start: Optional[Callable[[float], None]] = None,
    keep: Optional[Callable[[int, int], bool]] = None,
):
    """``callers`` threads; caller c sends its k-th request ``fire(c, k)``
    (returns a future) once its previous one is answered, until ``seconds``
    have passed.  Returns, per caller, an ``Outcome`` whose scheduled time is
    the submit time; its answers are kept where ``keep(c, k)`` (default:
    all), and are None elsewhere."""
    results = [dict(t=[], done=[], failed=[], answers=[]) for _ in range(callers)]

    def call(c: int, t0: float) -> None:
        rec = results[c]
        k = 0
        while True:
            t = time.perf_counter() - t0
            if t >= seconds:
                return
            ok, answer = True, None
            try:
                with _span("chipbench.submit", spans):
                    fut = fire(c, k)
                with _span("chipbench.wait", spans):
                    answer = fut.result(timeout=seconds - t + answer_deadline_s)
            except Exception:
                ok = False
            rec["t"].append(t)
            rec["done"].append(time.perf_counter() - t0)
            rec["failed"].append(not ok)
            rec["answers"].append(answer if keep is None or keep(c, k) else None)
            k += 1

    t0 = time.perf_counter()
    threads = [threading.Thread(target=call, args=(c, t0), name=f"chipbench-caller{c}", daemon=True)
               for c in range(callers)]
    for th in threads:
        th.start()
    if on_start is not None:
        on_start(t0)
    for th in threads:
        th.join(seconds + answer_deadline_s + 5.0)
    outs = []
    for rec in results:
        t = np.asarray(rec["t"], dtype=np.float64)
        outs.append(Outcome(scheduled_s=t, fired_s=t.copy(),
                            done_s=np.asarray(rec["done"], dtype=np.float64),
                            failed=np.asarray(rec["failed"], dtype=bool), answers=rec["answers"]))
    return outs


def largest_remainder(weights: Sequence[float], n: int) -> np.ndarray:
    """``n`` split over ``weights`` in whole counts that sum to ``n``."""
    w = np.asarray(weights, dtype=np.float64)
    raw = w / w.sum() * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    return counts


def fixed_multiset(weights: Sequence[float], n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` draws of index i in proportion to ``weights``, as whole counts
    (largest remainder) in an order shuffled by ``rng``: every seed draws
    the same multiset."""
    counts = largest_remainder(weights, n)
    return rng.permutation(np.repeat(np.arange(len(counts)), counts))
