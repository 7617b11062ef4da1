"""Chaos benchmark: open-loop serving under seeded fault injection.

``load_harness.py`` answers "how fast is the healthy service"; this harness
answers "what happens to everyone else when part of it is NOT healthy".  Each
chaos profile from ``repro.serve.chaos`` (forward raises, forward hangs, NaN
outputs, slow host) is driven through the SAME deterministic open-loop score
stream in three request windows:

    healthy prefix   requests [0, n/3)    injector disabled
    faulted window   requests [n/3, 2n/3) injector enabled
    recovery suffix  requests [2n/3, n)   injector disabled again

and the run is judged on *blast radius*, not raw speed:

* **zero lost futures** — every request in every profile resolves (answered,
  never dropped); under the score path's retry -> heuristic-fallback
  degradation there must be zero client-visible failures as well;
* **non-faulted p95** — p95 latency over the healthy + recovery windows,
  reported as a ratio against the same windows of a no-fault control run of
  the identical stream.  The gated scalar ``nonfaulted_p95_ratio_worst`` is
  the worst such ratio across profiles: a fault window must not poison the
  tail of requests outside it;
* fault-path accounting — injections fired, retries, degraded answers,
  non-finite detections, breaker opens (all from ``ServiceStats`` /
  ``CircuitBreaker``), plus a median/MAD straggler count of faulted-window
  latencies (``repro.launch.faults.straggler_outliers``) for the slow-host
  profile.

A corrupt-bundle phase runs outside the load loop: a real saved bundle is
byte-flipped on disk (``chaos.corrupt_bundle``) and must be rejected by
``CostModelBundle.load(verify=True)`` before it ever reaches a swap.

All fault probabilities/severities live in the profile catalog
(``chaos.profiles``); all serving thresholds the faults exercise (retry,
breaker) live on ``DispatchPolicy``.  Methodology: docs/robustness.md.

    PYTHONPATH=src python benchmarks/chaos_bench.py [--quick]
        [--p95-budget X]                       # absolute worst-ratio ceiling
        [--baseline FILE --max-regression F]   # ratio gate vs recorded run
"""

from __future__ import annotations

import argparse
import json
import tempfile

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.core import CostModelConfig, GNNConfig, init_cost_model
from repro.dsps import WorkloadGenerator
from repro.launch.faults import straggler_outliers
from repro.serve import (
    BundleIntegrityError,
    CostEstimator,
    CostModelBundle,
    PlacementService,
    latency_quantiles,
    poisson_arrivals,
    run_open_loop,
    score_request_stream,
)
from repro.serve.chaos import corrupt_bundle, profiles

METRICS = ("latency_p", "success", "backpressure")


def _models(hidden: int = 16, n_ensemble: int = 2):
    models = {}
    for i, metric in enumerate(METRICS):
        cfg = CostModelConfig(metric=metric, n_ensemble=n_ensemble, gnn=GNNConfig(hidden=hidden))
        models[metric] = (init_cost_model(jax.random.PRNGKey(i), cfg), cfg)
    return models


def mixed_structures(n_structures: int, seed: int):
    """Distinct structures over exactly TWO shape classes: jit traces are
    shape-keyed, so limiting shape diversity keeps the warmup ladder (and a
    fault-stalled drain's compile exposure) bounded while the request mix
    stays heterogeneous."""
    gen = WorkloadGenerator(seed=seed)
    kinds = ("linear", "two_way")
    return [
        (gen.query(kind=kinds[i % 2], name=f"chaos{i}"), gen.cluster(3 + i % 2))
        for i in range(n_structures)
    ]


def warm_shapes(est, structures, cands: int, max_rows: int, seed: int) -> int:
    """Compile every pow2 row bucket a coalesced drain can reach.

    A fault-stalled drain coalesces its backlog into bigger per-structure
    candidate matrices than healthy traffic ever builds; without this, the
    first stall buys multi-second XLA compiles *inside the faulted window*
    and the measured 'blast radius' is dominated by compile time, which a
    long-running service pays once, not per fault."""
    from repro.core.bucketing import bucket_size
    from repro.placement import sample_assignment_matrix

    rng = np.random.default_rng(seed)
    sizes = []
    r = max(1, cands)
    while True:
        b = bucket_size(r)
        sizes.append(b)
        if b >= max_rows:
            break
        r = b + 1
    for q, c in structures:
        for r in sizes:
            est.score(q, c, sample_assignment_matrix(q, c, r, rng), METRICS)
    return len(sizes)


def calibrate_rate(est, structures, cands: int, seed: int, n_probe: int = 16) -> float:
    """Serial closed-loop score rate on the measured structures (they may be
    warm — chaos runs are judged on blast radius, not cold-start)."""
    import time

    from repro.placement import sample_assignment_matrix

    rng = np.random.default_rng(seed)
    q, c = structures[0]
    a = sample_assignment_matrix(q, c, cands, rng)
    est.score(q, c, a, METRICS)  # compile outside the probe
    t0 = time.perf_counter()
    for _ in range(n_probe):
        est.score(q, c, a, METRICS)
    return n_probe / (time.perf_counter() - t0)


def run_profile(
    name,
    injector,
    est,
    structures,
    rate,
    n_requests,
    cands,
    seed,
    settle_s: float = 2.0,
    straggler_z: float = 3.0,
):
    """One profile through the three-window stream; returns (summary, p95s)."""
    svc = PlacementService(
        est,
        auto_start=True,
        double_buffer=True,
        cross_query=False,  # per-structure drains: shapes covered by warm_shapes
        warmup=structures,
        warmup_cands=cands,
        max_queue_depth=max(64, n_requests),  # deep: judging latency, not shedding
        overflow="reject",
        max_merged_mixes=0,
        seed=seed,
    )
    n1, n2 = n_requests // 3, 2 * n_requests // 3
    if injector is not None:
        injector.enabled = False
        est.add_hook(injector)
    try:
        base = score_request_stream(structures, n_requests, cands, seed=seed, metrics=METRICS)(svc)

        def windowed(i, fire):
            def go():
                if injector is not None:
                    # the window is request-indexed, so the fault schedule is
                    # a pure function of (profile seed, stream seed)
                    injector.enabled = n1 <= i < n2
                return fire()

            return go

        submits = [windowed(i, f) for i, f in enumerate(base)]
        # three independent arrival segments separated by settle gaps: the
        # faulted window's queue backlog must drain before the recovery
        # window is measured, or recovery latencies measure leftover
        # queueing, not recovery
        a1 = poisson_arrivals(rate, n1, seed=seed)
        a2 = poisson_arrivals(rate, n2 - n1, seed=seed + 1) + a1[-1] + settle_s
        a3 = poisson_arrivals(rate, n_requests - n2, seed=seed + 2) + a2[-1] + settle_s
        arrivals = np.concatenate([a1, a2, a3])
        rep = run_open_loop(svc, submits, arrivals, slo_s=None, timeout_s=600.0)
    finally:
        if injector is not None:
            est.remove_hook(injector)
        stats = svc.stats
        n_opens = svc.breaker.n_opens
        svc.close()

    lost = rep.n_requests - (rep.n_answered + rep.n_rejected + rep.n_failed)
    if lost != 0 or rep.n_rejected != 0:
        raise SystemExit(f"[{name}] lost/rejected futures: lost={lost} rejected={rep.n_rejected}")
    if rep.n_failed != 0:
        raise SystemExit(
            f"[{name}] {rep.n_failed} client-visible failures; the score path "
            "must degrade, not fail"
        )
    # with zero rejected/failed, latencies align 1:1 with request index
    lat = rep.latencies_s
    nonfaulted = np.concatenate([lat[:n1], lat[n2:]])
    _, nf_p95, _ = latency_quantiles(nonfaulted)
    _, f_p95, _ = latency_quantiles(lat[n1:n2])
    stragglers = straggler_outliers(
        {i: float(v) for i, v in enumerate(lat[n1:n2])}, straggler_z
    )
    summary = {
        "n_requests": rep.n_requests,
        "n_answered": rep.n_answered,
        "n_injected": injector.n_injected if injector is not None else 0,
        "nonfaulted_p95_ms": round(nf_p95 * 1e3, 3),
        "faulted_p95_ms": round(f_p95 * 1e3, 3),
        "n_retries": stats.n_retries,
        "n_degraded": stats.n_degraded,
        "n_nonfinite": stats.n_nonfinite,
        "n_failed_stat": stats.n_failed,
        "breaker_opens": n_opens,
        "n_faulted_window_stragglers": len(stragglers),
    }
    return summary, nf_p95


def corrupt_bundle_phase(seed: int) -> dict:
    """Save a real bundle, byte-flip it, and require verify-time rejection."""
    bundle = CostModelBundle(_models(hidden=8, n_ensemble=1), meta={"note": "chaos"})
    with tempfile.TemporaryDirectory() as d:
        bundle.save(d)
        CostModelBundle.load(d, verify=True)  # pristine copy passes
        path = corrupt_bundle(d, seed=seed)
        try:
            CostModelBundle.load(d, verify=True)
        except BundleIntegrityError as e:
            return {"rejected": True, "corrupted_file": path.rsplit("/", 2)[-1], "error": str(e)[:120]}
    raise SystemExit("corrupt bundle passed load(verify=True)")


def run(
    n_structures: int,
    n_requests: int,
    cands: int,
    seed: int,
    rate_factor: float,
    settle_s: float,
) -> dict:
    est = CostEstimator(_models())
    structures = mixed_structures(n_structures, seed)
    # worst-case coalescing: one structure's whole request share in one drain
    max_rows = -(-n_requests // max(1, n_structures)) * cands
    n_buckets = warm_shapes(est, structures, cands, max_rows, seed)
    serial = calibrate_rate(est, structures, cands, seed)
    # offer a small fraction of serial capacity: faults add service time, and
    # the harness must keep the healthy windows below saturation so
    # non-faulted p95 measures blast radius, not queueing collapse
    rate = serial * rate_factor

    res: dict = {
        "n_structures": n_structures,
        "n_requests": n_requests,
        "cands_per_request": cands,
        "seed": seed,
        "calibrated_serial_rps": round(serial, 1),
        "offered_rps": round(rate, 1),
        "warmed_row_buckets": n_buckets,
    }

    control, control_p95 = run_profile(
        "none", None, est, structures, rate, n_requests, cands, seed, settle_s
    )
    res["profile_none"] = control

    worst = 0.0
    for name, factory in profiles(seed).items():
        summary, nf_p95 = run_profile(
            name, factory(), est, structures, rate, n_requests, cands, seed, settle_s
        )
        if summary["n_injected"] == 0:
            raise SystemExit(f"[{name}] injector never fired; the profile tested nothing")
        ratio = nf_p95 / control_p95 if control_p95 > 0 else float("inf")
        summary["nonfaulted_p95_ratio"] = round(ratio, 3)
        worst = max(worst, ratio)
        res[f"profile_{name}"] = summary

    res["corrupt_bundle"] = corrupt_bundle_phase(seed)
    res["nonfaulted_p95_ratio_worst"] = round(worst, 3)
    return res


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--structures", type=int, default=8)
    ap.add_argument("--requests", type=int, default=120)
    ap.add_argument("--cands", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--rate-factor",
        type=float,
        default=0.25,
        help="offered rate as a fraction of calibrated serial capacity",
    )
    ap.add_argument(
        "--settle-s",
        type=float,
        default=2.0,
        help="quiet gap between request windows so backlog drains before "
        "the next window is measured",
    )
    ap.add_argument("--quick", action="store_true", help="small run for per-PR CI")
    ap.add_argument(
        "--p95-budget",
        type=float,
        default=6.0,
        help="absolute ceiling on nonfaulted_p95_ratio_worst",
    )
    ap.add_argument(
        "--baseline", type=str, default=None, help="JSON with the recorded ratio"
    )
    ap.add_argument(
        "--max-regression",
        type=float,
        default=0.10,
        help="allowed fractional growth of the worst ratio above the baseline",
    )
    args = ap.parse_args(argv)
    if args.quick:
        args.structures = min(args.structures, 6)
        args.requests = min(args.requests, 90)

    res = run(
        args.structures, args.requests, args.cands, args.seed, args.rate_factor, args.settle_s
    )
    print(json.dumps(res, indent=2))

    # not assert: these are the CI gate's invariants, they must survive python -O
    if res["profile_nan"]["n_nonfinite"] == 0:
        raise SystemExit("nan profile produced no NonFiniteEstimate detections")
    if res["nonfaulted_p95_ratio_worst"] > args.p95_budget:
        raise SystemExit(
            f"nonfaulted_p95_ratio_worst {res['nonfaulted_p95_ratio_worst']} over "
            f"budget {args.p95_budget}"
        )
    if args.baseline:
        with open(args.baseline) as f:
            base = json.load(f)
        ceiling = base["nonfaulted_p95_ratio_worst"] * (1.0 + args.max_regression)
        # latency-ratio gates are one-sided: lower is strictly better
        if res["nonfaulted_p95_ratio_worst"] > ceiling:
            raise SystemExit(
                f"nonfaulted_p95_ratio_worst {res['nonfaulted_p95_ratio_worst']} "
                f"regressed >{args.max_regression:.0%} above recorded baseline "
                f"{base['nonfaulted_p95_ratio_worst']} (ceiling {ceiling:.3f})"
            )


if __name__ == "__main__":
    main()
