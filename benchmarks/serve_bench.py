"""Serving microbenchmark: requests/s under three request-stream shapes.

Drives ``repro.serve.PlacementService`` with streams of small requests (the
paper's online pattern: many concurrent "parallel COSTREAM instance"
queries, each scoring a handful of candidates) over the SAME requests,
models, and service code path:

  --mode score (default)
      one hot query structure; ``serial`` (submit, wait, repeat — every
      request pays one full dispatch) vs ``coalesced`` (submit the whole
      stream, then gather — requests pile up and share fused bucket-padded
      stacked forwards);
  --mode mixed
      N DISTINCT query structures round-robin — the heterogeneous stream the
      cross-query broadcast-batch path exists for.  ``grouped``
      (cross_query=False: one forward per structure per drain, the pre-merge
      behavior) vs ``cross`` (cross_query=True: the whole drain merges into
      one signature-banded stacked forward per max_batch rows).  Both modes
      drain a pre-queued stream once (deterministic batch shapes);
  --mode estimate
      cost-estimate requests for batches of placed queries; ``serial`` vs
      ``coalesced`` submission, exercising the estimate coalescing path.

Every mode verifies its answers against direct ``CostEstimator`` calls
before timing, and the verification pass runs the exact drains that are
later timed, so every jit shape is warm and the ratios isolate batching —
not compilation.

    PYTHONPATH=src python benchmarks/serve_bench.py [--mode score|mixed|estimate]
        [--quick]
        [--policy default|tuned]               # dispatch policy (tuned: reported, never gated)
        [--min-speedup X]                      # mode ratio floor
        [--baseline FILE --max-regression F]   # ratio gate vs recorded run
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.core import CostModelConfig, GNNConfig, init_cost_model
from repro.core.bucketing import bucket_size
from repro.dsps import WorkloadGenerator
from repro.placement import sample_assignment_matrix
from repro.serve import CostEstimator, PlacementService
from repro.serve.policy import DispatchPolicy, active_policy, autotune, use_policy

METRICS = ("latency_p", "success", "backpressure")


def make_estimator(hidden: int = 32, n_ensemble: int = 2) -> CostEstimator:
    models = {}
    for i, metric in enumerate(METRICS):
        cfg = CostModelConfig(metric=metric, n_ensemble=n_ensemble, gnn=GNNConfig(hidden=hidden))
        models[metric] = (init_cost_model(jax.random.PRNGKey(i), cfg), cfg)
    # pick up the bench-selected policy (--policy tuned runs under use_policy)
    return CostEstimator(models, policy=active_policy())


def run(n_requests: int, cands_per_request: int, repeats: int, seed: int = 0) -> dict:
    repeats = max(1, repeats)
    gen = WorkloadGenerator(seed=seed)
    q = gen.query(kind="two_way", name="serve")
    c = gen.cluster(6)
    rng = np.random.default_rng(seed)
    # request payloads may share candidates (realistic: hot queries repeat);
    # cycle the distinct pool to fill n_requests x cands_per_request rows
    pool = sample_assignment_matrix(
        q, c, n_requests * cands_per_request, rng, max_tries_factor=400
    )
    assert len(pool) >= cands_per_request, "not enough distinct candidates"
    idx = np.arange(n_requests * cands_per_request) % len(pool)
    requests = [
        pool[idx[i * cands_per_request : (i + 1) * cands_per_request]]
        for i in range(n_requests)
    ]

    est = make_estimator()
    # warm every bucket shape the coalescer can produce (powers of two from a
    # single request up to the full stream), so timings exclude compilation
    b = bucket_size(cands_per_request)
    while True:
        est.score(q, c, pool[np.arange(b) % len(pool)], METRICS)
        if b >= bucket_size(n_requests * cands_per_request):
            break
        b *= 2

    # correctness first: both submission modes must answer exactly like the
    # shared facade, no matter how requests were batched
    ref = [est.score(q, c, r, METRICS) for r in requests]
    with PlacementService(est) as svc:
        serial = [svc.score(q, c, r, METRICS) for r in requests]
        futs = [svc.submit_score(q, c, r, METRICS) for r in requests]
        coalesced = [f.result() for f in futs]
    for name, got in (("serial", serial), ("coalesced", coalesced)):
        for want, have in zip(ref, got):
            for m in METRICS:
                np.testing.assert_allclose(have[m], want[m], rtol=1e-5, atol=1e-6, err_msg=f"{name}:{m}")

    # best-of-repeats: the gated quantity is a RATIO of two separately timed
    # windows, so a transient container stall inside either window skews it;
    # the per-mode minimum measures steady-state capability instead
    timings = {}
    forwards = {}
    for mode in ("serial", "coalesced"):
        best = np.inf
        with PlacementService(est) as svc:
            for _ in range(repeats):
                svc.stats.reset()
                t0 = time.perf_counter()
                if mode == "serial":
                    for r in requests:
                        svc.score(q, c, r, METRICS)
                else:
                    futs = [svc.submit_score(q, c, r, METRICS) for r in requests]
                    for f in futs:
                        f.result()
                best = min(best, time.perf_counter() - t0)
            forwards[mode] = svc.stats.n_forwards  # last repeat's count
        timings[mode] = best

    rate = {m: n_requests / t for m, t in timings.items()}
    return {
        "n_requests": n_requests,
        "cands_per_request": cands_per_request,
        "n_metrics": len(METRICS),
        "repeats": repeats,
        "serial_s": round(timings["serial"], 4),
        "coalesced_s": round(timings["coalesced"], 4),
        "serial_rps": round(rate["serial"], 1),
        "coalesced_rps": round(rate["coalesced"], 1),
        "serial_forwards": forwards["serial"],
        "coalesced_forwards": forwards["coalesced"],
        "coalesced_vs_serial": round(rate["coalesced"] / rate["serial"], 2),
    }


def _mixed_structures(n_structures: int, seed: int):
    """n DISTINCT (query, cluster) structures cycling the corpus query kinds."""
    gen = WorkloadGenerator(seed=seed)
    kinds = ("linear", "two_way", "three_way")
    return [
        (gen.query(kind=kinds[i % len(kinds)], name=f"mix{i}"), gen.cluster(3 + i % 6))
        for i in range(n_structures)
    ]


def _drain_once(svc, submit):
    """Pre-queue a whole stream, start the worker, gather: ONE deterministic
    drain (stable batch shapes — the methodology for drain-vs-drain ratios)."""
    futs = submit(svc)
    t0 = time.perf_counter()
    svc.start()
    results = [f.result() for f in futs]
    elapsed = time.perf_counter() - t0
    return results, elapsed


def run_mixed(
    n_structures: int, reqs_per_structure: int, cands: int, repeats: int, seed: int = 0
) -> dict:
    """Cross-query coalescing vs the per-structure-group drain on a stream of
    many DISTINCT small queries (requests round-robin the structures, so
    every drain sees all of them interleaved)."""
    repeats = max(1, repeats)
    structures = _mixed_structures(n_structures, seed)
    rng = np.random.default_rng(seed)
    requests = []
    for _ in range(reqs_per_structure):
        for q, c in structures:
            requests.append((q, c, sample_assignment_matrix(q, c, cands, rng)))

    est = make_estimator()
    ref = [est.score(q, c, a, METRICS) for q, c, a in requests]

    def submit(svc):
        return [svc.submit_score(q, c, a, METRICS) for q, c, a in requests]

    def make_svc(mode):
        # row_limit=None: the bench CONTRASTS the two drain strategies, so the
        # cross service must merge rather than adaptively fall back
        return PlacementService(
            est,
            auto_start=False,
            cross_query=(mode == "cross"),
            cross_query_row_limit=None,
        )

    # correctness first (this also warms every drain shape both modes use):
    # cross-query merging must be invisible to callers
    forwards = {}
    for mode in ("grouped", "cross"):
        svc = make_svc(mode)
        got, _ = _drain_once(svc, submit)
        svc.close()
        forwards[mode] = svc.stats.n_forwards
        for want, have in zip(ref, got):
            for m in METRICS:
                np.testing.assert_allclose(
                    have[m], want[m], rtol=1e-4, atol=1e-5, err_msg=f"{mode}:{m}"
                )

    timings = {}
    for mode in ("grouped", "cross"):
        best = np.inf
        for _ in range(repeats):
            svc = make_svc(mode)
            _, elapsed = _drain_once(svc, submit)
            svc.close()
            best = min(best, elapsed)
        timings[mode] = best

    n_requests = len(requests)
    rate = {m: n_requests / t for m, t in timings.items()}
    return {
        "mode": "mixed",
        "n_structures": n_structures,
        "n_requests": n_requests,
        "cands_per_request": cands,
        "n_metrics": len(METRICS),
        "repeats": repeats,
        "grouped_s": round(timings["grouped"], 4),
        "cross_s": round(timings["cross"], 4),
        "grouped_rps": round(rate["grouped"], 1),
        "cross_rps": round(rate["cross"], 1),
        "grouped_forwards": forwards["grouped"],
        "cross_forwards": forwards["cross"],
        "cross_vs_grouped": round(rate["cross"] / rate["grouped"], 2),
    }


def run_estimate(n_requests: int, graphs_per_request: int, repeats: int, seed: int = 0) -> dict:
    """Estimate-request coalescing: serial submit-and-wait vs a pre-queued
    drain of cost-estimate requests for batches of placed queries."""
    from repro.core.graph import batch_graphs, build_graph

    repeats = max(1, repeats)
    traces = WorkloadGenerator(seed=seed).corpus(n_requests * graphs_per_request)
    requests = [
        batch_graphs(
            [
                build_graph(t.query, t.cluster, t.placement)
                for t in traces[i * graphs_per_request : (i + 1) * graphs_per_request]
            ]
        )
        for i in range(n_requests)
    ]
    est = make_estimator()
    ref = [est.estimate(g, METRICS) for g in requests]

    def submit(svc):
        return [svc.submit_estimate(g, METRICS) for g in requests]

    # correctness + warmup for both submission patterns
    with PlacementService(est) as svc:
        serial = [svc.estimate(g, METRICS) for g in requests]
    svc_c = PlacementService(est, auto_start=False)
    coalesced, _ = _drain_once(svc_c, submit)
    svc_c.close()
    coalesced_forwards = svc_c.stats.n_forwards
    for name, got in (("serial", serial), ("coalesced", coalesced)):
        for want, have in zip(ref, got):
            for m in METRICS:
                np.testing.assert_allclose(
                    have[m], want[m], rtol=1e-4, atol=1e-5, err_msg=f"{name}:{m}"
                )

    timings = {}
    forwards = {"coalesced": coalesced_forwards}
    best = np.inf
    with PlacementService(est) as svc:
        for _ in range(repeats):
            svc.stats.reset()
            t0 = time.perf_counter()
            for g in requests:
                svc.estimate(g, METRICS)
            best = min(best, time.perf_counter() - t0)
        forwards["serial"] = svc.stats.n_forwards
    timings["serial"] = best
    best = np.inf
    for _ in range(repeats):
        svc = PlacementService(est, auto_start=False)
        _, elapsed = _drain_once(svc, submit)
        svc.close()
        best = min(best, elapsed)
    timings["coalesced"] = best

    rate = {m: n_requests / t for m, t in timings.items()}
    return {
        "mode": "estimate",
        "n_requests": n_requests,
        "graphs_per_request": graphs_per_request,
        "n_metrics": len(METRICS),
        "repeats": repeats,
        "serial_s": round(timings["serial"], 4),
        "coalesced_s": round(timings["coalesced"], 4),
        "serial_rps": round(rate["serial"], 1),
        "coalesced_rps": round(rate["coalesced"], 1),
        "serial_forwards": forwards["serial"],
        "coalesced_forwards": forwards["coalesced"],
        "coalesced_vs_serial": round(rate["coalesced"] / rate["serial"], 2),
    }


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", choices=("score", "mixed", "estimate"), default="score")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument(
        "--cands",
        type=int,
        default=None,
        help="candidates per request (default 8; mixed mode 2 — the "
        "dispatch-bound refinement-loop shape cross-query merging is built "
        "for: each distinct query scores a couple of alternative placements)",
    )
    ap.add_argument(
        "--structures", type=int, default=16, help="distinct query structures (mixed)"
    )
    ap.add_argument(
        "--graphs", type=int, default=4, help="graphs per estimate request"
    )
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--quick", action="store_true", help="small run for per-PR CI")
    ap.add_argument(
        "--policy",
        choices=("default", "tuned"),
        default="default",
        help="dispatch policy for the run: built-in defaults, or the host's "
        "autotuned profile (autotunes quick on first use, then reuses the "
        "cached per-host profile). Tuned runs are REPORTED, never gated: "
        "--min-speedup/--baseline are ignored under --policy tuned so CI "
        "floors stay pinned to the default policy",
    )
    ap.add_argument("--min-speedup", type=float, default=None, help="fail below this")
    ap.add_argument(
        "--baseline",
        type=str,
        default=None,
        help="JSON with this mode's recorded ratio",
    )
    ap.add_argument(
        "--max-regression",
        type=float,
        default=0.10,
        help="allowed fractional drop of the measured ratio below the baseline",
    )
    args = ap.parse_args(argv)
    if args.cands is None:
        args.cands = 2 if args.mode == "mixed" else 8
    if args.requests is None:
        args.requests = 48 if args.mode == "mixed" else 96
    if args.quick:
        args.repeats = 3
        args.requests = 32 if args.mode == "mixed" else 48

    if args.policy == "tuned":
        policy = autotune(quick=True).policy  # cached per-host profile after run 1
    else:
        policy = DispatchPolicy()

    with use_policy(policy):
        if args.mode == "mixed":
            reqs_per_structure = max(1, args.requests // args.structures)
            res = run_mixed(args.structures, reqs_per_structure, args.cands, args.repeats)
            ratio_key, fewer = "cross_vs_grouped", ("cross_forwards", "grouped_forwards")
        elif args.mode == "estimate":
            res = run_estimate(args.requests, args.graphs, args.repeats)
            ratio_key, fewer = "coalesced_vs_serial", ("coalesced_forwards", "serial_forwards")
        else:
            res = run(args.requests, args.cands, args.repeats)
            ratio_key, fewer = "coalesced_vs_serial", ("coalesced_forwards", "serial_forwards")
    res["policy"] = args.policy
    res["cross_query_row_limit"] = policy.cross_query_row_limit
    res["score_chunk"] = policy.score_chunk
    print(json.dumps(res, indent=2))
    if args.policy == "tuned":
        # tuned numbers are a report of what host calibration buys; the
        # recorded baselines were measured under the default policy, so
        # gating them here would compare across policies
        return
    # not assert: these are the CI gate's invariants, they must survive python -O
    if res[fewer[0]] >= res[fewer[1]]:
        raise SystemExit(
            "batching must issue fewer forwards than the baseline drain, got "
            f"{res[fewer[0]]} vs {res[fewer[1]]}"
        )
    if args.min_speedup is not None and res[ratio_key] < args.min_speedup:
        raise SystemExit(
            f"{ratio_key} speedup {res[ratio_key]}x below required {args.min_speedup}x"
        )
    if args.baseline:
        with open(args.baseline) as f:
            base = json.load(f)
        floor = base[ratio_key] * (1.0 - args.max_regression)
        if res[ratio_key] < floor:
            raise SystemExit(
                f"{ratio_key} ratio {res[ratio_key]} regressed >"
                f"{args.max_regression:.0%} below recorded baseline "
                f"{base[ratio_key]} (floor {floor:.3f})"
            )


if __name__ == "__main__":
    main()
