"""[Exp 2] Placement optimization (paper Fig. 9 + Fig. 10).

2a: for each query type, optimize 50 queries' initial placements with
COSTREAM and with the flat-vector baseline; report median speed-up of
simulator-measured L_p over the heuristic initial placement [32].

2b: the online-monitoring rescheduler [1]: initial slow-down factor vs. the
COSTREAM placement and the monitoring overhead until competitive.
"""

from __future__ import annotations

import numpy as np

from benchmarks.common import FlatRanker, fmt_table, save_result, serving_estimator
from repro.launch.compile_cache import enable_compile_cache
from repro.dsps import WorkloadGenerator, simulate
from repro.dsps.simulator import SimulatorConfig
from repro.placement import (
    PlacementOptimizer,
    heuristic_placement,
    online_monitoring_run,
    sample_assignment_matrix,
)

SIM = SimulatorConfig(noise_sigma=0.0)  # placement quality measured noise-free


def exp2a(n_queries: int = 50, k: int = 48, seed: int = 1234):
    opt = PlacementOptimizer(serving_estimator())
    flat = FlatRanker()
    gen = WorkloadGenerator(seed=seed)
    rng = np.random.default_rng(seed)
    rows = []
    for kind in ("linear", "two_way", "three_way"):
        speed_cs, speed_fv = [], []
        for i in range(n_queries):
            q = gen.query(kind=kind, name=f"{kind}{i}")
            c = gen.cluster(6)
            base = heuristic_placement(q, c)
            base_lat = simulate(q, c, base, SIM).latency_p

            res = opt.optimize(q, c, "latency_p", k=k, rng=rng)
            cs_lat = simulate(q, c, res.placement, SIM).latency_p
            speed_cs.append(base_lat / max(cs_lat, 1e-9))

            cands = sample_assignment_matrix(q, c, k, rng)
            if len(cands) and flat.models:
                fv_p = flat.pick(q, c, cands)
                fv_lat = simulate(q, c, fv_p, SIM).latency_p
                speed_fv.append(base_lat / max(fv_lat, 1e-9))
        rows.append(
            {
                "type": kind,
                "n": n_queries,
                "costream_median_speedup": round(float(np.median(speed_cs)), 2),
                "costream_p90_speedup": round(float(np.percentile(speed_cs, 90)), 2),
                "flat_median_speedup": round(float(np.median(speed_fv)), 2) if speed_fv else "n/a",
            }
        )
    print("\n[Exp 2a / Fig 9] initial-placement speedups over heuristic [32]")
    print(
        fmt_table(
            rows,
            ["type", "n", "costream_median_speedup", "costream_p90_speedup", "flat_median_speedup"],
        )
    )
    save_result("exp2a_fig9", rows)
    return rows


def exp2b(n_queries: int = 25, seed: int = 4321):
    opt = PlacementOptimizer(serving_estimator())
    gen = WorkloadGenerator(seed=seed)
    rng = np.random.default_rng(seed)
    slowdowns, overheads = [], []
    for i in range(n_queries):
        q = gen.query(kind="linear", name=f"mon{i}")
        c = gen.cluster(6)
        res = opt.optimize(q, c, "latency_p", k=48, rng=rng)
        target = simulate(q, c, res.placement, SIM).latency_p
        init = heuristic_placement(q, c)
        mon = online_monitoring_run(q, c, init, target_latency=target, sim=SIM)
        slowdowns.append(mon.initial_latency / max(target, 1e-9))
        if np.isfinite(mon.overhead_seconds):
            overheads.append(mon.overhead_seconds)
    payload = {
        "median_slowdown": float(np.median(slowdowns)),
        "max_slowdown": float(np.max(slowdowns)),
        "median_overhead_s": float(np.median(overheads)) if overheads else None,
        "max_overhead_s": float(np.max(overheads)) if overheads else None,
        "never_competitive_frac": 1.0 - len(overheads) / n_queries,
        "n": n_queries,
    }
    print("\n[Exp 2b / Fig 10] online-monitoring baseline vs COSTREAM initial placement")
    for k, v in payload.items():
        print(f"  {k}: {v}")
    save_result("exp2b_fig10", payload)
    return payload


def main():
    enable_compile_cache()
    exp2a()
    exp2b()


if __name__ == "__main__":
    main()
