"""Continuous-placement controller benchmark: drift + failure recovery.

A fleet of co-located queries starts from a contention-aware oracle
placement on a deliberately weak edge cluster, then a seeded scenario hits
it: event-rate drift (x8) on two queries, a node failure that orphans
everything on the strongest host, and a late capacity join.  Three lanes
ride the SAME deterministic ``FleetRuntime`` (docs/controller.md):

  static      never re-places anything — the pre-controller semantics.  Its
              fleet cost explodes when drift saturates a host and never
              recovers from the failure;
  controller  ``PlacementController`` with the DispatchPolicy knobs:
              EWMA/CUSUM drift detection, incremental re-placement of only
              the implicated operators, migration budget, cooldown;
  oracle      ``replan_every_tick=True``: every query fully re-planned every
              tick with an unbounded budget — the clairvoyant upper bound
              (and the migration-count price of it).

The decision-quality lanes score through a noise-free simulator oracle, so
``static_vs_controller_final`` (static / controller end-of-run fleet cost)
is DETERMINISTIC — a shift means the controller's behavior changed, not
timing noise.  The gates:

  * ``static_vs_controller_final >= --min-ratio`` (the controller must
    actually rescue the fleet);
  * ``controller.max_migration_mb <= DispatchPolicy.migration_budget_mb``
    (budget counter-asserted from the decision log);
  * ``controller.n_migrations <= oracle.n_migrations`` (stability: the
    budgeted/hysteresis loop must move less than the clairvoyant one);
  * replan p95 <= ``--max-replan-p95-ms`` on the ESTIMATOR lane: the same
    scenario re-planned through a real ``CostEstimator`` (tiny random-init
    ensembles — latency of the machinery, not model quality), run twice
    with identical seeds; the first run pays compiles, the warm second run
    is the SLO measurement and must replay the first's decision log
    bit-identically (determinism gate).

    PYTHONPATH=src python benchmarks/controller_bench.py [--quick]
        [--min-ratio X] [--max-replan-p95-ms MS]
        [--baseline FILE --max-regression F]
"""

from __future__ import annotations

import argparse
import json

from repro.launch.compile_cache import enable_compile_cache
from repro.control import (
    FleetRuntime,
    PlacementController,
    SimulatorScorer,
    build_scenario,
    run_static,
)
from repro.serve import active_policy

#: The estimator lane's metric set: the re-planner's target plus the two
#: feasibility gates it penalizes on.
METRICS = ("latency_e", "success", "backpressure")


def make_estimator(hidden: int = 32, n_ensemble: int = 2):
    """Tiny random-init ensembles: replan latency of the real scoring
    machinery (skeleton caches, merged cross-query forward), not model
    quality."""
    import jax

    from repro.core import CostModelConfig, GNNConfig, init_cost_model
    from repro.serve import CostEstimator

    models = {}
    for i, metric in enumerate(METRICS):
        cfg = CostModelConfig(
            metric=metric, n_ensemble=n_ensemble, gnn=GNNConfig(hidden=hidden)
        )
        models[metric] = (init_cost_model(jax.random.PRNGKey(i), cfg), cfg)
    return CostEstimator(models)


def run(n_queries: int, n_ticks: int, seed: int = 7) -> dict:
    fleet, cluster, events = build_scenario(n_queries, n_ticks, seed=seed)
    policy = active_policy().validate()

    def runtime() -> FleetRuntime:
        return FleetRuntime(fleet, cluster, events, seed=1, tick_s=policy.controller_tick_s)

    # -- decision-quality lanes: noise-free simulator oracle as the scorer,
    # so every number below is deterministic for the seed pair
    static = run_static(runtime(), n_ticks)
    ctl = PlacementController(runtime(), scorer=SimulatorScorer(), seed=0).run(n_ticks)
    oracle = PlacementController(
        runtime(), scorer=SimulatorScorer(), seed=0, replan_every_tick=True
    ).run(n_ticks)

    # -- latency lane: same scenario through a real CostEstimator.  Run twice
    # with identical seeds: run 1 pays every jit compile, run 2 is warm and is
    # the SLO measurement; its decision log must replay run 1's bit-identically
    est = make_estimator()
    est_cold = PlacementController(runtime(), estimator=est, seed=0).run(n_ticks)
    est_warm = PlacementController(runtime(), estimator=est, seed=0).run(n_ticks)
    if est_warm.decision_log() != est_cold.decision_log():
        raise SystemExit("estimator lane is not deterministic across replays")

    res = {
        "n_queries": n_queries,
        "n_ticks": n_ticks,
        "migration_budget_mb": policy.migration_budget_mb,
        "static": static.to_dict(),
        "controller": ctl.to_dict(),
        "oracle": oracle.to_dict(),
        "estimator_cold": est_cold.to_dict(),
        "estimator_warm": est_warm.to_dict(),
        "static_vs_controller_final": round(
            static.final_cost_ms / max(ctl.final_cost_ms, 1e-9), 3
        ),
        "controller_vs_oracle_final": round(
            ctl.final_cost_ms / max(oracle.final_cost_ms, 1e-9), 3
        ),
        "replan_p95_ms": round(est_warm.replan_p95_ms, 3),
    }
    return res


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--queries", type=int, default=8)
    ap.add_argument("--ticks", type=int, default=30)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--quick", action="store_true", help="small run for per-PR CI")
    ap.add_argument(
        "--min-ratio",
        type=float,
        default=None,
        help="fail if static_vs_controller_final is below this",
    )
    ap.add_argument(
        "--max-replan-p95-ms",
        type=float,
        default=None,
        help="fail if the warm estimator lane's replan p95 exceeds this",
    )
    ap.add_argument(
        "--baseline", type=str, default=None, help="JSON with the recorded ratio"
    )
    ap.add_argument(
        "--max-regression",
        type=float,
        default=0.10,
        help="allowed fractional drop of the measured ratio below the baseline",
    )
    args = ap.parse_args(argv)
    if args.quick:
        args.queries = min(args.queries, 6)
        args.ticks = min(args.ticks, 20)

    res = run(args.queries, args.ticks, seed=args.seed)
    print(json.dumps(res, indent=2))

    # not assert: these are the CI gate's invariants, they must survive python -O
    budget = res["migration_budget_mb"]
    if res["controller"]["max_migration_mb"] > budget + 1e-9:
        raise SystemExit(
            f"migration budget violated: largest move "
            f"{res['controller']['max_migration_mb']}MB > budget {budget}MB"
        )
    if res["controller"]["n_migrations"] > res["oracle"]["n_migrations"]:
        raise SystemExit(
            f"controller moved more than the replan-every-tick oracle "
            f"({res['controller']['n_migrations']} > {res['oracle']['n_migrations']})"
        )
    if args.min_ratio is not None and res["static_vs_controller_final"] < args.min_ratio:
        raise SystemExit(
            f"static_vs_controller_final {res['static_vs_controller_final']} below "
            f"required {args.min_ratio}"
        )
    if (
        args.max_replan_p95_ms is not None
        and res["replan_p95_ms"] > args.max_replan_p95_ms
    ):
        raise SystemExit(
            f"replan p95 {res['replan_p95_ms']}ms above SLO {args.max_replan_p95_ms}ms"
        )
    if args.baseline:
        with open(args.baseline) as f:
            base = json.load(f)
        floor = base["static_vs_controller_final"] * (1.0 - args.max_regression)
        if res["static_vs_controller_final"] < floor:
            raise SystemExit(
                f"static_vs_controller_final {res['static_vs_controller_final']} "
                f"regressed >{args.max_regression:.0%} below recorded baseline "
                f"{base['static_vs_controller_final']} (floor {floor:.3f})"
            )


if __name__ == "__main__":
    main()
