"""Placement-search microbenchmark: candidates scored per second.

Compares four scoring paths on the same candidate set and the same
(untrained) per-metric ensembles:

  seed path     ``score_candidates``   — per-candidate ``build_graph`` loop,
                graph batch rebuilt + re-transferred once PER METRIC;
  unfused path  the PR-1 fast path — one skeleton, but one
                ``placed_predict`` forward per metric (E launches each);
  fused path    ``CostEstimator.score`` (via ``score_assignments``) —
                per-metric ensembles stacked into ONE vmapped forward
                (``placed_predict_fused``), jnp banks;
  fused+pallas  the fused path with ``use_pallas=True``: stage-0/1/2 through
                the banked-MLP kernel, stage-3 through mp-update.  NOTE the
                kernel ops lower per backend (``kernels.active_lowering``):
                off-TPU the default lowering is the jnp oracle, so on this
                container ``pallas_vs_jnp`` measures the routing RESTRUCTURE
                (trimmed spans, banded mp-update), not Pallas codegen — the
                kernel-body win is a TPU measurement.

Also counts graph materializations per path (the fast paths must build each
candidate graph exactly once across all metrics).  The fused paths build no
host graph: their one materialization per candidate is the row of the
int32 host-index matrix handed to the device (``estimator.placed_indices``),
from which the forward builds the placement adjacency on the device.  Untrained ensembles are
fine here: scoring throughput does not depend on the weights' values.

    PYTHONPATH=src python benchmarks/placement_bench.py [--quick]
        [--min-speedup X]                 # fused vs seed floor
        [--baseline FILE --max-regression F]   # ratio gate vs recorded run
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
import repro.core.graph as graph_mod
import repro.placement.optimizer as optimizer_mod
import repro.serve.estimator as estimator_mod
from repro.core import CostModelConfig, GNNConfig, init_cost_model
from repro.core.graph import build_graph_skeleton, query_static
from repro.dsps import WorkloadGenerator
from repro.dsps.placement import Placement
from repro.placement import PlacementOptimizer, sample_assignment_matrix
from repro.serve.estimator import placed_predict

METRICS = ("latency_p", "success", "backpressure")


class BuildCounter:
    """Counts candidate-graph materializations in every build entry point."""

    def __init__(self):
        self.single = 0  # build_graph calls (one candidate each)
        self.batch = 0  # candidates materialized via build_a_place_batch
        self.indices = 0  # candidates handed to the device as host indices

    def install(self):
        self._orig_single = graph_mod.build_graph
        self._orig_batch = graph_mod.build_graph_batch
        self._orig_place = graph_mod.build_a_place_batch
        self._orig_indices = estimator_mod.placed_indices

        def counted_single(*a, **kw):
            self.single += 1
            return self._orig_single(*a, **kw)

        def counted_batch(query, cluster, assignments, *a, **kw):
            # no count here: build_graph_batch delegates to build_a_place_batch
            # (patched below), which counts the candidates exactly once
            return self._orig_batch(query, cluster, assignments, *a, **kw)

        def counted_place(query, cluster, assignments, *a, **kw):
            self.batch += len(np.asarray(assignments))
            return self._orig_place(query, cluster, assignments, *a, **kw)

        def counted_indices(assignments, *a, **kw):
            self.indices += len(assignments)
            return self._orig_indices(assignments, *a, **kw)

        graph_mod.build_graph = counted_single
        graph_mod.build_graph_batch = counted_batch
        graph_mod.build_a_place_batch = counted_place
        # the optimizer/estimator imported the names directly; patch their
        # module globals too (scoring lives on the CostEstimator facade now)
        optimizer_mod.build_graph = counted_single
        estimator_mod.build_graph = counted_single
        estimator_mod.build_graph_batch = counted_batch
        estimator_mod.build_a_place_batch = counted_place
        estimator_mod.placed_indices = counted_indices
        return self

    def uninstall(self):
        graph_mod.build_graph = self._orig_single
        graph_mod.build_graph_batch = self._orig_batch
        graph_mod.build_a_place_batch = self._orig_place
        optimizer_mod.build_graph = self._orig_single
        estimator_mod.build_graph = self._orig_single
        estimator_mod.build_graph_batch = self._orig_batch
        estimator_mod.build_a_place_batch = self._orig_place
        estimator_mod.placed_indices = self._orig_indices

    @property
    def total(self) -> int:
        return self.single + self.batch + self.indices

    def reset(self):
        self.single = self.batch = self.indices = 0


def make_models(hidden: int = 32, n_ensemble: int = 3, use_pallas: bool = False):
    """Per-metric ensembles sharing WEIGHTS across pallas/jnp variants, so the
    kernel-routing comparison is apples-to-apples on identical params."""
    models = {}
    for i, metric in enumerate(METRICS):
        cfg = CostModelConfig(
            metric=metric,
            n_ensemble=n_ensemble,
            gnn=GNNConfig(hidden=hidden, use_pallas=use_pallas),
        )
        models[metric] = (init_cost_model(jax.random.PRNGKey(i), cfg), cfg)
    return models


def run(n_candidates: int, repeats: int, seed: int = 0) -> dict:
    repeats = max(1, repeats)
    gen = WorkloadGenerator(seed=seed)
    q = gen.query(kind="two_way", name="bench")
    c = gen.cluster(6)
    rng = np.random.default_rng(seed)
    a = sample_assignment_matrix(q, c, n_candidates, rng, max_tries_factor=200)
    if len(a) != n_candidates:
        raise SystemExit(f"only {len(a)}/{n_candidates} distinct candidates available")
    candidates = [Placement.of(row) for row in a]

    models_jnp = make_models()
    models_pal = make_models(use_pallas=True)
    opt = PlacementOptimizer(models_jnp)  # fused jnp (+ seed path)
    opt_pal = PlacementOptimizer(models_pal)  # fused + kernel-routed

    # the PR-1 path: skeleton hoisted, but one forward per (metric, member);
    # a_place built per call exactly like the optimizer's scoring closure
    skel = jax.tree_util.tree_map(jnp.asarray, build_graph_skeleton(q, c))
    static = query_static(q)

    def seed_path():
        return {m: opt.score_candidates(q, c, candidates, m) for m in METRICS}

    def unfused_path():
        a_place = jnp.asarray(graph_mod.build_a_place_batch(q, c, a))
        return {
            m: placed_predict(models_jnp[m][0], skel, a_place, static, models_jnp[m][1])
            for m in METRICS
        }

    def fused_path():
        return opt.score_assignments(q, c, a, METRICS)

    def fused_pallas_path():
        return opt_pal.score_assignments(q, c, a, METRICS)

    # warm up every jit cache at the benchmark's bucket shape, then verify all
    # paths agree before trusting the timings
    ref = seed_path()
    for name, path in (
        ("unfused", unfused_path),
        ("fused", fused_path),
        ("fused_pallas", fused_pallas_path),
    ):
        got = path()
        for m in METRICS:
            np.testing.assert_allclose(
                got[m], ref[m], rtol=1e-4, atol=1e-4, err_msg=f"{name}:{m}"
            )

    counter = BuildCounter().install()
    try:
        timings, builds = {}, {}
        for name, path in (
            ("seed", seed_path),
            ("unfused", unfused_path),
            ("fused", fused_path),
            ("fused_pallas", fused_pallas_path),
        ):
            counter.reset()
            t0 = time.perf_counter()
            for _ in range(repeats):
                path()
            timings[name] = (time.perf_counter() - t0) / repeats
            builds[name] = counter.total / repeats
    finally:
        counter.uninstall()

    rate = {name: n_candidates / t for name, t in timings.items()}
    return {
        "n_candidates": n_candidates,
        "n_metrics": len(METRICS),
        "repeats": repeats,
        "seed_path_s": round(timings["seed"], 4),
        "unfused_path_s": round(timings["unfused"], 4),
        "fused_path_s": round(timings["fused"], 4),
        "fused_pallas_path_s": round(timings["fused_pallas"], 4),
        "seed_cands_per_s": round(rate["seed"], 1),
        "unfused_cands_per_s": round(rate["unfused"], 1),
        "fused_cands_per_s": round(rate["fused"], 1),
        "fused_pallas_cands_per_s": round(rate["fused_pallas"], 1),
        # headline ratios: fusion win, kernel-routing win, end-to-end win
        "speedup_fused_vs_seed": round(timings["seed"] / timings["fused"], 2),
        "fused_vs_unfused": round(rate["fused"] / rate["unfused"], 3),
        "pallas_vs_jnp": round(rate["fused_pallas"] / rate["fused"], 3),
        "fused_pallas_vs_unfused": round(rate["fused_pallas"] / rate["unfused"], 3),
        "seed_builds_per_candidate": round(builds["seed"] / n_candidates, 2),
        "fast_builds_per_candidate": round(builds["fused"] / n_candidates, 2),
    }


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--candidates", type=int, default=1024)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--quick", action="store_true", help="small run for per-PR CI")
    ap.add_argument("--min-speedup", type=float, default=None, help="fail below this")
    ap.add_argument(
        "--baseline",
        type=str,
        default=None,
        help="JSON with recorded fused_vs_unfused / pallas_vs_jnp ratios",
    )
    ap.add_argument(
        "--max-regression",
        type=float,
        default=0.10,
        help="allowed fractional drop of a measured ratio below the baseline",
    )
    args = ap.parse_args(argv)
    if args.quick:
        args.candidates, args.repeats = 256, 3

    res = run(args.candidates, args.repeats)
    print(json.dumps(res, indent=2))
    # not assert: these are the CI gate's invariants, they must survive python -O
    if res["fast_builds_per_candidate"] != 1.0:
        raise SystemExit(
            "fast path must build each candidate graph exactly once, got "
            f"{res['fast_builds_per_candidate']}"
        )
    if args.min_speedup is not None and res["speedup_fused_vs_seed"] < args.min_speedup:
        raise SystemExit(
            f"scoring speedup {res['speedup_fused_vs_seed']}x below required "
            f"{args.min_speedup}x"
        )
    if args.baseline:
        with open(args.baseline) as f:
            base = json.load(f)
        for key in ("fused_vs_unfused", "pallas_vs_jnp"):
            floor = base[key] * (1.0 - args.max_regression)
            if res[key] < floor:
                raise SystemExit(
                    f"{key} ratio {res[key]} regressed >"
                    f"{args.max_regression:.0%} below recorded baseline "
                    f"{base[key]} (floor {floor:.3f})"
                )


if __name__ == "__main__":
    main()
