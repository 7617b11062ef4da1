"""Fused-sweep kernel benchmark + CI gate.

Two claims, measured where each is measurable on this CPU container:

* **fused_vs_per_level** — the interpret lowering executes the actual kernel
  bodies, so per-launch cost is real there: one fused ``mp_sweep``
  interpretation of the whole banding table vs L sequential ``mp_update``
  interpretations.  The ratio is the launch-amortization the fusion buys
  (on TPU the same structure also keeps the row tile resident in VMEM across
  levels — unmeasurable here, same launch arithmetic).
* **merged_kernel_vs_jnp** — the kernel-routed merged engine on the
  jnp-oracle lowering, i.e. what serving actually runs on CPU after
  ``score_many`` lost its dense-broadcast fallback.  ``seg_gather``'s ref
  lowering IS the formerly-inline formulation, so this ratio must hold
  ~1.0: the gate is regression-only (routing must cost nothing).

Launch counts are asserted, not sampled: the harness wraps the Pallas
entry points with counters and fails if a fused forward issues anything but
ONE stage-3 launch.

Usage: PYTHONPATH=src python benchmarks/kernel_bench.py --quick \
        [--min-fused-ratio 1.2] [--baseline FILE --max-regression F]
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

try:
    from benchmarks.common import save_result
except ModuleNotFoundError:  # invoked as a script (scripts/ci.sh): repo root off path
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmarks.common import save_result
from repro.launch.compile_cache import enable_compile_cache
from repro.core.bucketing import batch_banding, bucket_size, exact_banding, pad_batch
from repro.core.gnn import GNNConfig, _banded_plan, apply_gnn_merged, init_gnn
from repro.core.graph import SLOT_RANGES, batch_graphs, build_a_place_batch, build_graph_skeleton
from repro.dsps.generator import WorkloadGenerator
from repro.kernels import mp_sweep as sweep_pkg
from repro.kernels import mp_update as update_pkg
from repro.kernels.mp_sweep.ops import mp_sweep
from repro.kernels.mp_update.ops import mp_update
from repro.placement import sample_assignment_matrix
from repro.training.batching import dataset_from_traces


def _best_of(fn, repeats):
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _sweep_case(n_traces, hidden, seed=0):
    ds = dataset_from_traces(WorkloadGenerator(seed=seed).corpus(n_traces), "latency_p")
    g = pad_batch(ds.graphs, bucket_size(ds.graphs.op_x.shape[0]))
    banding = batch_banding(g)
    levels = _banded_plan(banding, SLOT_RANGES).levels
    params = init_gnn(jax.random.PRNGKey(seed), GNNConfig(hidden=hidden))["op_upd"]
    B, N = g.op_x.shape[:2]
    h = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, N, hidden))
    args = (
        jnp.asarray(g.a_flow),
        jnp.asarray(g.op_depth),
        jnp.asarray(g.op_mask, jnp.float32),
    )
    return params, h, args, levels


def _counting(holder, key, fn):
    def wrapped(*a, **k):
        holder[key] += 1
        return fn(*a, **k)

    return wrapped


def run(n_traces: int, hidden: int, repeats: int) -> dict:
    res: dict = {"n_traces": n_traces, "hidden": hidden, "repeats": repeats}
    params, h, (a_flow, depth, mask), levels = _sweep_case(n_traces, hidden)
    res["levels"] = len(levels)

    # --- launch counting + fused-vs-per-level, on the interpret lowering ---
    prev = os.environ.get("REPRO_PALLAS_INTERPRET")
    os.environ["REPRO_PALLAS_INTERPRET"] = "1"
    counts = {"sweep": 0, "update": 0}
    orig_sweep = sweep_pkg.ops.mp_sweep_pallas
    orig_update = update_pkg.ops.mp_update_pallas
    sweep_pkg.ops.mp_sweep_pallas = _counting(counts, "sweep", orig_sweep)
    update_pkg.ops.mp_update_pallas = _counting(counts, "update", orig_update)
    try:

        def fused():
            return mp_sweep(params, h, a_flow, depth, mask, levels)

        def per_level():
            out = h
            for d, span, ranges, p in levels:
                out = mp_update(
                    params, out, a_flow, depth, mask, jnp.asarray(d, depth.dtype),
                    ranges, row_span=span, parent_rows=p,
                )
            return out

        err = float(jnp.abs(fused() - per_level()).max())
        res["maxerr_fused_vs_per_level"] = err
        res["fused_launches_per_forward"] = counts["sweep"]  # must be 1
        res["per_level_launches_per_forward"] = counts["update"]  # == levels
        # the counted parity call above already warmed both paths
        t_fused = _best_of(fused, repeats)
        t_loop = _best_of(per_level, repeats)
        res["fused_us"] = t_fused * 1e6
        res["per_level_us"] = t_loop * 1e6
        res["fused_vs_per_level"] = t_loop / t_fused
    finally:
        sweep_pkg.ops.mp_sweep_pallas = orig_sweep
        update_pkg.ops.mp_update_pallas = orig_update
        if prev is None:
            os.environ.pop("REPRO_PALLAS_INTERPRET", None)
        else:
            os.environ["REPRO_PALLAS_INTERPRET"] = prev

    # --- merged engine routing cost, on the serving (jnp-oracle) lowering ---
    gen = WorkloadGenerator(seed=7)
    cluster = gen.cluster(4)
    queries = [gen.query(kind=k, name=f"b{i}") for i, k in enumerate(("linear", "two_way"))]
    rng = np.random.default_rng(7)
    skels = batch_graphs([build_graph_skeleton(q, cluster) for q in queries])
    blocks, ids = [], []
    per_q = max(8, n_traces)
    for i, q in enumerate(queries):
        a = sample_assignment_matrix(q, cluster, per_q, rng, max_tries_factor=400)
        blocks.append(build_a_place_batch(q, cluster, a))
        ids.append(np.full(len(a), i, dtype=np.int32))
    banding = exact_banding(skels)
    max_parents = int(np.asarray(skels.a_flow).sum(axis=-2).max(initial=1))
    skels_j = jax.tree_util.tree_map(jnp.asarray, skels)
    skel_id = jnp.asarray(np.concatenate(ids))
    a_place = jnp.asarray(np.concatenate(blocks))
    cfg_j = GNNConfig(hidden=hidden)
    cfg_p = GNNConfig(hidden=hidden, use_pallas=True)
    stack = jax.tree_util.tree_map(
        lambda p: p[None], init_gnn(jax.random.PRNGKey(3), cfg_j)
    )

    def merged(cfg):
        return jax.jit(
            lambda p, sid, ap: apply_gnn_merged(
                p, skels_j, sid, ap, cfg, banding, max_parents
            )
        )

    f_j, f_p = merged(cfg_j), merged(cfg_p)
    err = float(jnp.abs(f_j(stack, skel_id, a_place) - f_p(stack, skel_id, a_place)).max())
    res["maxerr_merged"] = err
    t_j = _best_of(lambda: f_j(stack, skel_id, a_place), repeats)
    t_p = _best_of(lambda: f_p(stack, skel_id, a_place), repeats)
    res["merged_jnp_us"] = t_j * 1e6
    res["merged_kernel_us"] = t_p * 1e6
    res["merged_kernel_vs_jnp"] = t_j / t_p
    res["merged_rows"] = int(a_place.shape[0])
    return res


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--traces", type=int, default=48)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--quick", action="store_true", help="small run for per-PR CI")
    ap.add_argument(
        "--min-fused-ratio",
        type=float,
        default=None,
        help="fail if fused_vs_per_level (interpret lowering) is below this",
    )
    ap.add_argument(
        "--baseline",
        type=str,
        default=None,
        help="JSON with recorded fused_vs_per_level / merged_kernel_vs_jnp ratios",
    )
    ap.add_argument(
        "--max-regression",
        type=float,
        default=0.10,
        help="allowed fractional drop of a measured ratio below the baseline",
    )
    args = ap.parse_args(argv)
    if args.quick:
        args.traces, args.hidden, args.repeats = 24, 32, 3

    res = run(args.traces, args.hidden, args.repeats)
    print(json.dumps(res, indent=2))
    save_result("kernel_bench", res)
    # not assert: CI-gate invariants, they must survive python -O
    if res["fused_launches_per_forward"] != 1:
        raise SystemExit(
            "fused sweep must be ONE stage-3 launch per forward, got "
            f"{res['fused_launches_per_forward']}"
        )
    if res["per_level_launches_per_forward"] != res["levels"]:
        raise SystemExit("per-level path launch count does not match the banding table")
    for key in ("maxerr_fused_vs_per_level", "maxerr_merged"):
        if res[key] > 1e-4:
            raise SystemExit(f"parity violation: {key}={res[key]}")
    if args.min_fused_ratio is not None and res["fused_vs_per_level"] < args.min_fused_ratio:
        raise SystemExit(
            f"fused sweep only {res['fused_vs_per_level']:.2f}x over per-level "
            f"launches, required {args.min_fused_ratio}x"
        )
    if args.baseline:
        with open(args.baseline) as f:
            base = json.load(f)
        for key in ("fused_vs_per_level", "merged_kernel_vs_jnp"):
            floor = base[key] * (1.0 - args.max_regression)
            if res[key] < floor:
                raise SystemExit(
                    f"{key} ratio {res[key]:.3f} regressed >"
                    f"{args.max_regression:.0%} below recorded baseline "
                    f"{base[key]} (floor {floor:.3f})"
                )
    return res


if __name__ == "__main__":
    main()
