"""Training-step microbenchmark: steps/s and examples/s, seed vs unified.

Compares two implementations of one jitted training step (forward + backward
+ adam update) for a COSTREAM ensemble on identical data and weights:

  seed path     the pre-engine forward, replicated verbatim below: one
                per-member vmap of a per-graph vmap of a single-graph
                forward whose stage-3 sweep always scans all MAX_DEPTH
                levels at full row width;
  unified path  ``ensemble_loss`` on the unified engine
                (docs/forward_engine.md): banked MLPs run once across the
                whole padded batch, members ride one stacked forward, and
                the stage-3 sweep runs only the bucket's non-empty depth
                levels at their static ``row_span``/``parent_rows`` bands
                (``bucket_dataset``'s depth-major batches).

The unified path is additionally timed with **signature-exact row-trimmed
banding** (``bucket_dataset(exact=True)``): one bucket per distinct per-row
(type, depth) signature, stage-3 spans exact for that signature and padded
rows statically trimmed — strictly less stage-3 row work per step (asserted)
at the cost of one trace per signature.  Steps/s is the cross-mode
comparable quantity (identical batch shapes, less work per step).

Both steps are timed at the steady state (first call — the trace — excluded)
on the same bucketed batches, so the ratios isolate the engine restructure.
Untrained weights are fine: step time does not depend on the weights' values.

    PYTHONPATH=src python benchmarks/training_bench.py [--quick]
        [--min-speedup X]                      # unified vs seed steps/s floor
        [--min-exact-ratio X]                  # exact vs conservative steps/s floor
        [--baseline FILE --max-regression F]   # ratio gate vs recorded run
"""

from __future__ import annotations

import argparse
import json
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro import nn
from repro.core import CostModelConfig, GNNConfig, init_cost_model
from repro.core.graph import SLOT_RANGES
from repro.core.model import ensemble_loss, loss_fn
from repro.dsps import WorkloadGenerator
from repro.training import bucket_dataset, bucketed_batches, dataset_from_traces
from repro.training import optim


def _seed_apply_gnn(p, g, cfg: GNNConfig):
    """The seed-era single-graph forward (pre-unified-engine), kept verbatim
    as the benchmark baseline: full-width banked MLPs + a lax.scan over all
    ``max_depth`` levels regardless of the query's true depth."""
    op_mask = g.op_mask[:, None]
    hw_mask = g.hw_mask[:, None]
    h_ops = nn.apply_mlp_bank_slotted(p["op_enc"], g.op_x, SLOT_RANGES) * op_mask
    h_hw = nn.apply_mlp(p["hw_enc"], g.hw_x) * hw_mask
    msg_hw = g.a_place.T @ h_ops
    h_hw = nn.apply_mlp(p["hw_upd"], jnp.concatenate([h_hw, msg_hw], axis=-1)) * hw_mask
    msg_ops = g.a_place @ h_hw
    h_ops = (
        nn.apply_mlp_bank_slotted(
            p["op_upd"], jnp.concatenate([h_ops, msg_ops], axis=-1), SLOT_RANGES
        )
        * op_mask
    )

    def depth_step(h, d):
        msg = g.a_flow.T @ h
        upd = nn.apply_mlp_bank_slotted(
            p["op_upd"], jnp.concatenate([h, msg], axis=-1), SLOT_RANGES
        )
        sel = ((g.op_depth == d) & (g.op_mask > 0))[:, None]
        return jnp.where(sel, upd, h), None

    h_ops, _ = jax.lax.scan(
        depth_step, h_ops, jnp.arange(1, cfg.max_depth + 1, dtype=g.op_depth.dtype)
    )
    pooled = jnp.sum(h_ops * op_mask, axis=0) + jnp.sum(h_hw * hw_mask, axis=0)
    return nn.apply_mlp(p["out"], pooled)


def _make_steps(cfg: CostModelConfig, train_lr=1e-3):
    opt = optim.adam(lr=optim.constant_schedule(train_lr))

    def seed_loss(p, g, y):
        raw = jax.vmap(
            lambda pp: jax.vmap(lambda gg: _seed_apply_gnn(pp, gg, cfg.gnn))(g)[..., 0]
        )(p)
        return jnp.sum(jax.vmap(lambda r: loss_fn(cfg)(r, y))(raw))

    @jax.jit
    def seed_step(params, opt_state, g, y):
        loss_val, grads = jax.value_and_grad(lambda p: seed_loss(p, g, y))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optim.apply_updates(params, updates), opt_state, loss_val

    @partial(jax.jit, static_argnums=(4,))
    def unified_step(params, opt_state, g, y, banding):
        loss_val, grads = jax.value_and_grad(
            lambda p: ensemble_loss(p, g, y, cfg, banding)
        )(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optim.apply_updates(params, updates), opt_state, loss_val

    return opt, seed_step, unified_step


def _stage3_rows_per_step(batches) -> float:
    """Mean padded row-work of one step's stage-3 sweep: the sum of the
    banding's level span widths (the rows whose aggregation + banked-MLP
    update actually execute; everything else is statically skipped)."""
    return float(
        np.mean(
            [sum(stop - start for _, (start, stop), _ in b.levels) for _, _, b in batches]
        )
    )


def run(n_traces: int, batch_size: int, repeats: int, seed: int = 0) -> dict:
    traces = WorkloadGenerator(seed=seed).corpus(n_traces)
    ds = dataset_from_traces(traces, "latency_p")
    ds_cons, buckets = bucket_dataset(ds)
    # signature-exact row-trimmed bands: one trace per distinct query
    # signature, stage-3 spans exact for that signature (launch/train.py's
    # default for its large fixed corpora)
    ds_exact, buckets_exact = bucket_dataset(ds, exact=True)
    cfg = CostModelConfig(metric="latency_p", n_ensemble=3, gnn=GNNConfig())
    params = init_cost_model(jax.random.PRNGKey(0), cfg)
    opt, seed_step, unified_step = _make_steps(cfg)

    def materialize(dds, bbuckets):
        return [
            (jax.tree_util.tree_map(jnp.asarray, g), jnp.asarray(y), banding)
            for g, y, banding in bucketed_batches(dds, bbuckets, batch_size)
        ]

    batches = materialize(ds_cons, buckets)
    batches_exact = materialize(ds_exact, buckets_exact)
    assert batches and batches_exact, "corpus produced no batches"

    # sanity: identical loss on the first batch before trusting the timings
    g0, y0, band0 = batches[0]
    st = opt.init(params)
    _, _, l_seed = seed_step(params, st, g0, y0)
    _, _, l_uni = unified_step(params, st, g0, y0, band0)
    np.testing.assert_allclose(float(l_seed), float(l_uni), rtol=1e-4)

    def time_epochs(step, bb, with_banding: bool):
        # warmup epoch = compile every bucket's trace; then timed epochs
        def epoch():
            p, s = params, opt.init(params)
            for g, y, banding in bb:
                p, s, _ = step(p, s, g, y, banding) if with_banding else step(p, s, g, y)
            jax.block_until_ready(p)

        epoch()
        t0 = time.perf_counter()
        for _ in range(repeats):
            epoch()
        return (time.perf_counter() - t0) / repeats

    t_seed = time_epochs(seed_step, batches, with_banding=False)
    t_uni = time_epochs(unified_step, batches, with_banding=True)
    t_exact = time_epochs(unified_step, batches_exact, with_banding=True)
    steps, steps_exact = len(batches), len(batches_exact)
    examples = steps * batch_size
    # steps/s is the comparable per-step quantity: both modes step identical
    # (batch_size, MAX_OPS-or-trimmed) shapes, exact mode just does less of
    # the stage work per step (small corpora pay more per-signature epoch
    # tails, so epoch examples/s is NOT comparable across modes)
    rate_uni = steps / t_uni
    rate_exact = steps_exact / t_exact
    return {
        "n_traces": n_traces,
        "batch_size": batch_size,
        "repeats": repeats,
        "steps_per_epoch": steps,
        "exact_steps_per_epoch": steps_exact,
        "n_buckets": len(buckets),
        "n_signature_buckets": len(buckets_exact),
        "seed_steps_per_s": round(steps / t_seed, 2),
        "unified_steps_per_s": round(rate_uni, 2),
        "exact_steps_per_s": round(rate_exact, 2),
        "seed_examples_per_s": round(examples / t_seed, 1),
        "unified_examples_per_s": round(examples / t_uni, 1),
        "unified_vs_seed": round(t_seed / t_uni, 3),
        "exact_vs_seed": round(rate_exact / (steps / t_seed), 3),
        "exact_vs_unified": round(rate_exact / rate_uni, 3),
        "unified_stage3_rows_per_step": round(_stage3_rows_per_step(batches), 2),
        "exact_stage3_rows_per_step": round(_stage3_rows_per_step(batches_exact), 2),
    }


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--traces", type=int, default=2048)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--quick", action="store_true", help="small run for per-PR CI")
    ap.add_argument("--min-speedup", type=float, default=None, help="fail below this")
    ap.add_argument(
        "--min-exact-ratio",
        type=float,
        default=None,
        help="fail if exact-banding steps/s drops below this fraction of the "
        "bucket-conservative rate (1.0 = 'no slower')",
    )
    ap.add_argument(
        "--baseline",
        type=str,
        default=None,
        help="JSON with a recorded unified_vs_seed ratio",
    )
    ap.add_argument(
        "--max-regression",
        type=float,
        default=0.10,
        help="allowed fractional drop of the measured ratio below the baseline",
    )
    args = ap.parse_args(argv)
    if args.quick:
        args.traces, args.repeats = 768, 2

    res = run(args.traces, args.batch_size, args.repeats)
    print(json.dumps(res, indent=2))
    # not assert: these are the CI gate's invariants, they must survive python -O
    if res["exact_stage3_rows_per_step"] >= res["unified_stage3_rows_per_step"]:
        raise SystemExit(
            "signature-exact banding must do strictly less stage-3 row work "
            f"per step, got {res['exact_stage3_rows_per_step']} vs "
            f"{res['unified_stage3_rows_per_step']} (bucket-conservative)"
        )
    if args.min_exact_ratio is not None and res["exact_vs_unified"] < args.min_exact_ratio:
        raise SystemExit(
            f"exact-banding step rate is {res['exact_vs_unified']}x the "
            f"bucket-conservative rate, below required {args.min_exact_ratio}x"
        )
    if args.min_speedup is not None and res["unified_vs_seed"] < args.min_speedup:
        raise SystemExit(
            f"unified training step {res['unified_vs_seed']}x below required "
            f"{args.min_speedup}x over the seed path"
        )
    if args.baseline:
        with open(args.baseline) as f:
            base = json.load(f)
        floor = base["unified_vs_seed"] * (1.0 - args.max_regression)
        if res["unified_vs_seed"] < floor:
            raise SystemExit(
                f"unified_vs_seed ratio {res['unified_vs_seed']} regressed >"
                f"{args.max_regression:.0%} below recorded baseline "
                f"{base['unified_vs_seed']} (floor {floor:.3f})"
            )


if __name__ == "__main__":
    main()
