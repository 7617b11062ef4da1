"""Smoke run of COSTREAM's main path on one TPU chip.

    python chip_smoke.py

Run from the repository root on a machine with one TPU.  In one process it:

1. requires a TPU (``jax.devices()[0].platform == "tpu"``), never falling
   back to the CPU;
2. generates the full 22,000-trace training corpus from its seed and splits
   it as ``launch/train.py`` does;
3. trains the five per-metric cost models as 3-member ensembles at the
   paper's width (``GNNConfig()``) for a few steps each, at batch 512 with
   signature-exact banding;
4. saves them as a ``CostModelBundle``, loads it back with ``verify=True``
   and builds a ``CostEstimator`` from it;
5. serves refinement requests (16 structures, 2-8 candidates each: the
   merged cross-query path and its ``seg_gather`` kernels), estimate
   requests over held-out graphs, and initial-placement requests (1,024
   candidates each: the per-structure placed path) through a warmed
   ``PlacementService``;
6. runs placement search on two queries;
7. runs the drift controller over the canonical fleet scenario.

Every answer is compared with a plain float32 reference computed in the same
process on the host CPU: a per-graph ``forward_ensemble`` over
``build_graph`` with the unbanded scan plan (no merging, banding, placement
specialisation or kernels).  Regression costs are compared in log1p space;
classification votes where no member's reference logit lies within the
tolerance of 0.

One JSON line per phase reports its seconds, compile seconds, persistent
cache hits and misses, the cache directory, ``device_kind`` and
``peak_bytes_in_use``.  The last line is ``{"ok": true, "device": {...}}``;
any failed check exits non-zero before it is printed.
"""

from __future__ import annotations

import json
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.control import FleetRuntime, PlacementController, build_scenario  # noqa: E402
from repro.core.bucketing import bucket_size, exact_banding_cached  # noqa: E402
from repro.core.gnn import GNNConfig, apply_gnn_merged  # noqa: E402
from repro.core.graph import (  # noqa: E402
    JointGraph,
    batch_graphs,
    build_a_place_batch,
    build_graph,
    build_graph_skeleton,
)
from repro.core.model import (  # noqa: E402
    ALL_METRICS,
    CostModelConfig,
    ensemble_loss,
    forward_ensemble,
    init_cost_model,
    label_array,
)
from repro.dsps.generator import WorkloadGenerator  # noqa: E402
from repro.dsps.placement import Placement  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.train import CORPUS_SEED, MAIN_CORPUS, SPLIT_SEED  # noqa: E402
from repro.placement import sample_assignment_matrix  # noqa: E402
from repro.serve import CostEstimator, CostModelBundle, DispatchPolicy, PlacementService  # noqa: E402
from repro.serve.policy import use_policy  # noqa: E402
from repro.serve.stacking import stack_metric_models  # noqa: E402
from repro.training.batching import (  # noqa: E402
    GraphDataset,
    bucket_dataset,
    bucketed_batches,
    split_dataset,
)
from repro.training.loop import TrainConfig, train_cost_model  # noqa: E402

#: Largest allowed gap between the chip and the CPU float32 reference, in
#: raw output units: log1p(cost) for regression, logits for classification
#: (and relative, for the first-batch losses).  The gap comes from the
#: chip's default float32 matmul precision: on a TPU v5e the largest error
#: of this run was 0.0219 (log1p of latency_p); 0.05 leaves a margin of
#: more than 2x.
TOL = 0.05
RESULT_TIMEOUT_S = 600


@dataclass(frozen=True)
class Sizes:
    """How much of each phase runs.  The defaults are the chip run; the
    CPU test of this script passes tiny ones."""

    corpus: int = MAIN_CORPUS
    hidden: int = GNNConfig().hidden
    train_steps: int = 4
    batch: int = 512
    init_structures: int = 2
    init_cands: int = 1024
    refine_structures: int = 16
    estimate_graphs: int = 320
    control_ticks: int = 12


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# -- reference ---------------------------------------------------------------------


class Reference:
    """Plain float32 forward of the trained ensembles on the host CPU.

    Each graph is built on its own with ``build_graph`` and run through
    ``forward_ensemble`` with no banding (the full ``max_depth`` scan).
    """

    def __init__(self, models, gnn: GNNConfig, chunk: int = 256):
        self.cpu = jax.devices("cpu")[0]
        self.cfgs = {m: cfg for m, (_, cfg) in models.items()}
        self.params = {m: jax.device_put(p, self.cpu) for m, (p, _) in models.items()}
        self.chunk = chunk
        cfg = CostModelConfig(gnn=gnn)  # the forward reads only gnn / traditional_mp
        self._fwd = jax.jit(lambda p, g: forward_ensemble(p, g, cfg))

    def raw(self, graphs: JointGraph) -> dict:
        """metric -> (members, B) raw outputs."""
        n = int(graphs.op_x.shape[0])
        out = {m: [] for m in self.params}
        for s in range(0, n, self.chunk):
            part = [np.asarray(x)[s : s + self.chunk] for x in graphs]
            k = len(part[0])
            if k < self.chunk:  # one shape for every chunk: pad by repeating
                part = [np.concatenate([x, np.repeat(x[-1:], self.chunk - k, 0)]) for x in part]
            g = jax.device_put(JointGraph(*part), self.cpu)
            with jax.default_device(self.cpu):
                for m, p in self.params.items():
                    out[m].append(np.asarray(self._fwd(p, g))[:, :k])
        return {m: np.concatenate(v, axis=1) for m, v in out.items()}

    def graphs_for(self, query, cluster, assignments) -> JointGraph:
        return batch_graphs([build_graph(query, cluster, Placement.of(a)) for a in assignments])


def log1p_cost(raw: np.ndarray) -> np.ndarray:
    """An ensemble's regression answer from its (members, B) raw outputs:
    the mean member cost, in log1p space."""
    return np.log1p(np.clip(np.mean(np.expm1(raw), axis=0), 0.0, None))


class Comparison:
    """Chip answers against the reference.

    Records every comparison, the largest error and the first violation,
    so one run reports the whole gap; ``run`` checks ``violation`` at the
    end."""

    def __init__(self, tol: float):
        self.tol = tol
        self.max_err = 0.0
        self.worst = ""
        self.violation = None
        self.n_regression = 0
        self.n_votes = 0
        self.n_votes_skipped = 0

    def record(self, err: float, where: str, ok: bool, msg: str) -> None:
        if err > self.max_err:
            self.max_err, self.worst = err, where
        if not ok and self.violation is None:
            self.violation = msg

    def loss(self, what: str, chip: float, want: float) -> float:
        """A scalar loss, compared relative to max(1, |reference|)."""
        rel = abs(chip - want) / max(1.0, abs(want))
        self.record(rel, what, rel <= self.tol, f"{what}: loss {chip} vs reference {want}")
        return rel

    def answers(self, what: str, chip: dict, ref_raw: dict, cfgs: dict) -> None:
        for m, have in chip.items():
            raw = ref_raw[m]
            have = np.asarray(have)
            check(have.shape == raw.shape[1:], f"{what}/{m}: shape {have.shape} vs {raw.shape[1:]}")
            if cfgs[m].task == "regression":
                err = np.abs(np.log1p(have) - log1p_cost(raw))
                self.n_regression += err.size
                i = int(np.argmax(err))
                self.record(
                    float(err[i]),
                    f"{what}/{m}[{i}]",
                    err[i] <= self.tol,
                    f"{what}/{m}: log1p error {err[i]:.6g} > tolerance {self.tol} at {i}",
                )
            else:
                clear = np.all(np.abs(raw) > self.tol, axis=0)
                votes = (np.sum(raw > 0, axis=0) * 2 > raw.shape[0]).astype(np.int64)
                bad = np.flatnonzero(clear & (have != votes))
                self.n_votes += int(clear.sum())
                self.n_votes_skipped += int((~clear).sum())
                if len(bad) and self.violation is None:
                    self.violation = f"{what}/{m}: {len(bad)} votes differ, first at {bad[:1]}"

    def summary(self) -> dict:
        return {
            "tolerance": self.tol,
            "max_abs_err": self.max_err,
            "worst": self.worst,
            "n_regression_compared": self.n_regression,
            "n_votes_compared": self.n_votes,
            "n_votes_near_zero_skipped": self.n_votes_skipped,
            "violation": self.violation,
        }


# -- phases ------------------------------------------------------------------------


def make_corpus(n_traces: int, seed: int = CORPUS_SEED):
    """The training corpus and its featurized graphs (built once)."""
    traces = WorkloadGenerator(seed=seed).corpus(n_traces)
    graphs = batch_graphs([build_graph(t.query, t.cluster, t.placement) for t in traces])
    return traces, graphs


def train_models(traces, graphs, gnn: GNNConfig, steps: int, batch_size: int, n_ensemble: int = 3):
    """Train every metric for ``steps`` steps; return (models, per-metric report,
    per-metric (initial params, first batch)) for the loss check."""
    models, report, first = {}, {}, {}
    for metric in ALL_METRICS:
        ds = GraphDataset(graphs=graphs, labels=label_array(traces, metric))
        tr, va, _ = split_dataset(ds, seed=SPLIT_SEED)
        cfg = CostModelConfig(metric=metric, n_ensemble=n_ensemble, gnn=gnn)
        p0 = jax.tree_util.tree_map(np.asarray, init_cost_model(jax.random.PRNGKey(0), cfg))
        res = train_cost_model(
            tr,
            va,
            cfg,
            TrainConfig(
                epochs=1, batch_size=batch_size, lr=1.5e-3, exact_banding=True, max_steps=steps
            ),
            init_params=p0,
        )
        models[metric] = (res.params, cfg)
        report[metric] = {
            "steps": res.steps,
            "train_loss": res.history[-1]["train_loss"],
            "val_loss": res.history[-1]["val_loss"],
        }
        d_tr, buckets = bucket_dataset(tr, exact=True)
        first[metric] = (p0, next(bucketed_batches(d_tr, buckets, batch_size)))
    return models, report, first


def check_training(report: dict, first: dict, models: dict, ref: Reference, cmp: Comparison) -> dict:
    """Finite losses, and the first batch's loss at the initial parameters
    on the chip (signature-exact banding, as training runs it) against the
    reference (unbanded, on the CPU)."""
    chip_loss = jax.jit(ensemble_loss, static_argnums=(3, 4))
    ref_loss = jax.jit(ensemble_loss, static_argnums=(3,))
    losses = {}
    for metric, r in report.items():
        check(r["steps"] > 0, f"{metric}: no training step ran")
        check(np.isfinite(r["train_loss"]), f"{metric}: train loss {r['train_loss']}")
        check(np.isfinite(r["val_loss"]), f"{metric}: val loss {r['val_loss']}")
        p0, (g, y, banding) = first[metric]
        # the loss reads only the task and the GNN: one compile per task
        task_metric = ALL_METRICS[0] if models[metric][1].task == "regression" else "success"
        cfg = CostModelConfig(metric=task_metric, gnn=models[metric][1].gnn)
        chip = float(chip_loss(p0, g, y, cfg, banding))
        with jax.default_device(ref.cpu):
            g_cpu, y_cpu = jax.device_put((JointGraph(*map(np.asarray, g)), np.asarray(y)), ref.cpu)
            want = float(ref_loss(jax.device_put(p0, ref.cpu), g_cpu, y_cpu, cfg))
        rel = cmp.loss(f"train/{metric}/first_batch_loss", chip, want)
        losses[metric] = {"chip": chip, "reference": want, "rel_err": rel}
    return losses


def save_and_load(models, meta: dict, directory: str) -> CostEstimator:
    CostModelBundle(models=models, meta=meta).save(directory)
    bundle = CostModelBundle.load(directory, verify=True)
    return CostEstimator.from_bundle(bundle, policy=DispatchPolicy())


def make_requests(seed: int, sizes: Sizes, heldout: JointGraph):
    """(initial-placement structures, their items, refinement items,
    estimate batches over ``heldout`` graphs)."""
    gen = WorkloadGenerator(seed=seed)
    rng = np.random.default_rng(seed)
    structures, init = [], []
    for i in range(sizes.init_structures):
        # the largest shapes: three-way joins on clusters of the widest size
        q, c = gen.query(kind="three_way", name=f"init{i}"), gen.cluster(8)
        structures.append((q, c))
        for _ in range(2):
            init.append((q, c, sample_assignment_matrix(q, c, sizes.init_cands, rng)))
    refine = []
    kinds = ("linear", "two_way", "three_way")
    for i in range(sizes.refine_structures):
        q, c = gen.query(kind=kinds[i % 3], name=f"refine{i}"), gen.cluster()
        k = int(rng.integers(2, 9))
        refine.append((q, c, sample_assignment_matrix(q, c, k, rng)))
    per = max(1, sizes.estimate_graphs // 8)
    estimates = [
        JointGraph(*[np.asarray(x)[s : s + per] for x in heldout])
        for s in range(0, sizes.estimate_graphs, per)
    ]
    return structures, init, refine, estimates


def serve(est: CostEstimator, structures, init, refine, estimates, init_cands: int):
    """Serve every request through one warmed ``PlacementService``.

    Refinement and estimate requests are queued before ``start()`` so they
    drain together (the refinement mix takes the merged path); the
    initial-placement structures are then warmed up to ``init_cands`` one at
    a time and their requests served."""
    refine_structs = [(q, c) for q, c, _ in refine]
    svc = PlacementService(est, auto_start=False, warmup=refine_structs, policy=est.policy)
    try:
        f_ref = [svc.submit_score(q, c, a) for q, c, a in refine]
        f_est = [svc.submit_estimate(g) for g in estimates]
        t0 = time.perf_counter()
        svc.start()
        warm_s = time.perf_counter() - t0
        refine_out = [f.result(RESULT_TIMEOUT_S) for f in f_ref]
        estimate_out = [f.result(RESULT_TIMEOUT_S) for f in f_est]
        t0 = time.perf_counter()
        for q, c in structures:  # one at a time: no merged ladder for these
            svc.warm([(q, c)], max_cands=init_cands)
        warm_s += time.perf_counter() - t0
        f_init = [svc.submit_score(q, c, a) for q, c, a in init]
        init_out = [f.result(RESULT_TIMEOUT_S) for f in f_init]
    finally:
        svc.close()
    return init_out, refine_out, estimate_out, svc.stats, warm_s


def merged_lowering_text(est: CostEstimator, refine) -> str:
    """StableHLO of the merged cross-query forward at the refinement shapes."""
    structs = [(q, c) for q, c, _ in refine]
    skels = batch_graphs([build_graph_skeleton(q, c) for q, c in structs])
    banding = exact_banding_cached(skels)
    max_parents = int(np.asarray(skels.a_flow).sum(axis=-2).max(initial=1))
    ids = np.concatenate([np.full(len(a), i, np.int32) for i, (_, _, a) in enumerate(refine)])
    ap = np.concatenate([build_a_place_batch(q, c, a) for q, c, a in refine])
    pad = bucket_size(len(ids)) - len(ids)
    ids = np.concatenate([ids, np.repeat(ids[-1:], pad)])
    ap = np.concatenate([ap, np.repeat(ap[-1:], pad, axis=0)])
    stacked = stack_metric_models(est.models)
    fwd = jax.jit(apply_gnn_merged, static_argnums=(4, 5, 6))
    return fwd.lower(stacked.params, skels, ids, ap, stacked.cfgs[0].gnn, banding, max_parents).as_text()


def search(est: CostEstimator, structures, ref: Reference, cmp: Comparison) -> list:
    """``est.optimize`` per structure; the pick must be within ``cmp.tol`` of
    the reference optimum over the same (chip-feasible) candidates."""
    out = []
    for q, c in structures:
        res = est.optimize(q, c, target_metric="latency_p")
        pool = np.asarray([p.assignment for p in res.candidates])
        metrics = ("latency_p", "success", "backpressure")
        chip = est.score(q, c, pool, metrics)
        check(
            np.array_equal(chip["latency_p"], res.scores),
            "search scores differ from a direct score of the same candidates",
        )
        raw = ref.raw(ref.graphs_for(q, c, pool))
        cmp.answers("search", chip, raw, ref.cfgs)
        feasible = chip["success"].astype(bool) & chip["backpressure"].astype(bool)
        if not feasible.any():
            feasible[:] = True
        want = log1p_cost(raw["latency_p"])
        pick = int(np.flatnonzero((pool == np.asarray(res.placement.assignment)).all(axis=1))[0])
        gap = float(want[pick] - want[feasible].min())
        check(feasible[pick], "search picked a candidate the chip scored infeasible")
        cmp.record(gap, "search/gap", gap <= cmp.tol, f"search pick is {gap:.6g} above the optimum")
        out.append({"n_candidates": len(pool), "n_feasible": int(feasible.sum()), "gap": gap})
    return out


def control(est: CostEstimator, n_ticks: int) -> dict:
    fleet, cluster, events = build_scenario(6, 20)
    runtime = FleetRuntime(fleet, cluster, events, policy=est.policy)
    ctl = PlacementController(runtime, estimator=est, policy=est.policy)
    report = ctl.run(n_ticks)
    check(report.n_ticks >= n_ticks, f"controller ran {report.n_ticks} of {n_ticks} ticks")
    check(report.n_replans > 0, "controller never re-planned")
    costs = [r.fleet_cost_ms for r in report.records]
    check(bool(np.all(np.isfinite(costs))), "non-finite fleet cost")
    preds = [d.predicted_cost for r in report.records for d in r.decisions]
    check(bool(np.all(np.isfinite(preds))), "non-finite predicted cost in a decision")
    return {
        "ticks": report.n_ticks,
        "replans": report.n_replans,
        "migrations": report.n_migrations,
        "replan_p95_ms": report.replan_p95_ms,
        "final_cost_ms": report.final_cost_ms,
    }


def check_stats(stats) -> dict:
    fields = ("n_degraded", "n_retries", "n_nonfinite", "n_timeouts", "n_failed")
    got = {f: getattr(stats, f) for f in fields}
    for f, v in got.items():
        check(v == 0, f"service {f} = {v}")
    got["n_cross_query"] = stats.n_cross_query
    got["n_forwards"] = stats.n_forwards
    check(stats.n_cross_query > 0, "no request took the merged cross-query path")
    return got


# -- driver ------------------------------------------------------------------------


class Meter:
    """Per-phase wall time, compile time and persistent-cache counters.

    A context manager: JAX's monitoring listeners are process-wide, so they
    are registered on entry and removed on exit."""

    _DURATIONS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    }
    _EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "cache_lookups",
        "/jax/compilation_cache/cache_hits": "cache_hits",
        # JAX records a "miss" when it writes an entry; compiles faster than
        # jax_persistent_cache_min_compile_time_secs are neither hit nor kept
        "/jax/compilation_cache/cache_misses": "cache_writes",
    }

    def __init__(self, cache_dir):
        self.cache_dir = cache_dir
        self.dev = jax.devices()[0]
        self.counts = {}
        self.totals = {}

    def __enter__(self) -> "Meter":
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def _add(self, key: str, value: float) -> None:
        self.counts[key] = self.counts.get(key, 0) + value
        self.totals[key] = self.totals.get(key, 0) + value

    def _on_duration(self, event: str, secs: float, **_):
        key = self._DURATIONS.get(event)
        if key is not None:
            self._add(key, secs)
            if key == "backend_compile_s":  # compiled, or loaded from the cache
                self._add("n_compiles", 1)

    def _on_event(self, event: str, **_):
        key = self._EVENTS.get(event)
        if key is not None:
            self._add(key, 1)

    def _report(self, counts: dict) -> dict:
        keys = ("trace_s", "lower_s", "backend_compile_s", "n_compiles") + tuple(
            self._EVENTS.values()
        )
        return {k: counts.get(k, 0) for k in keys}

    def phase(self, name: str, fn):
        """Run ``fn``; return its result and the phase's report line."""
        self.counts = {}
        t0 = time.perf_counter()
        result = fn()
        line = {"phase": name, "seconds": time.perf_counter() - t0}
        line.update(self._report(self.counts))
        line.update(
            cache_dir=self.cache_dir,
            device_kind=self.dev.device_kind,
            peak_bytes_in_use=(self.dev.memory_stats() or {}).get("peak_bytes_in_use"),
        )
        return result, line

    def total(self) -> dict:
        return {"cache_dir": self.cache_dir, **self._report(self.totals)}


def emit(line: dict) -> None:
    print(json.dumps(line, default=float), flush=True)


def run(meter: Meter, sizes: Sizes = Sizes(), tol: float = TOL) -> dict:
    """Every phase, with its checks; returns the comparison summary."""
    (traces, graphs), line = meter.phase("corpus", lambda: make_corpus(sizes.corpus))
    emit({**line, "n_traces": len(traces)})

    gnn = GNNConfig(hidden=sizes.hidden)
    (models, report, first), line = meter.phase(
        "train", lambda: train_models(traces, graphs, gnn, sizes.train_steps, sizes.batch)
    )
    emit({**line, "hidden": gnn.hidden, "batch": sizes.batch, "metrics": report})

    ref = Reference(models, gnn)
    cmp = Comparison(tol)
    losses, line = meter.phase(
        "train_check", lambda: check_training(report, first, models, ref, cmp)
    )
    emit({**line, "first_batch_loss": losses})

    with tempfile.TemporaryDirectory() as bundle_dir:  # lazy loading: keep it alive
        meta = {"corpus_seed": CORPUS_SEED, "split_seed": SPLIT_SEED, "corpus_size": len(traces)}
        est, line = meter.phase("load", lambda: save_and_load(models, meta, bundle_dir))
        emit({**line, "metrics": list(est.metrics)})

        _, _, test = split_dataset(GraphDataset(graphs, np.zeros(len(traces))), seed=SPLIT_SEED)
        structures, init, refine, estimates = make_requests(CORPUS_SEED + 1, sizes, test.graphs)
        (init_out, refine_out, est_out, stats, warm_s), line = meter.phase(
            "serve", lambda: serve(est, structures, init, refine, estimates, sizes.init_cands)
        )
        for what, items, outs in (("init", init, init_out), ("refine", refine, refine_out)):
            for (q, c, a), have in zip(items, outs):
                cmp.answers(what, have, ref.raw(ref.graphs_for(q, c, a)), ref.cfgs)
        for g, have in zip(estimates, est_out):
            cmp.answers("estimate", have, ref.raw(g), ref.cfgs)
        counters = check_stats(stats)
        kernels = merged_lowering_text(est, refine).count("tpu_custom_call")
        check(
            meter.dev.platform != "tpu" or kernels > 0,
            "the merged forward holds no tpu_custom_call: Mosaic did not lower the kernels",
        )
        emit(
            {
                **line,
                "warm_s": warm_s,
                "requests": {"init": len(init), "refine": len(refine), "estimate": len(estimates)},
                "init_cands": [len(a) for _, _, a in init],
                "stats": counters,
                "merged_tpu_custom_calls": kernels,
                "compare": cmp.summary(),
            }
        )

        picks, line = meter.phase("search", lambda: search(est, structures, ref, cmp))
        emit({**line, "queries": picks})

        ctl, line = meter.phase("control", lambda: control(est, sizes.control_ticks))
        emit({**line, **ctl})

    summary = cmp.summary()
    emit({"phase": "summary", "compare": summary, **meter.total()})
    check(cmp.violation is None, f"answers differ from the reference: {cmp.violation}")
    return summary


def main(argv=None) -> int:
    platforms = jax.config.jax_platforms
    if platforms and "cpu" not in platforms.split(","):
        # the reference runs on the host CPU of the same process
        jax.config.update("jax_platforms", platforms + ",cpu")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (default device is {dev.platform}); nothing run", file=sys.stderr)
        return 2
    try:
        # an explicit policy: no host profile from the user's cache is read
        with Meter(enable_compile_cache()) as meter, use_policy(DispatchPolicy()):
            run(meter)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
