"""COSTREAM training driver: builds the benchmark corpus and trains every
model artifact the experiment harnesses need.

Stages (resumable; each skips finished artifacts):

  main       5 per-metric GNN ensembles (paper SIV-A) on the full corpus
  flat       flat-vector baselines [16] for the same 5 metrics
  extrap     8 restricted-range retrains for Exp 4 (4 hw dims x stronger/weaker)
  ablations  Exp 7a featurization variants + Exp 7b traditional message passing
  finetune   Exp 5b few-shot fine-tuning on filter-chain queries

Run:  PYTHONPATH=src python -m repro.launch.train --stage all
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pickle
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.core.flat_vector import FlatVectorConfig, featurize_flat_traces
from repro.core.graph import drop_hardware, drop_hw_features
from repro.core.model import (
    ALL_METRICS,
    CLASSIFICATION_METRICS,
    REGRESSION_METRICS,
    CostModelConfig,
)
from repro.dsps import ranges
from repro.dsps.generator import GeneratorConfig, WorkloadGenerator
from repro.launch import artifacts
from repro.launch.compile_cache import enable_compile_cache
from repro.training.batching import dataset_from_traces, split_dataset, split_indices
from repro.training.loop import TrainConfig, train_cost_model, train_flat_model

CORPUS_SEED = 42
SPLIT_SEED = 7
MAIN_CORPUS = 22_000
EXTRAP_CORPUS = 6_000
FINETUNE_N = 3_000


def corpus_cache(name: str, build) -> List:
    os.makedirs(artifacts.path("corpus"), exist_ok=True)
    p = artifacts.path("corpus", f"{name}.pkl")
    if os.path.exists(p):
        with open(p, "rb") as f:
            return pickle.load(f)
    traces = build()
    tmp = p + ".tmp"
    with open(tmp, "wb") as f:
        pickle.dump(traces, f)
    os.replace(tmp, p)
    return traces


def main_corpus() -> List:
    return corpus_cache(
        "main", lambda: WorkloadGenerator(seed=CORPUS_SEED).corpus(MAIN_CORPUS)
    )


def _train_one(
    traces,
    metric: str,
    name: str,
    n_ensemble: int,
    epochs: int,
    transform=None,
    traditional_mp: bool = False,
    extra: Optional[Dict] = None,
    seed: int = 0,
    verbose: bool = True,
):
    if artifacts.exists("costream", name):
        print(f"[skip] {name}")
        return
    t0 = time.time()
    ds = dataset_from_traces(traces, metric, transform=transform)
    tr, va, te = split_dataset(ds, seed=SPLIT_SEED)
    cfg = CostModelConfig(metric=metric, n_ensemble=n_ensemble, traditional_mp=traditional_mp)
    res = train_cost_model(
        tr,
        va,
        cfg,
        # signature-exact bands: these fixed corpora dwarf the batch size, so
        # the extra per-signature traces amortize and every step runs
        # row-trimmed stage-3 spans (benchmarks/training_bench.py)
        TrainConfig(
            epochs=epochs,
            batch_size=512,
            lr=1.5e-3,
            seed=seed,
            verbose=verbose,
            exact_banding=True,
        ),
    )
    artifacts.save_cost_model(
        name,
        res.params,
        cfg,
        extra={
            "best_val": res.best_val,
            "steps": res.steps,
            "history": res.history,
            "seconds": time.time() - t0,
            **(extra or {}),
        },
    )
    print(f"[done] {name} val={res.best_val:.4f} in {time.time() - t0:.0f}s")


def stage_main(epochs: int):
    traces = main_corpus()
    for metric in ALL_METRICS:
        _train_one(traces, metric, f"main_{metric}", n_ensemble=3, epochs=epochs)
    export_main_bundle(epochs)


def export_main_bundle(epochs: int):
    """Assemble the five per-metric ensembles into the ONE versioned serving
    artifact (repro.serve.CostModelBundle) the online path loads; the loose
    per-metric checkpoints stay as the resumable training artifacts."""
    from repro.serve.bundle import CostModelBundle, corpus_fingerprint

    if artifacts.bundle_exists("main"):
        print("[skip] bundle main")
        return
    missing = [m for m in ALL_METRICS if not artifacts.exists("costream", f"main_{m}")]
    if missing:
        print(f"[warn] bundle main not exported: metrics not trained yet {missing}")
        return
    bundle = CostModelBundle(
        models={m: artifacts.load_cost_model(f"main_{m}") for m in ALL_METRICS},
        meta={
            "stage": "main",
            "corpus_seed": CORPUS_SEED,
            "split_seed": SPLIT_SEED,
            "corpus_size": MAIN_CORPUS,
            # provenance: CostEstimator.from_bundle(corpus_fingerprint=...)
            # warns when served against data from a different corpus
            "corpus_fingerprint": corpus_fingerprint(main_corpus()),
            "epochs": epochs,
        },
    )
    artifacts.save_bundle("main", bundle)
    print(f"[done] bundle main ({', '.join(bundle.metrics)})")


def stage_flat(epochs: int):
    traces = main_corpus()
    x = featurize_flat_traces(traces)
    # the same partition split_dataset uses for the GNN models
    idx_tr, idx_va, _ = split_indices(len(traces), seed=SPLIT_SEED)
    from repro.core.model import label_array

    for metric in ALL_METRICS:
        name = f"flat_{metric}"
        if artifacts.exists("flat", name):
            print(f"[skip] {name}")
            continue
        y = label_array(traces, metric)
        task = "regression" if metric in REGRESSION_METRICS else "classification"
        cfg = FlatVectorConfig(task=task)
        params = train_flat_model(
            x[idx_tr],
            y[idx_tr],
            x[idx_va],
            y[idx_va],
            cfg,
            TrainConfig(epochs=epochs, batch_size=512, lr=1.5e-3),
        )
        artifacts.save_flat_model(name, params, cfg)
        print(f"[done] {name}")


def extrap_generator(direction: str, dim: str) -> GeneratorConfig:
    spec = ranges.extrapolation_ranges()[direction]["train"]
    kw = {}
    mapping = {
        "ram": ("ram_mb", "RAM_MB"),
        "cpu": ("cpu", "CPU"),
        "bandwidth": ("bandwidth_mbps", "BANDWIDTH_MBPS"),
        "latency": ("latency_ms", "LATENCY_MS"),
    }
    field, key = mapping[dim]
    kw[field] = tuple(spec[key])
    return GeneratorConfig().with_hardware(**kw)


def stage_extrap(epochs: int):
    for direction in ("stronger", "weaker"):
        for dim in ("ram", "cpu", "bandwidth", "latency"):
            cname = f"extrap_{direction}_{dim}"
            traces = corpus_cache(
                cname,
                lambda d=direction, m=dim: WorkloadGenerator(
                    extrap_generator(d, m), seed=CORPUS_SEED + hash((d, m)) % 1000
                ).corpus(EXTRAP_CORPUS),
            )
            for metric in ALL_METRICS:
                _train_one(
                    traces,
                    metric,
                    f"{cname}_{metric}",
                    n_ensemble=1,
                    epochs=epochs,
                    extra={"direction": direction, "dim": dim},
                    verbose=False,
                )


def stage_ablations(epochs: int):
    traces = main_corpus()
    # Exp 7a: featurization variants for L_e — plus an equal-budget "full"
    # model so the Fig-12 comparison is apples-to-apples at these epochs
    _train_one(traces, "latency_e", "ablate_full_latency_e", n_ensemble=3, epochs=epochs)
    _train_one(
        traces,
        "latency_e",
        "ablate_no_hw_nodes_latency_e",
        n_ensemble=3,
        epochs=epochs,
        transform=drop_hardware,
    )
    _train_one(
        traces,
        "latency_e",
        "ablate_no_hw_feats_latency_e",
        n_ensemble=3,
        epochs=epochs,
        transform=drop_hw_features,
    )
    # Exp 7b: traditional message passing for the regression metrics
    for metric in REGRESSION_METRICS:
        _train_one(
            traces,
            metric,
            f"ablate_traditional_{metric}",
            n_ensemble=3,
            epochs=epochs,
            traditional_mp=True,
        )


def chain_corpus(name: str, n: int, seed: int, chain_lengths=(2, 3, 4)) -> List:
    """Filter-chain queries unseen in training (Exp 5 / Exp 5b)."""
    from repro.dsps.generator import Trace
    from repro.dsps.simulator import simulate

    def build():
        gen = WorkloadGenerator(seed=seed)
        out = []
        for i in range(n):
            ln = chain_lengths[i % len(chain_lengths)]
            q = gen.linear_query(name=f"{name}{i}", n_filters=ln)
            c = gen.cluster()
            p = gen.placement(q, c)
            out.append(Trace(query=q, cluster=c, placement=p, labels=simulate(q, c, p, rng=gen.rng)))
        return out

    return corpus_cache(name, build)


def finetune_corpus() -> List:
    return chain_corpus("finetune_chains", FINETUNE_N, CORPUS_SEED + 5)


def stage_finetune(epochs: int):
    name = "finetune_throughput"
    if artifacts.exists("costream", name):
        print(f"[skip] {name}")
        return
    base_params, cfg = artifacts.load_cost_model("main_throughput")
    traces = finetune_corpus()
    ds = dataset_from_traces(traces, "throughput")
    tr, va, _ = split_dataset(ds, fractions=(0.9, 0.1, 0.0), seed=SPLIT_SEED)
    res = train_cost_model(
        tr,
        va,
        cfg,
        TrainConfig(epochs=epochs, batch_size=256, lr=3e-4, verbose=True),
        init_params=base_params,
    )
    artifacts.save_cost_model(name, res.params, cfg, extra={"finetuned_from": "main_throughput"})
    print(f"[done] {name}")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--stage", default="all", choices=["all", "main", "flat", "extrap", "ablations", "finetune"])
    ap.add_argument("--epochs", type=int, default=26)
    ap.add_argument("--extrap-epochs", type=int, default=12)
    ap.add_argument("--ablation-epochs", type=int, default=16)
    ap.add_argument("--finetune-epochs", type=int, default=8)
    args = ap.parse_args()

    t0 = time.time()
    if args.stage in ("all", "main"):
        stage_main(args.epochs)
    if args.stage in ("all", "flat"):
        stage_flat(args.epochs)
    if args.stage in ("all", "extrap"):
        stage_extrap(args.extrap_epochs)
    if args.stage in ("all", "ablations"):
        stage_ablations(args.ablation_epochs)
    if args.stage in ("all", "finetune"):
        stage_finetune(args.finetune_epochs)
    print(f"total {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()
