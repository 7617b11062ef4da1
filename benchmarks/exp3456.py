"""[Exp 3-6] Generalization experiments.

Exp 3 (Table IV): interpolation — unseen-but-in-range hardware values.
Exp 4 (Table V):  extrapolation — models trained on restricted hardware
                  ranges, evaluated beyond them (stronger and weaker).
Exp 5 (Table VIa + Fig 11): unseen filter-chain query patterns + fine-tuning.
Exp 6 (Table VIb): unseen real-world benchmark queries.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmarks.common import eval_costream, eval_flat, fmt_table, save_result
from repro.launch.compile_cache import enable_compile_cache
from repro.core import ALL_METRICS, REGRESSION_METRICS
from repro.dsps import ranges
from repro.dsps.generator import GeneratorConfig, Trace, WorkloadGenerator
from repro.dsps.simulator import simulate
from repro.dsps.benchmarks import sample_benchmark_query
from repro.launch.train import CORPUS_SEED, chain_corpus, extrap_generator


def _rows_for(cs: Dict, fv: Dict) -> List[Dict]:
    rows = []
    for m in ALL_METRICS:
        if m in REGRESSION_METRICS:
            rows.append(
                {
                    "metric": m,
                    "costream_q50": round(cs[m].get("q50", float("nan")), 2),
                    "costream_q95": round(cs[m].get("q95", float("nan")), 2),
                    "flat_q50": round(fv[m].get("q50", float("nan")), 2) if fv else "",
                    "flat_q95": round(fv[m].get("q95", float("nan")), 2) if fv else "",
                }
            )
        else:
            rows.append(
                {
                    "metric": m,
                    "costream_q50": f"{100 * cs[m].get('accuracy', float('nan')):.1f}%",
                    "flat_q50": f"{100 * fv[m].get('accuracy', float('nan')):.1f}%" if fv else "",
                }
            )
    return rows


def exp3_interpolation(n: int = 400):
    interp = ranges.interpolation_ranges()
    cfg = GeneratorConfig().with_hardware(
        cpu=tuple(interp["CPU"]),
        ram_mb=tuple(interp["RAM_MB"]),
        bandwidth_mbps=tuple(interp["BANDWIDTH_MBPS"]),
        latency_ms=tuple(interp["LATENCY_MS"]),
    )
    gen = WorkloadGenerator(cfg, seed=CORPUS_SEED + 100)
    traces = gen.corpus(n, name_prefix="interp")
    cs = eval_costream(traces)
    fv = eval_flat(traces)
    rows = _rows_for(cs, fv)
    print(f"\n[Exp 3 / Table IV] interpolation: unseen in-range hardware (n={n})")
    print(fmt_table(rows, ["metric", "costream_q50", "costream_q95", "flat_q50", "flat_q95"]))
    save_result("exp3_tableIV", rows)
    return rows


def exp4_extrapolation(n: int = 250):
    spec = ranges.extrapolation_ranges()
    mapping = {
        "ram": ("ram_mb", "RAM_MB"),
        "cpu": ("cpu", "CPU"),
        "bandwidth": ("bandwidth_mbps", "BANDWIDTH_MBPS"),
        "latency": ("latency_ms", "LATENCY_MS"),
    }
    all_rows = {}
    for direction in ("stronger", "weaker"):
        rows = []
        for dim, (field, key) in mapping.items():
            # eval corpus: the restricted dim drawn from OUT-OF-RANGE values,
            # the other dims from the restricted training ranges
            gen_cfg = extrap_generator(direction, dim).with_hardware(
                **{field: tuple(spec[direction]["eval"][key])}
            )
            gen = WorkloadGenerator(gen_cfg, seed=CORPUS_SEED + 200 + hash((direction, dim)) % 97)
            traces = gen.corpus(n, name_prefix=f"x{dim}")
            cs = eval_costream(traces, prefix=f"extrap_{direction}_{dim}")
            row = {"dim": dim}
            for m in ALL_METRICS:
                if m in REGRESSION_METRICS:
                    row[f"{m}_q50"] = round(cs[m].get("q50", float("nan")), 2)
                else:
                    row[f"{m}_acc"] = f"{100 * cs[m].get('accuracy', float('nan')):.1f}%"
            rows.append(row)
        all_rows[direction] = rows
        print(f"\n[Exp 4 / Table V] extrapolation towards {direction} resources (n={n})")
        cols = ["dim"] + [
            f"{m}_q50" if m in REGRESSION_METRICS else f"{m}_acc" for m in ALL_METRICS
        ]
        print(fmt_table(rows, cols))
    save_result("exp4_tableV", all_rows)
    return all_rows


def exp5_unseen_patterns(n: int = 250):
    rows = []
    for ln in (2, 3, 4):
        traces = chain_corpus(f"eval_chain_{ln}", n, CORPUS_SEED + 300 + ln, chain_lengths=(ln,))
        cs = eval_costream(traces)
        fv = eval_flat(traces)
        rows.append(
            {
                "pattern": f"{ln}-filter-chain",
                "T_q50_cs": round(cs["throughput"].get("q50", float("nan")), 2),
                "T_q50_flat": round(fv["throughput"].get("q50", float("nan")), 2),
                "Le_q50_cs": round(cs["latency_e"].get("q50", float("nan")), 2),
                "Le_q50_flat": round(fv["latency_e"].get("q50", float("nan")), 2),
                "S_acc_cs": f"{100 * cs['success'].get('accuracy', float('nan')):.0f}%",
                "S_acc_flat": f"{100 * fv['success'].get('accuracy', float('nan')):.0f}%",
            }
        )
    print(f"\n[Exp 5a / Table VIa] unseen filter-chain patterns (n={n} each)")
    print(
        fmt_table(
            rows,
            ["pattern", "T_q50_cs", "T_q50_flat", "Le_q50_cs", "Le_q50_flat", "S_acc_cs", "S_acc_flat"],
        )
    )
    save_result("exp5a_tableVIa", rows)

    # Fig 11: fine-tuned throughput model
    rows_ft = []
    for ln in (2, 3, 4):
        traces = chain_corpus(f"eval_chain_{ln}", n, CORPUS_SEED + 300 + ln, chain_lengths=(ln,))
        before = eval_costream(traces, metrics=("throughput",))
        after = eval_costream(traces, metrics=("throughput",), prefix="finetune")
        rows_ft.append(
            {
                "pattern": f"{ln}-filter-chain",
                "before_q50": round(before["throughput"].get("q50", float("nan")), 2),
                "after_q50": round(after["throughput"].get("q50", float("nan")), 2),
            }
        )
    print("\n[Exp 5b / Fig 11] throughput q50 before/after fine-tuning")
    print(fmt_table(rows_ft, ["pattern", "before_q50", "after_q50"]))
    save_result("exp5b_fig11", rows_ft)
    return rows, rows_ft


def exp6_unseen_benchmarks(n: int = 100):
    gen = WorkloadGenerator(seed=CORPUS_SEED + 400)
    rng = np.random.default_rng(CORPUS_SEED + 401)
    rows = []
    for name in ("advertisement", "spike_detection", "smart_grid_global", "smart_grid_local"):
        traces = []
        for i in range(n):
            q = sample_benchmark_query(name, rng)
            c = gen.cluster()
            p = gen.placement(q, c)
            traces.append(Trace(query=q, cluster=c, placement=p, labels=simulate(q, c, p, rng=gen.rng)))
        cs = eval_costream(traces)
        fv = eval_flat(traces)
        rows.append(
            {
                "benchmark": name,
                "T_q50_cs": round(cs["throughput"].get("q50", float("nan")), 2),
                "T_q50_flat": round(fv["throughput"].get("q50", float("nan")), 2),
                "Lp_q50_cs": round(cs["latency_p"].get("q50", float("nan")), 2),
                "Lp_q50_flat": round(fv["latency_p"].get("q50", float("nan")), 2),
                "Ro_acc_cs": f"{100 * cs['backpressure'].get('accuracy', float('nan')):.0f}%",
                "S_acc_cs": f"{100 * cs['success'].get('accuracy', float('nan')):.0f}%",
            }
        )
    print(f"\n[Exp 6 / Table VIb] unseen real-world benchmarks (n={n} each)")
    print(
        fmt_table(
            rows,
            ["benchmark", "T_q50_cs", "T_q50_flat", "Lp_q50_cs", "Lp_q50_flat", "Ro_acc_cs", "S_acc_cs"],
        )
    )
    save_result("exp6_tableVIb", rows)
    return rows


def main():
    enable_compile_cache()
    exp3_interpolation()
    exp4_extrapolation()
    exp5_unseen_patterns()
    exp6_unseen_benchmarks()


if __name__ == "__main__":
    main()
