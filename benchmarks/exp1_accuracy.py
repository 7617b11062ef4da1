"""[Exp 1] General prediction accuracy (paper Table III, Fig. 7, Fig. 8).

Overall q-errors/accuracy on the held-out test split, COSTREAM vs. the flat
vector baseline; then grouped by hardware feature buckets (Fig. 7) and by
query type (Fig. 8).
"""

from __future__ import annotations

import numpy as np

from benchmarks.common import (
    eval_costream,
    eval_flat,
    fmt_table,
    save_result,
    test_split_traces,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.core import ALL_METRICS, REGRESSION_METRICS
from repro.dsps.query import OpType


def table3():
    traces = test_split_traces()
    cs = eval_costream(traces)
    fv = eval_flat(traces)
    rows = []
    for m in ALL_METRICS:
        if m in REGRESSION_METRICS:
            rows.append(
                {
                    "metric": m,
                    "costream_q50": round(cs[m].get("q50", float("nan")), 2),
                    "costream_q95": round(cs[m].get("q95", float("nan")), 2),
                    "flat_q50": round(fv[m].get("q50", float("nan")), 2),
                    "flat_q95": round(fv[m].get("q95", float("nan")), 2),
                }
            )
        else:
            rows.append(
                {
                    "metric": m,
                    "costream_q50": f"{100 * cs[m].get('accuracy', float('nan')):.1f}%",
                    "costream_q95": "",
                    "flat_q50": f"{100 * fv[m].get('accuracy', float('nan')):.1f}%",
                    "flat_q95": "",
                }
            )
    print("\n[Exp 1 / Table III] overall test set (n=%d)" % len(traces))
    print(fmt_table(rows, ["metric", "costream_q50", "costream_q95", "flat_q50", "flat_q95"]))
    save_result("exp1_table3", {"rows": rows, "n": len(traces)})
    return rows


def fig7_hardware_buckets(n_buckets: int = 4):
    traces = test_split_traces()
    feats = {
        "cpu": lambda t: np.mean([n.cpu for n in t.cluster.nodes]),
        "ram": lambda t: np.mean([n.ram_mb for n in t.cluster.nodes]),
        "bandwidth": lambda t: np.mean([n.bandwidth_mbps for n in t.cluster.nodes]),
        "latency": lambda t: np.mean([n.latency_ms for n in t.cluster.nodes]),
    }
    out = {}
    for fname, fn in feats.items():
        vals = np.array([fn(t) for t in traces])
        edges = np.quantile(vals, np.linspace(0, 1, n_buckets + 1))
        rows = []
        for b in range(n_buckets):
            sel = (vals >= edges[b]) & (vals <= edges[b + 1])
            sub = [t for t, s in zip(traces, sel) if s]
            if len(sub) < 20:
                continue
            r = eval_costream(sub, metrics=("latency_e", "backpressure"))
            rows.append(
                {
                    "bucket": f"[{edges[b]:.0f},{edges[b + 1]:.0f}]",
                    "n": len(sub),
                    "latency_e_q50": round(r["latency_e"].get("q50", float("nan")), 2),
                    "bp_acc": f"{100 * r['backpressure'].get('accuracy', float('nan')):.1f}%",
                }
            )
        out[fname] = rows
        print(f"\n[Exp 1 / Fig 7] grouped by mean {fname}")
        print(fmt_table(rows, ["bucket", "n", "latency_e_q50", "bp_acc"]))
    save_result("exp1_fig7", out)
    return out


def fig8_query_types():
    traces = test_split_traces()
    kinds = {
        "linear": lambda q: q.count(OpType.JOIN) == 0,
        "2-way-join": lambda q: q.count(OpType.JOIN) == 1,
        "3-way-join": lambda q: q.count(OpType.JOIN) == 2,
    }
    rows = []
    for name, sel in kinds.items():
        sub = [t for t in traces if sel(t.query)]
        r = eval_costream(sub)
        rows.append(
            {
                "type": name,
                "n": len(sub),
                "T_q50": round(r["throughput"].get("q50", float("nan")), 2),
                "Lp_q50": round(r["latency_p"].get("q50", float("nan")), 2),
                "Le_q50": round(r["latency_e"].get("q50", float("nan")), 2),
                "S_acc": f"{100 * r['success'].get('accuracy', float('nan')):.1f}%",
                "Ro_acc": f"{100 * r['backpressure'].get('accuracy', float('nan')):.1f}%",
            }
        )
    print("\n[Exp 1 / Fig 8] grouped by query type")
    print(fmt_table(rows, ["type", "n", "T_q50", "Lp_q50", "Le_q50", "S_acc", "Ro_acc"]))
    save_result("exp1_fig8", rows)
    return rows


def main():
    enable_compile_cache()
    table3()
    fig7_hardware_buckets()
    fig8_query_types()


if __name__ == "__main__":
    main()
