"""[Exp 7] Ablations.

7a (Fig. 12): featurization — (1) operators only, (2) + placement structure
without hardware features, (3) full joint graph; L_e q-errors.
7b (Fig. 13): traditional symmetric message passing vs. the paper's 3-stage
scheme; regression q-errors.
"""

from __future__ import annotations

from benchmarks.common import eval_costream, fmt_table, save_result, test_split_traces
from repro.launch.compile_cache import enable_compile_cache
from repro.core import REGRESSION_METRICS
from repro.core.graph import drop_hardware, drop_hw_features


def exp7a():
    traces = test_split_traces()
    from repro.launch import artifacts as A

    # equal-budget "full" model if it exists, else the main 20-epoch model
    full_prefix = "ablate_full" if A.exists("costream", "ablate_full_latency_e") else "main"
    variants = [
        ("ops only (no hw nodes)", "ablate_no_hw_nodes", drop_hardware),
        ("+ placement, no hw feats", "ablate_no_hw_feats", drop_hw_features),
        ("full featurization", full_prefix, None),
    ]
    rows = []
    for label, prefix, transform in variants:
        r = eval_costream(traces, metrics=("latency_e",), prefix=prefix, transform=transform)
        rows.append(
            {
                "featurization": label,
                "Le_q50": round(r["latency_e"].get("q50", float("nan")), 2),
                "Le_q95": round(r["latency_e"].get("q95", float("nan")), 2),
            }
        )
    print("\n[Exp 7a / Fig 12] featurization ablation (L_e)")
    print(fmt_table(rows, ["featurization", "Le_q50", "Le_q95"]))
    save_result("exp7a_fig12", rows)
    return rows


def exp7b():
    traces = test_split_traces()
    rows = []
    for m in REGRESSION_METRICS:
        ours = eval_costream(traces, metrics=(m,), prefix="main")
        trad = eval_costream(traces, metrics=(m,), prefix="ablate_traditional")
        rows.append(
            {
                "metric": m,
                "ours_q50": round(ours[m].get("q50", float("nan")), 2),
                "traditional_q50": round(trad[m].get("q50", float("nan")), 2),
            }
        )
    print("\n[Exp 7b / Fig 13] message-passing scheme ablation")
    print(fmt_table(rows, ["metric", "ours_q50", "traditional_q50"]))
    save_result("exp7b_fig13", rows)
    return rows


def main():
    enable_compile_cache()
    exp7a()
    exp7b()


if __name__ == "__main__":
    main()
