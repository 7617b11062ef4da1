"""The program's own spans read off a traced run: the per-layer readings of
``chipbench/metrics/{submit,worker,featurize,dispatch,fetch,finalize}_ms.py``,
the drain worker's time by span, and the requests the service popped per
second inside the traced sub-window.

    python3 chipbench/span_readings.py --workload synth-initial --seconds 20 \
        --seeds 2147483911 2147483923

For each seed, one ``--trace 1`` run of the cell (set-up, warm-in, window,
as ``run.py`` makes it) in this one process, with ``spans.read`` in place of
``trace.read`` and the six span readers added to the cell's traced metrics.
Prints one JSON line per seed.  ``pops_per_s`` reads the service's own
counter, so it is there for a program without spans too: run this file from
a checkout of another commit with this file and ``spans.py`` laid over it,
on the same seeds, to compare the cost of the spans with a profiler
recording.  Not part of the benchmark's own runs.  Needs a TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
from contextlib import ExitStack
from pathlib import Path
from types import SimpleNamespace
from typing import Dict
from unittest import mock

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

#: The span readers, by the names their metrics would take in ``synth-initial``.
READERS = ["submit_ms.initial", "worker_ms.initial", "featurize_ms.initial",
           "dispatch_ms.initial", "fetch_ms.initial", "finalize_ms.initial"]


def readings(workload: str, seed: int, seconds: float, **run_cell_kw) -> Dict:
    """One traced run of ``workload`` with the program spans read."""
    from chipbench import bench
    from chipbench import spans as P
    from chipbench import trace as T

    kept: Dict = {"deltas": []}

    def read(path, *a, **k):
        kept["trace"] = P.read(path, *a, **k)
        return kept["trace"]

    spec_metrics, stats_delta = bench.Spec.metrics, bench._delta

    def delta(a, b):
        kept["deltas"].append(stats_delta(a, b))
        return kept["deltas"][-1]

    def metrics(self, cell, kind):
        out = spec_metrics(self, cell, kind)
        return out + [{"name": n, "unit": "ms"} for n in READERS] if kind == "per_layer" else out

    with ExitStack() as stack:
        stack.enter_context(mock.patch.object(bench, "T", SimpleNamespace(
            read=read, find=T.find, WINDOW_SPAN=T.WINDOW_SPAN)))
        stack.enter_context(mock.patch.object(bench.Spec, "metrics", metrics))
        stack.enter_context(mock.patch.object(bench, "_delta", delta))
        r = bench.run_cell(workload, seed, seconds, True, log=lambda *a, **k: None, **run_cell_kw)
    out = {"workload": workload, "seed": seed, "correct": r["correct"],
           "readings": {n: r["metrics"][n]["value"] for n in READERS if n in r["metrics"]},
           "device": r["device"], "breakdown": r.get("breakdown")}
    tr = kept.get("trace")
    if tr is None or len(kept["deltas"]) < 2:
        return out
    n = kept["deltas"][-1]["n_drained"]  # the traced sub-window's; the first is the window's
    out["traced"] = {"n_drained": n, "window_s": tr.window_s(), "busy_s": tr.busy_s(),
                     "pops_per_s": n / tr.window_s() if tr.window_s() > 0 else None}
    names = sorted({s.name for s in tr.program_spans})
    if n and names:
        out["span_ms_per_req"] = {nm: 1e3 * tr.span_seconds([nm]) / n for nm in names}
        out["self_ms_per_req"] = {nm: 1e3 * tr.span_seconds([nm], True) / n for nm in names}
        out["span_counts"] = {nm: sum(s.name == nm for s in tr.program_spans) for nm in names}
        launches = [s for s in tr.program_spans if s.name == "costream.launch"]
        out["launch_paths"] = sorted({str(s.stats.get("path")) for s in launches})
        out["req_per_launch"] = sum(s.stats.get("n_req", 0) for s in launches) / max(1, len(launches))
        line = tr.worker_line()
        if line is not None:
            out["worker_shares"] = tr.line_shares(line)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from chipbench import bench

    why = bench.main_check_device(1)
    if why is not None:
        print(f"span_readings: {why}", file=sys.stderr)
        return 2
    for seed in args.seeds:
        print(json.dumps(readings(args.workload, seed, args.seconds), default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
