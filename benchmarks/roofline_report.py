"""Aggregate the dry-run artifacts into the SRoofline table (deliverable (g))."""

from __future__ import annotations

import glob
import json
import os

from benchmarks.common import fmt_table, save_result
from repro.launch.compile_cache import enable_compile_cache
from repro.launch import artifacts


def load_cells(mesh: str = "single", tag: str = ""):
    cells = []
    for path in sorted(glob.glob(artifacts.path("dryrun", mesh + tag, "*.json"))):
        with open(path) as f:
            cells.append(json.load(f))
    return cells


def main(mesh: str = "single"):
    enable_compile_cache()
    cells = load_cells(mesh)
    rows = []
    for c in cells:
        if c["status"] == "skipped":
            rows.append({"arch": c["arch"], "shape": c["shape"], "bottleneck": "SKIP"})
            continue
        if c["status"] != "ok":
            rows.append({"arch": c["arch"], "shape": c["shape"], "bottleneck": "ERROR"})
            continue
        r = c["roofline"]
        rows.append(
            {
                "arch": c["arch"],
                "shape": c["shape"],
                "t_compute": f"{r['t_compute_s']:.2e}",
                "t_memory": f"{r['t_memory_s']:.2e}",
                "t_coll": f"{r['t_collective_s']:.2e}",
                "bottleneck": r["bottleneck"],
                "useful_flops": f"{r['useful_flops_ratio']:.2f}",
                "roofline_frac": f"{r['roofline_fraction']:.3f}",
                "temp_GB": f"{c['memory']['temp_size_in_bytes'] / 1e9:.1f}",
            }
        )
    print(f"\n[Roofline] mesh={mesh} ({len(rows)} cells)")
    print(
        fmt_table(
            rows,
            [
                "arch",
                "shape",
                "t_compute",
                "t_memory",
                "t_coll",
                "bottleneck",
                "useful_flops",
                "roofline_frac",
                "temp_GB",
            ],
        )
    )
    save_result(f"roofline_{mesh}", rows)
    return rows


if __name__ == "__main__":
    import sys

    main(sys.argv[1] if len(sys.argv) > 1 else "single")
