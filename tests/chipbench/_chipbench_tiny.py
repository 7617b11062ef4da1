"""Shared tiny sizes for the benchmark's CPU tests (not a test module)."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

#: A made-up row of the peak table for the CPU, which has none.
CPU_PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}

OPEN = {"pool": 3, "warm_cands": 64, "rate_rps": 20, "warm_in_s": 0.5, "check_requests": 10_000}
CLOSED = {"pool": 2, "cands": [32, 32], "cand_bank": 256, "callers": 2, "warm_in_s": 0.5,
          "check_share": 0.5, "check_rows": 16}


def tiny(workload: str) -> dict:
    return dict(CLOSED if workload == "synth-initial" else OPEN)
