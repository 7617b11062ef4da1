"""Readings that the limits of ``correct`` are set from: the program's and
the lower-precision control's, on the same answers of the timed path.

    python3 chipbench/limits.py --workload synth-initial --seconds 30 --seeds 1 2 3

For each seed, one run of the cell (set-up, warm-in, window, as ``run.py``
makes it) in this one process, with the control put in the program's place
(``run_cell(control=True)``): the window's compared answers are checked
against the float32 reference, then the control's answers -- the reference
computed in bfloat16 on the chip -- on the same requests take their place
and are judged by the cell's own limits.  Prints one JSON line per seed: the
program's readings, the control's, and the control's ``correct`` and
``compared``, which the limits must make false.  Not part of the
benchmark's own runs.  Needs a TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from chipbench import bench

    why = bench.main_check_device(1)
    if why is not None:
        print(f"limits: {why}", file=sys.stderr)
        return 2
    for seed in args.seeds:
        r = bench.run_cell(args.workload, seed, args.seconds, control=True, log=lambda *a, **k: None)
        cal = r.pop("calibration")
        cal.pop("trace")
        print(json.dumps({"workload": args.workload, "seed": seed, "metrics": r["metrics"],
                          "device": r["device"], **cal, "control_correct": r["correct"],
                          "control_compared": r["compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
