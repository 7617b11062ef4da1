"""Run one benchmark cell once, on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The run makes its weights and traffic from
``--seed``, warms up, measures for ``--seconds``, checks the window's answers
against the plain reference, and prints one JSON line last on standard
output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device`` and, traced, ``breakdown``; ``compared`` last.
The numbers compared are also the last lines on standard error.  Without a
TPU, or with fewer chips than the cell asks for, it exits 2 and prints no
result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    # the program's persistent cache: <checkout>/.jax_cache unless the environment names one
    enable_compile_cache()
    from chipbench import bench

    try:
        cell = bench.Spec(ROOT).cell(args.workload)
        why = bench.main_check_device(int(cell["chips"]))
        if why is not None:
            print(f"chipbench: {why}; nothing run", file=sys.stderr)
            return 2
        result = bench.run_cell(args.workload, args.seed, args.seconds, bool(args.trace), T_START)
    except bench.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for line in bench.compared_lines(result):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
