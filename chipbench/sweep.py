"""The one-off knee sweep of an open-loop cell, in one process with one set-up.

    python3 chipbench/sweep.py --workload synth-refine --seed 7 --seconds 10 \
        --rates 100 200 400 800 1600

Warms the cell's service once, then offers each rate (ascending) for
``--seconds`` and prints one JSON line per rate: p50/p99 latency, the
driver's lag, whether the backlog grew (the median latency of the last
quarter of the window's requests against the first quarter's), and failures.
The knee is the highest rate, below the first that fails, whose backlog does
not grow and whose p99 stays within ``--p99-limit-ms`` (the limit written,
with its reason, in the traffic mix's file); the last line gives it and the
0.8 of it that the cell offers.  Needs a TPU, like ``run.py``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def point(sess, rate: float, seconds: float, rng) -> dict:
    import numpy as np

    w = sess.drive(seconds, rng, rate=rate)
    rec = sess.record(w, seconds, rng)
    lat = rec.lat
    q = max(1, len(lat) // 4)
    first, last = float(np.median(lat[:q])), float(np.median(lat[-q:]))
    return {
        "rate_rps": rate,
        "requests": rec.attempted,
        "failed": rec.failed,
        "p50_ms": float(np.percentile(lat, 50)) * 1e3,
        "p99_ms": float(np.percentile(lat, 99)) * 1e3,
        "lag_p99_ms": float(np.percentile(rec.lag, 99)) * 1e3,
        "first_quarter_p50_ms": first * 1e3,
        "last_quarter_p50_ms": last * 1e3,
        "backlog_grows": bool(last > 2.0 * first + 0.005),
        "drains": w.stats["n_batches"],
        "merged_share": 100.0 * w.stats["n_cross_query"] / max(1, w.stats["n_drained"]),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--p99-limit-ms", type=float, required=True,
                    help="the cell's latency limit: a rate whose p99 exceeds it is past the knee")
    args = ap.parse_args(argv)

    import numpy as np

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from chipbench import bench
    from repro.serve.policy import use_policy

    why = bench.main_check_device(1)
    if why is not None:
        print(f"sweep: {why}", file=sys.stderr)
        return 2
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    sess = bench.Session(args.workload, args.seed)
    with use_policy(sess.policy):
        sess.warm_in(rng, rate=min(args.rates))
        print(json.dumps({"setup_s": time.perf_counter() - t0}), flush=True)
        knee = None
        for rate in sorted(args.rates):
            p = point(sess, rate, args.seconds, rng)
            print(json.dumps(p), flush=True)
            if p["failed"] or p["backlog_grows"] or p["p99_ms"] > args.p99_limit_ms:
                break
            knee = rate
        sess.close()
    print(json.dumps({"knee_rps": knee, "p99_limit_ms": args.p99_limit_ms,
                      "rate_rps": None if knee is None else 0.8 * knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
