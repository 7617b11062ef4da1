"""Kernel microbenchmarks: Pallas (interpret on CPU) vs jnp oracle.

On this CPU container the meaningful wall-clock number is the ORACLE path
(interpret-mode Pallas executes the kernel body in Python per grid program);
the kernel timings are reported for completeness and the correctness deltas
prove the kernels compute the same function. Real-TPU numbers come from the
same harness with interpret=False.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import save_result
from repro.launch.compile_cache import enable_compile_cache
from repro import nn
from repro.core.graph import SLOT_RANGES
from repro.kernels.banked_mlp.ops import banked_mlp_slotted
from repro.kernels.banked_mlp.ref import banked_mlp_slotted_ref
from repro.kernels.mp_update.ops import mp_update
from repro.kernels.mp_update.ref import mp_update_ref
from repro.kernels.rglru.ops import linear_scan
from repro.kernels.rglru.ref import linear_scan_ref


def _time(fn, *args, iters=10):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def main():
    enable_compile_cache()
    rows = []
    # banked MLP
    p = nn.init_mlp_bank(jax.random.PRNGKey(0), 5, [39, 64, 64])
    x = jax.random.normal(jax.random.PRNGKey(1), (256, 12, 39))
    ref = jax.jit(lambda p, x: banked_mlp_slotted_ref(p, x, SLOT_RANGES))
    ker = jax.jit(lambda p, x: banked_mlp_slotted(p, x, SLOT_RANGES))
    err = float(jnp.abs(ref(p, x) - ker(p, x)).max())
    rows.append(("banked_mlp_ref_B256", _time(ref, p, x), f"maxerr={err:.2e}"))
    rows.append(("banked_mlp_pallas_interp_B256", _time(ker, p, x, iters=2), "interpret"))

    # mp_update
    H = 64
    pu = nn.init_mlp_bank(jax.random.PRNGKey(2), 5, [2 * H, H, H])
    h = jax.random.normal(jax.random.PRNGKey(3), (256, 12, H))
    a = (jax.random.uniform(jax.random.PRNGKey(4), (256, 12, 12)) > 0.8).astype(jnp.float32)
    depth = jax.random.randint(jax.random.PRNGKey(5), (256, 12), 0, 6)
    mask = jnp.ones((256, 12))
    d = jnp.asarray(2, jnp.int32)
    refu = jax.jit(lambda: mp_update_ref(pu, h, a, depth, mask, d, SLOT_RANGES))
    keru = jax.jit(lambda: mp_update(pu, h, a, depth, mask, d, SLOT_RANGES))
    err = float(jnp.abs(refu() - keru()).max())
    rows.append(("mp_update_ref_B256", _time(refu), f"maxerr={err:.2e}"))
    rows.append(("mp_update_pallas_interp_B256", _time(keru, iters=2), "interpret"))

    # rglru linear scan
    B, T, D = 4, 1024, 256
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    aa = jax.random.uniform(ks[0], (B, T, D), minval=0.8, maxval=0.999)
    bb = jax.random.normal(ks[1], (B, T, D)) * 0.1
    h0 = jax.random.normal(ks[2], (B, D))
    refs = jax.jit(lambda: linear_scan_ref(aa, bb, h0))
    kers = jax.jit(lambda: linear_scan(aa, bb, h0))
    err = float(jnp.abs(refs() - kers()).max())
    rows.append((f"rglru_ref_B{B}_T{T}_D{D}", _time(refs), f"maxerr={err:.2e}"))
    rows.append((f"rglru_pallas_interp_B{B}_T{T}_D{D}", _time(kers, iters=2), "interpret"))

    print("\n[kernels] name,us_per_call,derived")
    for name, us, extra in rows:
        print(f"{name},{us:.1f},{extra}")
    save_result("kernels_bench", [{"name": n, "us": u, "note": e} for n, u, e in rows])
    return rows


if __name__ == "__main__":
    main()
