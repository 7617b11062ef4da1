"""Pallas kernel: fused slotted banked 2-layer MLP.

One program processes a tile of TB graphs: the whole (TB, N, F) node block
lives in VMEM together with all type-specific weight banks (they are tiny:
T <= 5, F <= 2*H, H <= 128 -> < 1 MiB), so both GEMM layers and the ReLU fuse
into a single VMEM-resident pass — the memory-bound alternative on small
graphs would round-trip HBM three times.

TPU sizing notes (v5e): VMEM 16 MiB. With TB = 128, N = 12, F = 128, fp32:
x tile 768 KiB, intermediate 384 KiB, out 384 KiB, weights < 1 MiB — well
under budget. The N x F panels are zero-padded to the (8, 128) fp32 tile by
Mosaic; matmul dims H1/H2 should be multiples of 128 for full MXU utilization
(the COSTREAM configs use H = 64: half-lane utilization, traded consciously —
the model is small and latency-bound, see DESIGN.md SS4).
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, out_ref, *, slot_ranges):
    x = x_ref[...]  # (TB, N, F)
    for t, start, stop in slot_ranges:
        xs = x[:, start:stop, :]  # (TB, S, F) static slice
        h = jnp.maximum(
            jax.lax.dot_general(
                xs,
                w1_ref[t],
                (((2,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            + b1_ref[t],
            0.0,
        )
        y = (
            jax.lax.dot_general(
                h,
                w2_ref[t],
                (((2,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            + b2_ref[t]
        )
        out_ref[:, start:stop, :] = y.astype(out_ref.dtype)


def banked_mlp_slotted_pallas(
    params,
    x: jax.Array,
    slot_ranges: Sequence[Tuple[int, int, int]],
    tile_b: int = 128,
    *,
    interpret: bool,
) -> jax.Array:
    """x: (B, N, F) -> (B, N, H2)."""
    l1, l2 = params["layers"]
    w1, b1 = l1["w"], l1["b"]  # (T,F,H1), (T,H1)
    w2, b2 = l2["w"], l2["b"]  # (T,H1,H2), (T,H2)
    B, N, F = x.shape
    H2 = w2.shape[-1]
    tb = min(tile_b, B)
    assert B % tb == 0, f"batch {B} not divisible by tile {tb}"

    grid = (B // tb,)
    return pl.pallas_call(
        functools.partial(_kernel, slot_ranges=tuple(slot_ranges)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tb, N, F), lambda i: (i, 0, 0)),
            pl.BlockSpec(w1.shape, lambda i: (0, 0, 0)),
            pl.BlockSpec(b1.shape, lambda i: (0, 0)),
            pl.BlockSpec(w2.shape, lambda i: (0, 0, 0)),
            pl.BlockSpec(b2.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tb, N, H2), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, N, H2), x.dtype),
        interpret=interpret,
    )(x, w1, b1, w2, b2)
