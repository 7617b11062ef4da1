"""Pallas kernel: chunked gated linear recurrence (RG-LRU state update).

    h_t = a_t * h_{t-1} + b_t        a, b: (B, T, D)

TPU adaptation of the GPU "parallel scan over warps" formulation: the TPU has
no shuffle-based scan, but its grid is executed *sequentially* per core, so we
tile T into chunks and carry the running state h in a VMEM scratch buffer
across grid steps (grid = (B/TB, T/TT), T innermost). Within a chunk the
recurrence is a short fori_loop over TT VMEM-resident (TB, D)-vector steps —
VPU work with zero HBM traffic until the chunk's outputs are flushed once.

For long-context decode (the 500k cells) this streams a/b exactly once from
HBM -> the kernel is purely bandwidth-bound, which is the roofline optimum
for this op (arithmetic intensity ~ 2 FLOP / 12 bytes).

VMEM sizing (v5e, 16 MiB, fp32): a/b/out tiles are (TB, TT, D); with TB=4,
TT=128, D=2560 that is 3 x 5 MiB + carry 40 KiB — in budget; callers shrink
tiles for wider D.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, h0_ref, out_ref, h_carry, *, tt: int):
    t_idx = pl.program_id(1)

    # initialize the carry at the first chunk of every batch tile
    @pl.when(t_idx == 0)
    def _():
        h_carry[...] = h0_ref[...].astype(h_carry.dtype)

    a = a_ref[...].astype(jnp.float32)  # (TB, TT, D)
    b = b_ref[...].astype(jnp.float32)
    h = h_carry[...]  # (TB, D) fp32

    def step(i, carry):
        h, out = carry
        h = a[:, i, :] * h + b[:, i, :]
        out = jax.lax.dynamic_update_index_in_dim(out, h, i, axis=1)
        return h, out

    out0 = jnp.zeros(a.shape, jnp.float32)
    h, out = jax.lax.fori_loop(0, tt, step, (h, out0))
    out_ref[...] = out.astype(out_ref.dtype)
    h_carry[...] = h


def linear_scan_pallas(
    a: jax.Array,
    b: jax.Array,
    h0: jax.Array,
    tile_b: int = 4,
    tile_t: int = 128,
    *,
    interpret: bool,
) -> jax.Array:
    B, T, D = a.shape
    tb, tt = min(tile_b, B), min(tile_t, T)
    assert B % tb == 0 and T % tt == 0, (B, T, tb, tt)
    grid = (B // tb, T // tt)  # T innermost: chunks run in carry order
    return pl.pallas_call(
        functools.partial(_kernel, tt=tt),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tb, tt, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((tb, tt, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((tb, D), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tb, tt, D), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, D), a.dtype),
        scratch_shapes=[pltpu.VMEM((tb, D), jnp.float32)],
        interpret=interpret,
    )(a, b, h0)
