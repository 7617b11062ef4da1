"""Pallas kernel: one fused SOURCES->OPS message-passing depth step.

Fuses, for a tile of TB graphs held in VMEM:
  1. parent aggregation      msg = a_flow^T @ h           (per-graph matmul)
  2. feature concat          z = [h, msg]                 (register-level)
  3. banked 2-layer MLP      upd = MLP'_{T(v)}(z)         (slot-ranged GEMMs)
  4. depth select            h'  = where(depth == d, upd, h)

Unfused, steps 1-4 are five HBM round-trips of the (B, N, H) state per scan
iteration; fused they are one read + one write — this is the hot inner loop
of COSTREAM training (max_depth iterations per forward).

VMEM budget (v5e, fp32, TB=128, N=12, H=64): h 384 KiB, a_flow 576 KiB,
weights (T=5) ~ 1.2 MiB, intermediates < 1 MiB -> comfortably resident.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(h_ref, a_ref, depth_ref, mask_ref, d_ref, w1_ref, b1_ref, w2_ref, b2_ref, out_ref, *, slot_ranges, row_span, parent_rows):
    h = h_ref[...]  # (TB, N, H)
    s, e = row_span  # static rows eligible for this depth step
    p = parent_rows  # static bound: a_flow[u, v] == 0 for u >= p, v in [s, e)
    # 1. parent aggregation, only for eligible rows against possible parents:
    #    msg[b, v] = sum_{u < p} a[b, u, v] * h[b, u]  for v in [s, e)
    a = a_ref[:, :p, s:e]  # (TB, p, e-s) static slice
    msg = jax.lax.dot_general(
        a, h[:, :p], (((1,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )  # contract over u -> (TB, e-s, H)
    # 2. concat
    z = jnp.concatenate([h[:, s:e, :], msg], axis=-1)  # (TB, e-s, 2H)
    # 3. banked MLP over static slot ranges (absolute rows inside [s, e))
    outs = []
    for t, start, stop in slot_ranges:
        zs = z[:, start - s : stop - s, :]
        hid = jnp.maximum(
            jax.lax.dot_general(
                zs, w1_ref[t], (((2,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            + b1_ref[t],
            0.0,
        )
        outs.append(
            jax.lax.dot_general(
                hid, w2_ref[t], (((2,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            + b2_ref[t]
        )
    upd = jnp.concatenate(outs, axis=1)
    # 4. depth select inside the span; rows outside pass through untouched
    d = d_ref[0]
    sel = (depth_ref[:, s:e, :] == d) & (mask_ref[:, s:e, :] > 0)  # (TB, e-s, 1)
    out_ref[...] = h.astype(out_ref.dtype)
    out_ref[:, s:e, :] = jnp.where(sel, upd, h[:, s:e]).astype(out_ref.dtype)


def mp_update_pallas(
    params,
    h: jax.Array,  # (B, N, H)
    a_flow: jax.Array,  # (B, N, N)
    depth: jax.Array,  # (B, N) int32
    mask: jax.Array,  # (B, N) float32
    d: jax.Array,  # () int32
    slot_ranges: Sequence[Tuple[int, int, int]],
    tile_b: int = 128,
    *,
    interpret: bool,
    row_span: Tuple[int, int] = None,
    parent_rows: int = None,
) -> jax.Array:
    """``row_span=(s, e)`` statically restricts the update to rows [s, e):
    aggregation, MLP, and select all run at span width and rows outside pass
    through — the query-specialized placed path sorts slots by depth so each
    depth level is one contiguous span, skipping the provably-unselected rows'
    dense work.  ``None`` means the full row axis (the generic scan path,
    where the updated depth is dynamic).  ``parent_rows=p`` additionally
    promises ``a_flow[u, v] == 0`` for ``u >= p, v`` in the span (depth-major
    layouts: parents precede the level), shrinking the aggregation GEMM's
    contraction axis."""
    l1, l2 = params["layers"]
    w1, b1, w2, b2 = l1["w"], l1["b"], l2["w"], l2["b"]
    B, N, H = h.shape
    tb = min(tile_b, B)
    assert B % tb == 0
    span = (0, N) if row_span is None else (int(row_span[0]), int(row_span[1]))
    assert 0 <= span[0] < span[1] <= N, (span, N)
    # the per-range outputs are concatenated back over the span, so the ranges
    # must tile [s, e) exactly, in order
    edge = span[0]
    for t, start, stop in slot_ranges:
        assert start == edge and start < stop <= span[1], (
            f"slot ranges must tile row span {span} contiguously, got {slot_ranges}"
        )
        edge = stop
    assert edge == span[1], (slot_ranges, span)
    p = N if parent_rows is None else int(parent_rows)
    assert 0 < p <= N, (p, N)
    d_arr = jnp.asarray(d, jnp.int32).reshape((1,))
    return pl.pallas_call(
        functools.partial(
            _kernel, slot_ranges=tuple(slot_ranges), row_span=span, parent_rows=p
        ),
        grid=(B // tb,),
        in_specs=[
            pl.BlockSpec((tb, N, H), lambda i: (i, 0, 0)),
            pl.BlockSpec((tb, N, N), lambda i: (i, 0, 0)),
            pl.BlockSpec((tb, N, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((tb, N, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec(w1.shape, lambda i: (0, 0, 0)),
            pl.BlockSpec(b1.shape, lambda i: (0, 0)),
            pl.BlockSpec(w2.shape, lambda i: (0, 0, 0)),
            pl.BlockSpec(b2.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tb, N, H), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, N, H), h.dtype),
        interpret=interpret,
    )(h, a_flow, depth[..., None], mask[..., None], d_arr, w1, b1, w2, b2)
