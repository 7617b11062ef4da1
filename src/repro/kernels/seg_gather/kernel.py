"""Pallas kernels: segment gather-sum and scatter-add as one-hot matmuls.

Gather/scatter have no native TPU lowering inside a kernel — but both are
SpMM-shaped, and the sparse operand is tiny (N = MAX_OPS rows): a one-hot
selection matrix built from a ``broadcasted_iota`` compare turns each into a
single batched ``dot_general`` that the MXU executes directly.

* ``gather_sum``:  out[b, r] = sum_p w[b,r,p] * h[b, idx[b,r,p]]
  The (idx, w) parent table collapses to a dense (R, N) weight matrix
  W[r, u] = sum_p [idx[r,p] == u] * w[r,p] — summing the one-hots over the
  P axis is exact because a row's parents are distinct — then out = W @ h.
* ``segment_sum``: out[b, s] = sum_{r: seg[b,r] == s} x[b, r]
  The one-hot transpose: out = onehot(seg)^T @ x.

Both tile the batch axis (``DispatchPolicy.seg_gather_tile`` caps the tile);
the gather's row axis is padded to a power of two by the wrapper so the
selection matmul hits MXU-friendly shapes, and the pad rows (zero weights)
are sliced back off.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _gather_kernel(h_ref, idx_ref, w_ref, out_ref):
    h = h_ref[...]  # (TB, N, H)
    idx = idx_ref[...]  # (TB, R, P) int32
    w = w_ref[...]  # (TB, R, P)
    n = h.shape[1]
    # one-hot selection: sel[b, r, p, u] = w[b, r, p] where idx[b, r, p] == u
    u = jax.lax.broadcasted_iota(jnp.int32, idx.shape + (n,), dimension=3)
    sel = jnp.where(idx[..., None] == u, w[..., None], 0.0)  # (TB, R, P, N)
    weights = sel.sum(axis=2)  # (TB, R, N): distinct parents -> exact
    out = jax.lax.dot_general(
        weights, h, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )  # (TB, R, H)
    out_ref[...] = out.astype(out_ref.dtype)


def _segment_kernel(x_ref, seg_ref, out_ref, *, n_seg):
    x = x_ref[...]  # (TB, N, H)
    seg = seg_ref[...]  # (TB, N) int32
    s = jax.lax.broadcasted_iota(jnp.int32, seg.shape + (n_seg,), dimension=2)
    onehot = (seg[..., None] == s).astype(x.dtype)  # (TB, N, S)
    out = jax.lax.dot_general(
        onehot, x, (((1,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )  # contract over rows -> (TB, S, H)
    out_ref[...] = out.astype(out_ref.dtype)


def gather_sum_pallas(
    h: jax.Array,  # (B, N, H)
    idx: jax.Array,  # (B, R, P) int
    w: jax.Array,  # (B, R, P)
    tile_b: int = 128,
    *,
    interpret: bool,
) -> jax.Array:
    B, N, H = h.shape
    _, R, P = idx.shape
    tb = min(tile_b, B)
    assert B % tb == 0
    r_pad = _pow2_at_least(R)
    if r_pad != R:  # pad rows carry zero weight: they gather h[:, 0] * 0
        pad = ((0, 0), (0, r_pad - R), (0, 0))
        idx = jnp.pad(idx, pad)
        w = jnp.pad(w, pad)
    out = pl.pallas_call(
        _gather_kernel,
        grid=(B // tb,),
        in_specs=[
            pl.BlockSpec((tb, N, H), lambda i: (i, 0, 0)),
            pl.BlockSpec((tb, r_pad, P), lambda i: (i, 0, 0)),
            pl.BlockSpec((tb, r_pad, P), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tb, r_pad, H), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, r_pad, H), h.dtype),
        interpret=interpret,
    )(h, idx.astype(jnp.int32), w)
    return out[:, :R] if r_pad != R else out


def segment_sum_pallas(
    x: jax.Array,  # (B, N, H)
    seg: jax.Array,  # (B, N) int
    n_seg: int,
    tile_b: int = 128,
    *,
    interpret: bool,
) -> jax.Array:
    B, N, H = x.shape
    tb = min(tile_b, B)
    assert B % tb == 0
    return pl.pallas_call(
        functools.partial(_segment_kernel, n_seg=int(n_seg)),
        grid=(B // tb,),
        in_specs=[
            pl.BlockSpec((tb, N, H), lambda i: (i, 0, 0)),
            pl.BlockSpec((tb, N), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tb, n_seg, H), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, int(n_seg), H), x.dtype),
        interpret=interpret,
    )(x, seg.astype(jnp.int32))
