"""Host-side data pipeline: trace corpus -> bucketed depth-major graph batches.

Features are materialized once (numpy); an epoch iterator then yields
device-ready jnp batches.  Padding policy is shared with the placement
scorer via ``core/bucketing.py``; a background prefetch thread
(``prefetch``) overlaps host featurization + device transfer with compute.

The training iterator is **bucketed by (n_ops, depth)** (``bucket_dataset``
/ ``bucketed_batches``): graphs of one bucket share a static
``graph.BatchBanding`` stage-3 plan, so the jitted train step compiles once
per bucket and each step runs only the bucket's non-empty depth levels at
their banded row spans, instead of MAX_DEPTH full-width sweeps.
"""

from __future__ import annotations

import queue
import threading
from dataclasses import dataclass
from itertools import groupby
from typing import Iterator, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bucketing import batch_banding_cached, exact_banding_cached
from repro.core.graph import (
    BatchBanding,
    JointGraph,
    batch_graphs,
    build_graph,
)
from repro.core.model import label_array
from repro.dsps.generator import Trace


@dataclass
class GraphDataset:
    graphs: JointGraph  # batched numpy arrays, leading dim = N
    labels: np.ndarray  # (N,) for the selected metric

    def __len__(self) -> int:
        return int(self.graphs.op_x.shape[0])

    def select(self, idx: Union[np.ndarray, slice]) -> "GraphDataset":
        """Row subset.  A ``slice`` (or a contiguous, step-1 index vector) is
        applied as a numpy view — zero copies of the eight graph fields — the
        epoch-shuffling hot path re-slices buckets every epoch and fancy
        indexing re-materialized the whole ``JointGraph`` each time."""
        if not isinstance(idx, slice):
            idx = np.asarray(idx)
            # guards: a boolean mask can compare element-equal to an arange
            # (True == 1) but means something else, and a negative start
            # would turn into a slice crossing the end of the array
            if (
                idx.ndim == 1
                and idx.size
                and idx.dtype != np.bool_
                and int(idx[0]) >= 0
                and np.array_equal(idx, np.arange(int(idx[0]), int(idx[0]) + idx.size))
            ):
                idx = slice(int(idx[0]), int(idx[0]) + idx.size)
        g = JointGraph(*[getattr(self.graphs, f)[idx] for f in JointGraph._fields])
        return GraphDataset(graphs=g, labels=self.labels[idx])


def dataset_from_traces(
    traces: List[Trace], metric: str, transform=None
) -> GraphDataset:
    singles = [build_graph(t.query, t.cluster, t.placement) for t in traces]
    if transform is not None:
        singles = [transform(g) for g in singles]
    return GraphDataset(graphs=batch_graphs(singles), labels=label_array(traces, metric))


def split_indices(
    n: int, fractions: Tuple[float, float, float] = (0.8, 0.1, 0.1), seed: int = 0
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Deterministic train/val/test index split (paper: 80/10/10).

    The permutation is derived from the raw PCG64 bit stream
    (``np.random.PCG64(seed).random_raw``) — the one stream numpy's
    compatibility policy (NEP 19) pins across releases.  ``Generator``
    distribution methods like ``permutation`` are explicitly allowed to
    change between versions, which would silently re-partition the corpus on
    an upgrade; argsort of the raw draws carries the bit stream's guarantee
    (a regression test pins the exact indices).  The single source of truth
    for split membership: reuse it wherever a sibling pipeline (e.g. the
    flat-vector baseline) must see the same trace partition.
    """
    perm = np.argsort(np.random.PCG64(seed).random_raw(n), kind="stable")
    n_tr = int(fractions[0] * n)
    n_va = int(fractions[1] * n)
    return perm[:n_tr], perm[n_tr : n_tr + n_va], perm[n_tr + n_va :]


def split_dataset(
    ds: GraphDataset, fractions: Tuple[float, float, float] = (0.8, 0.1, 0.1), seed: int = 0
) -> Tuple[GraphDataset, GraphDataset, GraphDataset]:
    """train/val/test split (paper: 80/10/10); see ``split_indices``."""
    tr, va, te = split_indices(len(ds), fractions, seed)
    return ds.select(tr), ds.select(va), ds.select(te)


def batches(
    ds: GraphDataset,
    batch_size: int,
    rng: Optional[np.random.Generator] = None,
    drop_remainder: bool = False,
) -> Iterator[Tuple[JointGraph, np.ndarray]]:
    """Plain (un-bucketed) epoch iterator; kept for eval and simple callers."""
    n = len(ds)
    order = rng.permutation(n) if rng is not None else np.arange(n)
    for start in range(0, n, batch_size):
        idx = order[start : start + batch_size]
        if drop_remainder and idx.size < batch_size:
            return
        if idx.size < batch_size:
            # pad by repeating (mask via weights is unnecessary: eval uses
            # unpadded path; training tolerates duplicate samples in the tail)
            idx = np.concatenate([idx, order[: batch_size - idx.size]])
        sub = ds.select(idx)
        yield sub.graphs, sub.labels


# -- (n_ops, depth)-bucketed iteration (the training fast path) -----------------


@dataclass(frozen=True)
class BucketSpec:
    """One bucket: a contiguous row range of the resorted dataset plus its
    static stage-3 banding (shared by every batch drawn from the bucket — the
    jit cache key).  Conservative buckets group by (n_ops, depth); exact
    buckets group by the full per-row (type, depth) signature."""

    n_ops: int
    depth: int
    start: int
    stop: int
    banding: BatchBanding

    def __len__(self) -> int:
        return self.stop - self.start


def bucket_dataset(
    ds: GraphDataset, exact: bool = False
) -> Tuple[GraphDataset, Tuple[BucketSpec, ...]]:
    """Sort the dataset into banding buckets and describe them.

    Returns the resorted dataset (one fancy-index pass — per-epoch work then
    selects contiguous views) and one ``BucketSpec`` per bucket.

    ``exact=False`` (default): stable-sort by (depth, n_ops), one bucket per
    distinct (n_ops, depth) key.  Same-depth buckets share one conservative
    banding, computed over the whole contiguous depth class: measured on CPU,
    its wider spans cost nothing against the dominant win (scanning ``depth``
    levels instead of MAX_DEPTH), while the jitted step then compiles once
    per *depth class* (~4 traces per corpus) instead of once per
    (n_ops, depth) pair (~16).  Every sub-batch of the class — padding
    included — is covered by the shared plan.

    ``exact=True``: one bucket per distinct per-row (type, depth)
    *signature* (``bucketing.batch_signature``), each carrying its
    signature-exact row-trimmed banding — stage work proportional to real
    rows, at the cost of one trace per signature (more traces only where
    signatures actually differ) and per-signature epoch tails.  The right
    trade for large fixed corpora (``launch/train.py``) where every
    signature class is much larger than a batch.

    Either way the bandings come from the signature-keyed cache, so repeated
    bucketing of views over one corpus (train/val splits, re-bucketing per
    stage) never recomputes a plan.
    """
    if not len(ds):
        return ds, ()
    mask = np.asarray(ds.graphs.op_mask) > 0
    n_ops = mask.sum(axis=-1).astype(np.int64)
    depth = (np.asarray(ds.graphs.op_depth) * mask).max(axis=-1).astype(np.int64)
    if exact:
        sig = np.where(mask, np.asarray(ds.graphs.op_depth), -1).astype(np.int64)
        _, inverse = np.unique(sig, axis=0, return_inverse=True)
        # secondary keys keep signature classes inside depth-major order
        order = np.lexsort((inverse, n_ops, depth))
        class_of = inverse[order]
    else:
        # depth-primary so buckets sharing a banding (= a depth class) stay
        # contiguous: bucketed_batches draws batches per banding group
        order = np.lexsort((n_ops, depth))
        class_of = None
    ds = ds.select(order)
    n_ops, depth = n_ops[order], depth[order]
    if exact:
        bounds = np.flatnonzero(np.diff(class_of) != 0)
    else:
        bounds = np.flatnonzero((np.diff(n_ops) != 0) | (np.diff(depth) != 0))
        shared = {}
        for d in np.unique(depth):
            rows = np.flatnonzero(depth == d)  # contiguous after the sort
            shared[int(d)] = _class_banding(ds, int(rows[0]), int(rows[-1]) + 1, exact=False)
    starts = np.concatenate([[0], bounds + 1])
    stops = np.concatenate([bounds + 1, [len(ds)]])
    buckets = tuple(
        BucketSpec(
            n_ops=int(n_ops[a]),
            depth=int(depth[a]),
            start=int(a),
            stop=int(b),
            banding=(
                _class_banding(ds, int(a), int(b), exact=True)
                if exact
                else shared[int(depth[a])]
            ),
        )
        for a, b in zip(starts, stops)
    )
    return ds, buckets


def _class_banding(ds: GraphDataset, start: int, stop: int, exact: bool) -> BatchBanding:
    """Banding for one contiguous class, via the signature-keyed cache.

    Both flavors key on ``bucketing.batch_signature`` — a banding is a pure
    function of the signature set — so zero-copy views over the same corpus
    rows (train/val splits, repeated ``bucket_dataset`` calls, merged serving
    chunks) reuse one cached plan instead of recomputing per view.
    """
    g = ds.select(slice(start, stop)).graphs
    return exact_banding_cached(g) if exact else batch_banding_cached(g)


def _banding_groups(buckets: Sequence[BucketSpec]):
    """Consecutive buckets sharing a banding (one group per depth class)."""
    return [
        (banding, list(group))
        for banding, group in groupby(buckets, key=lambda b: b.banding)
    ]


def bucketed_batches(
    ds: GraphDataset,
    buckets: Sequence[BucketSpec],
    batch_size: int,
    rng: Optional[np.random.Generator] = None,
    device: bool = False,
) -> Iterator[Tuple[JointGraph, np.ndarray, BatchBanding]]:
    """Depth-major epoch iterator over a ``bucket_dataset`` result.

    Yields ``(graphs, labels, banding)`` with every batch drawn from a single
    *banding group* (the contiguous buckets of one depth class — they share
    the static plan AND the padded batch shape, so mixing them in a batch is
    free).  Only each group's single epoch tail is padded to ``batch_size``,
    by wrapping the group's own (shuffled) order — the seed iterator's
    policy, applied per group: at most ``batch_size - 1`` duplicate samples
    per group per epoch.  Padding per-bucket tails instead would over-weight
    rare (n_ops, depth) shapes by up to batch_size/len(bucket) in the summed
    loss.  ``rng`` shuffles within buckets and interleaves the batch order
    across groups.  ``device=True`` converts to device arrays inside the
    iterator — under ``prefetch`` the transfer then runs on the worker
    thread, overlapped with the previous step's compute.
    """
    plan = []
    for banding, group in _banding_groups(buckets):
        parts = []
        for b in group:
            part = np.arange(b.start, b.stop)
            parts.append(rng.permutation(part) if rng is not None else part)
        idx = np.concatenate(parts)
        for s in range(0, len(idx), batch_size):
            take = idx[s : s + batch_size]
            if take.size < batch_size:  # wrap the group's order, like the seed
                take = np.concatenate([take, np.resize(idx, batch_size - take.size)])
            plan.append((take, banding))
    if rng is not None:
        plan = [plan[i] for i in rng.permutation(len(plan))]
    for take, banding in plan:
        sub = ds.select(take)
        g, y = sub.graphs, sub.labels
        if device:
            g = jax.tree_util.tree_map(jnp.asarray, g)
            y = jnp.asarray(y)
        yield g, y, banding


def n_batches(buckets: Sequence[BucketSpec], batch_size: int) -> int:
    """Steps per epoch of ``bucketed_batches`` (for LR schedules)."""
    return sum(
        -(-sum(len(b) for b in group) // batch_size)
        for _, group in _banding_groups(buckets)
    )


def prefetch(it: Iterator, size: int = 2) -> Iterator:
    """Background-thread prefetch (overlaps host prep with device compute)."""
    q: queue.Queue = queue.Queue(maxsize=size)
    stop = object()
    closed = threading.Event()

    def worker():
        try:
            for item in it:
                if closed.is_set():
                    return
                q.put(item)
        finally:
            q.put(stop)

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    try:
        while True:
            item = q.get()
            if item is stop:
                return
            yield item
    finally:
        # a consumer that stops early (``TrainConfig.max_steps``) must not
        # leave the worker blocked on a full queue: drain until it exits
        closed.set()
        while t.is_alive():
            try:
                q.get(timeout=0.1)
            except queue.Empty:
                pass
