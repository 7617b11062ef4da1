"""``CostEstimator``: the single inference facade over trained cost models.

One object answers every online query the paper's deployed model serves —
generic cost estimation for placed queries (``estimate``), candidate-placement
scoring (``score``), and full placement search (``optimize``) — constructed
from an in-memory model dict or a ``CostModelBundle``.  It owns all
serving-side state that used to be scattered across ``PlacementOptimizer``
and module-level dicts in ``core/model.py``:

* the per-(query, cluster) **skeleton LRU**: the featurized skeleton, its
  device transfer, and the trace-time ``QueryStatic``, shared by every
  ``score``/``optimize`` call on the same pair (the online-monitoring pattern
  re-scores one query every round);
* the per-metrics-tuple **stacked-ensemble cache**
  (``model.stack_metric_models``): all requested metrics ride ONE fused
  forward when their GNN configs are shape-identical;
* the **jitted-forward trace caches**.  These live at module level here
  (moved from ``core/model.py``): a trace is a pure function of (config,
  query structure, shapes, kernel lowering) — never of the estimator
  instance — so sharing them across estimators only deduplicates
  compilation.

Every dispatch tunable (chunk widths, cache capacities, routing crossovers)
comes from a ``serve.policy.DispatchPolicy`` — pass ``policy=`` or let the
constructor resolve the host profile / env override (``resolve_policy``).
The policy only moves performance knobs; predictions are policy-invariant
(test-pinned).

Scoring numerics are unchanged from the pre-facade path: docs/api.md is the
surface reference, docs/placement_search.md + docs/forward_engine.md the
engine internals.
"""

from __future__ import annotations

import threading
import warnings
from collections import OrderedDict
from collections.abc import Mapping
from functools import lru_cache, wraps  # lru_cache re-exported for tests/tools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bucketing import BatchBanding, exact_banding_cached
from repro.core.gnn import (
    apply_gnn_merged,
    apply_gnn_placed,
    apply_gnn_placed_stacked,
    validate_merged_parents,
)
from repro.core.graph import (
    JointGraph,
    QueryStatic,
    batch_graphs,
    bucket_size,
    build_a_place_batch,
    build_graph,
    build_graph_batch,
    build_graph_skeleton,
    merge_graph_batches,
    pad_batch,
    query_static,
    skeleton_cache_key,
)
from repro.core.model import CostModelConfig, forward_ensemble
from repro.kernels import active_lowering
from repro.serve.policy import DispatchPolicy, active_policy, resolve_policy
from repro.serve.stacking import (
    StackedEnsembles,
    _ensemble_vote,
    _split_votes,
    stack_metric_models,
)

# -- jitted forward caches --------------------------------------------------------
#
# Every cached factory takes the kernels' active lowering as part of its key:
# the lowering is read at trace time, so without it a flipped
# REPRO_PALLAS_INTERPRET after the first call would silently reuse stale traces.

_MISS = object()


def _policy_lru(fn):
    """``lru_cache`` whose capacity tracks the active ``DispatchPolicy``.

    All four trace-factory caches share ONE capacity knob
    (``trace_cache_size``; sizing rationale in serve/policy.py) instead of
    the old scattered ``maxsize=64/128/256`` literals.  Capacity is read at
    insertion time, so installing a tuned profile resizes the caches without
    a process restart.  Matches the ``functools`` surface the tests touch:
    ``__wrapped__`` and ``cache_clear``.
    """
    cache: "OrderedDict[Tuple, object]" = OrderedDict()
    lock = threading.Lock()

    @wraps(fn)
    def wrapper(*args):
        with lock:
            hit = cache.get(args, _MISS)
            if hit is not _MISS:
                cache.move_to_end(args)
                return hit
        value = fn(*args)  # outside the lock: jax.jit wrapping is reentrant
        with lock:
            cache[args] = value
            cap = active_policy().trace_cache_size
            while len(cache) > cap:
                cache.popitem(last=False)
        return value

    wrapper.cache_clear = cache.clear
    return wrapper


def _can_donate() -> bool:
    """Whether input-buffer donation pays on this backend.

    XLA:CPU cannot alias donated inputs to outputs — donation there only
    produces "donated buffer was not usable" warnings — so the deferred
    dispatch path donates on accelerator backends and stays a no-op on CPU.
    The flag joins the trace-factory keys (a donating trace and a
    non-donating one are different executables).
    """
    return jax.default_backend() != "cpu"


@_policy_lru
def _jitted_forward(cfg: CostModelConfig, lowering: str = "ref"):
    def forward(p, g):
        return forward_ensemble(p, g, cfg)

    return jax.jit(forward)


@_policy_lru
def _jitted_forward_stacked(
    gnn,
    traditional_mp: bool,
    banding: Optional[BatchBanding] = None,
    lowering: str = "ref",
    donate: bool = False,
):
    # metric only selects the loss/vote, never the forward; any metric works.
    # ``banding`` is the merged batch's static signature-exact stage-3 plan
    # (None: full-depth scan) — part of the trace key, like a shape.
    # ``donate`` releases the graph batch's device buffers to the launch —
    # only callers that built the batch themselves for this one call may pass
    # it (the merged drain path); ``estimate`` takes caller-owned batches.
    cfg = CostModelConfig(metric="latency_p", gnn=gnn, traditional_mp=traditional_mp)

    def forward_stacked(p, g):
        return forward_ensemble(p, g, cfg, banding)

    return jax.jit(forward_stacked, donate_argnums=(1,) if donate else ())


@_policy_lru
def _jitted_placed_forward(cfg: CostModelConfig, static: QueryStatic, lowering: str = "ref"):
    def placed_forward(p, skel, a_place):
        return jax.vmap(
            lambda pp: apply_gnn_placed(pp, skel, a_place, static, cfg.gnn)[..., 0]
        )(p)

    return jax.jit(placed_forward)


@_policy_lru
def _jitted_placed_forward_stacked(
    gnn,
    static: QueryStatic,
    n_hw: int,
    chunk: int = 0,
    lowering: str = "ref",
    donate: bool = False,
):
    # ``chunk`` (the policy's score_chunk) joins the key: the scan structure
    # it selects is part of the trace, exactly like a shape.  ``donate``
    # releases ``a_place`` (per-drain, caller-built) — never the skeleton,
    # which lives in the estimator's LRU across calls.
    def placed_forward_stacked(p, skel, a_place):
        return apply_gnn_placed_stacked(p, skel, a_place, static, gnn, n_hw, chunk)

    return jax.jit(placed_forward_stacked, donate_argnums=(2,) if donate else ())


@_policy_lru
def _jitted_merged_forward(
    gnn,
    banding: BatchBanding,
    max_parents: int,
    lowering: str = "ref",
    donate: bool = False,
):
    # the cross-query engine: S deduped skeletons + per-row (skel_id,
    # a_place); banding is the drain's signature-exact static plan.
    # ``donate`` releases the per-drain (skel_id, a_place) buffers — never
    # ``skels``, the cached device-resident skeleton stack of the mix.
    def merged_forward(p, skels, skel_id, a_place):
        return apply_gnn_merged(p, skels, skel_id, a_place, gnn, banding, max_parents)

    return jax.jit(merged_forward, donate_argnums=(2, 3) if donate else ())


class NonFiniteEstimate(RuntimeError):
    """An estimator output contained NaN/Inf.

    Raised by the always-on finiteness guard on every facade output
    (``estimate``/``score``/``estimate_many``/``score_many``) instead of
    returning garbage costs to the optimizer: a NaN cost compares false
    against everything, so an argmin over candidates would silently pick an
    arbitrary placement.  ``PlacementService`` counts these in
    ``ServiceStats.n_nonfinite`` and feeds them to the circuit breaker
    (docs/robustness.md).
    """


def _check_finite(kind: str, out):
    """Raise ``NonFiniteEstimate`` if any output array has NaN/Inf.

    ``out`` is a metric -> array dict or a sequence of them (the facade's
    two output shapes); one vectorized ``np.isfinite`` per array.
    """
    items = out if isinstance(out, (list, tuple)) else (out,)
    for d in items:
        if d is None:
            continue
        for m, v in d.items():
            v = np.asarray(v)
            if v.dtype.kind == "f" and not np.isfinite(v).all():
                bad = int(np.size(v) - np.count_nonzero(np.isfinite(v)))
                raise NonFiniteEstimate(
                    f"{kind} produced {bad} non-finite value(s) for metric "
                    f"{m!r} (shape {v.shape})"
                )
    return out


class DeferredResult:
    """Device work already dispatched; the host-side finalize is deferred.

    Every ``deferred=True`` facade call runs its host featurization and
    launches its jitted forwards eagerly (jax dispatch is asynchronous), then
    returns one of these instead of blocking on the device values.
    ``result()`` blocks and runs the remaining host work (convert, vote,
    split back per request).  ``PlacementService`` uses the split to
    featurize drain N+1 while drain N's device work is still running.
    """

    __slots__ = ("_finalize", "_value", "_done")

    def __init__(self, finalize):
        self._finalize = finalize
        self._done = False
        self._value = None

    def result(self):
        if not self._done:
            self._value = self._finalize()
            self._finalize = None  # drop captured device buffers
            self._done = True
        return self._value


def _maybe_defer(finalize, deferred: bool):
    return DeferredResult(finalize) if deferred else finalize()


def _fetch(raw) -> np.ndarray:
    """A forward's output on the host: blocks until the device is done."""
    with jax.profiler.TraceAnnotation("costream.fetch"):
        return np.asarray(raw)


# -- stateless scoring primitives -------------------------------------------------
#
# The numeric cores behind the facade methods.  Prefer the CostEstimator
# methods: these take raw params and do no skeleton/stack caching.


def ensemble_predict(params, g: JointGraph, cfg: CostModelConfig) -> np.ndarray:
    """Ensemble prediction in *cost space* for a batch of graphs."""
    raw = _jitted_forward(cfg, active_lowering())(params, g)
    return _ensemble_vote(np.asarray(raw), cfg)


def ensemble_proba(params, g: JointGraph, cfg: CostModelConfig) -> np.ndarray:
    """Mean over members of the per-member sigmoid probability."""
    assert cfg.task == "classification"
    raw = np.asarray(_jitted_forward(cfg, active_lowering())(params, g))
    return (1.0 / (1.0 + np.exp(-raw))).mean(axis=0)


def placed_predict(
    params, skel: JointGraph, a_place: jax.Array, static: QueryStatic, cfg: CostModelConfig
) -> np.ndarray:
    """Ensemble prediction over candidate placements of ONE query.

    ``skel`` is the shared unbatched skeleton, ``a_place`` the ``(B, O, W)``
    placement adjacencies.  Numerically equivalent to ``ensemble_predict`` on
    the broadcast batch, via the query-specialized forward (jit-cached per
    (config, query-structure) pair).  Not available for ``traditional_mp``
    ablation models — those don't have the 3-stage structure the
    specialization exploits; callers fall back to the generic path.
    """
    assert not cfg.traditional_mp, "use the generic path for traditional_mp models"
    fwd = _jitted_placed_forward(cfg, static, active_lowering())
    return _ensemble_vote(np.asarray(fwd(params, skel, a_place)), cfg)


def placed_predict_fused(
    stacked: StackedEnsembles,
    skel: JointGraph,
    a_place: jax.Array,
    static: QueryStatic,
    deferred: bool = False,
    chunk: Optional[int] = None,
    donate: bool = False,
) -> Dict[str, np.ndarray]:
    """All metrics' ensembles over one query's candidate placements, fused.

    One jitted ``apply_gnn_placed_stacked`` call evaluates every (metric,
    member) pair in a single launch per GNN stage, on the trimmed active-slot
    layout; the raw ``(sum_E, B)`` block is then split back per metric and
    voted exactly like ``placed_predict`` (the stacked-vs-loop equivalence
    test pins this to float tolerance).  ``deferred`` dispatches the forward
    and returns a ``DeferredResult`` whose ``result()`` blocks and splits.
    ``donate=True`` hands ``a_place``'s device buffer to the launch (freed
    for the output instead of held alive beside it) — pass it ONLY when the
    buffer was built for this call and never touched again, as the
    estimator's drain paths do; a no-op on CPU backends (``_can_donate``).
    """
    assert not stacked.cfgs[0].traditional_mp, (
        "use the generic path for traditional_mp models"
    )
    n_hw = int(np.asarray(skel.hw_mask).sum())
    if chunk is None:
        chunk = active_policy().score_chunk
    fwd = _jitted_placed_forward_stacked(
        stacked.cfgs[0].gnn, static, n_hw, chunk, active_lowering(),
        donate and _can_donate(),
    )
    raw = fwd(stacked.params, skel, a_place)
    return _maybe_defer(lambda: _split_votes(_fetch(raw), stacked), deferred)


# -- the facade -------------------------------------------------------------------


class CostEstimator:
    """Serving facade over a set of trained per-metric ensembles.

    ``models``: dict metric -> (params, CostModelConfig), exactly the shape
    ``CostModelBundle.models`` carries (``from_bundle`` is the one-liner).
    ``policy``: a ``DispatchPolicy``; omitted, the host profile / env
    override resolves one (``serve.policy.resolve_policy``).
    Thread-safety: individual calls are safe to issue from one thread at a
    time; ``PlacementService`` adds the concurrent micro-batching front-end.
    """

    def __init__(
        self,
        models: Dict[str, Tuple[object, CostModelConfig]],
        meta=None,
        policy: Optional[DispatchPolicy] = None,
    ):
        # plain dicts are copied (callers may mutate theirs); other Mappings
        # (bundle.LazyModels) pass through so laziness survives the facade
        self.models = dict(models) if type(models) is dict else models
        assert isinstance(self.models, Mapping), type(models)
        self.meta = dict(meta or {})
        self.policy = (policy if policy is not None else resolve_policy()).validate()
        # (query, cluster) pairs kept device-resident
        self.skeleton_cache_size = self.policy.skeleton_cache_size
        self._skeletons: "OrderedDict[Tuple, Tuple[JointGraph, JointGraph, QueryStatic]]" = (
            OrderedDict()
        )
        self._stacked: Dict[Tuple[str, ...], Optional[StackedEnsembles]] = {}
        # cross-query drain mixes: structure-key tuple -> (device skeleton
        # stack, banding, max_parents).  A recurring mix (the steady state of
        # a monitoring loop) re-enters with zero stacking/banding/transfer.
        self._merged_groups: "OrderedDict[Tuple, Tuple]" = OrderedDict()
        self._optimizer = None
        # fault-injection / observation hooks (serve.chaos): objects with
        # optional ``before(kind, n)`` / ``after(kind, out) -> out | None``
        self._hooks: List[object] = []

    # -- hooks (the chaos-injection and observation seam; docs/robustness.md) -----

    def add_hook(self, hook) -> None:
        """Install a call hook.  ``before(kind, n)`` runs at dispatch time of
        every facade call (``kind`` in {"estimate", "score", "estimate_many",
        "score_many"}, ``n`` the row/graph count) and may raise or block —
        exactly what a real fault does.  ``after(kind, out)`` runs at
        finalize time (inside ``DeferredResult.result()`` for deferred
        calls) and may return a replacement output; the finiteness guard
        runs AFTER all hooks, so injected NaNs are caught like real ones."""
        self._hooks.append(hook)

    def remove_hook(self, hook) -> None:
        self._hooks.remove(hook)

    def _before(self, kind: str, n: int) -> None:
        for h in self._hooks:
            before = getattr(h, "before", None)
            if before is not None:
                before(kind, n)

    def _finish(self, kind: str, finalize, deferred: bool):
        """Wrap a finalize thunk with after-hooks + the finiteness guard."""

        def run():
            out = finalize()
            for h in self._hooks:
                after = getattr(h, "after", None)
                if after is not None:
                    repl = after(kind, out)
                    if repl is not None:
                        out = repl
            return _check_finite(kind, out)

        return _maybe_defer(run, deferred)

    @classmethod
    def from_bundle(
        cls,
        bundle,
        corpus_fingerprint: Optional[str] = None,
        policy: Optional[DispatchPolicy] = None,
        strict_provenance: bool = False,
    ) -> "CostEstimator":
        """Facade over a bundle's models (laziness preserved).

        ``corpus_fingerprint`` (see ``bundle.corpus_fingerprint``) is the
        caller's expectation of the corpus the models were trained on; when
        both it and the bundle's recorded ``meta["corpus_fingerprint"]``
        exist and disagree, a warning flags the provenance mismatch — the
        models still serve (retraining on refreshed labels is legitimate),
        but silently comparing them against the wrong corpus is not.
        ``strict_provenance=True`` upgrades the warning to a
        ``bundle.BundleVersionError`` — the lifecycle path (candidate
        bundles promoted into a live service) must never serve a model of
        unknown ancestry.
        """
        meta = bundle.meta or {}
        recorded = meta.get("corpus_fingerprint")
        if (
            corpus_fingerprint is not None
            and recorded is not None
            and recorded != corpus_fingerprint
        ):
            msg = (
                f"bundle was trained on corpus {recorded!r} but the caller "
                f"expects {corpus_fingerprint!r}; predictions are served "
                "against data the models never saw (provenance mismatch)"
            )
            if strict_provenance:
                from repro.serve.bundle import BundleVersionError

                raise BundleVersionError(msg)
            warnings.warn(msg, stacklevel=2)
        return cls(bundle.models, meta=meta, policy=policy)

    @property
    def metrics(self) -> Tuple[str, ...]:
        return tuple(self.models)

    def config(self, metric: str) -> CostModelConfig:
        return self.models[metric][1]

    # -- generic batch estimation -------------------------------------------------

    @staticmethod
    def _host_graphs(batch) -> JointGraph:
        """A batched ``JointGraph`` as given, or a sequence of traces
        featurized into one on the host."""
        if isinstance(batch, JointGraph):
            return batch
        with jax.profiler.TraceAnnotation("costream.featurize", rows=len(batch)):
            return batch_graphs([build_graph(t.query, t.cluster, t.placement) for t in batch])

    @staticmethod
    def _as_graphs(batch) -> JointGraph:
        """A batched ``JointGraph``, or a sequence of traces to featurize,
        on the device."""
        return jax.tree_util.tree_map(jnp.asarray, CostEstimator._host_graphs(batch))

    def estimate(
        self, batch, metrics: Optional[Sequence[str]] = None, deferred: bool = False
    ) -> Dict[str, np.ndarray]:
        """Cost-space predictions for a batch of *placed* queries.

        ``batch`` is either a batched ``JointGraph`` or a sequence of traces
        (anything with ``.query``/``.cluster``/``.placement``), featurized
        here in one pass.  The batch is transferred to the device once and
        every requested ensemble (targets + success/backpressure filters)
        runs over the same resident batch; shape-identical per-metric configs
        (the COSTREAM default) are additionally fused into ONE stacked
        forward, heterogeneous configs fall back to a per-metric loop.
        Returns metric -> predictions aligned with the batch (``deferred``:
        a ``DeferredResult`` resolving to that dict once device work is done).
        """
        metrics = tuple(metrics) if metrics is not None else tuple(self.models)
        host = self._host_graphs(batch)
        rows = int(host.op_x.shape[0]) if host.op_x.ndim == 3 else 1
        self._before("estimate", rows)
        stacked = self._stacked_for(metrics)
        with jax.profiler.TraceAnnotation("costream.dispatch", rows=rows):
            g = jax.tree_util.tree_map(jnp.asarray, host)
            if stacked is None:  # mixed architectures: per-metric forwards, shared batch
                lowering = active_lowering()
                raws = {
                    m: _jitted_forward(self.models[m][1], lowering)(self.models[m][0], g)
                    for m in metrics
                }

                def finalize():
                    return {
                        m: _ensemble_vote(_fetch(raws[m]), self.models[m][1]) for m in metrics
                    }

            else:
                fwd = _jitted_forward_stacked(
                    stacked.cfgs[0].gnn, stacked.cfgs[0].traditional_mp, None, active_lowering()
                )
                raw = fwd(stacked.params, g)

                def finalize():
                    return _split_votes(_fetch(raw), stacked)

        return self._finish("estimate", finalize, deferred)

    def proba(self, batch, metric: str) -> np.ndarray:
        """Mean ensemble probability for one classification metric."""
        params, cfg = self.models[metric]
        return ensemble_proba(params, self._as_graphs(batch), cfg)

    # -- placement scoring --------------------------------------------------------

    def _skeleton_entry(
        self, query, cluster, key: Optional[Tuple] = None
    ) -> Tuple[JointGraph, JointGraph, QueryStatic]:
        """Cached (host skeleton, device skeleton, QueryStatic) for one pair.

        The host copy feeds the cross-query merge path (merging concatenates
        on the host before ONE device transfer); the device copy feeds the
        placed per-structure forwards.  Both ride the same LRU entry, so
        either path's hit warms the other.  ``key`` lets callers that already
        computed ``skeleton_cache_key`` (the service computes it at submit
        time) skip recomputing it — the key build is the most expensive host
        step on a warm cache."""
        if key is None:
            key = skeleton_cache_key(query, cluster)
        hit = self._skeletons.get(key)
        if hit is not None:
            self._skeletons.move_to_end(key)
            return hit
        host = build_graph_skeleton(query, cluster)
        entry = (host, jax.tree_util.tree_map(jnp.asarray, host), query_static(query))
        self._skeletons[key] = entry
        while len(self._skeletons) > self.skeleton_cache_size:
            self._skeletons.popitem(last=False)
        return entry

    def _skeleton_for(self, query, cluster) -> Tuple[JointGraph, QueryStatic]:
        """Cached (device-resident skeleton, QueryStatic) for one pair."""
        _, dev, static = self._skeleton_entry(query, cluster)
        return dev, static

    def _stacked_for(self, metrics: Tuple[str, ...]) -> Optional[StackedEnsembles]:
        """Fused ensemble stack for ``metrics``, or None if not fusable."""
        if metrics not in self._stacked:
            try:
                self._stacked[metrics] = stack_metric_models(self.models, metrics)
            except ValueError:  # heterogeneous per-metric configs
                self._stacked[metrics] = None
        return self._stacked[metrics]

    def scorer(self, query, cluster, metrics: Sequence[str], deferred: bool = False):
        """Scoring closure with the per-(query, cluster) work hoisted out.

        Refinement loops and repeated ``score``/``optimize`` calls re-score
        the same query; the skeleton, its device transfer, and the trace-time
        ``QueryStatic`` are identical throughout, so they come from the
        instance-level LRU (``_skeleton_for``) — at most ONE skeleton build
        per pair, and one fused stacked forward per scored batch.
        ``deferred`` makes the closure dispatch and return a
        ``DeferredResult`` instead of blocking on the device values.
        """
        metrics = tuple(metrics)
        if any(self.models[m][1].traditional_mp for m in metrics):
            # ablation models lack the 3-stage structure the specialized
            # forward exploits; build the full broadcast batch instead
            def score_generic(assignments: np.ndarray) -> Dict[str, np.ndarray]:
                n = len(assignments)
                if n == 0:  # not assert: callers (the service) rely on it under -O
                    raise ValueError("no candidates to score")
                with jax.profiler.TraceAnnotation("costream.featurize", rows=n):
                    graphs = pad_batch(
                        build_graph_batch(query, cluster, assignments), bucket_size(n)
                    )
                # hooks + the finiteness guard fire inside the delegated
                # ``estimate`` (kind "estimate"), not a second time here
                pending = self.estimate(graphs, metrics, deferred=True)
                return _maybe_defer(
                    lambda: {m: v[:n] for m, v in pending.result().items()}, deferred
                )

            return score_generic

        skel, static = self._skeleton_for(query, cluster)
        stacked = self._stacked_for(metrics)

        def score(assignments: np.ndarray) -> Dict[str, np.ndarray]:
            n = len(assignments)
            if n == 0:  # not assert: callers (the service) rely on it under -O
                raise ValueError("no candidates to score")
            self._before("score", n)
            with jax.profiler.TraceAnnotation("costream.featurize", rows=n):
                a_place = build_a_place_batch(query, cluster, assignments)
                pad = bucket_size(n) - n
                if pad:
                    a_place = np.concatenate([a_place, np.repeat(a_place[-1:], pad, axis=0)])
            with jax.profiler.TraceAnnotation("costream.dispatch", rows=n + pad):
                a_place = jnp.asarray(a_place)
                if stacked is not None:
                    # a_place was built above for this one call: donate its buffer
                    pending = placed_predict_fused(
                        stacked, skel, a_place, static, deferred=True,
                        chunk=self.policy.score_chunk, donate=True,
                    )

                    def finalize():
                        return {m: v[:n] for m, v in pending.result().items()}

                else:
                    # heterogeneous (non-fusable) configs: per-metric loop, computed
                    # eagerly — the rare path keeps no deferral, only the wrapper type
                    out = {
                        m: placed_predict(
                            self.models[m][0], skel, a_place, static, self.models[m][1]
                        )[:n]
                        for m in metrics
                    }

                    def finalize():
                        return out

            return self._finish("score", finalize, deferred)

        return score

    def score(
        self,
        query,
        cluster,
        assignments: np.ndarray,
        metrics: Optional[Sequence[str]] = None,
        deferred: bool = False,
    ) -> Dict[str, np.ndarray]:
        """Score an ``(N, n_ops)`` assignment matrix on every requested metric.

        One skeleton build per (query, cluster) pair (LRU-amortized), one
        bucket-padded stacked forward per call; padding rows are sliced off,
        so results are independent of the bucket and of batchmates.
        """
        metrics = tuple(metrics) if metrics is not None else tuple(self.models)
        return self.scorer(query, cluster, metrics, deferred=deferred)(
            np.asarray(assignments, dtype=np.int64)
        )

    # -- cross-query broadcast batches -------------------------------------------

    def supports_cross_query(self, metrics: Optional[Sequence[str]] = None) -> bool:
        """Whether ``metrics`` can ride one merged cross-query forward.

        Requires a fusable ensemble stack (shape-identical GNN configs) with
        the 3-stage structure (``traditional_mp`` ablation models aggregate
        over rounds, not stages, and keep their per-graph path).
        ``estimate_many``/``score_many`` fall back to per-request answers when
        this is False — the service uses it to route and count honestly.
        """
        metrics = tuple(metrics) if metrics is not None else tuple(self.models)
        stacked = self._stacked_for(metrics)
        return stacked is not None and not stacked.cfgs[0].traditional_mp

    def _merged_forward(
        self,
        merged: JointGraph,
        sizes: Sequence[int],
        metrics: Tuple[str, ...],
        max_rows: Optional[int],
        deferred: bool = False,
    ) -> List[Dict[str, np.ndarray]]:
        """One stacked forward per ``max_rows`` chunk of a merged host batch.

        Each chunk is bucket-padded (shape stability) and gets the
        signature-exact row-trimmed banding of the structures it actually
        contains (cached by signature hash — a recurring request mix reuses
        its plan AND its jit trace), so stage-3 work tracks real rows rather
        than the widest member.  Answers are split back per source batch.
        Every chunk is dispatched before any is blocked on; ``deferred``
        additionally defers the blocking itself to ``result()``.
        """
        stacked = self._stacked_for(metrics)
        total = int(merged.op_x.shape[0])
        step = max_rows if max_rows else total
        launched: List[Tuple[jax.Array, int]] = []
        fields = [np.asarray(x) for x in merged]
        for s in range(0, total, step):
            n = min(step, total - s)
            with jax.profiler.TraceAnnotation("costream.featurize", rows=n):
                chunk = pad_batch(JointGraph(*[x[s : s + step] for x in fields]), bucket_size(n))
                banding = exact_banding_cached(chunk)
            with jax.profiler.TraceAnnotation("costream.dispatch", rows=bucket_size(n)):
                # the chunk's device copy exists only for this launch: donate it
                fwd = _jitted_forward_stacked(
                    stacked.cfgs[0].gnn, False, banding, active_lowering(), _can_donate()
                )
                raw = fwd(stacked.params, jax.tree_util.tree_map(jnp.asarray, chunk))
            launched.append((raw, n))

        def finalize() -> List[Dict[str, np.ndarray]]:
            parts = [
                {m: v[:n] for m, v in _split_votes(_fetch(raw), stacked).items()}
                for raw, n in launched
            ]
            merged_out = {m: np.concatenate([p[m] for p in parts]) for m in metrics}
            out, off = [], 0
            for size in sizes:
                out.append({m: merged_out[m][off : off + size] for m in metrics})
                off += size
            return out

        return _maybe_defer(finalize, deferred)

    def estimate_many(
        self,
        batches: Sequence,
        metrics: Optional[Sequence[str]] = None,
        max_rows: Optional[int] = None,
        deferred: bool = False,
    ) -> List[Dict[str, np.ndarray]]:
        """``estimate`` for N independent batches through ONE fused forward.

        ``batches`` entries are batched ``JointGraph``s (single graphs are
        promoted) or trace sequences; structures may differ freely — every
        graph shares the canonical padded layout, so the batches concatenate
        along the batch axis (``graph.merge_graph_batches``) and one
        kernel-routed stacked forward per ``max_rows`` chunk answers
        everything.  Returns one metric -> predictions dict per input batch,
        order-aligned (``deferred``: a ``DeferredResult`` resolving to it).
        """
        metrics = tuple(metrics) if metrics is not None else tuple(self.models)
        batches = list(batches)
        if not batches:
            return _maybe_defer(lambda: [], deferred)
        host = []
        for b in batches:
            g = jax.tree_util.tree_map(np.asarray, self._host_graphs(b))
            if g.op_x.ndim == 2:  # single graph: promote to a batch of one
                g = jax.tree_util.tree_map(lambda x: x[None], g)
            host.append(g)
        total_graphs = sum(int(g.op_x.shape[0]) for g in host)
        if total_graphs == 0:
            raise ValueError("no graphs to estimate")
        if not self.supports_cross_query(metrics):
            # heterogeneous / ablation configs: per-batch fallback, chunked
            # and bucket-padded exactly like the merged path; every chunk is
            # dispatched before any is blocked on.  Hooks + the finiteness
            # guard fire inside the delegated ``estimate`` calls.
            pendings: List[Optional[List[Tuple]]] = []
            for g in host:
                total = int(g.op_x.shape[0])
                if total == 0:  # empty member: filled in below, like the
                    pendings.append(None)  # merged path's zero-width slice
                    continue
                step = max_rows if max_rows else total
                parts = []
                for s in range(0, total, step):
                    chunk = jax.tree_util.tree_map(lambda x: x[s : s + step], g)
                    n = int(chunk.op_x.shape[0])
                    parts.append(
                        (self.estimate(pad_batch(chunk, bucket_size(n)), metrics, deferred=True), n)
                    )
                pendings.append(parts)

            def finalize_fallback() -> List[Dict[str, np.ndarray]]:
                out: List[Optional[Dict[str, np.ndarray]]] = []
                for parts in pendings:
                    if parts is None:
                        out.append(None)
                        continue
                    done = [{m: v[:n] for m, v in p.result().items()} for p, n in parts]
                    out.append({m: np.concatenate([d[m] for d in done]) for m in metrics})
                template = next(o for o in out if o is not None)
                return [
                    o if o is not None else {m: template[m][:0] for m in metrics}
                    for o in out
                ]

            return _maybe_defer(finalize_fallback, deferred)
        self._before("estimate_many", total_graphs)
        with jax.profiler.TraceAnnotation("costream.featurize", rows=total_graphs):
            merged, sizes = merge_graph_batches(host)
        pending = self._merged_forward(merged, sizes, metrics, max_rows, deferred=True)
        return self._finish("estimate_many", pending.result, deferred)

    def score_many(
        self,
        requests: Sequence[Tuple],
        metrics: Optional[Sequence[str]] = None,
        max_rows: Optional[int] = None,
        keys: Optional[Sequence[Tuple]] = None,
        deferred: bool = False,
    ) -> List[Dict[str, np.ndarray]]:
        """``score`` for N distinct (query, cluster, assignments) requests
        through ONE fused forward.

        The serving hot path for a heterogeneous request stream: requests
        are regrouped structure-major, each structure contributing its
        LRU-cached skeleton ONCE (zero featurization passes warm) plus all
        its candidate rows, and a single stacked ``apply_gnn_merged`` forward
        per ``max_rows`` chunk scores every (metric, member, candidate)
        triple — O(1) forwards per drain instead of O(#structures), with
        stage work proportional to real rows (the drain's signature-exact
        banding).  ``keys`` optionally carries precomputed
        ``skeleton_cache_key``s (the service computes them at submit).
        Returns one metric -> (N_i,) dict per request, order-aligned; answers
        equal per-request ``score`` to float tolerance (the merged engine and
        the placement-specialized engine are the same math in different
        association orders).  ``use_pallas`` models ride the same merged
        engine: its gathers/scatters are kernel-routed through
        ``kernels/seg_gather`` (see ``gnn.apply_gnn_merged``).
        """
        metrics = tuple(metrics) if metrics is not None else tuple(self.models)
        requests = list(requests)
        if not requests:
            return _maybe_defer(lambda: [], deferred)
        if not self.supports_cross_query(metrics):
            # hooks + the guard fire inside the delegated ``score`` calls
            per_req = [self.score(q, c, a, metrics, deferred=True) for q, c, a in requests]
            return _maybe_defer(lambda: [p.result() for p in per_req], deferred)
        stacked = self._stacked_for(metrics)
        if keys is None:
            keys = [skeleton_cache_key(q, c) for q, c, _ in requests]

        # regroup structure-major: one skeleton + one concatenated candidate
        # block per structure; remember each request's slice for the split
        groups: "OrderedDict[Tuple, List[int]]" = OrderedDict()
        mats = []
        for i, (q, c, a) in enumerate(requests):
            a = np.asarray(a, dtype=np.int64)
            if len(a) == 0:  # not assert: the service relies on it under -O
                raise ValueError("no candidates to score")
            mats.append(a)
            groups.setdefault(keys[i], []).append(i)
        rows = sum(len(a) for a in mats)
        self._before("score_many", rows)

        with jax.profiler.TraceAnnotation("costream.featurize", rows=rows):
            index_of, skels_dev, banding, max_parents = self._merged_group_for(
                requests, groups
            )
            blocks, ids = [], []
            for key, idxs in groups.items():
                q, c, _ = requests[idxs[0]]
                block = build_a_place_batch(q, c, np.concatenate([mats[i] for i in idxs]))
                blocks.append(block)
                ids.append(np.full(len(block), index_of[key], dtype=np.int32))
            skel_id = np.concatenate(ids) if len(ids) > 1 else ids[0]
            a_place = np.concatenate(blocks) if len(blocks) > 1 else blocks[0]
        pending = self._merged_placements_forward(
            skels_dev, banding, max_parents, skel_id, a_place,
            [len(b) for b in blocks], stacked, metrics, max_rows, deferred=True,
        )

        def finalize() -> List[Dict[str, np.ndarray]]:
            # split each structure's block back onto its requests, in order
            per_group = pending.result()
            out: List[Optional[Dict[str, np.ndarray]]] = [None] * len(requests)
            for g_out, idxs in zip(per_group, groups.values()):
                off = 0
                for i in idxs:
                    n = len(mats[i])
                    out[i] = {m: g_out[m][off : off + n] for m in metrics}
                    off += n
            return out

        return self._finish("score_many", finalize, deferred)

    def _merged_group_for(self, requests, groups) -> Tuple:
        """(key -> skeleton index, device skeleton stack, banding,
        max_parents) for one drain mix.

        Keyed on the *set* of structure keys — drains of one recurring mix
        arrive in whatever order client threads raced, so the index mapping
        is part of the entry and callers build ``skel_id`` through it; the
        mix then pays stacking, banding, and the skeleton device transfer
        exactly once (the steady state of an online monitoring loop)."""
        mix_key = frozenset(groups)
        hit = self._merged_groups.get(mix_key)
        if hit is not None:
            self._merged_groups.move_to_end(mix_key)
            return hit
        index_of = {key: i for i, key in enumerate(groups)}
        skels = batch_graphs(
            [self._skeleton_entry(*requests[idxs[0]][:2], key)[0] for key, idxs in groups.items()]
        )
        banding = exact_banding_cached(skels)
        max_parents = int(np.asarray(skels.a_flow).sum(axis=-2).max(initial=1))
        # the derived bound must actually cover every row's in-degree — a
        # violation would mean silently-dropped parents (wrong sums), so the
        # invariant is checked HERE, where the parent tables' width is fixed
        # for the lifetime of the cached group
        validate_merged_parents(skels.a_flow, max_parents, what="merged drain mix")
        entry = (index_of, jax.tree_util.tree_map(jnp.asarray, skels), banding, max_parents)
        self._merged_groups[mix_key] = entry
        while len(self._merged_groups) > self.policy.merged_group_cache_size:
            self._merged_groups.popitem(last=False)
        return entry

    def _merged_placements_forward(
        self,
        skels_dev: JointGraph,
        banding: BatchBanding,
        max_parents: int,
        skel_id: np.ndarray,
        a_place: np.ndarray,
        sizes: Sequence[int],
        stacked: StackedEnsembles,
        metrics: Tuple[str, ...],
        max_rows: Optional[int],
        deferred: bool = False,
    ) -> List[Dict[str, np.ndarray]]:
        """Chunked ``apply_gnn_merged`` over a structure-major placement batch.

        The trace is keyed on the participating structures' signature set
        (via the cached exact banding) and the bucket-padded row count — a
        recurring drain mix reuses its plan, its jit trace, AND its
        device-resident skeleton stack (``_merged_group_for``).
        """
        # per-chunk (ids, ap) device copies exist only for their launch:
        # donate them so a double-buffered drain holds one live batch, not two
        fwd = _jitted_merged_forward(
            stacked.cfgs[0].gnn, banding, max_parents, active_lowering(), _can_donate()
        )
        total = int(a_place.shape[0])
        step = max_rows if max_rows else total
        launched: List[Tuple[jax.Array, int]] = []
        for s in range(0, total, step):
            ids, ap = skel_id[s : s + step], a_place[s : s + step]
            n = len(ids)
            pad = bucket_size(n) - n
            if pad:
                with jax.profiler.TraceAnnotation("costream.featurize", rows=n):
                    ids = np.concatenate([ids, np.repeat(ids[-1:], pad)])
                    ap = np.concatenate([ap, np.repeat(ap[-1:], pad, axis=0)])
            with jax.profiler.TraceAnnotation("costream.dispatch", rows=n + pad):
                raw = fwd(stacked.params, skels_dev, jnp.asarray(ids), jnp.asarray(ap))
            launched.append((raw, n))

        def finalize() -> List[Dict[str, np.ndarray]]:
            parts = [
                {m: v[:n] for m, v in _split_votes(_fetch(raw), stacked).items()}
                for raw, n in launched
            ]
            merged_out = {m: np.concatenate([p[m] for p in parts]) for m in metrics}
            out, off = [], 0
            for size in sizes:
                out.append({m: merged_out[m][off : off + size] for m in metrics})
                off += size
            return out

        return _maybe_defer(finalize, deferred)

    def optimize(self, query, cluster, target_metric: str = "latency_p", **kwargs):
        """Cost-based placement search (paper SV): sample -> score -> argopt.

        Delegates to a ``PlacementOptimizer`` sharing this estimator (and
        therefore its caches); see that class for the search knobs
        (``k``, ``refine_rounds``, ...).
        """
        if self._optimizer is None:
            from repro.placement.optimizer import PlacementOptimizer

            self._optimizer = PlacementOptimizer(self)
        return self._optimizer.optimize(query, cluster, target_metric, **kwargs)
