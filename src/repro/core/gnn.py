"""COSTREAM GNN: node-type encoders + the novel 3-stage message passing.

Implements Algorithm 1 of the paper on the padded dense ``JointGraph``:

  stage 0  h_v   = MLP_{T(v)}(x_v)                         (type-specific encoders)
  stage 1  OPS->HW   : hosts absorb the states of the operators placed on them
  stage 2  HW->OPS   : operators absorb the (updated) state of their host
  stage 3  SOURCES->OPS: states flow along the logical data flow in topological
                        order (depth-level steps with masked updates)
  readout  sum over all node states -> MLP_out -> prediction

Following the paper's text, every update is
``h'_v = MLP'_{T(v)}(concat(h_v, sum_{u in children(v)} h'_u))``.

ONE engine serves every consumer (see docs/forward_engine.md): the shared
stage-1/2/3 core ``_stages123`` takes a static ``StagePlan`` describing how
the stage-3 data-flow sweep runs —

* ``scan``   — a ``lax.scan`` over all ``max_depth`` levels with dynamic
  depth-select (the generic fallback for arbitrary batches);
* ``sweep``  — the banded plan FUSED: all non-empty depth levels of a
  bucket (``graph.BatchBanding``) run as ONE ``kernels/mp_sweep`` call with
  the banding table baked in as compile-time constants.  This is the
  training/serving path whenever a banding is present and the update bank
  is 2-layer (kernel-fusable);
* ``banded`` — the unfused fallback of ``sweep``: one statically-banded
  ``mp_update`` step per level (kept for >2-layer, jnp-only update banks);
* ``exact``  — the placement-specialized sweep unrolled over one query's
  ``QueryStatic.updates`` (only the slots that carry an operator at each
  level are recomputed).

``GNNConfig.use_pallas`` routes every plan kind through ``kernels/banked_mlp``
(stages 0-2) and ``kernels/mp_sweep`` / ``kernels/mp_update`` (stage 3);
configs the kernels cannot fuse raise loudly instead of silently falling
back.  The cross-query merged engine calls ``kernels/seg_gather`` whatever
``use_pallas`` says, so on the TPU it always runs those Pallas kernels.

``apply_gnn_traditional`` is the Exp-7b ablation: K rounds of symmetric
neighbor aggregation with shared (non-type-specific ordering) updates.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import nn
from repro.core.features import HW_FEATURE_DIM, N_OP_TYPES, OP_FEATURE_DIM
from repro.core.graph import (
    MAX_DEPTH,
    SLOT_RANGES,
    BatchBanding,
    JointGraph,
    QueryStatic,
)


@dataclass(frozen=True)
class GNNConfig:
    hidden: int = 64
    enc_layers: int = 2
    update_layers: int = 2
    readout_layers: int = 2
    max_depth: int = MAX_DEPTH
    n_outputs: int = 1
    use_pallas: bool = False  # route banked MLPs through the Pallas kernel


def init_gnn(key: jax.Array, cfg: GNNConfig) -> nn.Params:
    ks = jax.random.split(key, 6)
    h = cfg.hidden

    def sizes(d_in: int, n_layers: int, d_out: int):
        return [d_in] + [h] * (n_layers - 1) + [d_out]

    return {
        "op_enc": nn.init_mlp_bank(ks[0], N_OP_TYPES, sizes(OP_FEATURE_DIM, cfg.enc_layers, h)),
        "hw_enc": nn.init_mlp(ks[1], sizes(HW_FEATURE_DIM, cfg.enc_layers, h)),
        "op_upd": nn.init_mlp_bank(ks[2], N_OP_TYPES, sizes(2 * h, cfg.update_layers, h)),
        "hw_upd": nn.init_mlp(ks[3], sizes(2 * h, cfg.update_layers, h)),
        "out": nn.init_mlp(ks[4], sizes(h, cfg.readout_layers, cfg.n_outputs)),
    }


def _require_fusable(params: nn.Params, what: str) -> None:
    """``use_pallas`` must fail loudly, never silently fall back to jnp.

    The Pallas banked-MLP / mp-update kernels fuse exactly two layers; configs
    with a different depth cannot be routed through them, and pretending they
    were would make ``use_pallas`` a lie (the bug this guard exists to kill).
    """
    n = len(params["layers"])
    if n != 2:
        raise NotImplementedError(
            f"GNNConfig.use_pallas=True but '{what}' has {n} layers; the Pallas "
            "kernels fuse exactly two (enc_layers=update_layers=2). Use a "
            "2-layer config or set use_pallas=False."
        )


def _apply_bank(params, x, cfg: GNNConfig, ranges=SLOT_RANGES):
    """Type-specific MLP over a slot layout (default: graph.SLOT_RANGES)."""
    if cfg.use_pallas:
        from repro.kernels.banked_mlp import ops as bank_ops

        _require_fusable(params, "banked MLP (op_enc/op_upd)")
        return bank_ops.banked_mlp_slotted(params, x, ranges)
    return nn.apply_mlp_bank_slotted(params, x, ranges)


def _apply_shared(params, x, cfg: GNNConfig, what: str):
    """Shared (non-type-specific) MLP, e.g. hw_enc / hw_upd.

    Under ``use_pallas`` this routes through the banked-MLP kernel as a
    single-type bank covering the whole node axis — one slot range spanning
    all rows — so the hardware-side stages run in the same fused VMEM pass as
    the operator banks instead of silently staying on the jnp path.
    """
    if cfg.use_pallas:
        from repro.kernels.banked_mlp import ops as bank_ops

        _require_fusable(params, what)
        bank = {
            "layers": [{"w": l["w"][None], "b": l["b"][None]} for l in params["layers"]]
        }
        return bank_ops.banked_mlp_slotted(bank, x, ((0, 0, x.shape[-2]),))
    return nn.apply_mlp(params, x)


# ---------------------------------------------------------------------------
# The unified stage engine.
# ---------------------------------------------------------------------------


class StagePlan(NamedTuple):
    """Static description of the stage-3 data-flow sweep (jit-cache safe).

    ``kind``:
      * ``"scan"``   — ``lax.scan`` over depths ``1..depth_max``, full row
        width, dynamic depth-select (generic batches without banding);
      * ``"sweep"``  — ALL of ``levels`` in one fused ``kernels/mp_sweep``
        call (the banding table as compile-time constants; one stage-3
        launch per forward on the kernel path).  Chosen over ``banded``
        whenever the update bank is 2-layer;
      * ``"banded"`` — unrolled over ``levels``; each level runs at its static
        ``row_span`` with a static ``parent_rows`` contraction bound
        (bucketed training batches, ``graph.batch_banding``);
      * ``"exact"``  — the placement-specialized sweep: the jnp path unrolls
        ``updates`` (per level, the exact ``(row, type, parent_rows)``
        tuples), the Pallas path walks ``levels``.

    ``levels`` entries are ``(d, row_span | None, slot_ranges, parent_rows |
    None)`` with *absolute* row indices; ``slot_ranges`` must tile the span.
    """

    kind: str
    depth_max: int = 0
    levels: Tuple = ()
    updates: Tuple = ()


def _clip_ranges(ranges, start: int, stop: int):
    """Restrict slot ranges to [start, stop); result tiles the span exactly."""
    out = []
    for t, a, b in ranges:
        a2, b2 = max(a, start), min(b, stop)
        if a2 < b2:
            out.append((t, a2, b2))
    return tuple(out)


def _banded_plan(banding: BatchBanding, ranges=SLOT_RANGES, kind: str = "banded") -> StagePlan:
    return StagePlan(
        kind,
        levels=tuple(
            (d, span, _clip_ranges(ranges, *span), p) for d, span, p in banding.levels
        ),
    )


def _sweep_fusable(params: nn.Params) -> bool:
    """The fused sweep (and its oracle twin) handle exactly 2-layer banks."""
    return len(params["op_upd"]["layers"]) == 2


def _bank_member(p: nn.Params, t: int) -> nn.Params:
    """Extract one type's MLP from a stacked bank (leading type axis)."""
    return {"layers": [{"w": l["w"][t], "b": l["b"][t]} for l in p["layers"]]}


def _dataflow_sweep(
    params, h, a_flow, op_depth, op_mask, cfg: GNNConfig, ranges, plan: StagePlan
):
    """Stage 3: SOURCES->OPS along the data flow, per the static plan.

    ``h``/``a_flow``/``op_depth`` are rank-polymorphic (``(N, .)`` single,
    ``(B, N, .)`` batched); the ``exact`` jnp branch is the one exception —
    it indexes candidate batches explicitly (the placed path's layout).
    """
    if plan.kind == "sweep":
        mask_vec = (
            op_mask[..., 0] if op_mask is not None else jnp.ones(h.shape[:-1], jnp.float32)
        )
        if cfg.use_pallas:
            # the whole banding table in ONE kernel launch (vs one per level)
            from repro.kernels.mp_sweep import ops as sweep_ops

            _require_fusable(params["op_upd"], "op_upd (stage-3 mp_sweep)")
            return sweep_ops.mp_sweep(
                params["op_upd"], h, a_flow, op_depth, mask_vec, plan.levels
            )
        # jnp path: the sweep oracle IS the old per-level banded loop, with
        # the same injected banked apply — bitwise-identical numerics
        from repro.kernels.mp_sweep.ref import mp_sweep_ref

        return mp_sweep_ref(
            params["op_upd"],
            h,
            a_flow,
            op_depth,
            mask_vec,
            plan.levels,
            apply_fn=nn.apply_mlp_bank_slotted,
        )
    if cfg.use_pallas:
        from repro.kernels.mp_update import ops as mp_ops

        _require_fusable(params["op_upd"], "op_upd (stage-3 mp_update)")
        mask_vec = (
            op_mask[..., 0] if op_mask is not None else jnp.ones(h.shape[:-1], jnp.float32)
        )
        if plan.kind == "scan":

            def step(hh, d):
                return (
                    mp_ops.mp_update(
                        params["op_upd"], hh, a_flow, op_depth, mask_vec, d, ranges
                    ),
                    None,
                )

            h, _ = jax.lax.scan(
                step, h, jnp.arange(1, plan.depth_max + 1, dtype=op_depth.dtype)
            )
            return h
        for d, span, level_ranges, parent_hi in plan.levels:
            h = mp_ops.mp_update(
                params["op_upd"],
                h,
                a_flow,
                op_depth,
                mask_vec,
                jnp.asarray(d, op_depth.dtype),
                level_ranges,
                row_span=span,
                parent_rows=parent_hi,
            )
        return h

    sel_mask = None if op_mask is None else op_mask[..., 0] > 0

    def full_step(hh, d):
        msg = jnp.swapaxes(a_flow, -1, -2) @ hh  # msg[v] = sum over parents u
        upd = _apply_bank(params["op_upd"], jnp.concatenate([hh, msg], axis=-1), cfg, ranges)
        sel = op_depth == d
        if sel_mask is not None:
            sel = sel & sel_mask
        return jnp.where(sel[..., None], upd, hh)

    if plan.kind == "scan":
        h, _ = jax.lax.scan(
            lambda hh, d: (full_step(hh, d), None),
            h,
            jnp.arange(1, plan.depth_max + 1, dtype=op_depth.dtype),
        )
        return h
    if plan.kind == "banded":
        # the kernel oracle owns the span geometry; the banked apply is
        # injected so >2-layer (unfusable, jnp-only) banks work too
        from repro.kernels.mp_update.ref import mp_update_ref

        mask_vec = (
            op_mask[..., 0] if op_mask is not None else jnp.ones(h.shape[:-1], jnp.float32)
        )
        for d, span, level_ranges, parent_hi in plan.levels:
            h = mp_update_ref(
                params["op_upd"],
                h,
                a_flow,
                op_depth,
                mask_vec,
                jnp.asarray(d, op_depth.dtype),
                level_ranges,
                row_span=span,
                parent_rows=parent_hi,
                apply_fn=nn.apply_mlp_bank_slotted,
            )
        return h
    assert plan.kind == "exact", plan.kind
    for level in plan.updates:
        cols = [s for s, _, _ in level]
        news = []
        for s, t, parents in level:
            msg = sum(h[:, p] for p in parents[1:]) + h[:, parents[0]]
            x = jnp.concatenate([h[:, s], msg], axis=-1)  # (B, 2H)
            news.append(nn.apply_mlp(_bank_member(params["op_upd"], t), x))
        h = h.at[:, jnp.asarray(cols)].set(jnp.stack(news, axis=1))
    return h


def _stages123(
    params: nn.Params,
    h_ops0: jax.Array,  # (..., O', H) per-graph states, or (O', H) shared skeleton
    h_hw0: jax.Array,  # (..., W', H) / (W', H) matching h_ops0
    a_place: jax.Array,  # (..., O', W'); a leading candidate axis when shared
    a_flow: jax.Array,  # (..., O', O') or shared (O', O')
    op_depth: jax.Array,  # (..., O') int
    cfg: GNNConfig,
    *,
    ranges,  # slot ranges (type, start, stop) in THIS layout
    plan: StagePlan,
    op_mask: Optional[jax.Array] = None,  # (..., O', 1) or None when no padded rows
    hw_mask: Optional[jax.Array] = None,  # (..., W', 1) or None when no padded rows
) -> jax.Array:
    """Stages 1-3 + readout: the single core behind every forward.

    Two calling conventions, told apart by rank: the *generic* one (training,
    bulk scoring) passes per-graph stage-0 states with the same batch rank as
    ``a_place``; the *placed* one passes the unbatched shared-skeleton states
    against a ``(B, O', W')`` candidate batch — stage-0 work is then reused
    across all candidates and only broadcast where a stage needs it.
    """
    shared_skeleton = h_ops0.ndim < a_place.ndim

    # stage 1: OPS -> HW
    if shared_skeleton:
        b = a_place.shape[0]
        msg_hw = jnp.einsum("bow,oh->bwh", a_place, h_ops0)
        hw_in = jnp.concatenate(
            [jnp.broadcast_to(h_hw0, (b,) + h_hw0.shape), msg_hw], axis=-1
        )
    else:
        msg_hw = jnp.einsum("...ow,...oh->...wh", a_place, h_ops0)
        hw_in = jnp.concatenate([jnp.broadcast_to(h_hw0, msg_hw.shape), msg_hw], axis=-1)
    h_hw = _apply_shared(params["hw_upd"], hw_in, cfg, "hw_upd")
    if hw_mask is not None:
        h_hw = h_hw * hw_mask

    # stage 2: HW -> OPS
    msg_ops = jnp.einsum("...ow,...wh->...oh", a_place, h_hw)
    if shared_skeleton:
        ops_in = jnp.concatenate(
            [jnp.broadcast_to(h_ops0, msg_ops.shape), msg_ops], axis=-1
        )
    else:
        ops_in = jnp.concatenate([h_ops0, msg_ops], axis=-1)
    h = _apply_bank(params["op_upd"], ops_in, cfg, ranges)
    if op_mask is not None:
        h = h * op_mask

    # stage 3: data-flow sweep per the static plan
    h = _dataflow_sweep(params, h, a_flow, op_depth, op_mask, cfg, ranges, plan)

    # readout: rows are pre-masked, sum over the node axes
    pooled = jnp.sum(h, axis=-2) + jnp.sum(h_hw, axis=-2)
    return nn.apply_mlp(params["out"], pooled)


def _trim_rows(g: JointGraph, rows: Tuple[int, ...]) -> JointGraph:
    """Statically gather ``rows`` out of the padded operator axis.

    The dropped rows hold no operator in ANY graph of the batch (the
    ``exact_banding`` trim contract): their states are masked to exact zero
    before every reduction, so removing them changes no prediction or
    gradient — it only removes their dense work.  Hardware rows stay
    untouched (MAX_HW is small and ``a_place`` columns are per-host).
    """
    idx = jnp.asarray(rows)
    return g._replace(
        op_x=jnp.take(g.op_x, idx, axis=-2),
        op_type=jnp.take(g.op_type, idx, axis=-1),
        op_mask=jnp.take(g.op_mask, idx, axis=-1),
        op_depth=jnp.take(g.op_depth, idx, axis=-1),
        a_flow=jnp.take(jnp.take(g.a_flow, idx, axis=-2), idx, axis=-1),
        a_place=jnp.take(g.a_place, idx, axis=-2),
    )


def apply_gnn_batch(
    params: nn.Params,
    g: JointGraph,
    cfg: GNNConfig,
    banding: Optional[BatchBanding] = None,
) -> jax.Array:
    """Forward for a padded graph (batch) -> (..., n_outputs).

    Rank-polymorphic: a single ``(N, .)`` graph or a ``(B, N, .)`` batch run
    the same code — banked MLPs execute ONCE across the whole padded batch
    (one launch per stage), not per-graph under vmap.  ``banding`` (from
    ``bucketing.batch_banding`` / ``exact_banding``, static per bucket or
    per signature set) replaces the full ``max_depth`` stage-3 scan with the
    FUSED depth sweep over its non-empty levels (``StagePlan("sweep")``: one
    ``kernels/mp_sweep`` call for the whole table; >2-layer update banks fall
    back to the per-level ``banded`` loop); a banding carrying a row trim
    additionally gathers the batch onto its all-graphs-active row subset and
    runs EVERY stage there (``banding.ranges`` are that layout's type runs).
    Without a banding the sweep falls back to the seed-equivalent full scan.
    ``cfg.use_pallas`` routes stages 0-2 through ``kernels/banked_mlp`` and
    stage 3 through ``kernels/mp_sweep``/``kernels/mp_update`` (see module
    docstring).
    """
    ranges = SLOT_RANGES
    if banding is not None and banding.rows is not None:
        g = _trim_rows(g, banding.rows)
        ranges = banding.ranges
    op_mask = g.op_mask[..., None]
    hw_mask = g.hw_mask[..., None]
    h_ops0 = _apply_bank(params["op_enc"], g.op_x, cfg, ranges) * op_mask
    h_hw0 = _apply_shared(params["hw_enc"], g.hw_x, cfg, "hw_enc") * hw_mask
    plan = (
        StagePlan("scan", depth_max=cfg.max_depth)
        if banding is None
        else _banded_plan(
            banding, ranges, kind="sweep" if _sweep_fusable(params) else "banded"
        )
    )
    return _stages123(
        params,
        h_ops0,
        h_hw0,
        g.a_place,
        g.a_flow,
        g.op_depth,
        cfg,
        ranges=ranges,
        plan=plan,
        op_mask=op_mask,
        hw_mask=hw_mask,
    )


def apply_gnn(
    params: nn.Params,
    g: JointGraph,
    cfg: GNNConfig,
    banding: Optional[BatchBanding] = None,
) -> jax.Array:
    """Forward pass for ONE graph -> (n_outputs,); same engine as the batch."""
    return apply_gnn_batch(params, g, cfg, banding)


def apply_gnn_stacked(
    params: nn.Params,
    g: JointGraph,
    cfg: GNNConfig,
    banding: Optional[BatchBanding] = None,
) -> jax.Array:
    """ONE forward for member-stacked params over a shared graph batch.

    ``params`` leaves carry a leading member axis (an ensemble's members, or
    several metrics' ensembles concatenated by ``serve.stacking.stack_metric_models``);
    returns ``(members, B)`` raw outputs.  The batch — including its banding
    plan — is shared across members, so a training step issues one stacked
    forward instead of one per member.
    """
    return jax.vmap(lambda p: apply_gnn_batch(p, g, cfg, banding))(params)[..., 0]


def validate_merged_parents(a_flow, max_parents: int, what: str = "skeleton stack") -> None:
    """Raise when any row's data-flow in-degree exceeds ``max_parents``.

    The merged engine's parent tables keep only the top ``max_parents``
    entries of each ``a_flow`` column (``argsort(-flow_in)[..., :P]``): a row
    with more parents would have them silently dropped and the stage-3 sums
    would be WRONG, not slow.  Host-side (concrete arrays only) — the
    estimator calls it at merged-group build time, and ``apply_gnn_merged``
    re-checks eager concrete inputs for direct callers.
    """
    indeg = np.asarray(a_flow).sum(axis=-2)
    worst = int(indeg.max(initial=0))
    if worst > max_parents:
        loc = tuple(int(v) for v in np.argwhere(indeg > max_parents)[0])
        raise ValueError(
            f"merged cross-query engine: {what} row {loc} has data-flow "
            f"in-degree {worst} > max_parents={max_parents}; the parent-table "
            "gather would silently drop parents and return wrong sums. Pass "
            "max_parents >= the stack's true maximum in-degree "
            "(a_flow.sum(axis=-2).max(), as serve.estimator derives it)."
        )


def apply_gnn_merged(
    params: nn.Params,
    skels: JointGraph,  # (S, N, .) stacked skeletons (``a_place`` ignored)
    skel_id: jax.Array,  # (B,) int: row -> skeleton
    a_place: jax.Array,  # (B, N, W) one-hot placement adjacency per row
    cfg: GNNConfig,
    banding: BatchBanding,
    max_parents: int = 2,
) -> jax.Array:
    """ONE member-stacked forward over candidates of S DISTINCT structures.

    The cross-query serving engine: a merged drain's rows reference their
    structure through ``skel_id`` instead of materializing per-row skeleton
    copies, and the graph's sparsity is static — every operator has at most
    ``max_parents`` data-flow parents and exactly one host — so the
    aggregations that the generic batched engine expresses as per-graph
    adjacency matmuls (batched tiny GEMMs, dispatch-bound on CPU backends)
    become gathers and W-unrolled masked sums:

      * stage 0 runs on the S unique skeletons and is *gathered* per row —
        candidates of one structure never re-encode its operators;
      * stage 1 (OPS->HW) is a per-row segment scatter-add: each host state
        accumulates the operator states placed on it;
      * stage 2 (HW->OPS) gathers each operator's single host state;
      * stage 3 levels gather each in-span row's ``max_parents`` parent
        states (per-skeleton parent tables, built once from ``a_flow``) and
        run the banked update at the banding's static ``row_span``.

    Numerically equal to ``apply_gnn_stacked`` on the expanded broadcast
    batch to float tolerance (same sums, different association — the
    mixed-stream parity tests pin it).  The gathers/scatters route through
    ``kernels/seg_gather`` (one-hot SpMM kernels on TPU, the very same
    take_along_axis / scatter-add formulations on the jnp ref lowering), so
    ``use_pallas`` configs are served by this engine too — the banked MLPs
    then run through ``kernels/banked_mlp`` like every other path.
    ``banding`` must come from ``bucketing.exact_banding_cached`` over
    ``skels`` (signature sets are padding-invariant, so it also covers every
    chunk of the batch).  Returns ``(members, B)`` raw outputs.
    """
    from repro.kernels.seg_gather import ops as seg_ops

    try:
        flow_host = np.asarray(skels.a_flow)  # concrete (eager) inputs only
    except Exception:  # traced under jit: the estimator validated at group build
        flow_host = None
    if flow_host is not None:
        validate_merged_parents(flow_host, max_parents)
    ranges = SLOT_RANGES
    if banding.rows is not None:
        skels = _trim_rows(skels, banding.rows)
        a_place = jnp.take(a_place, jnp.asarray(banding.rows), axis=-2)
        ranges = banding.ranges
    plan = _banded_plan(banding, ranges)
    n_hw = skels.hw_x.shape[-2]

    # static sparsity, derived once per trace: parent tables per skeleton
    # (columns of a_flow hold each row's parents) and one host per row
    flow_in = jnp.swapaxes(skels.a_flow, -1, -2)  # (S, N, N): [v, u] = u -> v
    pidx = jnp.argsort(-flow_in, axis=-1)[..., :max_parents]  # (S, N, P)
    pmask = jnp.take_along_axis(flow_in, pidx, axis=-1)  # (S, N, P) in {0,1}
    row_pidx = pidx[skel_id]  # (B, N, P)
    row_pmask = pmask[skel_id]  # (B, N, P)
    host = jnp.argmax(a_place, axis=-1)  # (B, N)
    placed = jnp.max(a_place, axis=-1)[..., None]  # (B, N, 1): 0 for padded rows
    op_mask_s = skels.op_mask[..., None]  # (S, N, 1)
    hw_mask_b = skels.hw_mask[skel_id][..., None]  # (B, W, 1)
    op_mask_b = op_mask_s[skel_id]  # (B, N, 1)
    depth_b = skels.op_depth[skel_id]  # (B, N)

    def member_fwd(pp):
        # stage 0 on the S skeletons only, gathered out per candidate row
        h_ops_s = _apply_bank(pp["op_enc"], skels.op_x, cfg, ranges) * op_mask_s
        h_hw_s = _apply_shared(pp["hw_enc"], skels.hw_x, cfg, "hw_enc") * skels.hw_mask[..., None]
        h0 = h_ops_s[skel_id]  # (B, N, H)
        hw0 = h_hw_s[skel_id]  # (B, W, H)

        # stage 1: hosts absorb their operators (segment scatter-add per row)
        msg_hw = seg_ops.segment_sum(h0 * placed, host, n_hw)  # (B, W, H)
        h_hw = _apply_shared(pp["hw_upd"], jnp.concatenate([hw0, msg_hw], -1), cfg, "hw_upd")
        h_hw = h_hw * hw_mask_b

        # stage 2: operators absorb their single host's state (gather, P=1)
        msg_ops = seg_ops.gather_sum(h_hw, host[..., None], placed)
        h = _apply_bank(pp["op_upd"], jnp.concatenate([h0, msg_ops], -1), cfg, ranges)
        h = h * op_mask_b

        # stage 3: banded levels; parents gathered, never contracted
        for d, (s, e), level_ranges, _ in plan.levels:
            msg = seg_ops.gather_sum(h, row_pidx[:, s:e], row_pmask[:, s:e])
            z = jnp.concatenate([h[:, s:e], msg], axis=-1)
            shifted = tuple((t, a - s, b - s) for t, a, b in level_ranges)
            upd = _apply_bank(pp["op_upd"], z, cfg, shifted)
            sel = ((depth_b[:, s:e] == d) & (op_mask_b[:, s:e, 0] > 0))[..., None]
            h = h.at[:, s:e].set(jnp.where(sel, upd, h[:, s:e]))

        pooled = jnp.sum(h, axis=-2) + jnp.sum(h_hw, axis=-2)
        return nn.apply_mlp(pp["out"], pooled)[..., 0]

    return jax.vmap(member_fwd)(params)


def apply_gnn_placed(
    params: nn.Params,
    skel: JointGraph,
    a_place: jax.Array,
    static: QueryStatic,
    cfg: GNNConfig,
) -> jax.Array:
    """Placement-batch forward: one query, ``(B, O, W)`` candidate placements.

    Numerically identical to ``apply_gnn_batch`` on the broadcast batch (the
    parity tests in tests/test_placement.py pin this), but exploits that every
    candidate shares the skeleton:

      * stage 0 encoders run ONCE on the unbatched skeleton (placement-
        invariant) and are broadcast, not recomputed per candidate;
      * the stage-3 data-flow sweep only touches depth levels the query
        actually has (``static.updates``): on the jnp path each level updates
        just the slots holding an operator at that depth (narrow matmuls); on
        the Pallas path each level is one fused ``mp_update`` launch.

    ``cfg.use_pallas`` is honored on every stage: the stage-0 encoders and
    stage-1/2 updates route through ``kernels/banked_mlp`` (the shared
    hardware MLPs as single-type banks) and the stage-3 sweep through
    ``kernels/mp_update``.  The kernel ops pick a lowering per backend —
    Pallas on TPU, the jnp oracle elsewhere, ``REPRO_PALLAS_INTERPRET=1``
    forces the interpreter (see ``kernels.active_lowering``).  The readout
    MLP stays jnp by design — one tiny dense GEMM with no banked/slotted
    structure for the kernels to fuse. Configs the kernels cannot fuse raise
    loudly instead of silently falling back (see ``_require_fusable``).
    """
    op_mask = skel.op_mask[:, None]  # (O,1)
    hw_mask = skel.hw_mask[:, None]  # (W,1)

    # stage 0: shared across candidates
    h_ops0 = _apply_bank(params["op_enc"], skel.op_x, cfg) * op_mask
    h_hw0 = _apply_shared(params["hw_enc"], skel.hw_x, cfg, "hw_enc") * hw_mask

    # full padded layout: no contiguous spans available, full-width levels
    plan = StagePlan(
        "exact",
        levels=tuple(
            (d, None, SLOT_RANGES, None)
            for d, level in enumerate(static.updates, start=1)
            if level
        ),
        updates=static.updates,
    )
    return _stages123(
        params,
        h_ops0,
        h_hw0,
        a_place,
        skel.a_flow,
        skel.op_depth,
        cfg,
        ranges=SLOT_RANGES,
        plan=plan,
        op_mask=op_mask,
        hw_mask=hw_mask,
    )


def _slot_type(slot: int) -> int:
    for t, start, stop in SLOT_RANGES:
        if start <= slot < stop:
            return t
    raise ValueError(f"slot {slot} outside SLOT_RANGES")


def _type_runs(order, offset: int = 0):
    """Maximal runs of equal node type over ``order`` as (type, start, stop)."""
    runs = []
    for i, s in enumerate(order):
        t = _slot_type(s)
        if runs and runs[-1][0] == t:
            runs[-1][2] = offset + i + 1
        else:
            runs.append([t, offset + i, offset + i + 1])
    return tuple(tuple(r) for r in runs)


def _trimmed_layout(static: QueryStatic):
    """Trace-time remap of the padded slot layout to active slots only,
    ordered by (depth, slot).

    Depth-major order makes every stage-3 level one CONTIGUOUS row span, so
    the Pallas ``mp_update`` can statically restrict each depth step to the
    rows it actually updates (``row_span``); within a level, slot order keeps
    same-type operators adjacent, so banked MLPs still see few type runs.
    Returns (order: slot ids, ranges: type runs over the whole order,
    updates: stage-3 updates remapped to row positions, levels: per nonempty
    depth level (d, (start, stop) row span, type runs inside the span,
    parent-row bound)).
    """
    depth_of = {s: 0 for s in static.active}
    for d, level in enumerate(static.updates, start=1):
        for s, _, _ in level:
            depth_of[s] = d
    order = sorted(static.active, key=lambda s: (depth_of[s], s))
    pos = {s: i for i, s in enumerate(order)}
    updates = tuple(
        tuple((pos[s], t, tuple(pos[p] for p in parents)) for s, t, parents in level)
        for level in static.updates
    )
    levels = []
    for d, level in enumerate(static.updates, start=1):
        if not level:
            continue
        rows = sorted(pos[s] for s, _, _ in level)
        assert rows == list(range(rows[0], rows[-1] + 1)), "level not contiguous"
        span = (rows[0], rows[-1] + 1)
        # parents have strictly smaller depth, i.e. strictly earlier rows
        levels.append((d, span, _type_runs(order[span[0] : span[1]], offset=span[0]), span[0]))
    return tuple(order), _type_runs(order), updates, tuple(levels)


def trimmed_columns(static: QueryStatic, slots) -> Tuple[int, ...]:
    """The operator id behind each row of the trimmed layout.

    ``slots`` is ``graph.slot_index(query)`` (operator id -> padded slot);
    its inverse maps ``_trimmed_layout(static)``'s slot order to columns of
    the query's ``(B, n_ops)`` assignment matrix.  A tuple of ints, so it
    can join a trace key beside ``static``.
    """
    op_of = {int(s): op for op, s in enumerate(slots)}
    return tuple(op_of[s] for s in _trimmed_layout(static)[0])


def apply_gnn_placed_stacked(
    params: nn.Params,
    skel: JointGraph,
    a_place: jax.Array,
    static: QueryStatic,
    cfg: GNNConfig,
    n_hw: int,
    chunk: Optional[int] = None,
) -> jax.Array:
    """ONE forward for a whole stack of ensembles: ``params`` leaves carry a
    leading member axis (ensemble members x metrics, see
    ``serve.stacking.stack_metric_models``); returns ``(members, B)`` raw outputs.

    Beyond fusing the per-(metric, member) launches of ``apply_gnn_placed``
    into one vmapped call per stage, the restructure buys two things the
    per-metric path cannot express:

      * **slot trimming** — every stage runs on the ``len(static.active)``
        slots that hold a real operator and the ``n_hw`` real hosts, not the
        MAX_OPS/MAX_HW padded layout: the padded rows are provably zero
        (masked before every reduction), so dropping them changes no
        prediction while cutting the wasted dense FLOPs;
      * **batch chunking** — with all members resident at once, the candidate
        axis is scanned in ``chunk``-sized panels so the per-stage activation
        working set stays cache-resident on CPU-class backends (a no-op for
        ``B <= chunk``; pass ``chunk=0`` to disable).  ``chunk=None`` (the
        default) reads the active ``DispatchPolicy``'s ``score_chunk`` —
        callers that thread an explicit policy (the serving facade) pass the
        width themselves.

    ``cfg.use_pallas`` routes through the same kernels as
    ``apply_gnn_placed``, with the trimmed type runs as the kernels' slot
    layout and each stage-3 depth level as a static ``row_span`` for
    ``mp_update`` (the depth-major trimmed order makes levels contiguous).

    ``a_place`` is the ``(B, MAX_OPS, MAX_HW)`` one-hot placement batch;
    ``apply_gnn_placed_stacked_idx`` is the same forward over host indices.
    """
    idx = jnp.asarray(_trimmed_layout(static)[0])
    return _placed_stacked(
        params, skel, a_place, lambda ap: ap[:, idx, :n_hw], static, cfg, n_hw, chunk
    )


def apply_gnn_placed_stacked_idx(
    params: nn.Params,
    skel: JointGraph,
    assign: jax.Array,
    cols: Tuple[int, ...],
    static: QueryStatic,
    cfg: GNNConfig,
    n_hw: int,
    chunk: Optional[int] = None,
) -> jax.Array:
    """``apply_gnn_placed_stacked`` over the ``(B, n_ops)`` int matrix of
    each candidate's host per operator (``cols = trimmed_columns(static,
    slot_index(query))``).  The trimmed one-hot ``a[b, j, h] =
    (assign[b, cols[j]] == h)`` is built on the device, one panel at a time:
    the same exact 0/1 values the one-hot entry reads, so the outputs are
    bit-identical.  Host ids must lie in ``[0, n_hw)``; the caller checks
    (``graph.check_host_range``)."""
    cols_idx = jnp.asarray(cols)
    hosts = jnp.arange(n_hw, dtype=assign.dtype)

    def one_hot(a):
        return (a[:, cols_idx, None] == hosts).astype(jnp.float32)

    return _placed_stacked(params, skel, assign, one_hot, static, cfg, n_hw, chunk)


def _placed_stacked(params, skel, cands, trim, static, cfg, n_hw, chunk):
    """The body of both stacked placed entries: ``cands`` is the per-candidate
    input (leading axis B), ``trim`` maps a panel of it to the ``(b, n, n_hw)``
    trimmed placement adjacency."""
    if chunk is None:
        from repro.serve.policy import active_policy  # lazy: core never pulls serve at import

        chunk = active_policy().score_chunk
    order, ranges, updates, levels = _trimmed_layout(static)
    idx = jnp.asarray(order)
    op_x = skel.op_x[idx]  # (n, F)
    hw_x = skel.hw_x[:n_hw]  # (n_hw, F_hw)
    a_flow = skel.a_flow[idx][:, idx]  # (n, n)
    op_depth = skel.op_depth[idx]  # (n,)
    B = cands.shape[0]
    plan = StagePlan("exact", levels=levels, updates=updates)

    # stage 0 is placement-invariant: once per member, outside the chunk scan
    def stage0(pp):
        return (
            _apply_bank(pp["op_enc"], op_x, cfg, ranges),
            _apply_shared(pp["hw_enc"], hw_x, cfg, "hw_enc"),
        )

    h0_ops, h0_hw = jax.vmap(stage0)(params)  # (E, n, H), (E, n_hw, H)

    def member_fwd(pp, h_ops0, h_hw0, ap):
        return _stages123(
            pp, h_ops0, h_hw0, ap, a_flow, op_depth, cfg, ranges=ranges, plan=plan
        )[..., 0]

    vmapped = jax.vmap(member_fwd, in_axes=(0, 0, 0, None))

    def fwd(panel):
        return vmapped(params, h0_ops, h0_hw, trim(panel))  # (E, b)

    if chunk and B > chunk and B % chunk == 0:
        panels = cands.reshape(B // chunk, chunk, *cands.shape[1:])
        _, outs = jax.lax.scan(lambda carry, c: (carry, fwd(c)), None, panels)
        return outs.transpose(1, 0, 2).reshape(outs.shape[1], B)  # outs: (B/chunk, E, chunk)
    return fwd(cands)


# ---------------------------------------------------------------------------
# Exp 7b ablation: "traditional" message passing — every node is updated from
# all of its neighbors each round, regardless of node type and stage ordering.
# ---------------------------------------------------------------------------


def apply_gnn_traditional(
    params: nn.Params, g: JointGraph, cfg: GNNConfig, n_rounds: int = 3
) -> jax.Array:
    op_mask = g.op_mask[:, None]
    hw_mask = g.hw_mask[:, None]

    h_ops = _apply_bank(params["op_enc"], g.op_x, cfg) * op_mask
    h_hw = _apply_shared(params["hw_enc"], g.hw_x, cfg, "hw_enc") * hw_mask

    # symmetric adjacency: data flow (both directions) + placement (both ways)
    a_sym = g.a_flow + g.a_flow.T  # (O,O)

    def round_step(carry, _):
        h_o, h_w = carry
        msg_o = a_sym @ h_o + g.a_place @ h_w
        msg_w = g.a_place.T @ h_o
        h_o2 = (
            _apply_bank(params["op_upd"], jnp.concatenate([h_o, msg_o], axis=-1), cfg)
            * op_mask
        )
        h_w2 = (
            _apply_shared(params["hw_upd"], jnp.concatenate([h_w, msg_w], axis=-1), cfg, "hw_upd")
            * hw_mask
        )
        return (h_o2, h_w2), None

    (h_ops, h_hw), _ = jax.lax.scan(round_step, (h_ops, h_hw), None, length=n_rounds)
    pooled = jnp.sum(h_ops * op_mask, axis=0) + jnp.sum(h_hw * hw_mask, axis=0)
    return nn.apply_mlp(params["out"], pooled)
