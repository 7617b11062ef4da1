"""Compile the main path's kernels and programs for a TPU v5e, without a chip.

The TPU compiler is installed with JAX: it compiles for a described ``v5e``
topology that is not attached, and refuses what the chip's compiler would
refuse (unaligned Mosaic layouts, too much VMEM).  Interpret-mode tests
cannot see those failures.  Nothing runs here, so these tests say nothing
about results or times.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and the suite runs under several
workers.  The persistent compilation cache is off around these compiles (a
described-device executable cannot be read back without a chip).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.bucketing import bucket_size, exact_banding_cached
from repro.core.features import OP_FEATURE_DIM
from repro.core.gnn import GNNConfig, _banded_plan, _clip_ranges, trimmed_columns
from repro.core.graph import (
    SLOT_RANGES,
    batch_graphs,
    build_graph_skeleton,
    query_static,
    slot_index,
)
from repro.core.model import CostModelConfig, ensemble_loss, init_cost_model
from repro.dsps.generator import WorkloadGenerator
from repro.kernels.banked_mlp.kernel import banked_mlp_slotted_pallas
from repro.kernels.mp_sweep.kernel import mp_sweep_pallas
from repro.kernels.mp_update.kernel import mp_update_pallas
from repro.kernels.seg_gather.kernel import gather_sum_pallas, segment_sum_pallas
from repro.training import optim
from repro.training.batching import bucket_dataset, bucketed_batches, dataset_from_traces

HIDDEN = 64
MEMBERS = 15  # five metrics x three-member ensembles, stacked for serving
N_OPS, N_HW = 12, 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()
        mp.undo()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _specs(sharding, tree):
    """Shapes (with the described device) of a pytree of arrays or shapes."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=sharding), tree
    )


def _compile(fn, *args, **jit_kw):
    """Compile for the described chip; return the compiled program's text."""
    return jax.jit(fn, **jit_kw).lower(*args).compile().as_text()


def _structures(n, seed=11):
    gen = WorkloadGenerator(seed=seed)
    kinds = ("linear", "two_way", "three_way")
    return [(gen.query(kind=kinds[i % 3], name=f"c{i}"), gen.cluster()) for i in range(n)]


def _stacked_params(one_chip, cfg: GNNConfig, members: int = MEMBERS):
    model = CostModelConfig(gnn=cfg, n_ensemble=members)
    return _specs(one_chip, jax.eval_shape(lambda: init_cost_model(jax.random.PRNGKey(0), model)))


# -- seg_gather: the merged engine's gathers and scatters ---------------------------


@pytest.mark.parametrize("rows", [8, 256])
@pytest.mark.parametrize("n_parents", [1, 3])
@pytest.mark.parametrize("members", [None, MEMBERS])
def test_gather_sum_compiles(one_chip, rows, n_parents, members):
    h = _spec(one_chip, (rows, N_OPS, HIDDEN))
    idx = _spec(one_chip, (rows, 7, n_parents), jnp.int32)
    w = _spec(one_chip, (rows, 7, n_parents))

    def fn(h, idx, w):
        return gather_sum_pallas(h, idx, w, tile_b=min(rows, 128), interpret=False)

    if members is not None:  # the members' states, one parent table
        h = _spec(one_chip, (members, rows, N_OPS, HIDDEN))
        fn = jax.vmap(fn, in_axes=(0, None, None))
    assert "tpu_custom_call" in _compile(fn, h, idx, w)


@pytest.mark.parametrize("rows", [8, 256])
@pytest.mark.parametrize("members", [None, MEMBERS])
def test_segment_sum_compiles(one_chip, rows, members):
    x = _spec(one_chip, (rows, N_OPS, HIDDEN))
    seg = _spec(one_chip, (rows, N_OPS), jnp.int32)

    def fn(x, seg):
        return segment_sum_pallas(x, seg, N_HW, tile_b=min(rows, 128), interpret=False)

    if members is not None:
        x = _spec(one_chip, (members, rows, N_OPS, HIDDEN))
        fn = jax.vmap(fn, in_axes=(0, None))
    assert "tpu_custom_call" in _compile(fn, x, seg)


def test_merged_forward_compiles_with_kernels(one_chip, monkeypatch):
    """The estimator's whole merged cross-query forward, as the chip runs it:
    the lowering forced to Pallas where the ops read it and in the trace key."""
    from repro.kernels.seg_gather import ops as seg_ops
    from repro.serve.estimator import _jitted_merged_forward

    monkeypatch.setattr(seg_ops, "_lowering", lambda: "pallas")
    structs = _structures(16)
    skels = batch_graphs([build_graph_skeleton(q, c) for q, c in structs])
    banding = exact_banding_cached(skels)
    max_parents = int(np.asarray(skels.a_flow).sum(axis=-2).max(initial=1))
    rows = bucket_size(16 * 5)
    fwd = _jitted_merged_forward(GNNConfig(hidden=HIDDEN), banding, max_parents, "pallas", True)
    text = (
        fwd.lower(
            _stacked_params(one_chip, GNNConfig(hidden=HIDDEN)),
            _specs(one_chip, skels),
            _spec(one_chip, (rows,), jnp.int32),
            _spec(one_chip, (rows, N_OPS, N_HW)),
        )
        .compile()
        .as_text()
    )
    assert "tpu_custom_call" in text


# -- the other kernels ---------------------------------------------------------------


@pytest.mark.parametrize("f_in", [OP_FEATURE_DIM, 2 * HIDDEN])
def test_banked_mlp_compiles(one_chip, f_in):
    t = len(SLOT_RANGES)
    params = {
        "layers": [
            {"w": _spec(one_chip, (t, f_in, HIDDEN)), "b": _spec(one_chip, (t, HIDDEN))},
            {"w": _spec(one_chip, (t, HIDDEN, HIDDEN)), "b": _spec(one_chip, (t, HIDDEN))},
        ]
    }

    def fn(p, x):
        return banked_mlp_slotted_pallas(p, x, SLOT_RANGES, tile_b=128, interpret=False)

    assert "tpu_custom_call" in _compile(fn, params, _spec(one_chip, (256, N_OPS, f_in)))


def _update_bank(one_chip):
    t = len(SLOT_RANGES)
    return {
        "layers": [
            {"w": _spec(one_chip, (t, 2 * HIDDEN, HIDDEN)), "b": _spec(one_chip, (t, HIDDEN))},
            {"w": _spec(one_chip, (t, HIDDEN, HIDDEN)), "b": _spec(one_chip, (t, HIDDEN))},
        ]
    }


def _stage3_operands(one_chip, rows=256, n=N_OPS):
    return (
        _spec(one_chip, (rows, n, HIDDEN)),
        _spec(one_chip, (rows, n, n)),
        _spec(one_chip, (rows, n), jnp.int32),
        _spec(one_chip, (rows, n)),
    )


@pytest.mark.parametrize("exact", [False, True])
def test_mp_sweep_compiles(one_chip, exact):
    """The fused stage-3 sweep at the levels of the deepest training bucket
    (``exact``: a signature-exact banding, rows trimmed to the active ones)."""
    traces = WorkloadGenerator(seed=3).corpus(200)
    _, buckets = bucket_dataset(dataset_from_traces(traces, "latency_p"), exact=exact)
    banding = max((b.banding for b in buckets), key=lambda b: len(b.levels))
    trimmed = banding.rows is not None
    levels = _banded_plan(banding, banding.ranges if trimmed else SLOT_RANGES).levels
    assert len(levels) > 1

    def fn(p, h, a, d, m):
        return mp_sweep_pallas(p, h, a, d, m, levels, tile_b=128, interpret=False)

    n = len(banding.rows) if trimmed else N_OPS
    args = _stage3_operands(one_chip, n=n)
    assert "tpu_custom_call" in _compile(fn, _update_bank(one_chip), *args)


def test_mp_update_compiles(one_chip):
    def fn(p, h, a, d, m, dd):
        return mp_update_pallas(
            p, h, a, d, m, dd, _clip_ranges(SLOT_RANGES, 3, 9), tile_b=128,
            interpret=False, row_span=(3, 9), parent_rows=7,
        )

    args = _stage3_operands(one_chip) + (_spec(one_chip, (), jnp.int32),)
    assert "tpu_custom_call" in _compile(fn, _update_bank(one_chip), *args)


# -- whole programs of the main path ---------------------------------------------------


def test_placed_forward_compiles_for_1024_candidates(one_chip):
    """Initial placement: the stacked per-structure forward, 1,024 candidates."""
    from repro.serve.estimator import _jitted_placed_forward_stacked

    (q, c), = _structures(1, seed=5)
    skel = build_graph_skeleton(q, c)
    static = query_static(q)
    fwd = _jitted_placed_forward_stacked(
        GNNConfig(hidden=HIDDEN), static, trimmed_columns(static, slot_index(q)),
        c.n_nodes(), 256, "ref",
    )
    fwd.lower(
        _stacked_params(one_chip, GNNConfig(hidden=HIDDEN)),
        _specs(one_chip, skel),
        _spec(one_chip, (1024, q.n_ops()), jnp.int32),
    ).compile()


def test_training_step_compiles_at_batch_512(one_chip):
    """One training step as ``launch/train.py`` runs it: batch 512, a
    signature-exact banding, a 3-member ensemble, Adam."""
    traces = WorkloadGenerator(seed=42).corpus(600)
    ds, buckets = bucket_dataset(dataset_from_traces(traces, "latency_p"), exact=True)
    g, y, banding = next(bucketed_batches(ds, buckets, 512))
    cfg = CostModelConfig(metric="latency_p", gnn=GNNConfig(hidden=HIDDEN), n_ensemble=3)
    opt = optim.adam(lr=1.5e-3, weight_decay=1e-5, max_grad_norm=5.0)
    params = jax.eval_shape(lambda: init_cost_model(jax.random.PRNGKey(0), cfg))
    opt_state = jax.eval_shape(opt.init, params)

    def step(params, opt_state, g, y):
        loss, grads = jax.value_and_grad(lambda p: ensemble_loss(p, g, y, cfg, banding))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optim.apply_updates(params, updates), opt_state, loss

    _compile(
        step,
        _specs(one_chip, params),
        _specs(one_chip, opt_state),
        _specs(one_chip, g),
        _specs(one_chip, y),
        donate_argnums=(0, 1),
    )
