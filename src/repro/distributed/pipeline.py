"""Pipeline parallelism: GPipe-style microbatch pipeline under shard_map.

Stages live on a ``pipe`` mesh axis; activations move stage-to-stage with
collective_permute. The schedule is the classic fill-run-drain loop: with M
microbatches and K stages the bubble fraction is (K-1)/(M+K-1). Used for the
very deep assigned archs (deepseek-67b: 95 layers) as an alternative to pure
FSDP+TP when cross-slice bandwidth is scarce — see EXPERIMENTS.md SPerf for
the measured trade-off on the dry-run.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_forward(
    stage_fn: Callable[[Any, jax.Array], jax.Array],  # (stage_params, x) -> y
    mesh,
    pipe_axis: str = "pipe",
):
    """Returns pipelined(params_stacked, x_microbatched).

    params_stacked: leaves with leading dim = n_stages (sharded over pipe).
    x_microbatched: (M, mb, ...) microbatches, replicated into every stage;
    stage k processes microbatch m at tick t = m + k.
    Output: (M, mb, ...) final-stage outputs.
    """
    n_stages = mesh.shape[pipe_axis]

    def run(params, xs):
        # params: stage-local slice (leading dim 1) after shard_map split
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        k = jax.lax.axis_index(pipe_axis)
        M = xs.shape[0]
        ticks = M + n_stages - 1
        buf = jnp.zeros_like(xs[0])  # current activation held by this stage
        outs = jnp.zeros_like(xs)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any); others use permuted input
            x_in = jnp.where(k == 0, xs[jnp.minimum(t, M - 1)], buf)
            active = (t - k >= 0) & (t - k < M)
            y = stage_fn(params, x_in)
            y = jnp.where(active, y, buf)
            # last stage writes its finished microbatch
            outs = jax.lax.cond(
                active & (k == n_stages - 1),
                lambda o: o.at[jnp.clip(t - k, 0, M - 1)].set(y),
                lambda o: o,
                outs,
            )
            # shift activations downstream: stage k -> k+1
            nxt = jax.lax.ppermute(
                y, pipe_axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            return nxt, outs

        buf, outs = jax.lax.fori_loop(0, ticks, tick, (buf, outs))
        # only the last stage holds real outputs; broadcast them to all stages
        outs = jax.lax.ppermute(
            outs,
            pipe_axis,
            [((n_stages - 1 + i) % n_stages, i) for i in range(n_stages)],
        ) if n_stages > 1 else outs
        return outs

    return jax.shard_map(
        run,
        mesh=mesh,
        in_specs=(P(pipe_axis), P()),
        out_specs=P(),
        check_vma=False,
    )
