"""Training loops for the COSTREAM cost models + the flat-vector baseline.

The same ``train_cost_model`` drives the single-host CPU path and the SPMD
mesh path: graph batches are sharded over the (pod, data) axes, the stacked
ensemble over ``model``.  Training consumes the unified GNN engine
(docs/forward_engine.md): epochs iterate (n_ops, depth) buckets whose static
``BatchBanding`` keys the jitted step's trace cache, and each step issues ONE
stacked forward for all ensemble members.  Optional gradient compression
(top-k error feedback or int8) is applied in the DP reduction path under
shard_map. Checkpoints are written atomically every ``ckpt_every`` steps;
``resume=True`` continues from the newest one (fault tolerance).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.flat_vector import (
    FlatVectorConfig,
    forward_flat,
    init_flat_model,
)
from repro.core.graph import batch_banding
from repro.core.model import (
    CostModelConfig,
    bce_loss,
    ensemble_loss,
    init_cost_model,
    msle_loss,
)
from repro.training import optim
from repro.training.batching import (
    GraphDataset,
    bucket_dataset,
    bucketed_batches,
    n_batches,
    prefetch,
)
from repro.training.checkpoint import restore_checkpoint, save_checkpoint
from repro.training.compression import (
    EFState,
    ef_init,
    int8_roundtrip,
    topk_with_error_feedback,
)


@dataclass
class TrainConfig:
    epochs: int = 30
    batch_size: int = 256
    lr: float = 1e-3
    weight_decay: float = 1e-5
    max_grad_norm: float = 5.0
    seed: int = 0
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 200
    resume: bool = False
    compression: Optional[str] = None  # None | "topk" | "int8"
    # signature-exact row-trimmed stage-3 bands (one trace per distinct query
    # signature instead of per depth class) — worth it for large fixed
    # corpora where every signature class dwarfs a batch (launch/train.py)
    exact_banding: bool = False
    # cap on optimizer steps over the whole run (None: every epoch runs)
    max_steps: Optional[int] = None
    topk_frac: float = 0.05
    early_stop_patience: int = 6
    log_every: int = 50
    verbose: bool = False


@dataclass
class TrainResult:
    params: object
    history: List[Dict[str, float]]
    best_val: float
    steps: int


def _maybe_compress(grads, ef, key, cfg: TrainConfig):
    if cfg.compression == "topk":
        grads, ef, _ = topk_with_error_feedback(grads, ef, cfg.topk_frac)
    elif cfg.compression == "int8":
        grads = int8_roundtrip(grads, key)
    return grads, ef


def train_cost_model(
    dataset_train: GraphDataset,
    dataset_val: GraphDataset,
    model_cfg: CostModelConfig,
    train_cfg: TrainConfig = TrainConfig(),
    init_params=None,
) -> TrainResult:
    key = jax.random.PRNGKey(train_cfg.seed)
    key, init_key = jax.random.split(key)
    params = init_params if init_params is not None else init_cost_model(init_key, model_cfg)

    # bucket once: every epoch then iterates depth-major buckets whose static
    # banding keys the jitted step's trace cache — (n_ops, depth) classes by
    # default, per-signature exact bands under ``exact_banding``
    dataset_train, buckets = bucket_dataset(dataset_train, exact=train_cfg.exact_banding)
    steps_per_epoch = max(1, n_batches(buckets, train_cfg.batch_size))
    total = steps_per_epoch * train_cfg.epochs
    if train_cfg.max_steps is not None:
        total = min(total, train_cfg.max_steps)
    opt = optim.adam(
        lr=optim.cosine_schedule(train_cfg.lr, total, warmup_steps=min(100, total // 10)),
        weight_decay=train_cfg.weight_decay,
        max_grad_norm=train_cfg.max_grad_norm,
    )
    opt_state = opt.init(params)
    ef = ef_init(params)

    start_step = 0
    if train_cfg.resume and train_cfg.ckpt_dir:
        restored, step, _ = restore_checkpoint(
            train_cfg.ckpt_dir, (params, opt_state, ef)
        )
        if restored is not None:
            params, opt_state, ef = restored
            start_step = int(step)

    # ``banding`` is the bucket's static stage-3 plan: part of the jit cache
    # key (one trace per bucket), not a traced operand.  The loss runs ONE
    # stacked engine forward for all ensemble members.
    @partial(jax.jit, static_argnums=(6,), donate_argnums=(0, 1, 2))
    def train_step(params, opt_state, ef, g, y, key, banding):
        def loss(p):
            return ensemble_loss(p, g, y, model_cfg, banding)

        loss_val, grads = jax.value_and_grad(loss)(params)
        grads, ef = _maybe_compress(grads, ef, key, train_cfg)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optim.apply_updates(params, updates)
        return params, opt_state, ef, loss_val

    @partial(jax.jit, static_argnums=(3,))
    def val_loss_fn(params, g, y, banding):
        return ensemble_loss(params, g, y, model_cfg, banding) / model_cfg.n_ensemble

    rng = np.random.default_rng(train_cfg.seed + 1)
    history: List[Dict[str, float]] = []
    best_val = float("inf")
    best_params = params
    bad_epochs = 0
    step = start_step

    val_g = jax.tree_util.tree_map(jnp.asarray, dataset_val.graphs)
    val_y = jnp.asarray(dataset_val.labels)
    val_banding = batch_banding(dataset_val.graphs) if len(dataset_val) else None

    for epoch in range(train_cfg.epochs):
        t0 = time.time()
        epoch_losses = []
        # prefetch worker produces device-resident depth-major batches
        it = prefetch(
            bucketed_batches(
                dataset_train, buckets, train_cfg.batch_size, rng=rng, device=True
            )
        )
        for g, y, banding in it:
            key, sub = jax.random.split(key)
            params, opt_state, ef, loss_val = train_step(
                params, opt_state, ef, g, y, sub, banding
            )
            epoch_losses.append(float(loss_val))
            step += 1
            if train_cfg.ckpt_dir and step % train_cfg.ckpt_every == 0:
                save_checkpoint(train_cfg.ckpt_dir, step, (params, opt_state, ef))
            if train_cfg.max_steps is not None and step >= train_cfg.max_steps:
                break
        vl = (
            float(val_loss_fn(params, val_g, val_y, val_banding))
            if len(dataset_val)
            else float("nan")
        )
        history.append(
            {
                "epoch": epoch,
                "train_loss": float(np.mean(epoch_losses)),
                "val_loss": vl,
                "seconds": time.time() - t0,
            }
        )
        if train_cfg.verbose:
            print(
                f"[{model_cfg.metric}] epoch {epoch} train {history[-1]['train_loss']:.4f} "
                f"val {vl:.4f} ({history[-1]['seconds']:.1f}s)"
            )
        out_of_steps = train_cfg.max_steps is not None and step >= train_cfg.max_steps
        if vl < best_val - 1e-4:
            best_val = vl
            # snapshot to host numpy: live device buffers would be deleted by
            # buffer donation in later train steps
            best_params = jax.tree_util.tree_map(np.asarray, params)
            bad_epochs = 0
        else:
            bad_epochs += 1
            if bad_epochs >= train_cfg.early_stop_patience:
                break
        if out_of_steps:
            break

    if train_cfg.ckpt_dir:
        save_checkpoint(train_cfg.ckpt_dir, step, (best_params, opt_state, ef))
    return TrainResult(params=best_params, history=history, best_val=best_val, steps=step)


# -- flat-vector baseline ---------------------------------------------------------------


def train_flat_model(
    x_train: np.ndarray,
    y_train: np.ndarray,
    x_val: np.ndarray,
    y_val: np.ndarray,
    cfg: FlatVectorConfig,
    train_cfg: TrainConfig = TrainConfig(),
):
    key = jax.random.PRNGKey(train_cfg.seed)
    key, init_key = jax.random.split(key)
    params = init_flat_model(init_key, cfg)
    steps_per_epoch = max(1, len(x_train) // train_cfg.batch_size)
    total = steps_per_epoch * train_cfg.epochs
    opt = optim.adam(
        lr=optim.cosine_schedule(train_cfg.lr, total, warmup_steps=min(100, total // 10)),
        weight_decay=train_cfg.weight_decay,
        max_grad_norm=train_cfg.max_grad_norm,
    )
    opt_state = opt.init(params)
    base_loss = msle_loss if cfg.task == "regression" else bce_loss

    @partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, x, y):
        def loss(p):
            return base_loss(forward_flat(p, x), y)

        loss_val, grads = jax.value_and_grad(loss)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optim.apply_updates(params, updates), opt_state, loss_val

    @jax.jit
    def val_loss_fn(params):
        return base_loss(forward_flat(params, jnp.asarray(x_val)), jnp.asarray(y_val))

    rng = np.random.default_rng(train_cfg.seed)
    best_val, best_params, bad = float("inf"), params, 0
    for epoch in range(train_cfg.epochs):
        order = rng.permutation(len(x_train))
        for s in range(0, len(order), train_cfg.batch_size):
            idx = order[s : s + train_cfg.batch_size]
            if idx.size < 2:
                continue
            params, opt_state, _ = train_step(
                params, opt_state, jnp.asarray(x_train[idx]), jnp.asarray(y_train[idx])
            )
        vl = float(val_loss_fn(params)) if len(x_val) else float("nan")
        if vl < best_val - 1e-4:
            # host snapshot: later donated steps delete the device buffers
            best_val, best_params, bad = vl, jax.tree_util.tree_map(np.asarray, params), 0
        else:
            bad += 1
            if bad >= train_cfg.early_stop_patience:
                break
    return best_params


def predict_flat(params, x: np.ndarray, task: str) -> np.ndarray:
    raw = np.asarray(forward_flat(params, jnp.asarray(x)))
    if task == "regression":
        return np.expm1(raw).clip(min=0.0)
    return (raw > 0).astype(np.int64)
