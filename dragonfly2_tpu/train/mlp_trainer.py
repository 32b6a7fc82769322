"""Data-parallel MLP training (BASELINE config #1).

One jit-compiled train step: state replicated, batch sharded over the
``data`` mesh axis, state buffers donated (in-place updates in HBM, no
per-step reallocation). The gradient average is whatever collective XLA
chooses for the mesh — ICI allreduce on a slice, nothing on one chip.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax.training import train_state

from dragonfly2_tpu.data.pipeline import ArrayDataset
from dragonfly2_tpu.models.mlp import MLPBandwidthPredictor, Normalizer
from dragonfly2_tpu.parallel import MeshContext, data_parallel_mesh
from dragonfly2_tpu.train.step_budget import StepBudget, epoch_mean


@dataclass(frozen=True)
class MLPTrainConfig:
    hidden: Sequence[int] = (128, 128, 64)
    learning_rate: float = 3e-3
    weight_decay: float = 1e-4
    batch_size: int = 8192
    epochs: int = 5
    seed: int = 0
    eval_fraction: float = 0.1
    warmup_steps: int = 100
    # Wall-clock budget for the step loop (compile excluded); None = run
    # all epochs (see GNNTrainConfig.max_seconds).
    max_seconds: float | None = None
    # Incremental publishing hooks (see GNNTrainConfig): progress fires
    # every ~25 completed steps with (steps, samples_per_sec); compile
    # fires once with the first-step compile seconds.
    progress_callback: object = None
    compile_callback: object = None


@dataclass
class MLPTrainResult:
    params: dict
    normalizer: Normalizer
    target_norm: Normalizer  # over log1p(y): centering makes zero-init sane
    config: MLPTrainConfig
    # Registry metrics on the raw MB/s scale (manager/models/model.go mlp
    # schema: mse/mae).
    mse: float
    mae: float
    samples_per_sec: float
    history: list = field(default_factory=list)

    @property
    def model(self) -> MLPBandwidthPredictor:
        return MLPBandwidthPredictor(hidden=tuple(self.config.hidden))


def _make_train_step(model: MLPBandwidthPredictor, mesh: MeshContext,
                     t_mean: float, t_std: float):
    def train_step(state: train_state.TrainState, x, y):
        def loss_fn(params):
            pred = state.apply_fn(params, x)
            return jnp.mean((pred - (jnp.log1p(y) - t_mean) / t_std) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        return state.apply_gradients(grads=grads), loss

    return jax.jit(
        train_step,
        in_shardings=(None, mesh.batch_sharding, mesh.batch_sharding),
        donate_argnums=(0,),
    )


def _make_eval_step(model: MLPBandwidthPredictor, mesh: MeshContext,
                    t_mean: float, t_std: float):
    def eval_step(params, x, y):
        pred_raw = jnp.expm1(model.apply(params, x) * t_std + t_mean)
        err = pred_raw - y
        return jnp.sum(err**2), jnp.sum(jnp.abs(err)), jnp.asarray(x.shape[0], jnp.float32)

    return jax.jit(eval_step, in_shardings=(None, mesh.batch_sharding, mesh.batch_sharding))


def train_mlp(
    X: np.ndarray,
    y: np.ndarray,
    config: MLPTrainConfig = MLPTrainConfig(),
    mesh: MeshContext | None = None,
    *,
    init_params=None,
    normalizer: Normalizer | None = None,
    target_norm: Normalizer | None = None,
) -> MLPTrainResult:
    """Train the bandwidth predictor on pair examples.

    ``X``: [n, FEATURE_DIM] float32 (raw, unnormalized); ``y``: [n] MB/s.
    ``init_params``/``normalizer``/``target_norm`` warm-start from an
    existing model — the federated local-round path (train/federated.py),
    where every cluster must share one normalization for FedAvg of raw
    parameters to be meaningful.
    """
    mesh = mesh or data_parallel_mesh()
    train_ds, eval_ds = ArrayDataset(X, y).split(config.eval_fraction, config.seed)
    # Batch must split evenly over the data axis (static shapes) and not
    # exceed the train split (or no batch would ever be yielded).
    batch_size = (min(config.batch_size, len(train_ds)) // mesh.n_data) * mesh.n_data
    if batch_size == 0:
        raise ValueError(
            f"train split ({len(train_ds)} rows) smaller than the data-parallel "
            f"degree ({mesh.n_data}); provide more data or a smaller mesh"
        )
    if normalizer is None:
        normalizer = Normalizer.fit(train_ds.arrays[0])
    if target_norm is None:
        target_norm = Normalizer.fit(np.log1p(train_ds.arrays[1])[:, None])
    t_mean, t_std = float(target_norm.mean[0]), float(target_norm.std[0])
    # Normalize once host-side; the (x - mean)/std is fused trivially anyway
    # but doing it here keeps the jitted graph free of constants that would
    # be re-baked when statistics change.
    train_ds = ArrayDataset(normalizer(train_ds.arrays[0]), train_ds.arrays[1])
    eval_norm = normalizer(eval_ds.arrays[0])

    model = MLPBandwidthPredictor(hidden=tuple(config.hidden))
    params = (init_params if init_params is not None else
              model.init(jax.random.key(config.seed),
                         jnp.zeros((1, X.shape[1]))))
    steps_per_epoch = max(len(train_ds) // batch_size, 1)
    total_steps = max(config.epochs * steps_per_epoch, 2)
    warmup = min(config.warmup_steps, total_steps // 10 + 1)
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, config.learning_rate, warmup, total_steps,
    )
    tx = optax.adamw(schedule, weight_decay=config.weight_decay)
    state = train_state.TrainState.create(apply_fn=model.apply, params=params, tx=tx)
    state = mesh.put_replicated(state)

    train_step = _make_train_step(model, mesh, t_mean, t_std)
    eval_step = _make_eval_step(model, mesh, t_mean, t_std)

    history = []
    budget = StepBudget(config.max_seconds,
                        on_compile=config.compile_callback,
                        on_progress=config.progress_callback)
    stop = False
    for epoch in range(config.epochs):
        losses = []
        for bx, by in train_ds.batches(batch_size, seed=config.seed,
                                       epoch=epoch):
            state, loss = train_step(state, mesh.put_batch(bx),
                                     mesh.put_batch(by))
            if mesh.serialize_launches:
                jax.block_until_ready(loss)
            losses.append(loss)
            if budget.tick(len(bx), loss):
                stop = True
                break
        if losses:
            history.append(epoch_mean(losses))
        if stop:
            break
    jax.block_until_ready(state.params)
    budget.finish()

    # Eval in fixed-size chunks (pad the tail by wrapping — metrics are
    # sums, so we mask instead: just iterate full batches + remainder on
    # host for exactness at small scale).
    se = ae = cnt = 0.0
    eval_bs = batch_size
    n_eval = len(eval_ds)
    for s in range(0, n_eval - eval_bs + 1, eval_bs):
        a, b, c = eval_step(
            state.params,
            mesh.put_batch(eval_norm[s : s + eval_bs]),
            mesh.put_batch(eval_ds.arrays[1][s : s + eval_bs]),
        )
        se, ae, cnt = se + float(a), ae + float(b), cnt + float(c)
    rem = n_eval % eval_bs
    if rem:
        tail_x = eval_norm[n_eval - rem :]
        tail_y = eval_ds.arrays[1][n_eval - rem :]
        out = model.apply(state.params, jnp.asarray(tail_x)) * t_std + t_mean
        pred = np.asarray(jnp.expm1(out))
        se += float(((pred - tail_y) ** 2).sum())
        ae += float(np.abs(pred - tail_y).sum())
        cnt += len(tail_y)

    # eval_fraction=0 is a legal config (e.g. final refit on all data):
    # metrics are simply undefined then, not a crash.
    mse = se / cnt if cnt else float("nan")
    mae = ae / cnt if cnt else float("nan")

    return MLPTrainResult(
        params=jax.device_get(state.params),
        normalizer=normalizer,
        target_norm=target_norm,
        config=config,
        mse=mse,
        mae=mae,
        samples_per_sec=budget.samples_per_sec(batch_size),
        history=history,
    )


def bandwidth_examples_from_corpus(
    corpus, piece_mb: float = 4.0,
) -> tuple[np.ndarray, np.ndarray]:
    """(X [n, FEATURE_DIM] float32, y [n] MB/s) from a replay corpus —
    the bandwidth predictor's view of the SAME realized evidence the
    cost model trains on: each candidate's realized per-piece cost
    (seconds for a ``piece_mb``-sized piece) inverted into achieved
    bandwidth. Accepts a ``ColumnarCorpus`` (whole-corpus mask ops over
    the mmap'd columns, no per-row parse) or a ReplayDecision sequence;
    costs are floored at 0.1 ms so a clock-resolution cost cannot mint
    an absurd bandwidth label."""
    from dragonfly2_tpu.train.cost_trainer import cost_examples_from_corpus

    X, cost_s = cost_examples_from_corpus(corpus)
    y = (piece_mb / np.maximum(cost_s, 1e-4)).astype(np.float32)
    return X, y
