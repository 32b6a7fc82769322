"""A train loop the harness has never seen, for ``test_standin.py``: a
stack of square dense layers trained on rows of a table, written the
way the program's loops are (a ``StepBudget`` from the module's own
name, a function called ``train_step`` jitted through the module's own
``jax``, the state donated), so that the harness's two swaps bite. It
stands for a model kind whose state is large: at the configuration's
own sizes twelve 6144 x 6144 layers, 0.45 billion parameters."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax.training import train_state

from dragonfly2_tpu.train.step_budget import StepBudget

# A test's look at the device: called after every step with the arrays
# the loop itself holds.
PROBE = None


def init_params(seed: int, width: int, layers: int) -> dict:
    root = jax.random.key(seed)
    params = {}
    for i in range(layers):
        params[f"layer_{i}"] = {
            "kernel": jax.random.normal(
                jax.random.fold_in(root, i), (width, width), jnp.float32)
            * jnp.float32(1.0 / math.sqrt(width)),
            "bias": jnp.zeros(width, jnp.float32)}
    params["head"] = {
        "kernel": jax.random.normal(
            jax.random.fold_in(root, layers), (width, 1), jnp.float32)
        * jnp.float32(1.0 / math.sqrt(width)),
        "bias": jnp.zeros(1, jnp.float32)}
    return params


def logits_of(params: dict, x, layers: int):
    h = x.astype(jnp.bfloat16)
    for i in range(layers):
        layer = params[f"layer_{i}"]
        h = jax.nn.relu(h @ layer["kernel"].astype(jnp.bfloat16)
                        + layer["bias"].astype(jnp.bfloat16))
    head = params["head"]
    z = jnp.matmul(h, head["kernel"].astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    return z[:, 0] + head["bias"][0]


def train(features, labels, *, width: int, layers: int, batch: int,
          learning_rate: float, weight_decay: float, epochs: int, seed: int,
          max_seconds: float) -> None:
    n = len(features)
    per_epoch = max(n // batch, 1)
    total = max(epochs * per_epoch, 2)
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, min(100, total // 10 + 1), total)
    state = train_state.TrainState.create(
        apply_fn=None, params=init_params(seed, width, layers),
        tx=optax.adamw(schedule, weight_decay=weight_decay))
    x, y = jnp.asarray(features), jnp.asarray(labels)

    def train_step(state, x, y, ids):
        def loss_fn(params):
            z = logits_of(params, x[ids], layers)
            return optax.sigmoid_binary_cross_entropy(z, y[ids]).mean()
        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        return state.apply_gradients(grads=grads), loss

    step = jax.jit(train_step, donate_argnums=0)
    budget = StepBudget(max_seconds=max_seconds)
    losses, stop = [], False
    for epoch in range(epochs):
        order = np.random.default_rng((seed, epoch)).permutation(n)
        for i in range(per_epoch):
            ids = jnp.asarray(order[i * batch:(i + 1) * batch], jnp.int32)
            state, loss = step(state, x, y, ids)
            losses.append(loss)
            stop = budget.tick(batch, loss)
            if PROBE is not None:
                PROBE(budget, [x, y, ids, *losses, *jax.tree.leaves(state)])
            if stop:
                break
        if stop:
            break
    jax.block_until_ready(state.params)
    budget.finish()
