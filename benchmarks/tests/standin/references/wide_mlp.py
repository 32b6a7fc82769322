"""Plain reference for the stand-in kind ``wide_mlp``: the same stack
of dense layers in float32, its own weights from the seed, its own
batch order, the loss and its gradient in row blocks, AdamW written out
(``references/common.py``). Imports nothing of the program."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references import common

ROW_BLOCK = 1024


def init_params(seed: int, width: int, layers: int) -> dict:
    root = jax.random.key(seed)
    scale = jnp.float32(1.0 / math.sqrt(width))
    shapes = [(f"layer_{i}", (width, width)) for i in range(layers)]
    shapes.append(("head", (width, 1)))
    out = {}
    for i, (name, shape) in enumerate(shapes):
        out[f"{name}/kernel"] = jax.random.normal(
            jax.random.fold_in(root, i), shape, jnp.float32) * scale
        out[f"{name}/bias"] = jnp.zeros(shape[1], jnp.float32)
    return out


def readings(spec: dict, arrays: dict, seed: int, steps: int,
             precision: str = "float32", keep_rows: float = 1.0,
             frozen: bool = False) -> dict:
    model, opt = spec["model"], spec["optimizer"]
    layers = model["layers"]
    x, y = jnp.asarray(arrays["features"]), jnp.asarray(arrays["labels"])
    batch = spec["batch"]
    per_epoch = max(len(x) // batch, 1)
    total = max(spec["epochs"] * per_epoch, 2)
    if steps > per_epoch:
        raise ValueError("the reference follows steps of the first epoch only")
    order = np.random.default_rng((seed, 0)).permutation(len(x))
    batches = [order[i * batch:(i + 1) * batch] for i in range(steps)]
    kept = max(int(batch * keep_rows), 1)
    block = min(max(kept // 2, 1), ROW_BLOCK)
    if (kept // 2) % block:
        raise ValueError(f"{kept // 2} rows do not split into blocks of "
                         f"{block}")
    rnd = common.rounder(precision)

    # ``weight`` 1: the block's summed loss; 0: its summed logits.
    @jax.jit
    def block_sum(params, x_, y_, ids, weight):
        def loss(p):
            h = x_[ids]
            for i in range(layers):
                h = jax.nn.relu(common.dense(
                    h, common.layer(p, f"layer_{i}"), rnd))
            z = common.dense(h, common.layer(p, "head"), rnd)[:, 0]
            return (weight * common.sigmoid_bce(z, y_[ids])
                    + (1.0 - weight) * z).sum()
        return jax.value_and_grad(loss)(params)

    def step(params, ids, count, rows=None, weight=1.0):
        rows = rows or kept
        total_loss, total_grad = 0.0, None
        for row0 in range(0, rows, block):
            loss, grad = block_sum(
                params, x, y, jnp.asarray(ids[row0:row0 + block], jnp.int32),
                jnp.float32(weight))
            total_loss = total_loss + loss
            total_grad = grad if total_grad is None else jax.tree.map(
                jnp.add, total_grad, grad)
        return total_loss / rows, jax.tree.map(lambda g: g / rows, total_grad)

    return common.follow(
        init_params(seed, model["width"], layers), batches, step, {
            "learning_rate": opt["learning_rate"],
            "weight_decay": opt["weight_decay"],
            "warmup": common.warmup_steps(total), "total_steps": total},
        frozen=frozen,
        logit_grad=lambda params, ids: step(params, ids, 0, weight=0.0)[1])
