"""Plain reference for the stand-in kind ``wide_mlp``: the same stack
of dense layers in float32, its own weights from the seed, its own
batch order, the loss and its gradient in row blocks added into one
running sum, AdamW written out (``references/common.py: follow``).
Imports nothing of the program."""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references import common

ROW_BLOCK = 1024
# A test's look at the device: called before and after every block's
# program with no argument.
PROBE = None


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


def readings(spec: dict, arrays: dict, seed: int, steps: int, sink,
             precision: str = "float32", keep_rows: float = 1.0,
             frozen: bool = False):
    model, opt = spec["model"], spec["optimizer"]
    layers = model["layers"]
    x, y = jnp.asarray(arrays["features"]), jnp.asarray(arrays["labels"])
    batch = spec["batch"]
    per_epoch = max(len(x) // batch, 1)
    total = max(spec["epochs"] * per_epoch, 2)
    if steps > per_epoch:
        raise ValueError("the reference follows steps of the first epoch only")
    order = np.random.default_rng((seed, 0)).permutation(len(x))
    kept = max(int(batch * keep_rows), 1)
    block = min(max(batch // 2, 1), ROW_BLOCK)
    if batch % block or kept % block:
        raise ValueError(f"{batch} and {kept} rows do not split into "
                         f"blocks of {block}")
    # A batch is its blocks of rows: the parts of ``common.summed``.
    batches = [order[i * batch:(i + 1) * batch].reshape(-1, block)
               for i in range(steps)]
    rnd = common.rounder(precision)

    def block_sum(params, ids, weight):
        """``weight`` 1: the block's summed loss; 0: its summed logits."""
        def loss(p):
            h = x[ids]
            for i in range(layers):
                h = jax.nn.relu(common.dense(
                    h, common.layer(p, f"layer_{i}"), rnd))
            z = common.dense(h, common.layer(p, "head"), rnd)[:, 0]
            return (weight * common.sigmoid_bce(z, y[ids])
                    + (1.0 - weight) * z).sum()
        return jax.value_and_grad(loss)(params)

    @jax.jit
    def first(params, ids, weight):
        return block_sum(params, ids, weight)

    @partial(jax.jit, donate_argnums=1)
    def more(params, into, ids, weight):
        value, grad = block_sum(params, ids, weight)
        return value, jax.tree.map(jnp.add, into, grad)

    def part(params, ids, weight, into):
        if PROBE is not None:
            PROBE()
        ids = jnp.asarray(ids, jnp.int32)
        weight = jnp.float32(weight)
        value, into = (first(params, ids, weight) if into is None
                       else more(params, into, ids, weight))
        if PROBE is not None:
            PROBE()
        return (value, len(ids)), into

    mean_gradient = common.summed(part, kept // block)

    def logit(params, ids):
        return mean_gradient(params, ids, 0, weight=0.0)[1]

    return common.follow(
        lambda: init_params(seed, model["width"], layers), batches,
        mean_gradient, {
            "learning_rate": opt["learning_rate"],
            "weight_decay": opt["weight_decay"],
            "warmup": common.warmup_steps(total), "total_steps": total},
        sink, frozen=frozen, logit=logit)
