"""What the plain references share: the arithmetic precision they are
asked to compute in, parameter initialisation from the seed, and AdamW
with the trainers' warm-up schedule, all written out.

Nothing here imports the program or flax/optax. The references make
their own weights: the trainers draw theirs with ``model.init(
jax.random.key(seed))``, and flax derives each parameter's key by
folding the SHA-1 of its path and its per-scope counter into that root
key, then draws kernels from LeCun-normal and sets biases to zero and
LayerNorm scales to one. :func:`init_params` does the same from the
widths alone, so the comparison's ``init_gap`` (program's initial
parameters against the reference's) is an exact one.
"""

from __future__ import annotations

import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np

# "float32": every product at full float32 (the reference proper).
# "fp8": the control, the nearest precision under the bfloat16 the
# configurations state, as fp8 training is done: each product's operands
# rounded to float8 e4m3 on the way forward and the cotangents that come
# back to them to float8 e5m2, each with a per-tensor scale; the
# products themselves and everything else in float32.
PRECISIONS = ("float32", "fp8")


def _scaled_round(x, dtype):
    scale = float(jnp.finfo(dtype).max) / jnp.maximum(
        jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def _fp8(x):
    return _scaled_round(x, jnp.float8_e4m3fn)


_fp8.defvjp(lambda x: (_fp8(x), None),
            lambda _, ct: (_scaled_round(ct, jnp.float8_e5m2),))


def rounder(precision: str):
    """The rounding a product's operands get under ``precision``."""
    if precision == "float32":
        return lambda x: x
    if precision == "fp8":
        return _fp8
    raise ValueError(f"unknown precision {precision!r}; one of {PRECISIONS}")


def dense(x, layer, rnd):
    return jnp.matmul(rnd(x), rnd(layer["kernel"]),
                      precision="highest") + layer["bias"]


def layer_norm(x, layer, eps=1e-6):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * layer["scale"] + layer["bias"]


def sigmoid_bce(logits, labels):
    """Per-row binary cross-entropy on logits."""
    return (jnp.maximum(logits, 0) - logits * labels
            + jnp.log1p(jnp.exp(-jnp.abs(logits))))


# -- parameters from the seed ------------------------------------------------

def _param_key(root, path: tuple):
    m = hashlib.sha1()
    for part in path:
        if isinstance(part, str):
            m.update(part.encode("utf-8"))
        else:
            m.update(part.to_bytes((part.bit_length() + 7) // 8, "big"))
    return jax.random.fold_in(
        root, jnp.uint32(int.from_bytes(m.digest()[:4], "big")))


def init_params(seed: int, spec: dict) -> dict:
    """``spec``: ``{"a/b": ("dense", fan_in, fan_out) | ("norm", width)}``
    → ``{"a/b/kernel": ..., "a/b/bias": ...}`` (flat, float32)."""
    root = jax.random.key(seed)
    lecun = jax.nn.initializers.lecun_normal()
    out = {}
    for name, (kind, *shape) in spec.items():
        scope = tuple(name.split("/"))
        if kind == "dense":
            out[f"{name}/kernel"] = lecun(
                _param_key(root, scope + (1,)), tuple(shape), jnp.float32)
            out[f"{name}/bias"] = jnp.zeros(shape[-1], jnp.float32)
        elif kind == "norm":
            out[f"{name}/scale"] = jnp.ones(shape[0], jnp.float32)
            out[f"{name}/bias"] = jnp.zeros(shape[0], jnp.float32)
        else:
            raise ValueError(kind)
    return out


def layer(params: dict, name: str) -> dict:
    prefix = name + "/"
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix) and "/" not in k[len(prefix):]}


# -- AdamW under a linear warm-up and cosine decay ---------------------------

def learning_rate(count: int, peak: float, warmup: int, total: int) -> float:
    """The trainers' schedule: 0 → ``peak`` over ``warmup`` updates,
    then a cosine to 0 at ``total``. ``count`` is 0 for the first update."""
    if count < warmup:
        return peak * count / warmup
    frac = min((count - warmup) / max(total - warmup, 1), 1.0)
    return peak * 0.5 * (1.0 + math.cos(math.pi * frac))


def warmup_steps(total_steps: int) -> int:
    return min(100, total_steps // 10 + 1)


class AdamW:
    def __init__(self, params: dict, weight_decay: float,
                 b1=0.9, b2=0.999, eps=1e-8):
        self.b1, self.b2, self.eps, self.wd = b1, b2, eps, weight_decay
        self.mu = {k: jnp.zeros_like(v) for k, v in params.items()}
        self.nu = {k: jnp.zeros_like(v) for k, v in params.items()}
        self.count = 0

    def update(self, params: dict, grads: dict, lr: float) -> dict:
        self.count += 1
        t = self.count
        new = {}
        for k, p in params.items():
            g = grads[k]
            self.mu[k] = self.b1 * self.mu[k] + (1 - self.b1) * g
            self.nu[k] = self.b2 * self.nu[k] + (1 - self.b2) * g * g
            m_hat = self.mu[k] / (1 - self.b1 ** t)
            v_hat = self.nu[k] / (1 - self.b2 ** t)
            new[k] = p - lr * (m_hat / (jnp.sqrt(v_hat) + self.eps)
                               + self.wd * p)
        return new


def row_weights(batch: int, kept: int):
    """Per-row weights of a mean over the first ``kept`` of ``batch``
    rows: the whole batch's mean, or, with rows left out, the mean over
    the rest at the batch's own shape."""
    return (np.arange(batch) < kept).astype(np.float32) / np.float32(kept)


def follow(params: dict, batches, loss_and_grad, optimizer: dict,
           frozen: bool = False, logit_grad=None) -> dict:
    """Drive ``loss_and_grad(params, batch, step, rows=None)`` through
    ``batches`` under AdamW and return what the comparison reads (host
    arrays). ``frozen`` plants the fault "a step that returns its state
    unchanged" for the control's readings."""
    # Each tree goes to the host as it is made: the device holds the
    # parameters, Adam's moments and one gradient, whatever the steps.
    get = lambda tree: {k: np.asarray(v) for k, v in tree.items()}  # noqa: E731
    adam = AdamW(params, optimizer["weight_decay"])
    start = get(params)
    # The gradient of the first batch's mean logit: the scale of a
    # gradient before the rows' residuals cancel in it.
    scale = get(logit_grad(params, batches[0])) if logit_grad else {}
    losses, all_grads, halves = [], [], []
    at_start = True
    for step, batch in enumerate(batches):
        loss, grads = loss_and_grad(params, batch, step)
        all_grads.append(get(grads))
        if at_start:
            # The same gradient over the first half of its rows alone:
            # the direction a gradient moves in when rows do not weigh
            # the same. Read while the parameters are the initial ones
            # (the schedule's first learning rate is 0: two steps).
            halves.append(get(
                loss_and_grad(params, batch, step, rows=len(batch) // 2)[1]))
        losses.append(float(loss))
        lr = learning_rate(step, optimizer["learning_rate"],
                           optimizer["warmup"], optimizer["total_steps"])
        at_start = at_start and lr == 0.0
        if not frozen:
            params = adam.update(params, grads, lr)
    return {"params_before": start, "grads": all_grads,
            "params_after": get(params), "losses": losses,
            "logit_grad": scale, "grads_first_half": halves}
