"""What the plain references share: the arithmetic precision they are
asked to compute in, parameter initialisation from the seed, AdamW with
the trainers' warm-up schedule, all written out, and :func:`follow`, the
one producer of what the comparison reads (the sequence kinds' batch
order, per-sequence sums and first-half gradients in
:func:`sequence_readings`).

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
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.compare import host

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
    """AdamW a leaf and an operation at a time, the bias corrections
    taken on the host (:func:`adamw_eager`)."""

    def __init__(self, params: dict, weight_decay: float,
                 b1=0.9, b2=0.999, eps=1e-8):
        self.b1, self.b2, self.eps, self.wd = b1, b2, eps, weight_decay
        self.mu = {k: jnp.zeros_like(v) for k, v in params.items()}
        self.nu = {k: jnp.zeros_like(v) for k, v in params.items()}
        self.count = 0

    def update(self, params: dict, grads: dict, lr: float) -> dict:
        self.count += 1
        new = {}
        for k, p in params.items():
            (new[k],), (self.mu[k],), (self.nu[k],) = adamw_eager(
                [p], [self.mu[k]], [self.nu[k]], [grads[k]], lr, self.count,
                self.wd, self.b1, self.b2, self.eps)
        return new


def adamw_eager(ps, ms, vs, gs, lr, t, weight_decay, b1=0.9, b2=0.999,
                eps=1e-8):
    """AdamW over lists of leaves (parameters, moments, gradients), an
    operation at a time, ``t`` counted from 1: a Python number, so the
    bias corrections 1 - β^t are taken in double precision on the
    host. Returns the new parameters and moments, as lists."""
    new = ([], [], [])
    for p, m, v, g in zip(ps, ms, vs, gs):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        m_hat = m / (1 - b1 ** t)
        v_hat = v / (1 - b2 ** t)
        for out, x in zip(new, (
                p - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + weight_decay * p),
                m, v)):
            out.append(x)
    return new


def row_weights(batch: int, kept: int):
    """Per-row weights of a mean over the first ``kept`` of ``batch``
    rows: the whole batch's mean, or, with rows left out, the mean over
    the rest at the batch's own shape."""
    return (np.arange(batch) < kept).astype(np.float32) / np.float32(kept)


# -- what the comparison reads, made a tree at a time ------------------------

# The compiler's least effort, for a reference's many small programs
# (PERF.md section 6: full effort compiles some seven times as
# long; the programs then run 1.5-2 times longer).
QUICKLY = {"exec_time_optimization_effort": -1.0,
           "memory_fitting_effort": -1.0}

# The small programs between the kinds' own take lists of leaves (a
# layer's, or a whole tree's), so that one is compiled for each layer's
# shapes and not one for each leaf's.
_quick = partial(jax.jit, compiler_options=QUICKLY)
_accumulate = _quick(lambda into, more: [a + b for a, b in zip(into, more)],
                     donate_argnums=0)
_divide = _quick(lambda leaves, n: [x / n for x in leaves])
_mean = _quick(lambda leaves, n: [x / n for x in leaves], donate_argnums=0)
_norms = _quick(lambda leaves: [
    jnp.sqrt(jnp.sum(jnp.square(g))).reshape(1) for g in leaves])
_add = _quick(jnp.add)
_count = _quick(lambda c: jnp.maximum(c, 1).astype(jnp.float32))

# :func:`adamw_eager` in one program, ``t`` traced: its bias corrections
# are taken in float32 on the device.
adamw_fused = _quick(adamw_eager, donate_argnums=(1, 2))

UPDATES = {"host": adamw_eager, "device": adamw_fused}


def groups(names) -> list:
    """Leaf names in lists by their first part (a layer, the embedding,
    the head), in order."""
    out = {}
    for k in names:
        out.setdefault(k.split("/", 1)[0], []).append(k)
    return list(out.values())


class Sum:
    """A gradient made a piece at a time. With a running sum (``into``)
    each piece is added into its leaves as it comes, each leaf donated
    to its sum, so that no whole gradient is held beside the sum; without
    one the pieces are kept as they come."""

    def __init__(self, into=None):
        self.into, self.made = into, {}

    def add(self, pieces: dict) -> None:
        if self.into is None:
            self.made.update(pieces)
            return
        names = list(pieces)
        sums = _accumulate([self.into[k] for k in names],
                           [pieces[k] for k in names])
        self.into.update(zip(names, sums))

    def tree(self, order) -> dict:
        """The sum, or the pieces in ``order``'s order."""
        if self.into is not None:
            return self.into
        return {k: self.made[k] for k in order}


def host_tree(tree: dict) -> dict:
    """A flat tree of device arrays as host arrays, each leaf dropped
    from the device as it lands."""
    return {k: host(tree.pop(k)) for k in list(tree)}


def summed(part, kept: int):
    """A batch's mean gradient as a running sum over its parts (whole
    sequences, row blocks): ``part(params, i, weight, into) -> ((value,
    count), into)`` adds part ``i``'s summed value and gradient into the
    running sum ``into`` (or starts it, where ``into`` is None). Returns
    ``mean_gradient(params, ids, step, weight=1.0, half=None) -> (loss,
    gradient)`` over the first ``kept`` of ``ids``; with ``half`` given,
    the mean over the first half of ``ids`` is handed to it as host
    arrays on the way, out of the same sum."""
    def mean_gradient(params, ids, step, weight=1.0, half=None):
        mid = len(ids) // 2
        if half is not None and kept < mid:
            raise ValueError(f"{kept} parts kept, fewer than half of "
                             f"{len(ids)}")
        value = count = into = None
        for n, i in enumerate(ids[:kept]):
            (v, c), into = part(params, i, weight, into)
            value = v if value is None else _add(value, v)
            count = c if count is None else _add(count, c)
            if half is not None and n + 1 == mid:
                rows, shares = _count(count), {}
                for names in groups(into):
                    shares.update(zip(names, map(host, _divide(
                        [into[k] for k in names], rows))))
                half(shares)
        n, names = _count(count), list(into)
        return value / n, dict(zip(names, _mean(
            [into.pop(k) for k in names], n)))
    return mean_gradient


def whole(step):
    """``mean_gradient`` (as :func:`summed` gives it) from ``step(params,
    ids, count, rows=None)``, the mean over the batch's rows, or over its
    first ``rows``, and its gradient, each made whole."""
    def mean_gradient(params, ids, count, weight=1.0, half=None):
        if half is not None:
            half(host_tree(step(params, ids, count, rows=len(ids) // 2)[1]))
        return step(params, ids, count)
    return mean_gradient


def _update(params: dict, moments, pending: list, grads: dict, t: int,
            lr: float, weight_decay: float, update, keep: bool):
    """One AdamW update a layer's leaves at a time (:func:`groups`),
    after the ``pending`` ones (``(gradient on the host, t, lr)``:
    updates that moved nothing, made now, in order). Adam's moments come
    from the host (from zero where there are none yet) and go back to it
    where ``keep``; each layer's parameters, moments and gradient are
    given up as its new leaves are made."""
    new, kept = {}, ({}, {})
    for names in groups(params):
        p = [params.pop(k) for k in names]
        if moments is None:
            m, v = ([jnp.zeros_like(x) for x in p] for _ in range(2))
        else:
            m, v = ([jnp.asarray(x.pop(k)) for k in names] for x in moments)
        for g, at, rate in pending:
            p, m, v = update(p, m, v, [jnp.asarray(g.pop(k)) for k in names],
                             rate, at, weight_decay)
        p, m, v = update(p, m, v, [grads.pop(k) for k in names], lr, t,
                         weight_decay)
        new.update(zip(names, p))
        del p
        if keep:
            for k, mk, vk in zip(names, m, v):
                kept[0][k], kept[1][k] = host(mk), host(vk)
    return new, (kept if keep else None)


def follow(initial, batches, mean_gradient, optimizer: dict, sink, *,
           frozen: bool = False, logit=None, corrections: str = "host"):
    """Drive ``mean_gradient(params, ids, step, weight=1.0, half=None)``
    through ``batches`` under AdamW and the trainers' schedule from
    ``initial()``, the parameters from the seed, and hand ``sink`` (the
    comparison's ``compare.Fold``) each tree as it is made: the initial
    parameters; ``logit(params, ids)``, a tree whose leaves' squared
    norms are those of the gradient of the first batch's mean logit (the
    scale of a gradient before the rows' residuals cancel in it); at each
    step on the initial parameters (the schedule's first learning rate is
    0: two steps) the gradient over the first half of the batch's rows,
    then each step's loss and gradient; last the parameters after the
    steps, with the initial ones drawn again from the seed. ``frozen``
    plants the fault "a step that returns its state unchanged";
    ``corrections`` names the update's form (:data:`UPDATES`).

    Between its programs the device holds the parameters and the one
    gradient being made (:func:`summed`'s running sum): Adam's moments
    wait on the host, and an update that moves nothing (a learning rate
    of 0 on zero moments) is left until the next one, which makes it
    first from that step's gradient, kept on the host. The first
    gradient goes to the host as it is made: the comparison keeps it for
    the second step's walk. Returns ``sink``."""
    params = initial()
    sink.initial(params)
    if logit is not None:
        sink.logit(logit(params, batches[0]))
    update = UPDATES[corrections]
    moments, pending, at_start = None, [], True
    for step, batch in enumerate(batches):
        half = partial(sink.half, step) if at_start else None
        loss, grads = mean_gradient(params, batch, step, half=half)
        lr = learning_rate(step, optimizer["learning_rate"],
                           optimizer["warmup"], optimizer["total_steps"])
        deferred = not frozen and moments is None and lr == 0.0
        if step == 0 or deferred:
            grads = host_tree(grads)
        sink.gradient(step, loss, grads)
        at_start = at_start and lr == 0.0
        if frozen:
            continue
        if deferred:
            pending.append((grads, step + 1, lr))
            continue
        params, moments = _update(
            params, moments, pending, grads, step + 1, lr,
            optimizer["weight_decay"], update, keep=step + 1 < len(batches))
        pending = []
    del moments, pending
    sink.final(params, initial())
    return sink


def sequence_readings(spec: dict, arrays: dict, seed: int, steps: int,
                      initial, gradient, sink, keep_rows: float = 1.0,
                      frozen: bool = False, corrections: str = "host"):
    """:func:`follow` for a kind trained on packed sequences: the
    trainer's batch order (the first epoch's permutation of the rows from
    ``default_rng((seed, 11))``, cut into consecutive batches of whole
    sequences), each step's mean over the target positions of its first
    ``keep_rows`` of the batch's sequences, summed a sequence at a time
    through ``gradient(params, tokens, segments, positions, weight,
    into=None) -> ((sum, count), gradient)`` (the kind's one sequence;
    with ``into``, its gradient added into that running sum as it is
    made), and the gradient of the mean target logit (``weight`` 0)
    handed over as its leaves' norms."""
    rows, batch = arrays["tokens"].shape[0], spec["batch"]
    per_epoch = max(rows // batch, 1)
    total = max(spec["epochs"] * per_epoch, 2)
    if steps > per_epoch:
        raise ValueError("the reference follows steps of the first epoch only")
    order = np.random.default_rng((seed, 11)).permutation(rows)
    batches = [order[i * batch:(i + 1) * batch] for i in range(steps)]

    def part(params, i, weight, into):
        return gradient(params, *(arrays[k][i] for k in (
            "tokens", "segments", "positions")), jnp.float32(weight),
            into=into)

    mean_gradient = summed(part, max(int(batch * keep_rows), 1))

    def logit(params, ids):
        grads = mean_gradient(params, ids, 0, weight=0.0)[1]
        names = list(grads)
        return dict(zip(names, _norms([grads.pop(k) for k in names])))

    opt = spec["optimizer"]
    return follow(initial, batches, mean_gradient, {
        "learning_rate": opt["learning_rate"],
        "weight_decay": opt["weight_decay"],
        "warmup": warmup_steps(total), "total_steps": total},
        sink, frozen=frozen, logit=logit, corrections=corrections)
