"""Plain reference for the ``graphsage`` kind: the CSR of the probe
graph, two-hop fan-out sampling with replacement, the two mean-aggregate
layers, the pair head, the loss, its gradients and AdamW, in float32
with every product at full precision.

Sampling is part of the step's definition, so it is written out here:
per step two scalar salts come from threefry (``fold_in(key(seed + 1),
step)``, split, 32 bits each); slot ``i`` of a hop (row-major position
in ``[B, 2, f1]`` or ``[B, 2, f1, f2]``) draws ``lowbias32(lowbias32(i +
salt) ^ salt·0x9E3779B9) mod degree`` as its offset into the node's CSR
row. The batch is followed in blocks of rows (the loss is a mean over
rows), so the cell's own batch fits in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import common

ROW_BLOCK = 16384


def csr(n: int, src, dst, rtt_ns):
    order = np.argsort(src, kind="stable")
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=indptr[1:])
    return (indptr.astype(np.int32), dst[order].astype(np.int32),
            np.log1p(rtt_ns[order] / 1e6).astype(np.float32))


def param_spec(model: dict, n_features: int) -> dict:
    h, e = model["hidden"], model["embed"]
    return {
        "SageLayer_0/Dense_0": ("dense", 2 * (n_features + 1), h),
        "SageLayer_1/Dense_0": ("dense", 2 * h, e),
        "Dense_0": ("dense", 4 * e, h),
        "Dense_1": ("dense", h, 1),
    }


def _lowbias32(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def _draw(tables, nodes, position, salt):
    """One sampled neighbour for each entry of ``nodes``; ``position`` is
    the slot's row-major index in the whole batch's hop tensor."""
    indptr, indices, edge_rtt = tables
    start = indptr[nodes]
    deg = indptr[nodes + 1] - start
    bits = _lowbias32(_lowbias32(position + salt)
                      ^ (salt * jnp.uint32(0x9E3779B9)))
    offs = (bits % jnp.maximum(deg, 1).astype(jnp.uint32)).astype(jnp.int32)
    pos = jnp.minimum(start + offs, len(indices) - 1)
    mask = (deg > 0).astype(jnp.float32)
    return jnp.where(deg > 0, indices[pos], 0), edge_rtt[pos] * mask, mask


def _mean(x, mask):
    return (x * mask[..., None]).sum(-2) / jnp.maximum(
        mask.sum(-1), 1.0)[..., None]


def block_logits(params, tables, feat, fanouts, src, dst, row0, salts, rnd):
    """Logits of one block of rows whose first row is ``row0`` of the
    batch."""
    f1, f2 = fanouts
    rows = len(src)
    centers = jnp.stack([src, dst], axis=-1)                    # [R, 2]
    u32 = lambda a: a.astype(jnp.uint32)  # noqa: E731
    slot1 = ((u32(row0) + u32(jnp.arange(rows)))[:, None, None] * 2
             + u32(jnp.arange(2))[None, :, None]) * f1 \
        + u32(jnp.arange(f1))[None, None, :]                    # [R, 2, f1]
    slot2 = slot1[..., None] * f2 + u32(jnp.arange(f2))         # [R,2,f1,f2]
    nbr1, rtt1, mask1 = _draw(
        tables, jnp.broadcast_to(centers[..., None], slot1.shape),
        slot1, salts[0])
    nbr2, rtt2, mask2 = _draw(
        tables, jnp.broadcast_to(nbr1[..., None], slot2.shape),
        slot2, salts[1])
    mask2 = mask2 * mask1[..., None]
    rtt2 = rtt2 * mask2

    x_center = feat[centers]
    x1 = jnp.concatenate([feat[nbr1], rtt1[..., None]], -1)
    x2 = jnp.concatenate([feat[nbr2], rtt2[..., None]], -1)
    l1 = common.layer(params, "SageLayer_0/Dense_0")
    l2 = common.layer(params, "SageLayer_1/Dense_0")
    h1_nbr = jax.nn.relu(common.dense(
        jnp.concatenate([x1, _mean(x2, mask2)], -1), l1, rnd))
    center0 = jnp.concatenate(
        [x_center, jnp.zeros(x_center.shape[:-1] + (1,))], -1)
    h1_center = jax.nn.relu(common.dense(
        jnp.concatenate([center0, _mean(x1, mask1)], -1), l1, rnd))
    h2 = jax.nn.relu(common.dense(
        jnp.concatenate([h1_center, _mean(h1_nbr, mask1)], -1), l2, rnd))
    a, b = h2[:, 0], h2[:, 1]
    pair = jnp.concatenate([a, b, a * b, jnp.abs(a - b)], -1)
    z = jax.nn.relu(common.dense(pair, common.layer(params, "Dense_0"), rnd))
    return common.dense(z, common.layer(params, "Dense_1"), rnd)[:, 0]


def readings(config: dict, graph: dict, seed: int, steps: int, sink,
             precision: str = "float32", keep_rows: float = 1.0,
             frozen: bool = False):
    """Follow the trainer's first ``steps`` from the seed, handing
    ``sink`` what the comparison reads as it is made. For the control's
    readings ``keep_rows`` < 1 plants the fault "part of the
    batch left out, the mean taken over the rest" and ``frozen`` the
    fault "a step that returns its state unchanged"."""
    model, opt = config["model"], config["optimizer"]
    fanouts = tuple(model["fanouts"])
    n = len(graph["node_features"])
    src, dst = graph["edge_src"], graph["edge_dst"]
    labels = (graph["edge_rtt_ns"] < opt["rtt_threshold_ns"]).astype(
        np.float32)
    tables = tuple(map(jnp.asarray, csr(n, src, dst, graph["edge_rtt_ns"])))
    feat = jnp.asarray(graph["node_features"])
    batch = min(config["batch"], len(src))
    per_epoch = max(len(src) // batch, 1)
    total = max(config["epochs"] * per_epoch, 2)
    # The trainer's feed: epoch e walks default_rng((seed, e))'s
    # permutation of the edge ids in consecutive batches.
    order = np.random.default_rng((seed, 0)).permutation(len(src))
    if steps > per_epoch:
        raise ValueError("the reference follows steps of the first epoch only")
    kept = max(int(batch * keep_rows), 1)
    block = min(max(kept // 2, 1), ROW_BLOCK)
    if (kept // 2) % block:
        raise ValueError(f"{kept // 2} rows do not split into blocks of "
                         f"{block}")
    batches = [order[i * batch:(i + 1) * batch] for i in range(steps)]

    rnd = common.rounder(precision)
    base_key = jax.random.key(seed + 1)

    # The graph goes in as arguments: as constants it would be part of
    # the program, and every seed would compile anew.
    # ``weight`` 1: the block's summed loss; 0: its summed logits.
    @jax.jit
    def block_sum(params, tables_, feat_, s, d, y, row0, salts, weight):
        def loss(p):
            z = block_logits(p, tables_, feat_, fanouts, s, d, row0, salts,
                             rnd)
            return (weight * common.sigmoid_bce(z, y)
                    + (1.0 - weight) * z).sum()
        return jax.value_and_grad(loss)(params)

    def step(params, ids, count, rows=None, weight=1.0):
        rows = rows or kept
        k1, k2 = jax.random.split(jax.random.fold_in(base_key, count))
        salts = jnp.stack([jax.random.bits(k1, (), jnp.uint32),
                           jax.random.bits(k2, (), jnp.uint32)])
        total_loss, total_grad = 0.0, None
        for row0 in range(0, rows, block):
            part = ids[row0:row0 + block]
            loss, grad = block_sum(
                params, tables, feat, jnp.asarray(src[part]),
                jnp.asarray(dst[part]),
                jnp.asarray(labels[part]), jnp.int32(row0), salts,
                jnp.float32(weight))
            total_loss = total_loss + loss
            total_grad = grad if total_grad is None else jax.tree.map(
                jnp.add, total_grad, grad)
        return total_loss / rows, jax.tree.map(lambda g: g / rows, total_grad)

    spec = param_spec(model, feat.shape[1])
    return common.follow(
        lambda: common.init_params(seed, spec), batches, common.whole(step), {
            "learning_rate": opt["learning_rate"],
            "weight_decay": opt["weight_decay"],
            "warmup": common.warmup_steps(total), "total_steps": total},
        sink, frozen=frozen,
        logit=lambda params, ids: step(params, ids, 0, weight=0.0)[1])
