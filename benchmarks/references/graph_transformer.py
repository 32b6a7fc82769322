"""Plain reference for the ``graph_transformer`` kind: the probe graph's
neighbour lists, full-topology neighbour attention, the edge head, the
loss, its gradients by plain autodiff (the attention gathers' backward
is the scatter-add autodiff gives, not the trainer's inverse index) and
AdamW, in float32 with every product at full precision.

Written from the model's equations (pre-LN blocks: LN → q, k, v → per
row a softmax over its listed neighbours of q·k/√d − log1p(rtt_ms) →
out-projection → residual; LN → 2× MLP with tanh-GELU → residual; final
LN → embedding; edge head on [emb_src | emb_dst]). Attention runs in
blocks of rows under ``jax.checkpoint`` so that the cell's own size
fits beside nothing else on the chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import common

NEG = -1e9


def neighbour_lists(n: int, src, dst, rtt_ns, cap: int):
    """Symmetrised probe graph with self loops → ``nbr [N, K]`` (−1
    pads) and ``val [N, K]`` = −log1p(best observed rtt in ms), each
    row's best ``cap`` by value."""
    value = (-np.log1p(rtt_ns.astype(np.float64) / 1e6)).astype(np.float32)
    rows = np.concatenate([src, dst, np.arange(n)]).astype(np.int64)
    cols = np.concatenate([dst, src, np.arange(n)]).astype(np.int64)
    vals = np.concatenate([value, value, np.zeros(n, np.float32)])
    # Best value first within each (row, col), then keep one of each pair.
    order = np.lexsort((-vals, cols, rows))
    rows, cols, vals = rows[order], cols[order], vals[order]
    first = np.r_[True, (rows[1:] != rows[:-1]) | (cols[1:] != cols[:-1])]
    rows, cols, vals = rows[first], cols[first], vals[first]
    order = np.lexsort((cols, -vals, rows))
    rows, cols, vals = rows[order], cols[order], vals[order]
    starts = np.flatnonzero(np.r_[True, rows[1:] != rows[:-1]])
    rank = np.arange(len(rows)) - np.repeat(
        starts, np.diff(np.r_[starts, len(rows)]))
    keep = rank < cap
    rows, cols, vals, rank = rows[keep], cols[keep], vals[keep], rank[keep]
    width = int(rank.max()) + 1
    nbr = np.full((n, width), -1, np.int32)
    val = np.zeros((n, width), np.float32)
    nbr[rows, rank] = cols
    val[rows, rank] = vals
    return nbr, val


def param_spec(model: dict, n_features: int) -> dict:
    h, e = model["hidden"], model["embed"]
    spec = {"input_proj": ("dense", n_features, h)}
    for i in range(model["layers"]):
        b = f"blocks_{i}"
        spec[f"{b}/LayerNorm_0"] = ("norm", h)
        for j in range(4):
            spec[f"{b}/Dense_{j}"] = ("dense", h, h)
        spec[f"{b}/LayerNorm_1"] = ("norm", h)
        spec[f"{b}/Dense_4"] = ("dense", h, 2 * h)
        spec[f"{b}/Dense_5"] = ("dense", 2 * h, h)
    spec["final_norm"] = ("norm", h)
    spec["embed_proj"] = ("dense", h, e)
    spec["head_hidden"] = ("dense", 2 * e, e)
    spec["head_out"] = ("dense", e, 1)
    return spec


def _row_block(n: int, most: int = 6400) -> int:
    return next(b for b in range(min(n, most), 0, -1) if n % b == 0)


def _attention(q, k, v, nbr, val, rnd):
    """q, k, v ``[N, heads, d]``; each row over its own neighbour list."""
    n, heads, d = q.shape
    block = _row_block(n)

    @jax.checkpoint
    def rows(args):
        qb, nb, vb = args
        pad = nb < 0
        idx = jnp.where(pad, 0, nb)
        kg, vg = k[idx], v[idx]                       # [R, K, heads, d]
        s = jnp.einsum("rhd,rkhd->rhk", rnd(qb), rnd(kg),
                       precision="highest") / np.sqrt(d)
        s = jnp.where(pad[:, None, :], NEG, s + vb[:, None, :])
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("rhk,rkhd->rhd", rnd(p), rnd(vg),
                          precision="highest")

    split = lambda a: a.reshape(n // block, block, *a.shape[1:])  # noqa: E731
    return jax.lax.map(rows, (split(q), split(nbr), split(val))).reshape(
        n, heads, d)


def logits(params, model, feat, nbr, val, src, dst, rnd):
    lay = lambda name: common.layer(params, name)  # noqa: E731
    heads = model["heads"]
    h = common.dense(feat, lay("input_proj"), rnd)
    for i in range(model["layers"]):
        b = f"blocks_{i}"
        x = common.layer_norm(h, lay(f"{b}/LayerNorm_0"))
        q, k, v = (common.dense(x, lay(f"{b}/Dense_{j}"), rnd).reshape(
            len(x), heads, -1) for j in range(3))
        att = _attention(q, k, v, nbr, val, rnd).reshape(len(x), -1)
        h = h + common.dense(att, lay(f"{b}/Dense_3"), rnd)
        y = common.layer_norm(h, lay(f"{b}/LayerNorm_1"))
        y = jax.nn.gelu(common.dense(y, lay(f"{b}/Dense_4"), rnd),
                        approximate=True)
        h = h + common.dense(y, lay(f"{b}/Dense_5"), rnd)
    emb = common.dense(common.layer_norm(h, lay("final_norm")),
                       lay("embed_proj"), rnd)
    pair = jnp.concatenate([emb[src], emb[dst]], axis=-1)
    z = jax.nn.relu(common.dense(pair, lay("head_hidden"), rnd))
    return common.dense(z, lay("head_out"), rnd)[:, 0]


def readings(config: dict, graph: dict, seed: int, steps: int, sink,
             precision: str = "float32", keep_rows: float = 1.0,
             frozen: bool = False):
    """Follow the trainer's first ``steps`` from the seed, handing
    ``sink`` what the comparison reads as it is made. For the control's
    readings ``keep_rows`` < 1 plants the fault "part of the
    batch left out, the mean taken over the rest" and ``frozen`` the
    fault "a step that returns its state unchanged"."""
    model, opt = config["model"], config["optimizer"]
    n = len(graph["node_features"])
    src, dst = graph["edge_src"], graph["edge_dst"]
    labels = (graph["edge_rtt_ns"] < opt["rtt_threshold_ns"]).astype(
        np.float32)
    nbr, val = neighbour_lists(n, src, dst, graph["edge_rtt_ns"],
                               model["neighbor_cap"])
    batch = min(config["batch"], len(src))
    per_epoch = max(len(src) // batch, 1)
    total = max(config["epochs"] * per_epoch, 2)
    # The trainer's feed: one permutation of the edge ids per epoch from
    # default_rng((seed, 7)), cut into consecutive batches.
    order = np.random.default_rng((seed, 7)).permutation(len(src))
    kept = max(int(batch * keep_rows), 1)
    batches = [order[i * batch:(i + 1) * batch] for i in range(steps)]

    rnd = common.rounder(precision)
    feat, nbr_d, val_d = map(jnp.asarray, (graph["node_features"], nbr, val))

    # The graph goes in as arguments: as constants it would be part of
    # the program, and every seed would compile anew.
    @jax.jit
    def loss_and_grad(params, graph_, s, d, y, w):
        def loss(p):
            z = logits(p, model, *graph_, s, d, rnd)
            return (common.sigmoid_bce(z, y) * w).sum()
        return jax.value_and_grad(loss)(params)

    @jax.jit
    def mean_logit_grad(params, graph_, s, d):
        return jax.grad(
            lambda p: logits(p, model, *graph_, s, d, rnd).mean())(params)

    def step(params, ids, _, rows=None):
        return loss_and_grad(
            params, (feat, nbr_d, val_d), jnp.asarray(src[ids]),
            jnp.asarray(dst[ids]), jnp.asarray(labels[ids]),
            jnp.asarray(common.row_weights(len(ids), rows or kept)))

    def logit_grad(params, ids):
        return mean_logit_grad(params, (feat, nbr_d, val_d),
                               jnp.asarray(src[ids]), jnp.asarray(dst[ids]))

    spec = param_spec(model, graph["node_features"].shape[1])
    return common.follow(
        lambda: common.init_params(seed, spec), batches, common.whole(step), {
            "learning_rate": opt["learning_rate"],
            "weight_decay": opt["weight_decay"],
            "warmup": common.warmup_steps(total), "total_steps": total},
        sink, frozen=frozen, logit=logit_grad)
