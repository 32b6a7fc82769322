"""On-chip step-time probe for config #3's train step: decomposes the
GAT throughput number into forward / backward(autodiff scatter) /
backward(inverse-index gather) so backward-path changes are judged by
direct step timing, not end-to-end samples/sec (which folds in eval
and host effects). Run ALONE — concurrent load on the host poisons the
dispatch loop.
"""
import json
import statistics
import sys
import time

sys.path.insert(0, "/root/repo")
from dragonfly2_tpu.utils.compilecache import enable_compilation_cache

enable_compilation_cache()

import jax
import jax.numpy as jnp
import numpy as np
import optax

from dragonfly2_tpu.data import SyntheticCluster
from dragonfly2_tpu.models.graph_transformer import (
    GraphTransformer, build_inverse_index, build_neighbor_lists,
)
from dragonfly2_tpu.train.gat_trainer import edge_split, pad_graph_sparse

HIDDEN, EMBED, LAYERS, HEADS, CAP, BATCH = 128, 64, 2, 4, 64, 8192

out = {"platform": jax.devices()[0].platform}
cluster = SyntheticCluster(n_hosts=20_000, seed=0)
graph = cluster.probe_graph(500_000)
labels = graph.edge_labels(1_000_000).astype(np.float32)
train_ids, _ = edge_split(graph, 0.02, 0)
nbr, val = build_neighbor_lists(
    graph.n_nodes, graph.edge_src[train_ids], graph.edge_dst[train_ids],
    graph.edge_rtt_ns[train_ids], cap=CAP)
feat, nbr, val, _ = pad_graph_sparse(graph.node_features, nbr, val, 1)
inv = build_inverse_index(nbr, val)
out["inv_shape"] = list(inv.rows.shape)

model = GraphTransformer(hidden=HIDDEN, embed=EMBED, layers=LAYERS,
                         heads=HEADS, attention="gather")
params = model.init(jax.random.key(0), jnp.asarray(feat), jnp.asarray(nbr),
                    jnp.asarray(val), jnp.zeros(2, jnp.int32),
                    jnp.zeros(2, jnp.int32))
tx = optax.adamw(1e-3)
opt = tx.init(params)

rng = np.random.default_rng(0)
ids = rng.choice(train_ids, BATCH, replace=False)
src = jnp.asarray(graph.edge_src[ids])
dst = jnp.asarray(graph.edge_dst[ids])
y = jnp.asarray(labels[ids])
feat_d, nbr_d, val_d = map(jnp.asarray, (feat, nbr, val))
inv_d = jax.tree.map(jnp.asarray, inv)


def timeit(fn, *args, reps=8):
    r = jax.block_until_ready(fn(*args))  # compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        r = jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    del r
    return round(statistics.median(ts) * 1e3, 1)


@jax.jit
def fwd(p):
    logits = model.apply(p, feat_d, nbr_d, val_d, src, dst)
    return optax.sigmoid_binary_cross_entropy(logits, y).mean()


def make_step(use_inv):
    def loss_fn(p):
        logits = model.apply(p, feat_d, nbr_d, val_d, src, dst,
                             inv=inv_d if use_inv else None)
        return optax.sigmoid_binary_cross_entropy(logits, y).mean()

    @jax.jit
    def step(p, o):
        loss, g = jax.value_and_grad(loss_fn)(p)
        up, o2 = tx.update(g, o, p)
        return optax.apply_updates(p, up), o2, loss

    return step

out["fwd_ms"] = timeit(fwd, params)
s_scatter = make_step(False)
out["fwd_bwd_scatter_ms"] = timeit(s_scatter, params, opt)
s_inv = make_step(True)
out["fwd_bwd_inverse_ms"] = timeit(s_inv, params, opt)
print(json.dumps(out), flush=True)
if len(sys.argv) > 1:
    with open(sys.argv[1], "w") as f:
        json.dump(out, f, indent=1)
