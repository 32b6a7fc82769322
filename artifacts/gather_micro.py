"""Microbench of neighbor-gather BACKWARD formulations on-chip.

The candidate kernels all compute d_table[j] = sum of ct rows whose
neighbor slot references j, at config #3 shapes (N=20k, K=64, in-degree
pad D=81, h=4, w=32). Run ALONE (single-core box).
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

from dragonfly2_tpu.data import SyntheticCluster
from dragonfly2_tpu.models.graph_transformer import (
    build_inverse_index, build_neighbor_lists,
)
from dragonfly2_tpu.train.gat_trainer import edge_split, pad_graph_sparse

N_HOSTS, CAP, H, W = 20_000, 64, 4, 32

cluster = SyntheticCluster(n_hosts=N_HOSTS, seed=0)
graph = cluster.probe_graph(500_000)
train_ids, _ = edge_split(graph, 0.02, 0)
nbr, val = build_neighbor_lists(
    graph.n_nodes, graph.edge_src[train_ids], graph.edge_dst[train_ids],
    graph.edge_rtt_ns[train_ids], cap=CAP)
feat, nbr, val, _ = pad_graph_sparse(graph.node_features, nbr, val, 1)
inv = build_inverse_index(nbr)
n, k_width = nbr.shape
d_max = inv.shape[1]

rng = np.random.default_rng(0)
ct = jnp.asarray(rng.standard_normal((n, k_width, H, W)), jnp.float32)
pad = nbr >= n
idx_d = jnp.asarray(np.where(pad, 0, nbr))
padmask_d = jnp.asarray(pad)
inv_d = jnp.asarray(inv)
invpad_d = jnp.asarray(inv < 0)
safe_d = jnp.asarray(np.where(inv < 0, 0, inv))
# variant: pad slots point at one sacrificial zero row appended to flat
safe_last_d = jnp.asarray(np.where(inv < 0, n * k_width, inv))

table = jnp.asarray(rng.standard_normal((n, H, W)), jnp.float32)


def timeit(fn, *args, reps=10):
    r = jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        r = jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    del r
    return round(statistics.median(ts) * 1e3, 2)


@jax.jit
def scatter_add(ct_):
    # what autodiff's transpose emits (duplicate-index scatter-add),
    # with pad-slot cotangents zeroed the way the attention mask does
    ct_ = jnp.where(padmask_d[..., None, None], 0.0, ct_)
    return jnp.zeros((n, H, W), jnp.float32).at[idx_d].add(ct_)


@jax.jit
def inv_gather_current(ct_):
    # the shipped _neighbor_gather_bwd: gather rows, mask, f32 sum
    flat = ct_.reshape(n * k_width, H, W)
    contrib = flat[safe_d]
    contrib = jnp.where(invpad_d[..., None, None], 0.0,
                        contrib.astype(jnp.float32))
    return contrib.sum(axis=1)


@jax.jit
def inv_gather_wide(ct_):
    # rows reshaped to [*, H*W]=128 lanes before the gather
    flat = ct_.reshape(n * k_width, H * W)
    contrib = flat[safe_d]
    contrib = jnp.where(invpad_d[..., None], 0.0, contrib)
    return contrib.sum(axis=1, dtype=jnp.float32).reshape(n, H, W)


@jax.jit
def inv_gather_zero_row(ct_):
    # sacrificial zero row instead of the post-gather mask
    flat = ct_.reshape(n * k_width, H * W)
    flat = jnp.concatenate([flat, jnp.zeros((1, H * W), ct_.dtype)])
    contrib = flat[safe_last_d]
    return contrib.sum(axis=1, dtype=jnp.float32).reshape(n, H, W)


@jax.jit
def fwd_gather_current(t):
    return t[idx_d]


@jax.jit
def fwd_gather_wide(t):
    return t.reshape(n, H * W)[idx_d].reshape(n, k_width, H, W)


ct2 = jnp.asarray(rng.standard_normal((n, k_width, H, 2 * W)), jnp.float32)
table2 = jnp.asarray(rng.standard_normal((n, H, 2 * W)), jnp.float32)


@jax.jit
def inv_gather_fused(ct_):
    flat = ct_.reshape(n * k_width, H * 2 * W)
    contrib = flat[safe_d]
    contrib = jnp.where(invpad_d[..., None], 0.0, contrib)
    return contrib.sum(axis=1, dtype=jnp.float32).reshape(n, H, 2 * W)


out = {"platform": jax.devices()[0].platform,
       "shapes": {"n": int(n), "k": int(k_width), "d_max": int(d_max)}}
# same gather formulation, double-width [k|v] table (jit retraces on
# the wider shape): same bytes as two narrow gathers, half the rows
out["fwd_gather_fused_kv_ms"] = timeit(fwd_gather_current, table2)
out["inv_fused_kv_ms"] = timeit(inv_gather_fused, ct2)
out["scatter_add_ms"] = timeit(scatter_add, ct)
out["inv_current_ms"] = timeit(inv_gather_current, ct)
out["inv_wide_ms"] = timeit(inv_gather_wide, ct)
out["inv_zero_row_ms"] = timeit(inv_gather_zero_row, ct)
out["fwd_gather_ms"] = timeit(fwd_gather_current, table)
out["fwd_gather_wide_ms"] = timeit(fwd_gather_wide, table)

if jax.devices()[0].platform == "tpu":
    # VMEM-resident pallas kernels at the REAL config #3 shapes: the
    # bf16 fused [k|v] table (10.2 MB, fits VMEM) and its cotangent.
    # Each measurement is individually guarded: a kernel failure must
    # not discard the XLA numbers of an unattended run.
    from dragonfly2_tpu.ops.table_gather import (
        table_gather, table_scatter_add)

    kv_bf16 = jnp.asarray(
        rng.standard_normal((n, 2 * H * W)), jnp.bfloat16)
    flat_idx = jnp.asarray(np.where(pad, 0, nbr).reshape(-1), jnp.int32)
    ct_bf16 = jnp.asarray(
        rng.standard_normal((n * k_width, 2 * H * W)), jnp.bfloat16)

    def guarded(key, fn, *args):
        try:
            out[key] = timeit(fn, *args)
        except Exception as e:  # noqa: BLE001 — record, keep benching
            out[key] = None
            out[key + "_error"] = f"{type(e).__name__}: {e}"[:300]

    guarded("pallas_fwd_gather_ms",
            lambda ix: table_gather(kv_bf16, ix), flat_idx)
    guarded("pallas_scatter_add_ms",
            lambda c: table_scatter_add(c, flat_idx, n), ct_bf16)
    # XLA same-shape baselines (bf16 fused rows) for a fair A/B
    guarded("xla_fwd_gather_bf16_fused_ms",
            lambda ix: kv_bf16[ix], flat_idx)
    guarded("xla_scatter_add_bf16_fused_ms",
            lambda c: jnp.zeros((n, 2 * H * W), jnp.float32).at[flat_idx]
            .add(c.astype(jnp.float32)), ct_bf16)
    try:
        pg = jax.block_until_ready(table_gather(kv_bf16, flat_idx))
        xg = jax.block_until_ready(kv_bf16[flat_idx])
        out["pallas_fwd_max_diff"] = float(
            jnp.max(jnp.abs(pg.astype(jnp.float32)
                            - xg.astype(jnp.float32))))
    except Exception as e:  # noqa: BLE001
        out["pallas_fwd_max_diff_error"] = f"{type(e).__name__}: {e}"[:300]
# numerics cross-check
a = jax.block_until_ready(scatter_add(ct))
b = jax.block_until_ready(inv_gather_wide(ct))
c = jax.block_until_ready(inv_gather_zero_row(ct))
out["max_abs_diff_wide"] = float(jnp.max(jnp.abs(a - b)))
out["max_abs_diff_zero_row"] = float(jnp.max(jnp.abs(a - c)))
print(json.dumps(out), flush=True)
if len(sys.argv) > 1:
    with open(sys.argv[1], "w") as f:
        json.dump(out, f, indent=1)
