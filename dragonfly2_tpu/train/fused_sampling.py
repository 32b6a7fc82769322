"""On-device neighbor sampling: the whole GraphSAGE step in one XLA program.

Round-2 measured 16.1k samples/sec/chip with host-side sampling — the step
was dominated by numpy fancy-indexing over ~1M positions per batch plus
~15 MB/step of H2D index/mask traffic, while the chip's matmul work is
~2 GFLOP/step (<1 ms on a v5e MXU). TPU-first fix: put the CSR adjacency
(int32 indices + f32 RTTs, ~16 MB at 2M edges) and the node-feature table
in HBM once, replicated, and do fanout sampling INSIDE the jitted train
step — threefry bits → mod-degree offsets → position gathers — so
sampling, gather, and matmuls fuse into one program and the host ships
only a [B] int32 edge-id slice per step (~32 KB).

Static shapes throughout: every array's shape is a pure function of
(B, fanouts, F), so XLA compiles exactly one program; sampling uses
replacement (same estimator as the host sampler, data/graph_sampler.py)
and zero-degree nodes get masked padded slots.

Sharding: edge-id batches shard over ``data``; tables and params
replicate; every table gather states ``out_sharding`` explicitly (each
device gathers its own index shard locally — no collective); XLA inserts
the gradient allreduce over ICI.  Reference counterpart: this fills
trainer/training/training.go:82-90's trainGNN stub; there is no reference
implementation to compare against.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from dragonfly2_tpu.data.graph_sampler import CSRGraph
from dragonfly2_tpu.models.graphsage import GraphSAGE
from dragonfly2_tpu.parallel import MeshContext


class GraphTables(NamedTuple):
    """Device-resident, replicated graph state for fused-sampling steps."""

    indptr: jax.Array         # [N+1] int32 — CSR row starts
    indices: jax.Array        # [E] int32 — neighbor node ids
    edge_rtt: jax.Array       # [E] float32 — log1p(rtt_ms)
    node_features: jax.Array  # [N, F] float32


class EdgeTables(NamedTuple):
    """Device-resident target-edge split (train or eval)."""

    src: jax.Array     # [M] int32
    dst: jax.Array     # [M] int32
    labels: jax.Array  # [M] float32


def put_graph_tables(csr: CSRGraph, mesh: MeshContext) -> GraphTables:
    return GraphTables(*(
        jax.device_put(a, mesh.replicated) for a in (
            # int32 row starts: 2G-edge graphs are beyond one chip's HBM
            # anyway, so narrow indptr halves a hot gather's footprint.
            csr.indptr.astype(np.int32),
            csr.indices,
            csr.edge_rtt,
            csr.node_features,
        )
    ))


def put_edge_tables(src: np.ndarray, dst: np.ndarray, labels: np.ndarray,
                    mesh: MeshContext) -> EdgeTables:
    return EdgeTables(
        jax.device_put(src.astype(np.int32), mesh.replicated),
        jax.device_put(dst.astype(np.int32), mesh.replicated),
        jax.device_put(labels.astype(np.float32), mesh.replicated),
    )


def _gather(table: jax.Array, idx: jax.Array, out_sharding) -> jax.Array:
    if out_sharding is None:
        return table[idx]
    return table.at[idx].get(out_sharding=out_sharding)


def _lowbias32(x: jax.Array) -> jax.Array:
    """32-bit avalanche hash (lowbias32) — pure elementwise integer ops."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def _hashed_bits(salt: jax.Array, shape: tuple) -> jax.Array:
    """Deterministic uniform u32s from (salt, global position).

    Why not ``jax.random.bits`` here: threefry over a big batch-sharded
    shape makes GSPMD all-gather partial RNG state inside the threefry
    loop on every step — wasted ICI bandwidth, and it deadlocks XLA:CPU's
    in-process collectives under overlapped launches (observed on the
    8-device virtual mesh). A counter-based hash of the global position
    is iota + elementwise ops only: partitions over any mesh with ZERO
    collectives, and identical results regardless of device count.
    Threefry stays for the scalar per-step salts, so streams across
    steps/hops remain independent.
    """
    idx = jnp.zeros(shape, jnp.uint32)
    mult = 1
    for d in reversed(range(len(shape))):
        idx = idx + jax.lax.broadcasted_iota(jnp.uint32, shape, d) * jnp.uint32(mult)
        mult *= shape[d]
    return _lowbias32(_lowbias32(idx + salt) ^ (salt * jnp.uint32(0x9E3779B9)))


def sample_neighbors(graph: GraphTables, nodes: jax.Array, fanout: int,
                     salt: jax.Array, out_sharding=None):
    """Fanout-sample WITH replacement for each node; returns
    (nbr_idx, rtt, mask), each ``nodes.shape + (fanout,)``.

    Mirrors CSRGraph.sample_neighbors (host half) exactly: padded slots
    (zero-degree nodes) carry index 0 / rtt 0 / mask 0; positive-degree
    nodes always fill all ``fanout`` replacement-sampled slots.
    """
    start = _gather(graph.indptr, nodes, out_sharding)
    deg = _gather(graph.indptr, nodes + 1, out_sharding) - start
    bits = _hashed_bits(salt, nodes.shape + (fanout,))
    safe_deg = jnp.maximum(deg, 1).astype(jnp.uint32)
    offs = (bits % safe_deg[..., None]).astype(jnp.int32)
    pos = start[..., None] + offs
    # Zero-degree tail nodes point at indptr[-1] == E (out of bounds);
    # their mask is 0, any in-bounds position works — clamp.
    pos = jnp.minimum(pos, graph.indices.shape[0] - 1)
    nbr = _gather(graph.indices, pos, out_sharding)
    rtt = _gather(graph.edge_rtt, pos, out_sharding)
    mask = jnp.broadcast_to(
        (deg > 0).astype(jnp.float32)[..., None], pos.shape)
    return jnp.where(mask > 0, nbr, 0), rtt * mask, mask


def sample_and_apply(model: GraphSAGE, params, graph: GraphTables,
                     src, dst, key: jax.Array, fanouts: tuple,
                     out_sharding=None):
    """Sample the 2-hop neighborhood on device and run the forward pass.

    ``key`` only seeds two SCALAR salts (tiny replicated threefry); the
    per-slot randomness comes from the counter hash above.

    The phases carry ``df2.*`` scopes (``df2.sample.hop1``,
    ``df2.sample.hop2``, ``df2.features``, ``df2.model``): metadata on
    the operations, by which ``df2-trace-tool train`` splits a device
    trace (docs/OBSERVABILITY.md "Training loops").
    """
    f1, f2 = fanouts
    k1, k2 = jax.random.split(key)
    s1 = jax.random.bits(k1, (), jnp.uint32)
    s2 = jax.random.bits(k2, (), jnp.uint32)
    centers = jnp.stack([src, dst], axis=-1)                     # [B, 2]
    with jax.named_scope("df2.sample.hop1"):
        nbr1, rtt1, mask1 = sample_neighbors(
            graph, centers, f1, s1, out_sharding)
    with jax.named_scope("df2.sample.hop2"):
        nbr2, rtt2, mask2 = sample_neighbors(
            graph, nbr1, f2, s2, out_sharding)
        mask2 = mask2 * mask1[..., None]
    with jax.named_scope("df2.features"):
        feat0 = _gather(graph.node_features, centers, out_sharding)
        feat1 = _gather(graph.node_features, nbr1, out_sharding)
        feat2 = _gather(graph.node_features, nbr2, out_sharding)
    with jax.named_scope("df2.model"):
        return model.apply(params, feat0, feat1, rtt1, mask1,
                           feat2, rtt2 * mask2, mask2)


def _batch_rows(edges: EdgeTables, edge_ids, out_sharding):
    """(src, dst, labels) of one id batch, under ``df2.batch``."""
    with jax.named_scope("df2.batch"):
        return tuple(_gather(table, edge_ids, out_sharding)
                     for table in edges)


def _fused_update(model: GraphSAGE, state, graph: GraphTables,
                  edges: EdgeTables, edge_ids, key, fanouts: tuple,
                  out_sharding):
    """One optimizer step on one id batch: the body the one-step and the
    multi-step programs share, so both carry the same scopes."""
    src, dst, labels = _batch_rows(edges, edge_ids, out_sharding)

    def loss_fn(params):
        logits = sample_and_apply(
            model, params, graph, src, dst, key, fanouts, out_sharding)
        with jax.named_scope("df2.loss"):
            return optax.sigmoid_binary_cross_entropy(logits, labels).mean()

    loss, grads = jax.value_and_grad(loss_fn)(state.params)
    with jax.named_scope("df2.optimizer"):
        state = state.apply_gradients(grads=grads)
    return state, loss


def make_fused_train_step(model: GraphSAGE, mesh: MeshContext,
                          fanouts: tuple):
    """jit: (state, graph, edges, edge_ids[B], key) → (state, loss).

    The key is folded with ``state.step`` inside the program, so one
    compiled step serves every iteration with fresh sampling randomness.
    """
    b = mesh.batch_sharding

    def train_step(state, graph, edges, edge_ids, key):
        key = jax.random.fold_in(key, state.step)
        return _fused_update(model, state, graph, edges, edge_ids, key,
                             fanouts, b)

    return jax.jit(
        train_step,
        in_shardings=(None, mesh.replicated, mesh.replicated, b,
                      mesh.replicated),
        donate_argnums=(0,),
    )


def make_fused_multi_step(model: GraphSAGE, mesh: MeshContext,
                          fanouts: tuple, steps_per_call: int):
    """jit: (state, graph, edges, edge_ids[K, B], key) → (state, losses[K]).

    K fused steps under one ``lax.scan`` — one dispatch amortizes the
    host→device round trip across K optimizer updates. Where per-step
    dispatch is the throughput ceiling (a host-bound pipeline), scan
    moves the loop onto the device the XLA-idiomatic way (no Python
    control flow in the compiled program).
    """
    b = mesh.batch_sharding
    ids_sharding = mesh.shard_spec(None, "data")  # [K, B]: B over data

    def multi_step(state, graph, edges, edge_ids_k, key):
        def body(state, edge_ids):
            step_key = jax.random.fold_in(key, state.step)
            return _fused_update(model, state, graph, edges, edge_ids,
                                 step_key, fanouts, b)

        return jax.lax.scan(body, state, edge_ids_k)

    return jax.jit(
        multi_step,
        in_shardings=(None, mesh.replicated, mesh.replicated, ids_sharding,
                      mesh.replicated),
        donate_argnums=(0,),
    )


def make_fused_eval_step(model: GraphSAGE, mesh: MeshContext,
                         fanouts: tuple):
    """jit: (params, graph, edges, edge_ids[B], weights[B], key) →
    [tp, fp, fn, tn] — confusion-matrix accumulation with tail-padding
    rows zero-weighted so every eval edge counts exactly once."""
    b = mesh.batch_sharding

    def eval_step(params, graph, edges, edge_ids, weights, key):
        # Caller folds a per-chunk key (slicing a sharded edge_ids inside
        # the program would force an unimplementable reshard).
        src, dst, labels = _batch_rows(edges, edge_ids, b)
        logits = sample_and_apply(
            model, params, graph, src, dst, key, fanouts, b)
        pred = (logits > 0).astype(jnp.float32)
        tp = jnp.sum(weights * pred * labels)
        fp = jnp.sum(weights * pred * (1 - labels))
        fn = jnp.sum(weights * (1 - pred) * labels)
        tn = jnp.sum(weights * (1 - pred) * (1 - labels))
        return jnp.stack([tp, fp, fn, tn])

    return jax.jit(
        eval_step,
        in_shardings=(None, mesh.replicated, mesh.replicated, b, b,
                      mesh.replicated),
    )
