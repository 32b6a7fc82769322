"""On-device neighbor sampling: the whole GraphSAGE step in one XLA program.

The graph lives in HBM once, replicated, and fan-out sampling runs
INSIDE the jitted train step (counter-hash bits, modulo the degree), so
sampling, feature gathers and matmuls are one program and the host ships
a ``[B]`` int32 edge-id slice a step.

**The tables.** On the v5e a scalar gather costs 7-14 ns an index
whatever its table; a row gather costs 2.8 ns a row of 1 KB out of the
chip's fast memory (which holds one table of some 100 MB at a time; the
compiler moves tables in and out) and 12 ns out of HBM. At batch 131,072
and fan-outs (10, 5) the CSR sampler's 37.2M scalar gathers were 403 of
the step's 489 ms (ledger, PR 27: ``sample_ms``; hop 2 alone 359 ms). So
:func:`put_graph_tables` lays the CSR out as per-host **rows**
(:class:`RowTables`): neighbour ids and RTTs (the bits of the CSR's
``float32``, held as ``int32``) padded with zeros to ``row_width``
lanes, the longest row and one lane for the degree (the ids' last lane)
rounded up to 128. A hop then fetches one row a *node* from each table
(5.8M row gathers a step) and picks its ``fanout`` slots inside the row
on the vector unit (compare against a lane iota, sum over the lanes:
exact), in slices of the batch under ``lax.map`` so that a device holds
at most :data:`ROW_CHUNK_BYTES` of fetched rows, and with the two tables
read one after the other so that each is in fast memory when it is
read: 54 ms where the CSR path took 403 (PERF.md section 5, PR 28).

**When the CSR path is taken.** Padding pays while rows are of one
order: where the padded tables would hold more than
:data:`ROW_PAD_FACTOR` times the CSR's entries *and* more than
:data:`ROW_PAD_FREE_BYTES` (a power-law graph with one hub),
``put_graph_tables`` keeps the CSR (:class:`GraphTables`) and
``sample_neighbors`` its position gathers. Both paths draw the same
offsets from the same hash: their samples are equal bit for bit
(``tests/test_fused_sampling.py``). ``sampler_row_width`` of the
``training`` block says which one a process placed (0: CSR).

**The layout.** A hop's ids, RTTs, mask and feature rows have one order
from the sampler's slices to the model's sums: fan-out axes leading, the
newest first, and the batch trailing, in the lanes (``[f1, 2, B]``,
``[f2, f1, 2, B]``: the axes of the host sampler's ``[B, 2, f1, f2]`` in
reverse; :func:`sample_neighbors` has the contract, models/graphsage.py
the model's side). The select produces a hop that way and nothing turns
it into batch order: until PR 34 a transpose of every hop, copies of
hop 2's ids and two relayouts of its 13.1M feature rows (lane-padded,
3.4 GB) were 43.5 of the step's 133.4 ms (ledger, PR 33).

Static shapes throughout: every array's shape is a pure function of
(B, fanouts, F, row width), so XLA compiles exactly one program; sampling
uses replacement (same estimator as the host sampler,
data/graph_sampler.py) and zero-degree nodes get masked padded slots.

Sharding: edge-id batches shard over ``data``; tables and params
replicate; every table gather states ``out_sharding`` explicitly (each
device gathers its own index shard locally — no collective); XLA inserts
the gradient allreduce over ICI.  Reference counterpart: this fills
trainer/training/training.go:82-90's trainGNN stub; there is no reference
implementation to compare against.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from dragonfly2_tpu.data.graph_sampler import CSRGraph
from dragonfly2_tpu.models.graphsage import GraphSAGE
from dragonfly2_tpu.parallel import MeshContext
from dragonfly2_tpu.train.step_budget import TRAINING

LANES = 128
# The row tables are taken unless padding costs more than this many
# times the CSR's entries AND more than this many bytes (both tables).
ROW_PAD_FACTOR = 4
ROW_PAD_FREE_BYTES = 256 << 20
# Fetched rows (both tables) a device holds at once; a hop over more
# nodes than that runs in slices of the batch. A slice is also the work
# that covers the second table's copy into fast memory: at the cells'
# shapes the compiler starts that copy inside hop 2's loop for slices of
# 327,680 nodes (8 of them under this limit) and left the table in HBM
# for 163,840 (tests/test_chip_compile.py holds the four fetches).
ROW_CHUNK_BYTES = 1 << 30


class GraphTables(NamedTuple):
    """Device-resident, replicated graph state, CSR form: what a graph
    whose rows would pad too far keeps (module text)."""

    indptr: jax.Array         # [N+1] int32 — CSR row starts
    indices: jax.Array        # [E] int32 — neighbor node ids
    edge_rtt: jax.Array       # [E] float32 — log1p(rtt_ms)
    node_features: jax.Array  # [N, F] float32

    row_width = 0


class RowTables(NamedTuple):
    """Device-resident, replicated graph state, one lane-dense row a
    host. Lanes past a row's degree hold 0; the ids' last lane holds the
    degree."""

    nbr_rows: jax.Array       # [N, W] int32 — neighbor node ids | degree
    rtt_rows: jax.Array       # [N, W] int32 — the bits of float32 log1p(rtt_ms)
    node_features: jax.Array  # [N, F] float32

    @property
    def row_width(self) -> int:
        return self.nbr_rows.shape[1]


class EdgeTables(NamedTuple):
    """Device-resident target-edge split (train or eval)."""

    src: jax.Array     # [M] int32
    dst: jax.Array     # [M] int32
    labels: jax.Array  # [M] float32


def row_width(csr: CSRGraph) -> int:
    """Lanes of a host's row in :class:`RowTables`, or 0 where the graph
    keeps its CSR form: the one rule of the module's text."""
    degree = np.diff(csr.indptr)
    width = -(-(int(degree.max(initial=0)) + 1) // LANES) * LANES
    padded = csr.n_nodes * width
    if (padded > ROW_PAD_FACTOR * len(csr.indices)
            and 2 * 4 * padded > ROW_PAD_FREE_BYTES):
        return 0
    return width


def put_graph_tables(csr: CSRGraph, mesh: MeshContext):
    width = row_width(csr)
    TRAINING.set(sampler_row_width=width)
    if not width:
        tables = GraphTables(
            # int32 row starts: 2G-edge graphs are beyond one chip's HBM
            # anyway, so narrow indptr halves a hot gather's footprint.
            csr.indptr.astype(np.int32), csr.indices, csr.edge_rtt,
            csr.node_features)
    else:
        degree = np.diff(csr.indptr)
        # Entry e of host h's row goes to lane e - indptr[h] of row h.
        at = np.arange(len(csr.indices)) + np.repeat(
            np.arange(csr.n_nodes) * width - csr.indptr[:-1], degree)
        nbr = np.zeros((csr.n_nodes, width), np.int32)
        rtt = np.zeros((csr.n_nodes, width), np.float32)
        nbr.reshape(-1)[at] = csr.indices
        rtt.reshape(-1)[at] = csr.edge_rtt
        nbr[:, -1] = degree
        # As int32: a slot's RTT is picked like its id, and only the
        # picked slots are read as float32 again (on the chip a bitcast
        # of the fetched rows is a pass over them).
        tables = RowTables(nbr, rtt.view(np.int32), csr.node_features)
    return type(tables)(*(jax.device_put(a, mesh.replicated) for a in tables))


def put_edge_tables(src: np.ndarray, dst: np.ndarray, labels: np.ndarray,
                    mesh: MeshContext) -> EdgeTables:
    return EdgeTables(
        jax.device_put(src.astype(np.int32), mesh.replicated),
        jax.device_put(dst.astype(np.int32), mesh.replicated),
        jax.device_put(labels.astype(np.float32), mesh.replicated),
    )


def _batch_at(out_sharding, axis: int):
    """The sharding of an array whose ``axis`` is the batch, given a
    ``[B]`` vector's (None on one device: nothing to state)."""
    if out_sharding is None:
        return None
    return jax.sharding.NamedSharding(
        out_sharding.mesh,
        jax.sharding.PartitionSpec(*(None,) * axis, *out_sharding.spec))


def gather_nodes(table: jax.Array, idx: jax.Array, out_sharding) -> jax.Array:
    """``table[idx]`` for an index array whose LAST axis is the batch (the
    module's one layout): under a mesh each device gathers its own shard
    of the batch locally, which a typed sharding has to be told."""
    if out_sharding is None:
        return table[idx]
    return table.at[idx].get(
        out_sharding=_batch_at(out_sharding, idx.ndim - 1))


def _reshape(x: jax.Array, shape: tuple, out_sharding, axis: int):
    """A reshape that cuts or joins the batch: ``axis`` of the result is
    the sharded one (a typed sharding cannot guess which factor is)."""
    if out_sharding is None:
        return x.reshape(shape)
    return jnp.reshape(x, shape, out_sharding=_batch_at(out_sharding, axis))


def _lowbias32(x: jax.Array) -> jax.Array:
    """32-bit avalanche hash (lowbias32) — pure elementwise integer ops."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def _hash_at(salt: jax.Array, idx: jax.Array) -> jax.Array:
    """Deterministic uniform u32s from (salt, position ``idx``).

    Why not ``jax.random.bits`` here: threefry over a big batch-sharded
    shape makes GSPMD all-gather partial RNG state inside the threefry
    loop on every step — wasted ICI bandwidth, and it deadlocks XLA:CPU's
    in-process collectives under overlapped launches (observed on the
    8-device virtual mesh). A counter-based hash of a slot's position is
    iota + elementwise ops only: partitions over any mesh with ZERO
    collectives, and identical results regardless of device count.
    Threefry stays for the scalar per-step salts, so streams across
    steps/hops remain independent.
    """
    return _lowbias32(_lowbias32(idx + salt) ^ (salt * jnp.uint32(0x9E3779B9)))


def _lead_positions(lead: tuple) -> np.ndarray:
    """Where each leading index of a ``[*lead, B]`` tensor, taken row by
    row, stands among the trailing axes of the logical ``[B, *reversed
    lead]`` tensor: node ``(l, b)`` is at ``_lead_positions(lead)[l] +
    prod(lead) * b`` there."""
    n = math.prod(lead)
    return np.arange(n, dtype=np.uint32).reshape(lead[::-1]).T.reshape(n)


def sample_neighbors(graph, nodes: jax.Array, fanout: int,
                     salt: jax.Array, out_sharding=None):
    """Fanout-sample WITH replacement for each node of ``nodes [*lead,
    B]``; returns (nbr_idx, rtt, mask), each ``[fanout, *lead, B]``.

    **The layout** (models/graphsage.py has the model's side): a hop's
    tensors carry the batch as their last axis (in the lanes; the one
    sharded axis, ``out_sharding`` being a ``[B]`` vector's sharding) and
    every fan-out before it, the newest first: the axes of the host
    sampler's ``[B, 2, f1, f2]`` in reverse. The row sampler's slices
    produce a hop that way, and so it leaves: nothing is transposed into
    batch order, hop 2 runs over hop 1's ids as hop 1 left them, and the
    features are gathered in that order.

    **The samples** are those of that logical tensor: slot ``k`` of a
    node hashes ``position * fanout + k``, the node's ``position`` being
    its row-major index in ``nodes`` with the axes reversed
    (``benchmarks/references/graphsage.py: _draw``'s definition), so
    ``result.T`` equals the batch-major sampler's tensor bit for bit.
    Otherwise as CSRGraph.sample_neighbors (host half): padded slots
    (zero-degree nodes) carry index 0 / rtt 0 / mask 0; positive-degree
    nodes always fill all ``fanout`` replacement-sampled slots. ``graph``
    is what :func:`put_graph_tables` placed; either form gives the same
    samples in the same layout.
    """
    lead, batch = nodes.shape[:-1], nodes.shape[-1]
    rows = math.prod(lead)
    if isinstance(graph, GraphTables):
        at = (jnp.asarray(_lead_positions(lead)).reshape(lead + (1,))
              + jax.lax.broadcasted_iota(
                  jnp.uint32, nodes.shape, len(lead),
                  out_sharding=_batch_at(out_sharding, len(lead)))
              * jnp.uint32(rows))
        return _sample_csr(graph, nodes, at, fanout, salt, out_sharding)
    held = (batch if out_sharding is None
            else out_sharding.shard_shape((batch,))[0])
    row_bytes = 2 * 4 * graph.nbr_rows.shape[1] * rows
    chunks = min(-(-held * row_bytes // ROW_CHUNK_BYTES), held)
    chunks = next(c for c in range(chunks, held + 1) if held % c == 0)
    # Slice j is batch rows [j * size, (j + 1) * size) of every device's
    # shard of the batch, under every leading index, shards first so that
    # it flattens shard by shard: cutting moves nothing, on one device or
    # on several.
    shards, size = batch // held, held // chunks
    slices = jnp.transpose(
        _reshape(nodes, (rows, shards, chunks, size), out_sharding, axis=1),
        (2, 1, 0, 3))
    leads = jnp.asarray(_lead_positions(lead))

    def one(xs):
        at = _positions(xs[0].shape, xs[1] * jnp.uint32(size), leads, held,
                        out_sharding)
        return _sample_rows(graph, _reshape(xs[0], (at.size,), out_sharding, 0),
                            at, fanout, salt, out_sharding)

    firsts = jnp.arange(chunks, dtype=jnp.uint32)
    if chunks == 1:
        nbr, rtt, has = (x[None] for x in one((slices[0], firsts[0])))
    else:
        nbr, rtt, has = jax.lax.map(one, (slices, firsts))

    def whole(x, tail):
        """``[chunks, *tail, nodes of a slice]`` as ``[*tail, *lead, B]``:
        the slices take their places inside the batch, each a run of
        ``size`` lanes that moves whole; nothing is transposed into batch
        order, the batch stays in the lanes."""
        n = len(tail)
        x = _reshape(x, (chunks,) + tail + (shards, rows, size),
                     out_sharding, axis=1 + n)
        x = jnp.transpose(x, (*range(1, 1 + n), 2 + n, 1 + n, 0, 3 + n))
        return _reshape(x, tail + nodes.shape, out_sharding,
                        axis=n + len(lead))

    mask = jnp.broadcast_to(whole(has, ())[None], (fanout,) + nodes.shape)
    return whole(nbr, (fanout,)), whole(rtt, (fanout,)), mask


def _sample_csr(graph: GraphTables, nodes, at, fanout, salt, out_sharding):
    """The hop on the CSR tables, ``at`` the nodes' positions."""
    start = gather_nodes(graph.indptr, nodes, out_sharding)
    deg = gather_nodes(graph.indptr, nodes + 1, out_sharding) - start
    slot = jax.lax.broadcasted_iota(
        jnp.uint32, (fanout,) + (1,) * nodes.ndim, 0)
    bits = _hash_at(salt, at[None] * jnp.uint32(fanout) + slot)
    safe_deg = jnp.maximum(deg, 1).astype(jnp.uint32)
    pos = start[None] + (bits % safe_deg[None]).astype(jnp.int32)
    # Zero-degree tail nodes point at indptr[-1] == E (out of bounds);
    # their mask is 0, any in-bounds position works — clamp.
    pos = jnp.minimum(pos, graph.indices.shape[0] - 1)
    nbr = gather_nodes(graph.indices, pos, out_sharding)
    rtt = gather_nodes(graph.edge_rtt, pos, out_sharding)
    mask = jnp.broadcast_to(
        (deg > 0).astype(jnp.float32)[None], pos.shape)
    return jnp.where(mask > 0, nbr, 0), rtt * mask, mask


def _pick(tables: tuple, offs: jax.Array) -> tuple:
    """``rows[m, offs[f, m]]`` as ``[f, m]`` for each int32 ``rows [m,
    W]`` of ``tables``, with no gather: one lane of each row survives the
    select, so the sum over the lanes is that lane's value, bit for bit.
    (Slots leading and nodes in the lanes: the hop's layout.) One reduce
    over all the tables, so that the lanes are compared with the offsets
    once whatever the compiler would make of two."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, tables[0].shape[1]), 2)
    hit = offs[:, :, None] == lane
    return jax.lax.reduce(
        tuple(jnp.where(hit, rows[None], 0) for rows in tables),
        (jnp.int32(0),) * len(tables),
        lambda x, y: tuple(a + b for a, b in zip(x, y)), (2,))


def _positions(shape: tuple, first, leads, held: int,
               out_sharding) -> jax.Array:
    """Where the nodes of a slice ``[shards, rows, size]`` stand in the
    hop's logical tensor (:func:`sample_neighbors`), flat: ``nodes[s, l,
    i]`` is batch row ``s * held + first + i`` of the row that stands at
    ``leads[l]`` among the ``rows`` of a batch row there. The hash takes
    a slot's position in the whole hop, so a slice draws the offsets the
    CSR path draws."""
    # Born sharded: a device counts its own shard's positions only.
    shard, i = (jax.lax.broadcasted_iota(jnp.uint32, shape, d,
                                         out_sharding=out_sharding)
                for d in (0, 2))
    at = ((shard * jnp.uint32(held) + first + i) * jnp.uint32(shape[1])
          + leads[:, None])
    return _reshape(at, (at.size,), out_sharding, 0)


def _sample_rows(graph: RowTables, nodes, at, fanout: int, salt,
                 out_sharding):
    """One slice of a hop on the row tables, over its ``m`` nodes (flat,
    ``at`` their positions in the whole hop): ``(ids [fanout, m], RTTs
    [fanout, m], degree > 0 [m])``."""
    with jax.named_scope("df2.sample.rows"):
        ids = gather_nodes(graph.nbr_rows, nodes, out_sharding)
    with jax.named_scope("df2.sample.pick"):
        slot = jax.lax.broadcasted_iota(jnp.uint32, (fanout, 1), 0)
        # The barrier keeps the degrees one column read, not one a use.
        deg = jax.lax.optimization_barrier(ids[:, -1])
        # A zero-degree row is all zeros: offset 0 picks index 0, RTT 0.
        offs = (_hash_at(salt, at[None, :] * jnp.uint32(fanout) + slot)
                % jnp.maximum(deg, 1).astype(jnp.uint32)[None, :]
                ).astype(jnp.int32)
    # The RTT rows are fetched once the offsets are drawn, so the tables
    # are read one after the other: the chip's fast memory holds one of
    # them (102 MB) at a time, the compiler moves each in ahead of its
    # fetch, and a row out of it costs a quarter of one out of HBM.
    offs, nodes = jax.lax.optimization_barrier((offs, nodes))
    with jax.named_scope("df2.sample.rows"):
        rtts = gather_nodes(graph.rtt_rows, nodes, out_sharding)
    with jax.named_scope("df2.sample.pick"):
        nbr, rtt = _pick((ids, rtts), offs)
    return (nbr, jax.lax.bitcast_convert_type(rtt, jnp.float32),
            (deg > 0).astype(jnp.float32))


def sample_two_hops(graph, src, dst, key: jax.Array, fanouts: tuple,
                    out_sharding=None):
    """The 2-hop neighbourhood of a batch of edges, sampled on device in
    the module's one layout: ``(centers [2, B], nbr1 [f1, 2, B], rtt1,
    mask1, nbr2 [f2, f1, 2, B], rtt2, mask2)``, the second hop's mask
    already zero under a padded first-hop slot.

    ``key`` only seeds two SCALAR salts (tiny replicated threefry); the
    per-slot randomness comes from the counter hash above.
    """
    f1, f2 = fanouts
    k1, k2 = jax.random.split(key)
    s1 = jax.random.bits(k1, (), jnp.uint32)
    s2 = jax.random.bits(k2, (), jnp.uint32)
    centers = jnp.stack([src, dst], axis=0)                      # [2, B]
    with jax.named_scope("df2.sample.hop1"):
        nbr1, rtt1, mask1 = sample_neighbors(
            graph, centers, f1, s1, out_sharding)
    with jax.named_scope("df2.sample.hop2"):
        nbr2, rtt2, mask2 = sample_neighbors(
            graph, nbr1, f2, s2, out_sharding)
        mask2 = mask2 * mask1[None]
    return centers, nbr1, rtt1, mask1, nbr2, rtt2, mask2


def sample_and_apply(model: GraphSAGE, params, graph,
                     src, dst, key: jax.Array, fanouts: tuple,
                     out_sharding=None):
    """Sample the 2-hop neighborhood on device and run the forward pass:
    ids, RTTs, masks and feature rows go from the sampler's slices to the
    model's sums in one order (:func:`sample_neighbors`), fan-outs
    leading and the batch trailing, and are never laid out again.

    The phases carry ``df2.*`` scopes (``df2.sample.hop1``,
    ``df2.sample.hop2``, ``df2.features``, ``df2.model``): metadata on
    the operations, by which ``df2-trace-tool train`` splits a device
    trace (docs/OBSERVABILITY.md "Training loops").
    """
    centers, nbr1, rtt1, mask1, nbr2, rtt2, mask2 = sample_two_hops(
        graph, src, dst, key, fanouts, out_sharding)
    with jax.named_scope("df2.features"):
        feat0, feat1, feat2 = (
            gather_nodes(graph.node_features, idx, out_sharding)
            for idx in (centers, nbr1, nbr2))
    with jax.named_scope("df2.model"):
        return model.apply(params, feat0, feat1, rtt1, mask1,
                           feat2, rtt2 * mask2, mask2)


def _batch_rows(edges: EdgeTables, edge_ids, out_sharding):
    """(src, dst, labels) of one id batch, under ``df2.batch``."""
    with jax.named_scope("df2.batch"):
        return tuple(gather_nodes(table, edge_ids, out_sharding)
                     for table in edges)


def _fused_update(model: GraphSAGE, state, graph,
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
