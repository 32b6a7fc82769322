"""GraphTransformer — neighbour attention over the cluster topology
(BASELINE config #3, the scale-out GNN).

Where GraphSAGE (config #2) trains on sampled fixed-fanout subgraphs, this
model attends over the ENTIRE probe graph at once: every host embedding is
refined by multi-head attention restricted to its probe neighbors, with the
measured RTT injected as an additive attention bias.

The graph structure lives in **padded per-node neighbor lists** —
``nbr [N, K]`` int32 ids and ``val [N, K]`` float32 RTT biases, K = capped
max degree (a dense ``[N, N]`` bias and mask would need a 40 GB score
matrix per head at 100k hosts). One attention arithmetic reads them, in
two layouts with identical semantics:

- ``attention="gather"`` (default): neighbor-gather attention, O(N·K·H)
  compute and memory (``gather_graph_attention``), lane-dense. Training
  gives it the lists' host-built transpose (``build_inverse_index``) and
  takes its hand-written backward, which sums dk and dv host by host
  out of a table small enough for the chip's fast memory: no scatter-add
  and no gather out of an ``[N·K, …]`` cotangent. The shape of
  degree-capped probe graphs, where scoring all N key columns would spend
  an N/K ≈ 1000× factor masking columns that can never attend. One chip,
  or rows over a ``data`` mesh (and heads over ``model``).
- ``attention="ring"``: K/V stay row-sharded and rotate around the
  device ring via ``lax.ppermute`` (``ring_graph_attention``) — no
  full-width K/V at all; each visiting block is scored against the local
  rows' lists in ``chunk``-column sub-blocks with an online softmax.

Common sharding: queries/neighbor lists/accumulators are row-sharded
over the mesh's ``data`` axis (each device owns N/d query rows); in
gather mode K/V go full-width — one O(N·H) all-gather over ICI per layer
(25 MB at 100k hosts); ring mode trades that gather for d ppermute hops.

Reference parity: Dragonfly2 leaves GNN training a stub
(`/root/reference/trainer/training/training.go`); the topology features
mirror its probe schema (`/root/reference/scheduler/networktopology/`).
The model/scale targets come from BASELINE.md config #3.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

NEG_INF = -1e9
# Neighbor-list pad sentinel: never inside [0, N) for any padded N, so a
# pad slot is out of range of every key block and scatters nothing.
PAD_ID = np.int32(2**30)


def check_attention(mode: str) -> None:
    """The two layouts of the one attention; nothing else is a mode."""
    if mode not in ("gather", "ring"):
        raise ValueError(
            f"attention must be 'gather' or 'ring', not {mode!r}")


def _mesh_empty() -> bool:
    return jax.sharding.get_abstract_mesh().empty


def _value_spec(x) -> tuple | None:
    """The PartitionSpec of a (traced) value, ndim-normalized, under
    explicit sharding; None outside a mesh context. Explicit mode makes
    shardings part of the type, so this is trace-time static — modules
    can BRANCH on weight placement instead of taking layout flags."""
    if _mesh_empty():
        return None
    spec = tuple(jax.typeof(x).sharding.spec)
    return spec + (None,) * (x.ndim - len(spec))


def replicate(x):
    """All-gather a row-sharded activation to full width when running
    under an explicit mesh (K/V and the embedding table are full-width —
    O(N·H), the cheap part); no-op outside a mesh context. Only the
    LEADING (row) axis is gathered — feature/head axes keep their
    sharding, so tensor-parallel activations stay tensor-parallel."""
    spec = _value_spec(x)
    if spec is None:
        return x
    return jax.sharding.reshard(x, P(None, *spec[1:]))


def build_neighbor_lists(
    n_nodes: int,
    edge_src: np.ndarray,
    edge_dst: np.ndarray,
    edge_rtt_ns: np.ndarray,
    cap: int = 128,
) -> tuple[np.ndarray, np.ndarray]:
    """Host-side: padded neighbor lists (nbr [N, K] int32, val [N, K] f32).

    ``val`` is −log1p(rtt_ms) for a probed edge (faster paths get larger
    bias → more attention). Probes are directed; both directions are
    added since parent quality is what either endpoint observed, and
    repeated sightings of a pair resolve to the BEST observed RTT —
    order-independent, never last-write-wins. Every node carries a
    self slot (bias 0 — the max possible, so it survives any cap) and
    keeps its best-``cap`` neighbors by bias; pad slots are ``PAD_ID``.
    Each (row, col) appears at most once — the chunked-attention scatter
    relies on this dedup invariant.
    """
    rtt_ms = edge_rtt_ns.astype(np.float64) / 1e6
    value = -np.log1p(rtt_ms).astype(np.float32)
    src = edge_src.astype(np.int64)
    dst = edge_dst.astype(np.int64)
    # Symmetrize + self loops, then dedup to best value per (row, col).
    idx = np.arange(n_nodes, dtype=np.int64)
    keys = np.concatenate([
        src * n_nodes + dst,
        dst * n_nodes + src,
        idx * n_nodes + idx,
    ])
    vals = np.concatenate([value, value, np.zeros(n_nodes, np.float32)])
    order = np.argsort(keys, kind="stable")
    k_sorted, v_sorted = keys[order], vals[order]
    starts = np.flatnonzero(np.r_[True, k_sorted[1:] != k_sorted[:-1]])
    uniq_key = k_sorted[starts]
    uniq_val = np.maximum.reduceat(v_sorted, starts)
    rows = (uniq_key // n_nodes).astype(np.int64)
    cols = (uniq_key % n_nodes).astype(np.int32)

    # Rank within each row by descending bias; keep rank < cap. The self
    # slot (bias 0 = row max, biases are ≤ 0) always survives.
    by_row = np.lexsort((-uniq_val, rows))
    rows, cols, uniq_val = rows[by_row], cols[by_row], uniq_val[by_row]
    row_start = np.flatnonzero(np.r_[True, rows[1:] != rows[:-1]])
    rank = np.arange(len(rows)) - np.repeat(
        row_start, np.diff(np.r_[row_start, len(rows)]))
    keep = rank < cap
    rows, cols, uniq_val, rank = (
        rows[keep], cols[keep], uniq_val[keep], rank[keep])

    k_width = max(int(rank.max()) + 1 if len(rank) else 1, 1)
    nbr = np.full((n_nodes, k_width), PAD_ID, dtype=np.int32)
    val = np.zeros((n_nodes, k_width), dtype=np.float32)
    nbr[rows, rank] = cols
    val[rows, rank] = uniq_val
    return nbr, val


def pad_graph_sparse(
    node_features: np.ndarray,
    nbr: np.ndarray,
    val: np.ndarray,
    multiple: int,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Pad node count up to ``multiple`` so rows shard evenly. Phantom
    rows get a self slot (they attend only to themselves — keeps the
    softmax denominator nonzero) and scatter nothing into real rows
    (no real neighbor list points at a phantom id)."""
    n = node_features.shape[0]
    padded = ((n + multiple - 1) // multiple) * multiple
    if padded == n:
        return node_features, nbr, val, n
    extra = padded - n
    node_features = np.pad(node_features, ((0, extra), (0, 0)))
    pad_nbr = np.full((extra, nbr.shape[1]), PAD_ID, dtype=np.int32)
    pad_nbr[:, 0] = np.arange(n, padded, dtype=np.int32)
    nbr = np.concatenate([nbr, pad_nbr])
    val = np.concatenate([val, np.zeros((extra, val.shape[1]), np.float32)])
    return node_features, nbr, val, n


def _divisor_block(n: int, chunk: int) -> int:
    """Largest divisor of ``n`` that is ≤ ``chunk`` (≥ 1). Host-side,
    static shapes — the ring of one device scans ALL rows, which the
    ring padding rule aligned per device but not globally (e.g. n=104
    over 8 devices with chunk=16)."""
    best = 1
    d = 1
    while d * d <= n:
        if n % d == 0:
            if d <= chunk:
                best = max(best, d)
            if n // d <= chunk:
                best = max(best, n // d)
        d += 1
    return best


def _block_bias(nbr, val, start, block):
    """[rows, block] (bias, mask) for key columns [start, start+block),
    scattered on device from the neighbor lists. Scatter-ADD is exact
    because build_neighbor_lists dedups (row, col) pairs; pad slots
    (PAD_ID) are out of range of every block and contribute nothing.
    A per-device scatter: its callers hold local arrays (a shard_map
    body, or no mesh at all)."""
    in_range = (nbr >= start) & (nbr < start + block)
    col = jnp.clip(nbr - start, 0, block - 1)
    rows_iota = jax.lax.broadcasted_iota(jnp.int32, nbr.shape, 0)
    base = jnp.broadcast_to(val[:, :1] * 0, (nbr.shape[0], block))
    bias = base.at[rows_iota, col].add(jnp.where(in_range, val, 0.0))
    hits = base.at[rows_iota, col].add(in_range.astype(val.dtype))
    return bias, hits > 0


def ring_graph_attention(q, k, v, nbr, val, chunk, axis="data"):
    """Neighbor-masked attention with K/V blocks ppermute-ing around the
    device ring — K/V NEVER go full-width, so per-device memory is
    O(N/d · (heads·head_dim + K)): the layout for topologies past the
    point where even the O(N·H) replicated K/V table binds.

    An online softmax over key blocks: each visiting block's bias/mask
    is scattered from the LOCAL rows' neighbor lists at the block's
    global offset — all per-device ops, differentiable through ppermute
    with no custom VJP. Each ring step scans the received block in
    ``chunk``-column sub-blocks (rematerialized) to bound the score
    tile at ``[rows, heads, chunk]``.

    q/k/v: [N, heads, head_dim] row-sharded over ``axis``; nbr/val:
    [N, K] row-sharded. Without an ambient mesh (``model.init`` outside
    ``jax.set_mesh``, a single-process run) it is the ring of one
    device: the same scan over all rows, no collectives.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or axis not in mesh.shape:
        # The GLOBAL row count is only guaranteed divisible by
        # per-DEVICE chunks (ring padding aligns n/d, not n, to
        # ``chunk``), so shrink the block to a divisor of n.
        return _ring_rows(q, k, v, nbr, val, axis=None,
                          block=_divisor_block(q.shape[0], chunk))
    n_loc = q.shape[0] // mesh.shape[axis]
    block = min(chunk, n_loc)
    assert n_loc % block == 0, (n_loc, block)
    spec3, spec2 = P(axis, None, None), P(axis, None)
    return jax.shard_map(
        partial(_ring_rows, axis=axis, block=block),
        mesh=mesh, in_specs=(spec3, spec3, spec3, spec2, spec2),
        out_specs=spec3)(q, k, v, nbr, val)


def _ring_rows(ql, kl, vl, nbrl, vall, *, axis, block):
    """One device's rows of :func:`ring_graph_attention`: one ring step
    a device of ``axis``, each scoring the visiting K/V block in
    ``block``-column sub-blocks. ``axis`` None is the ring of one
    (nothing to permute)."""
    n_loc = ql.shape[0]
    scale = 1.0 / np.sqrt(ql.shape[-1])
    n_dev = 1 if axis is None else jax.lax.axis_size(axis)
    my_idx = 0 if axis is None else jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    m = ql.astype(jnp.float32).sum(-1) * 0 + NEG_INF         # [n_loc, h]
    l = jnp.zeros_like(m)
    acc = (ql * 0).astype(jnp.float32)

    # Memory discipline (round 5): the ring loop is a lax.scan whose
    # CHECKPOINTED body is one whole ring step — the backward saves
    # only per-ring-step carries (m, l, acc, and the visiting K/V
    # block: O(n_loc·H) × d steps) and recomputes a step's inner
    # sub-block scan when it needs that step's gradients. The
    # round-4 layout (python-unrolled steps, checkpoint on the
    # sub-block body) let the inner scans save the f32 acc carry at
    # EVERY sub-block of every step — O(n_loc·H·n_blocks) residents,
    # measured 3.08 GB vs gather mode's 0.45 GB on a 100k-node
    # train step; this layout measures 0.33 GB (see
    # tests/test_gat.py::TestScale::test_ring_memory_below_gather).
    def ring_step(carry, step_i):
        m, l, acc, kb, vb = carry
        src_idx = (my_idx - step_i) % n_dev                  # block owner
        base_pos = src_idx * n_loc

        def sub(sub_carry, j):
            m, l, acc = sub_carry
            kj = jax.lax.dynamic_slice_in_dim(kb, j * block, block, 0)
            vj = jax.lax.dynamic_slice_in_dim(vb, j * block, block, 0)
            bias, mask = _block_bias(
                nbrl, vall, base_pos + j * block, block)
            # f32 straight from the MXU, not a bf16 product converted
            # up: on the v5e (libtpu 0.0.34) the VJP of the row max
            # below over a converted bf16 dot came back NaN in every
            # element of dq and dk (found by PR 21's chip run;
            # tests_tpu pins it).
            s = jnp.einsum("nhd,bhd->nhb", ql, kj,
                           preferred_element_type=jnp.float32) * scale
            s = s + bias[:, None, :]
            s = jnp.where(mask[:, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            # mask multiplication (not just the where) guards
            # fully-masked rows: exp(NEG_INF − NEG_INF) = 1 would
            # otherwise pollute l.
            p = jnp.exp(s - m_new[..., None]) * mask[:, None, :]
            fold = jnp.exp(m - m_new)
            l = l * fold + p.sum(-1)
            acc = acc * fold[..., None] + jnp.einsum(
                "nhb,bhd->nhd", p.astype(ql.dtype), vj
            ).astype(jnp.float32)
            return (m_new, l, acc), None

        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(sub), (m, l, acc),
            jnp.arange(n_loc // block))
        if axis is not None:
            kb = jax.lax.ppermute(kb, axis, perm)
            vb = jax.lax.ppermute(vb, axis, perm)
        return (m, l, acc, kb, vb), None

    (m, l, acc, _, _), _ = jax.lax.scan(
        jax.checkpoint(ring_step), (m, l, acc, kl, vl),
        jnp.arange(n_dev))
    return (acc / jnp.maximum(l, 1e-20)[..., None]).astype(ql.dtype)


class InverseIndex(NamedTuple):
    """Host-side transpose of the neighbor lists, as the attention's
    backward reads it (:func:`build_inverse_index`). Slot ``(j, t)``
    stands for one list slot ``(i, s)`` with ``nbr[i, s] == j``."""

    rows: np.ndarray   # [N, D] int32: the listing host i; -1 in pad slots
    vals: np.ndarray   # [N, D] float32: that slot's bias val[i, s]; 0 in pads


def _sublane_tile(dtype) -> int:
    """Rows of one TPU tile of ``dtype``: 8 of 32 bits, 16 of bfloat16
    (two rows share a sublane)."""
    return 32 // jnp.dtype(dtype).itemsize


def build_inverse_index(nbr: np.ndarray, val: np.ndarray,
                        dtype=jnp.bfloat16) -> InverseIndex:
    """Host-side transpose of the neighbor lists: for every host j the
    hosts i that list it (``nbr[i, s] == j``, in the order of i) and the
    bias ``val[i, s]`` of each listing, padded to the widest in-degree
    D. The graph does not change during a run, so both are built once.

    They let the attention's backward sum a host's dK and dV over the
    hosts that list it (:func:`_attention_bwd`) instead of scattering
    every list slot's cotangent into it: the duplicate-index scatter-add
    that autodiff's transpose emits serializes on a TPU. D is rounded up
    to the sublane tile of the compute ``dtype`` (76 -> 80 in
    ``gat-fleet50k``), so that the rows fetched by ``[N, D]`` indices
    come out in whole tiles and are not moved again. Capped rows keep a
    symmetrized graph's in-degree near the cap.
    """
    n = nbr.shape[0]
    rows, slots = np.nonzero(nbr != PAD_ID)
    cols = nbr[rows, slots]
    order = np.argsort(cols, kind="stable")
    cols, rows, slots = cols[order], rows[order], slots[order]
    start = np.flatnonzero(np.r_[True, cols[1:] != cols[:-1]])
    counts = np.diff(np.r_[start, len(cols)])
    d_max = max(int(counts.max()) if len(counts) else 1, 1)
    tile = _sublane_tile(dtype)
    width = -(-d_max // tile) * tile
    rank = np.arange(len(cols)) - np.repeat(start, counts)
    inv_rows = np.full((n, width), -1, dtype=np.int32)
    inv_vals = np.zeros((n, width), dtype=np.float32)
    inv_rows[cols, rank] = rows
    inv_vals[cols, rank] = val[rows, slots]
    return InverseIndex(inv_rows, inv_vals)


def _row_gather(table, idx):
    """``[N, C]`` table gathered to ``[N, K, C]`` by row indices."""
    if _mesh_empty():
        return table[idx]
    # Rows shard over data; the lane axis keeps whatever sharding the
    # table carries (the 'model' axis under tensor parallelism).
    spec = P("data", None, _value_spec(table)[1])
    return table.at[idx].get(out_sharding=spec)


def _head_indicator(heads: int, head_dim: int, dtype):
    """0/1 ``[heads·head_dim, heads]``: lane c belongs to head c // head_dim.
    A product with it sums lanes within each head; one with its
    transpose spreads a per-head number over the head's lanes."""
    return jnp.asarray(
        np.kron(np.eye(heads), np.ones((head_dim, 1))), dtype)


def gather_graph_attention(q, k, v, nbr, val, inv=None, *, heads):
    """Neighbor-gather attention: each query row attends to exactly its
    ≤K listed neighbors — O(N·K·H) compute AND memory. Scoring all N
    key columns per row (what block attention does) spends an N/K factor
    masking columns that can never attend; on a degree-capped probe
    graph (K ≤ 128 vs N = 100k+) that is ~1000× the work.

    Everything ``[N, K, …]`` stays **lane-dense**: ``hidden`` =
    heads·head_dim lanes (128 at the trainer's widths, one TPU tile
    row), heads side by side, never split into ``[heads, head_dim]``.
    One gather of the ``[k | v]`` table (two ``hidden``-wide halves, so
    both slices are tile-aligned) gives ``[N, K, 2·hidden]`` rows. A
    head's score is the sum of its lanes of q·k: a product with the 0/1
    :func:`_head_indicator` matrix, in float32 from the MXU. Bias, pad
    mask and softmax run over K on ``[N, K, heads]``; the probabilities
    go back to lanes by the indicator's transpose, are multiplied into
    the v lanes and summed over K in float32. (With the head axis split
    — as ``einsum("nhd,nkhd->nhk")`` or as broadcasts over
    ``[N, K, heads, head_dim]`` alike — the v5e compiler lays the
    gathered rows' cotangent out heads-major and re-lays it in a
    256-iteration ``while``: a third of ``gat-fleet50k.train``'s step
    until PR 25; ``tests/test_chip_compile.py`` keeps it from coming
    back.)

    Without ``inv`` (evaluation, serving) the backward is autodiff's.
    With it (training) the whole attention has one hand-written backward
    (:func:`_attention_bwd`): dq and the bias's gradient row by row from
    the forward's gathered rows, dk and dv host by host over the hosts
    that list each, out of one small table. No ``[N·K, 2·hidden]``
    cotangent of the gathered rows is written for a gather or a
    scatter-add to read. The forward is the same arithmetic either way,
    to the bit.

    q: ``[N, hidden]`` row-sharded; k/v: ``[N, hidden]`` full-width;
    nbr/val: ``[N, K]`` row-sharded; ``inv``: :func:`build_inverse_index`
    of ``nbr`` and ``val`` (training), row-sharded, or None. Returns
    ``[N, hidden]``. Every row holds a self slot, so the softmax
    denominator is never empty.
    """
    lanes = None if _mesh_empty() else _value_spec(q)[1]
    if lanes is None:
        return _gather_attention(q, k, v, nbr, val, inv, heads=heads)
    # Tensor parallelism: the projections came out with their lanes (so
    # their heads) split over ``lanes``. Heads never mix, so each shard
    # runs the same arithmetic, forward and backward, on the heads it
    # holds.
    mesh = jax.sharding.get_abstract_mesh()
    local = partial(_gather_attention, heads=heads // mesh.shape[lanes])
    cols = P(None, lanes)
    return jax.shard_map(
        local, mesh=mesh, axis_names={lanes},
        in_specs=(cols, cols, cols, P(), P(), P()), out_specs=cols,
    )(q, k, v, nbr, val, inv)


def _gather_attention(q, k, v, nbr, val, inv, *, heads):
    """:func:`gather_graph_attention` over the heads one device holds."""
    if inv is None:
        return _attention_forward(heads, q, k, v, nbr, val)[0]
    return _attention_by_host(heads, q, k, v, nbr, val, inv)


def _head_sums(row, gathered, ind):
    """``[N, K, heads]`` float32: per head, the sum over its lanes of
    ``row[n] * gathered[n, k]``. The lane products are exact in float32
    and rounded to the compute dtype because the head sum is an MXU
    product, whose operands are bfloat16 (a float32 operand is truncated
    to it, or costs six passes at "highest"); the sum itself is
    float32."""
    f32 = jnp.float32
    prod = (row.astype(f32)[:, None, :] * gathered.astype(f32)).astype(
        row.dtype)
    return jnp.einsum("nkc,ch->nkh", prod, ind, preferred_element_type=f32)


def _scores(row, gathered, bias, pad, ind, scale):
    """Biased, masked scores ``[N, K, heads]`` in float32."""
    s = _head_sums(row, gathered, ind) * scale
    s = s + bias[:, :, None]
    return jnp.where(pad[:, :, None], NEG_INF, s)


def _spread_sum(weight, gathered, ind):
    """``[N, lanes]`` float32: ``Σ_k weight[n, k, head of lane] *
    gathered[n, k, lane]``. The per-head weights go to lanes by the
    indicator's transpose (an MXU product: compute-dtype operands); the
    lane products and the sum over K are float32."""
    f32 = jnp.float32
    wl = jnp.einsum("nkh,ch->nkc", weight, ind)
    return (wl.astype(f32) * gathered.astype(f32)).sum(axis=1)


def _attention_forward(heads, q, k, v, nbr, val):
    """Output ``[N, hidden]`` and, for the backward, the gathered
    ``[k | v]`` rows and the biased, masked float32 scores."""
    n, hidden = q.shape
    pad = nbr >= n                     # PAD_ID (and nothing else) is ≥ N
    idx = jnp.where(pad, 0, nbr)
    # ONE gather of the [k | v] table instead of two: a row gather's
    # cost follows the number of rows, not their bytes.
    kv = jnp.concatenate([k, v], axis=-1)          # [N, 2·hidden]
    with jax.named_scope("df2.attn.gather"):
        kvg = _row_gather(kv, idx)                 # [N, K, 2·hidden]
    kg, vg = kvg[..., :hidden], kvg[..., hidden:]
    ind = _head_indicator(heads, hidden // heads, q.dtype)
    s = _scores(q, kg, val, pad, ind, 1.0 / np.sqrt(hidden // heads))
    p = jax.nn.softmax(s, axis=1).astype(q.dtype)  # [N, K, heads]
    return _spread_sum(p, vg, ind).astype(q.dtype), kvg, s


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _attention_by_host(heads, q, k, v, nbr, val, inv):
    """The training path: :func:`_attention_forward`'s output with
    :func:`_attention_bwd` for its backward."""
    return _attention_forward(heads, q, k, v, nbr, val)[0]


def _attention_by_host_fwd(heads, q, k, v, nbr, val, inv):
    out, kvg, s = _attention_forward(heads, q, k, v, nbr, val)
    # Per row and head, all the backward needs of the softmax: it
    # recomputes a slot's probability as exp(s - lse), as every fused
    # attention backward does.
    lse = jax.nn.logsumexp(s, axis=1)              # [N, heads] float32
    return out, (q, k, v, nbr, val, inv, kvg, lse)


# A float32's 24 significant bits, in bfloat16 numbers of 8.
_FLOAT32_PARTS = 3


def _float32_lanes(x, dtype):
    """Float32 ``[N, c]`` as ``[N, 3·c]`` lanes of ``dtype`` whose sum is
    x exactly: each part is what the parts before it left over, rounded
    to bfloat16. So a table of the compute dtype carries float32
    statistics in its own rows, and :func:`_lane_picker` reads them back
    from fetched rows as an MXU product with float32 sums (the MXU's
    operands are bfloat16 whatever ``dtype`` is; a ``bitcast_convert``
    of fetched rows, or a slice of a few of their lanes, is a pass over
    them on the v5e: PERF.md section 7)."""
    parts, rest = [], x
    for _ in range(_FLOAT32_PARTS):
        part = rest.astype(jnp.bfloat16).astype(jnp.float32)
        parts.append(part.astype(dtype))
        rest = rest - part
    return jnp.concatenate(parts, axis=-1)


def _lane_picker(lanes: int, width: int, dtype):
    """0/1 ``[lanes, width]``: lane c (of the first ``3·width``; the rest
    are padding) is a part of number c % width. The product of
    :func:`_float32_lanes`' lanes with it is the float32 they carry."""
    pick = np.zeros((lanes, width))
    pick[:_FLOAT32_PARTS * width] = np.tile(np.eye(width),
                                            (_FLOAT32_PARTS, 1))
    return jnp.asarray(pick, dtype)


def _local_rows(x):
    """A full-width ``[N, C]`` value's rows as the ``data`` axis shards
    them (the inverse of :func:`replicate`; a slice, no traffic)."""
    spec = _value_spec(x)
    if spec is None:
        return x
    return jax.sharding.reshard(x, P("data", *spec[1:]))


def _attention_bwd(heads, residuals, d_out):
    """Backward of the whole attention, in two halves that share only
    small ``[N, …]`` tensors.

    With ``p = exp(s - lse)`` a slot's probability, ``dp = dO_i · v_j``
    per head and ``delta_i = Σ_s p · dp`` per head (= ``dO_i · out_i``),
    the score's cotangent is ``ds = p · (dp - delta_i)``.

    **Row-major** (listing host i, its K slots, the forward's gathered
    rows): ``dq[i] = scale · Σ_s ds · k_j`` and the bias's gradient
    ``dval[i, s] = Σ_h ds``.

    **Source-major** (listed host j, the D hosts that list it; scope
    ``df2.attn.gather_bwd``): one row gather of the table
    ``[q | dO | lse | delta]`` at the listing hosts ``inv.rows[j, t]``,
    the slot's bias from ``inv.vals[j, t]``, the same ``ds`` and ``p``
    recomputed against row j of k and v, and ``dK[j] = scale · Σ_t ds ·
    q_i``, ``dV[j] = Σ_t p · dO_i`` summed over t in float32. The table
    is ``N`` rows of under 1 KB (38 MB at 50,000 hosts), which the v5e
    compiler holds in fast memory, where a row reads 3 ns; the
    ``[N·K, 2·hidden]`` cotangent that an inverse-index gather would
    read (1.6 GB) lies in HBM at 13 ns a row. Pad slots of ``inv`` are
    masked like pad slots of ``nbr``: their probability is exactly 0.
    """
    q, k, v, nbr, val, inv, kvg, lse = residuals
    n, hidden = q.shape
    ind = _head_indicator(heads, hidden // heads, q.dtype)
    scale = 1.0 / np.sqrt(hidden // heads)

    kg, vg = kvg[..., :hidden], kvg[..., hidden:]
    p = jnp.exp(_scores(q, kg, val, nbr >= n, ind, scale) - lse[:, None, :])
    dp = _head_sums(d_out, vg, ind)
    # Summed from the very p and dp that make ds (not from the rounded
    # output), so that a row's ds sum to 0 as the softmax's own backward
    # has them.
    delta = (p * dp).sum(axis=1)                             # [N, heads]
    ds = p * (dp - delta[:, None, :])
    d_val = ds.sum(axis=-1)
    # Under tensor parallelism each shard has summed the heads it holds,
    # and the bias is every shard's: its gradient is the sum over them.
    shards = tuple(jax.typeof(d_val).vma - jax.typeof(val).vma)
    if shards:
        d_val = jax.lax.psum(d_val, shards)
    d_q = (_spread_sum(ds.astype(q.dtype), kg, ind) * scale).astype(q.dtype)

    with jax.named_scope("df2.attn.gather_bwd"):
        stats = jnp.concatenate([lse, delta], axis=-1)       # [N, 2·heads]
        table = replicate(jnp.concatenate(
            [q, d_out, _float32_lanes(stats, q.dtype)], axis=-1))
        # The statistics' lanes end a 128-lane tile of their own, so the
        # product that reads them takes whole tiles of the fetched rows;
        # left 24 lanes wide, the v5e compiler slices them out of every
        # fetched row in a pass of its own first.
        table = jnp.pad(table, ((0, 0), (0, -table.shape[1] % 128)))
        slot_pad = inv.rows < 0
        got = _row_gather(table, jnp.where(slot_pad, 0, inv.rows))
        qg, dog = got[..., :hidden], got[..., hidden:2 * hidden]
        stats = jnp.einsum(
            "ndc,cs->nds", got[..., 2 * hidden:],
            _lane_picker(got.shape[-1] - 2 * hidden, 2 * heads, q.dtype),
            preferred_element_type=jnp.float32)      # [N, D, 2·heads]
        k_j, v_j = _local_rows(k), _local_rows(v)
        p = jnp.exp(_scores(k_j, qg, inv.vals, slot_pad, ind, scale)
                    - stats[..., :heads])
        ds = p * (_head_sums(v_j, dog, ind) - stats[..., heads:])
        d_k = (_spread_sum(ds.astype(q.dtype), qg, ind) * scale).astype(
            k.dtype)
        d_v = _spread_sum(p.astype(q.dtype), dog, ind).astype(v.dtype)
        # k and v are full-width (their cotangents must match): gather
        # the row-sharded sums back to full width under a mesh.
        d_k, d_v = replicate(d_k), replicate(d_v)
    return d_q, d_k, d_v, None, d_val, None


_attention_by_host.defvjp(_attention_by_host_fwd, _attention_bwd)


class TPDense(nn.Module):
    """``nn.Dense`` twin (identical param layout, naming, and init) that
    follows its KERNEL's mesh placement at trace time — Megatron-style
    tensor parallelism without parameter boxing (SURVEY §2.7 stretch:
    sharded GNN layer weights, not just activations):

    - replicated kernel → exactly ``nn.Dense``;
    - column-sharded kernel ``[in, out@model]`` → plain matmul;
      activations come out feature-sharded over ``model``;
    - row-sharded kernel ``[in@model, out]`` → the contraction runs
      under ``auto_axes`` so XLA inserts the partial-sum + allreduce
      (the Megatron row-parallel reduce over ICI).

    Explicit sharding makes weight placement part of the value's TYPE,
    so the trainer shards the param tree with ``device_put`` and this
    module adapts — model code carries no layout flags and single-
    device/checkpoint paths are byte-identical to ``nn.Dense``.
    """

    features: int
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.features), self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros,
                          (self.features,), self.param_dtype)
        x = x.astype(self.dtype)
        kernel = kernel.astype(self.dtype)
        bias = bias.astype(self.dtype)
        kspec = _value_spec(kernel)
        if kspec is not None and kspec[0] is not None:
            axis = kspec[0]
            xspec = _value_spec(x)
            out_spec = P(*xspec[:-1], None)
            y = jax.sharding.auto_axes(
                jnp.matmul, axes=axis, out_sharding=out_spec)(x, kernel)
        else:
            y = jnp.matmul(x, kernel)
        return y + bias


class GraphAttentionBlock(nn.Module):
    """Pre-LN multi-head neighbor-masked attention + MLP, residual
    throughout. Two modes, one arithmetic: ``attention="gather"``
    (default) is O(N·K) neighbor-gather attention against full-width
    K/V; ``"ring"`` keeps K/V row-sharded and ppermutes them around the
    mesh (no full-width K/V at all), scoring each visiting block in
    ``chunk``-column sub-blocks — the one thing ``chunk`` is for. Any
    other value raises.

    All six Dense layers are :class:`TPDense` under their original
    ``Dense_i`` names (param trees stay checkpoint-compatible, and are
    the same tree in both modes): shard q/k/v + MLP-up kernels
    column-wise and out/MLP-down row-wise over a ``model`` mesh axis and
    the block runs Megatron tensor-parallel — heads split across
    devices, one allreduce per projection pair."""

    hidden: int
    heads: int
    chunk: int = 1024
    attention: str = "gather"
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, h, nbr, val, inv=None):
        # h: [N, H] row-sharded; nbr/val: [N, K] row-sharded; inv
        # (optional, [N, D] leaves) = host-built transpose of the lists,
        # with which gather mode takes its hand-written backward
        check_attention(self.attention)
        x = nn.LayerNorm(dtype=self.dtype)(h)
        q = TPDense(self.hidden, dtype=self.dtype, name="Dense_0")(x)
        k = TPDense(self.hidden, dtype=self.dtype, name="Dense_1")(x)
        v = TPDense(self.hidden, dtype=self.dtype, name="Dense_2")(x)

        if self.attention == "ring":
            # K/V stay row-sharded; blocks ppermute around the ring.
            def split(t):  # [N, H] -> [N, heads, head_dim]
                return t.reshape(-1, self.heads, self.hidden // self.heads)

            out = ring_graph_attention(split(q), split(k), split(v),
                                       nbr, val, self.chunk)
        else:
            # Queries keep their row sharding; K/V go full-width (O(N·H)
            # all-gather over ICI) and are consumed per neighbor.
            out = gather_graph_attention(q, replicate(k), replicate(v),
                                         nbr, val, inv, heads=self.heads)
        out = out.reshape(-1, self.hidden)
        out = TPDense(self.hidden, dtype=self.dtype, name="Dense_3")(out)
        h = h + out
        # MLP block
        y = nn.LayerNorm(dtype=self.dtype)(h)
        y = TPDense(self.hidden * 2, dtype=self.dtype, name="Dense_4")(y)
        y = nn.gelu(y)
        y = TPDense(self.hidden, dtype=self.dtype, name="Dense_5")(y)
        return h + y


class GraphTransformer(nn.Module):
    """L attention blocks over the full topology + edge scoring head.

    ``__call__`` returns per-edge logits for (src, dst) index arrays —
    same contract as GraphSAGE's edge head, so eval/registry plumbing is
    shared.
    """

    hidden: int = 128
    embed: int = 64
    layers: int = 2
    heads: int = 4
    chunk: int = 1024
    attention: str = "gather"
    dtype: jnp.dtype = jnp.bfloat16

    def setup(self):
        self.input_proj = nn.Dense(self.hidden, dtype=self.dtype,
                                   param_dtype=jnp.float32)
        self.blocks = [
            GraphAttentionBlock(self.hidden, self.heads, self.chunk,
                                self.attention, self.dtype)
            for _ in range(self.layers)
        ]
        self.final_norm = nn.LayerNorm(dtype=self.dtype)
        self.embed_proj = nn.Dense(self.embed, dtype=self.dtype,
                                   param_dtype=jnp.float32)
        self.head_hidden = nn.Dense(self.embed, dtype=self.dtype,
                                    param_dtype=jnp.float32)
        self.head_out = nn.Dense(1, dtype=jnp.float32,
                                 param_dtype=jnp.float32)

    def node_embeddings(self, node_features, nbr, val, inv=None):
        """[N, F] → [N, E]; exposed for serving (embedding export).
        ``inv`` (optional, training) = :func:`build_inverse_index` of the
        padded ``nbr`` and ``val``: the attention's backward then sums
        dk and dv host by host and scatters nothing."""
        h = self.input_proj(node_features.astype(self.dtype))
        for block in self.blocks:
            h = block(h, nbr, val, inv)
        return self.embed_proj(self.final_norm(h))

    def score_pairs(self, emb, edge_src, edge_dst):
        """Edge logits from an ALREADY-COMPUTED embedding table — the
        serving fast path: the sidecar runs ``node_embeddings`` once at
        model load, then every request is one gather + this tiny head."""
        src = emb[edge_src]                                    # [B, E]
        dst = emb[edge_dst]
        pair = jnp.concatenate([src, dst], axis=-1)
        x = nn.relu(self.head_hidden(pair))
        return self.head_out(x)[..., 0]

    def __call__(self, node_features, nbr, val, edge_src, edge_dst,
                 inv=None):
        emb = self.node_embeddings(node_features, nbr, val, inv)  # [N, E]
        # One all-gather of the (small) embedding table per step; edge
        # index gathers then stay local.
        emb = replicate(emb)
        return self.score_pairs(emb, edge_src, edge_dst)
