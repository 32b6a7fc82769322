"""Softmax attention over a selection of keys that is data, as TPU
kernels: each query attends to the keys a mask keeps for it, one mask
for all heads, the mask one bit a pair.

JAX's splash-attention kernel takes a computed mask only as whole
``[block, block]`` tiles of 32-bit words beside the keys (4.3 GB for one
32k sequence, fetched again for every head); the kernels here read the
packed bits (134 MB, 128 KB a tile). Otherwise they are the usual
blocked attention: scores of a ``[block, block]`` tile on the MXU, an
online softmax in float32, nothing ``[S, S]`` in HBM; the backward pass
recomputes a tile's probabilities from the forward's log-sum-exp, ``dq``
in one kernel (query blocks outer) and ``dk``, ``dv`` in another (key
blocks outer).

A grid step is one ``[block, block]`` tile for all the query heads of
one key-value head (:func:`heads_per_step` of them: the whole group
wherever its blocks fit the kernels' memory, as at the chip's shapes).
The step fetches the key, value and mask tiles once for the group and
unpacks the mask once; each head's scores, softmax and products then
run in turn over it, and ``dk``, ``dv`` take the group's heads into the
same two sums. A tile that holds no member is not computed, and its
keys are not fetched: block indices are clamped to each row's (each
column's) range of tiles that hold one.

The packed layout (:func:`pack_mask`): the keys are cut into blocks of
``block`` columns, and bit ``b`` of byte ``j`` of a block is its column
``b · block / 8 + j``, so that the mask of a block's ``b``-th eighth is
bit ``b`` of a whole ``[rows, block / 8]`` byte tile: unpacking is a
shift and a compare, no lane moves. On the chip ``block`` is 1,024 (a
byte tile is 128 lanes wide).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# What a dropped score is set to: finite, so that a row whose first
# tiles hold none of its members meets no inf - inf (the splash kernel's
# value and reason).
MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b
_VMEM_LIMIT = 64 * 2**20
# A head's float32 ``[block, block]`` temporaries in a grid step, which
# run one head at a time: scores, probabilities, their cotangent, the
# row statistics on every column. An upper count: at the chip's shape
# (1,024-key tiles, 8 heads of 128 in bfloat16) it sizes dq's step at
# 61.3 MB, where Mosaic's own count is 40.6 MB.
_TILE_TEMPORARIES = 6


def pack_mask(mask, block: int):
    """``mask`` ``[S, K]`` (bool) as one bit a pair, ``[S, K / 8]``
    uint8, in the layout above."""
    rows, keys = mask.shape
    parts = mask.reshape(rows, keys // block, 8, block // 8).astype(jnp.uint8)
    packed = (parts << jnp.arange(8, dtype=jnp.uint8)[None, None, :, None]
              ).sum(2, dtype=jnp.uint8)
    return packed.reshape(rows, keys // 8)


def unpack_mask(packed, block: int):
    """The inverse of :func:`pack_mask`."""
    rows = packed.shape[0]
    parts = packed.reshape(rows, -1, 1, block // 8)
    bits = (parts >> jnp.arange(8, dtype=jnp.uint8)[None, None, :, None]) & 1
    return bits.reshape(rows, -1).astype(bool)


def tile_tables(packed, block: int):
    """Which ``[block, block]`` tiles hold a member, flat ``[nq · nk]``
    int32 over ``[query block, key block]``, and the first and last such
    key block of each query block and query block of each key block
    (``[4, n]`` int32: first key, last key, first query, last query)."""
    n = packed.shape[0] // block
    held = (packed.reshape(n, block, n, block // 8) != 0).any((1, 3))

    def ends(has):
        first = jnp.argmax(has, -1)
        last = has.shape[-1] - 1 - jnp.argmax(has[:, ::-1], -1)
        return first, jnp.maximum(last, first)

    return (held.reshape(-1).astype(jnp.int32),
            jnp.stack(ends(held) + ends(held.T)).astype(jnp.int32))


def heads_per_step(group: int, block: int, hd: int, itemsize: int) -> int:
    """The query heads a grid step takes: the largest divisor of
    ``group`` (a key-value head's query heads) whose blocks,
    double-buffered, and scratch fit the kernels' memory beside the
    shared tiles and one head's temporaries. Sized by the ``dq`` kernel,
    which holds the most a head: its q, do and dq blocks, the
    log-sum-exp and delta on 128 lanes, a float32 accumulator."""
    tile = block * block * 4
    shared = (2 * 2 * block * hd * itemsize        # k, v
              + 2 * block * block // 8             # the mask's byte tile
              + tile                               # the mask unpacked
              + _TILE_TEMPORARIES * tile)
    per_head = (2 * 3 * block * hd * itemsize      # q, do, dq
                + 2 * 2 * block * LANES * 4        # log-sum-exp, delta
                + block * hd * 4)                  # accumulator
    return max((d for d in range(1, group + 1)
                if group % d == 0 and shared + d * per_head <= _VMEM_LIMIT),
               default=1)


def _grid(heads, kv_heads, length, hd, itemsize, block):
    """Query heads a step, steps a key-value head, tiles a row."""
    group = heads // kv_heads
    step = heads_per_step(group, block, hd, itemsize)
    return step, group // step, length // block


def grid_steps(heads: int, kv_heads: int, length: int, hd: int, dtype,
               block: int) -> int:
    """The grid steps of one kernel call at these shapes (forward, dq
    and dk with dv alike): a step for each ``[block, block]`` tile and
    each :func:`heads_per_step` query heads."""
    step, _, n = _grid(heads, kv_heads, length, hd,
                       jnp.dtype(dtype).itemsize, block)
    return heads // step * n * n


def _unpack(mask_ref, kept_ref):
    """A byte tile ``[rows, block / 8]`` as the tile's mask ``[rows,
    block]`` (1 where kept) in ``kept_ref``: bit ``b`` is the ``b``-th
    eighth of the columns."""
    bits = mask_ref[...].astype(jnp.int32)
    width = bits.shape[1]
    for b in range(8):
        kept_ref[:, b * width:(b + 1) * width] = (bits >> b) & 1


def _columns(x, width: int):
    """``x`` ``[rows, LANES]`` (one value a row, on every lane) as
    ``[rows, width]``."""
    return jnp.tile(x, (1, width // LANES)) if width > LANES else x[:, :width]


def _forward_kernel(held_ref, ends_ref, q_ref, k_ref, v_ref, mask_ref,
                    o_ref, lse_ref, m_ref, l_ref, acc_ref, kept_ref, *,
                    blocks: int):
    i, j = pl.program_id(1), pl.program_id(2)
    heads = q_ref.shape[0]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(held_ref[i * blocks + j] != 0)
    def _():
        _unpack(mask_ref, kept_ref)

        @pl.loop(0, heads)
        def _(h):
            scores = jax.lax.dot_general(q_ref[h], k_ref[...], _NT,
                                         preferred_element_type=jnp.float32)
            scores = jnp.where(kept_ref[...] != 0, scores, MASK_VALUE)
            m_prev, l_prev = m_ref[h], l_ref[h]
            m_next = jnp.maximum(m_prev, scores.max(-1, keepdims=True))
            p = jnp.exp(scores - _columns(m_next, scores.shape[1]))
            alpha = jnp.exp(m_prev - m_next)
            l_ref[h] = alpha * l_prev + p.sum(-1, keepdims=True)
            m_ref[h] = m_next
            acc_ref[h] = (
                acc_ref[h] * _columns(alpha, acc_ref.shape[2])
                + jnp.dot(p.astype(v_ref.dtype), v_ref[...],
                          preferred_element_type=jnp.float32))

    @pl.when(j == blocks - 1)
    def _():
        @pl.loop(0, heads)
        def _(h):
            l = l_ref[h]
            o_ref[h] = (acc_ref[h] / _columns(l, acc_ref.shape[2])
                        ).astype(o_ref.dtype)
            lse_ref[h] = m_ref[h] + jnp.log(l)


def _tile_gradients(h, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    kept_ref):
    """Of head ``h`` of the step on one tile: the probabilities ``p``
    and the scores' cotangent ``ds = p · (do · vᵀ - delta)``, both
    ``[bq, bk]`` float32."""
    scores = jax.lax.dot_general(q_ref[h], k_ref[...], _NT,
                                 preferred_element_type=jnp.float32)
    width = scores.shape[1]
    p = jnp.where(kept_ref[...] != 0,
                  jnp.exp(scores - _columns(lse_ref[h], width)), 0.0)
    dp = jax.lax.dot_general(do_ref[h], v_ref[...], _NT,
                             preferred_element_type=jnp.float32)
    return p, p * (dp - _columns(delta_ref[h], width))


def _dq_kernel(held_ref, ends_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, mask_ref, dq_ref, acc_ref, kept_ref, *,
               blocks: int):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(held_ref[i * blocks + j] != 0)
    def _():
        _unpack(mask_ref, kept_ref)

        @pl.loop(0, q_ref.shape[0])
        def _(h):
            _, ds = _tile_gradients(h, q_ref, k_ref, v_ref, do_ref, lse_ref,
                                    delta_ref, kept_ref)
            acc_ref[h] += jnp.dot(ds.astype(k_ref.dtype), k_ref[...],
                                  preferred_element_type=jnp.float32)

    @pl.when(j == blocks - 1)
    def _():
        dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(held_ref, ends_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, mask_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                kept_ref, *, blocks: int, parts: int):
    j, g, i = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when((g == 0) & (i == 0))
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(held_ref[i * blocks + j] != 0)
    def _():
        _unpack(mask_ref, kept_ref)

        @pl.loop(0, q_ref.shape[0])
        def _(h):
            p, ds = _tile_gradients(h, q_ref, k_ref, v_ref, do_ref, lse_ref,
                                    delta_ref, kept_ref)
            dv_acc[...] += jax.lax.dot_general(
                p.astype(do_ref.dtype), do_ref[h], _TN,
                preferred_element_type=jnp.float32)
            dk_acc[...] += jax.lax.dot_general(
                ds.astype(q_ref.dtype), q_ref[h], _TN,
                preferred_element_type=jnp.float32)

    @pl.when((g == parts - 1) & (i == blocks - 1))
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _call(kernel, grid, in_specs, out_specs, out_shape, scratch, semantics,
          interpret):
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)


def _key_block(i, j, ends):
    """Key block ``j`` of query block ``i``, held to the row's range of
    tiles with a member (a tile outside it is not computed, and asking
    for its neighbour's keys again fetches nothing)."""
    return jnp.clip(j, ends[0, i], ends[1, i])


def _query_block(j, i, ends):
    """The same of query block ``i`` in key block ``j``'s column."""
    return jnp.clip(i, ends[2, j], ends[3, j])


def _forward(q, k, v, packed, tables, block, interpret):
    heads, length, hd = q.shape
    step, parts, n = _grid(heads, k.shape[0], length, hd, q.dtype.itemsize,
                           block)
    query = pl.BlockSpec((step, block, hd), lambda h, i, j, *_: (h, i, 0))
    keys = pl.BlockSpec((None, block, hd), lambda h, i, j, held, ends: (
        h // parts, _key_block(i, j, ends), 0))
    out, lse = _call(
        partial(_forward_kernel, blocks=n), (heads // step, n, n),
        [query, keys, keys,
         pl.BlockSpec((block, block // 8), lambda h, i, j, held, ends: (
             i, _key_block(i, j, ends)))],
        [query, pl.BlockSpec((step, block, LANES),
                             lambda h, i, j, *_: (h, i, 0))],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct((heads, length, LANES), jnp.float32)],
        [pltpu.VMEM((step, block, LANES), jnp.float32),
         pltpu.VMEM((step, block, LANES), jnp.float32),
         pltpu.VMEM((step, block, hd), jnp.float32),
         pltpu.VMEM((block, block), jnp.int32)],
        ("parallel", "parallel", "arbitrary"), interpret,
    )(*tables, q, k, v, jax.lax.bitcast_convert_type(packed, jnp.int8))
    return out, lse[..., 0]


def _backward(q, k, v, packed, tables, out, lse, d_out, block, interpret):
    heads, length, hd = q.shape
    kv_heads = k.shape[0]
    step, parts, n = _grid(heads, kv_heads, length, hd, q.dtype.itemsize,
                           block)
    bits = jax.lax.bitcast_convert_type(packed, jnp.int8)
    # One value a row, on every lane of a tile: the forward's
    # log-sum-exp, and delta = rowsum(do · o).
    lse = jnp.broadcast_to(lse[..., None], (heads, length, LANES))
    delta = jnp.broadcast_to(
        (d_out.astype(jnp.float32) * out.astype(jnp.float32)).sum(
            -1, keepdims=True), (heads, length, LANES))
    unpacked = pltpu.VMEM((block, block), jnp.int32)

    query = pl.BlockSpec((step, block, hd), lambda h, i, j, *_: (h, i, 0))
    row = pl.BlockSpec((step, block, LANES), lambda h, i, j, *_: (h, i, 0))
    keys = pl.BlockSpec((None, block, hd), lambda h, i, j, held, ends: (
        h // parts, _key_block(i, j, ends), 0))
    dq = _call(
        partial(_dq_kernel, blocks=n), (heads // step, n, n),
        [query, keys, keys, query, row, row,
         pl.BlockSpec((block, block // 8), lambda h, i, j, held, ends: (
             i, _key_block(i, j, ends)))],
        query, jax.ShapeDtypeStruct(q.shape, q.dtype),
        [pltpu.VMEM((step, block, hd), jnp.float32), unpacked],
        ("parallel", "parallel", "arbitrary"), interpret,
    )(*tables, q, k, v, d_out, lse, delta, bits)

    query = pl.BlockSpec((step, block, hd), lambda c, j, g, i, held, ends: (
        c * parts + g, _query_block(j, i, ends), 0))
    row = pl.BlockSpec((step, block, LANES), lambda c, j, g, i, held, ends: (
        c * parts + g, _query_block(j, i, ends), 0))
    keys = pl.BlockSpec((None, block, hd), lambda c, j, g, i, *_: (c, j, 0))
    dk, dv = _call(
        partial(_dkv_kernel, blocks=n, parts=parts), (kv_heads, n, parts, n),
        [query, keys, keys, query, row, row,
         pl.BlockSpec((block, block // 8), lambda c, j, g, i, held, ends: (
             _query_block(j, i, ends), j))],
        [keys, keys],
        [jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype)],
        [pltpu.VMEM((block, hd), jnp.float32),
         pltpu.VMEM((block, hd), jnp.float32), unpacked],
        ("parallel", "parallel", "arbitrary", "arbitrary"), interpret,
    )(*tables, q, k, v, d_out, lse, delta, bits)
    return dq, dk, dv


def packed_attention(q, k, v, packed, block: int, interpret: bool = False,
                     tables=None):
    """``softmax over the kept s of q_t · k_s`` times ``v_s``: ``q``
    ``[H, S, hd]`` already scaled, ``k`` and ``v`` ``[KV, S, hd]`` (query
    heads ``g·c .. g·c + g - 1`` on key-value head ``c``), ``packed``
    :func:`pack_mask`'s ``[S, S / 8]`` at this ``block``, which is also
    the kernels' tile, and ``tables`` its :func:`tile_tables` where the
    caller has them. Every query keeps at least one key."""
    if tables is None:
        tables = tile_tables(packed, block)
    return _attention(q, k, v, packed, tables, block, interpret)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _attention(q, k, v, packed, tables, block, interpret):
    return _forward(q, k, v, packed, tables, block, interpret)[0]


def _attention_fwd(q, k, v, packed, tables, block, interpret):
    out, lse = _forward(q, k, v, packed, tables, block, interpret)
    return out, (q, k, v, packed, tables, out, lse)


def _attention_bwd(block, interpret, saved, d_out):
    with jax.named_scope("df2.seq.attn_sparse"):
        return _backward(*saved, d_out, block, interpret) + (None, None)


_attention.defvjp(_attention_fwd, _attention_bwd)
