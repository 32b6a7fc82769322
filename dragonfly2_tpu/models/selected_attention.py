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
blocks outer, a key-value head's group of query heads summed inside).
A tile that holds no member is not computed, and its keys are not
fetched: block indices are clamped to each row's (each column's) range
of tiles that hold one.

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


def _kept(mask_ref):
    """A byte tile ``[rows, block / 8]`` as the tile's mask ``[rows,
    block]``."""
    bits = mask_ref[...].astype(jnp.int32)
    return jnp.concatenate([(bits >> b) & 1 for b in range(8)], axis=1) != 0


def _columns(x, width: int):
    """``x`` ``[rows, LANES]`` (one value a row, on every lane) as
    ``[rows, width]``."""
    return jnp.tile(x, (1, width // LANES)) if width > LANES else x[:, :width]


def _forward_kernel(held_ref, ends_ref, q_ref, k_ref, v_ref, mask_ref,
                    o_ref, lse_ref, m_ref, l_ref, acc_ref, *, blocks: int):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(held_ref[i * blocks + j] != 0)
    def _():
        scores = jax.lax.dot_general(q_ref[...], k_ref[...], _NT,
                                     preferred_element_type=jnp.float32)
        scores = jnp.where(_kept(mask_ref), scores, MASK_VALUE)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_next = jnp.maximum(m_prev, scores.max(-1, keepdims=True))
        p = jnp.exp(scores - _columns(m_next, scores.shape[1]))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[...] = alpha * l_prev + p.sum(-1, keepdims=True)
        m_ref[...] = m_next
        acc_ref[...] = (
            acc_ref[...] * _columns(alpha, acc_ref.shape[1])
            + jnp.dot(p.astype(v_ref.dtype), v_ref[...],
                      preferred_element_type=jnp.float32))

    @pl.when(j == blocks - 1)
    def _():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / _columns(l, acc_ref.shape[1])
                      ).astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l)


def _tile_gradients(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref):
    """Of one tile: the probabilities ``p`` and the scores' cotangent
    ``ds = p · (do · vᵀ - delta)``, both ``[bq, bk]`` float32."""
    scores = jax.lax.dot_general(q_ref[...], k_ref[...], _NT,
                                 preferred_element_type=jnp.float32)
    width = scores.shape[1]
    p = jnp.where(_kept(mask_ref),
                  jnp.exp(scores - _columns(lse_ref[...], width)), 0.0)
    dp = jax.lax.dot_general(do_ref[...], v_ref[...], _NT,
                             preferred_element_type=jnp.float32)
    return p, p * (dp - _columns(delta_ref[...], width))


def _dq_kernel(held_ref, ends_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, mask_ref, dq_ref, acc_ref, *, blocks: int):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(held_ref[i * blocks + j] != 0)
    def _():
        _, ds = _tile_gradients(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                delta_ref, mask_ref)
        acc_ref[...] += jnp.dot(ds.astype(k_ref.dtype), k_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(j == blocks - 1)
    def _():
        dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(held_ref, ends_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, mask_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                blocks: int, group: int):
    j, g, i = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when((g == 0) & (i == 0))
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(held_ref[i * blocks + j] != 0)
    def _():
        p, ds = _tile_gradients(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                delta_ref, mask_ref)
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[...], _TN,
            preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[...], _TN,
            preferred_element_type=jnp.float32)

    @pl.when((g == group - 1) & (i == blocks - 1))
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
    group, n = heads // k.shape[0], length // block
    query = pl.BlockSpec((None, block, hd), lambda h, i, j, *_: (h, i, 0))
    keys = pl.BlockSpec((None, block, hd), lambda h, i, j, held, ends: (
        h // group, _key_block(i, j, ends), 0))
    out, lse = _call(
        partial(_forward_kernel, blocks=n), (heads, n, n),
        [query, keys, keys,
         pl.BlockSpec((block, block // 8), lambda h, i, j, held, ends: (
             i, _key_block(i, j, ends)))],
        [query, pl.BlockSpec((None, block, LANES),
                             lambda h, i, j, *_: (h, i, 0))],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct((heads, length, LANES), jnp.float32)],
        [pltpu.VMEM((block, LANES), jnp.float32),
         pltpu.VMEM((block, LANES), jnp.float32),
         pltpu.VMEM((block, hd), jnp.float32)],
        ("parallel", "parallel", "arbitrary"), interpret,
    )(*tables, q, k, v, jax.lax.bitcast_convert_type(packed, jnp.int8))
    return out, lse[..., 0]


def _backward(q, k, v, packed, tables, out, lse, d_out, block, interpret):
    heads, length, hd = q.shape
    kv_heads = k.shape[0]
    group, n = heads // kv_heads, length // block
    bits = jax.lax.bitcast_convert_type(packed, jnp.int8)
    # One value a row, on every lane of a tile: the forward's
    # log-sum-exp, and delta = rowsum(do · o).
    lse = jnp.broadcast_to(lse[..., None], (heads, length, LANES))
    delta = jnp.broadcast_to(
        (d_out.astype(jnp.float32) * out.astype(jnp.float32)).sum(
            -1, keepdims=True), (heads, length, LANES))

    query = pl.BlockSpec((None, block, hd), lambda h, i, j, *_: (h, i, 0))
    row = pl.BlockSpec((None, block, LANES), lambda h, i, j, *_: (h, i, 0))
    keys = pl.BlockSpec((None, block, hd), lambda h, i, j, held, ends: (
        h // group, _key_block(i, j, ends), 0))
    dq = _call(
        partial(_dq_kernel, blocks=n), (heads, n, n),
        [query, keys, keys, query, row, row,
         pl.BlockSpec((block, block // 8), lambda h, i, j, held, ends: (
             i, _key_block(i, j, ends)))],
        query, jax.ShapeDtypeStruct(q.shape, q.dtype),
        [pltpu.VMEM((block, hd), jnp.float32)],
        ("parallel", "parallel", "arbitrary"), interpret,
    )(*tables, q, k, v, d_out, lse, delta, bits)

    query = pl.BlockSpec((None, block, hd), lambda c, j, g, i, held, ends: (
        c * group + g, _query_block(j, i, ends), 0))
    row = pl.BlockSpec((None, block, LANES), lambda c, j, g, i, held, ends: (
        c * group + g, _query_block(j, i, ends), 0))
    keys = pl.BlockSpec((None, block, hd), lambda c, j, g, i, *_: (c, j, 0))
    dk, dv = _call(
        partial(_dkv_kernel, blocks=n, group=group), (kv_heads, n, group, n),
        [query, keys, keys, query, row, row,
         pl.BlockSpec((block, block // 8), lambda c, j, g, i, held, ends: (
             _query_block(j, i, ends), j))],
        [keys, keys],
        [jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype)],
        [pltpu.VMEM((block, hd), jnp.float32),
         pltpu.VMEM((block, hd), jnp.float32)],
        ("parallel", "parallel", "arbitrary", "arbitrary"), interpret,
    )(*tables, q, k, v, d_out, lse, delta, bits)
    return dq, dk, dv


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def packed_attention(q, k, v, packed, block: int, interpret: bool = False):
    """``softmax over the kept s of q_t · k_s`` times ``v_s``: ``q``
    ``[H, S, hd]`` already scaled, ``k`` and ``v`` ``[KV, S, hd]`` (query
    heads ``g·c .. g·c + g - 1`` on key-value head ``c``), ``packed``
    :func:`pack_mask`'s ``[S, S / 8]`` at this ``block``, which is also
    the kernels' tile. Every query keeps at least one key."""
    return _forward(q, k, v, packed, tile_tables(packed, block), block,
                    interpret)[0]


def _packed_attention_fwd(q, k, v, packed, block, interpret):
    tables = tile_tables(packed, block)
    out, lse = _forward(q, k, v, packed, tables, block, interpret)
    return out, (q, k, v, packed, tables, out, lse)


def _packed_attention_bwd(block, interpret, saved, d_out):
    with jax.named_scope("df2.seq.attn_sparse"):
        return _backward(*saved, d_out, block, interpret) + (None,)


packed_attention.defvjp(_packed_attention_fwd, _packed_attention_bwd)
