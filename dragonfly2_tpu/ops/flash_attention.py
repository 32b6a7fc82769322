"""Pallas TPU flash-attention kernels.

Two entry points, one algebra (online softmax with (max, sum, acc)
scratch carried across the key-block grid, so no score matrix ever
exists in HBM and each tile's QK^T / P·V land on the MXU back-to-back):

- :func:`flash_attention` — plain (optionally causal) sequence
  attention over ``[T, heads, head_dim]``. A standalone primitive for
  sequence models built on this framework; exercised hermetically under
  ``interpret=True`` and on the TPU smoke tier.
- :func:`graph_flash_attention` — the PRODUCTION kernel: neighbor-
  masked graph attention with the RTT bias scattered from per-row
  neighbor lists *inside* the kernel, tile by tile in VMEM. This is the
  inner loop of ``GraphTransformer`` "blocks" mode on a single TPU
  device (``models/graph_transformer.py`` selects it over the XLA
  ``lax.scan`` path), which the serving-side embedding export
  (``inference/scorer.py`` → ``node_embeddings``) runs at model load.

Scope: FORWARD is the pallas kernel; backward (``jax.custom_vjp``)
recomputes through the XLA chunked online-softmax scan
(:func:`chunked_attention` for the sequence kernel, the graph scan for
the graph kernel) — O(T·block) residents, the same memory class as the
forward, so differentiating through the kernels at training-scale T
never materializes a dense score matrix. Multi-device composition:
``parallel/ring_attention.py`` (K/V rotation) and
``parallel/ulysses.py`` (all-to-all head partition, which runs THIS
kernel per device); the kernel itself is a per-device program.

Layouts: public API takes ``[T, heads, head_dim]`` (the repo's
convention); the kernels run ``[heads, T, head_dim]`` so each grid step
owns one contiguous (head, block) tile.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e9


def _dense_reference(q, k, v, causal: bool, t_real: int):
    """XLA fallback path (small T). q/k/v: [T, h, d] (padded)."""
    t = q.shape[0]
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("nhd,mhd->hnm", q, k).astype(jnp.float32) * scale
    mask = (jnp.arange(t) < t_real)[None, None, :]
    if causal:
        mask = mask & (jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
                       )[None, ...]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1) * mask
    return jnp.einsum("hnm,mhd->nhd", p.astype(q.dtype), v)


def chunked_attention(q, k, v, causal: bool = False, block: int = 512):
    """Key-blocked online-softmax attention in plain XLA — the same
    algebra as the pallas kernel at O(T·block) residents instead of the
    dense O(T²) score matrix. Three roles: the kernel's BACKWARD
    recompute path (differentiating this under ``jax.checkpoint`` keeps
    training-scale T inside the flash memory class), the off-TPU local
    attention inside :func:`~dragonfly2_tpu.parallel.ulysses_attention`,
    and a long-T forward fallback. q/k/v: [T, h, d]."""
    t = q.shape[0]
    scale = 1.0 / np.sqrt(q.shape[-1])
    block = min(block, t)
    # Pad K/V to whole blocks: a ragged tail would make dynamic_slice
    # CLAMP its start and silently re-read earlier keys; the k_pos
    # mask keeps phantom keys out of the softmax.
    pad = (-t) % block
    if pad:
        k = jnp.pad(k, ((0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, pad), (0, 0), (0, 0)))
    n_blocks = (t + pad) // block
    q_pos = jnp.arange(t)

    # Carries derive from q (not fresh constants) so the scan stays
    # legal inside shard_map, where constants are axis-unvarying.
    m = (q.astype(jnp.float32).sum(-1) * 0 + NEG_INF).swapaxes(-1, -2)
    l = jnp.zeros_like(m)                                  # [h, T]
    acc = (q * 0).astype(jnp.float32)                      # [T, h, d]

    def step(carry, j):
        m, l, acc = carry
        start = j * block
        kj = jax.lax.dynamic_slice_in_dim(k, start, block, 0)
        vj = jax.lax.dynamic_slice_in_dim(v, start, block, 0)
        # f32 from the dot itself: see sparse_graph_attention (the VJP
        # of a max over a converted bf16 dot is NaN on the v5e).
        s = jnp.einsum("nhd,mhd->hnm", q, kj,
                       preferred_element_type=jnp.float32) * scale
        k_pos = start + jnp.arange(block)
        mask = (k_pos < t)[None, None, :]
        if causal:
            mask = mask & (q_pos[:, None] >= k_pos[None, :])[None]
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None]) * mask
        fold = jnp.exp(m - m_new)
        l = l * fold + p.sum(-1)
        acc = acc * fold.swapaxes(-1, -2)[..., None] + jnp.einsum(
            "hnm,mhd->nhd", p.astype(q.dtype), vj).astype(jnp.float32)
        return (m_new, l, acc), None

    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(step), (m, l, acc), jnp.arange(n_blocks))
    denom = jnp.maximum(l, 1e-20).swapaxes(-1, -2)[..., None]
    return (acc / denom).astype(q.dtype)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, block_q: int, block_k: int, t_real: int, causal: bool):
    j = pl.program_id(2)
    n_k = pl.num_programs(2)
    i = pl.program_id(1)

    @pl.when(j == 0)
    def _reset():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = i * block_q
    k_start = j * block_k
    # Block-level causal skip: a key block strictly in the future of the
    # whole query block contributes nothing — don't even load it.
    run_pred = (k_start <= q_start + block_q - 1) if causal \
        else jnp.bool_(True)

    @pl.when(run_pred)
    def _compute():
        q = q_ref[0]                                   # [block_q, d]
        kb = k_ref[0]                                  # [block_k, d]
        vb = v_ref[0]
        scale = 1.0 / np.sqrt(q.shape[-1])
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        mask = k_pos < t_real
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            mask = mask & (q_pos >= k_pos)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None]) * mask
        fold = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * fold + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * fold[:, None] + jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_k - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-20)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _pallas_forward(q, k, v, causal: bool, t_real: int,
                    block_q: int, block_k: int, interpret: bool):
    """q/k/v: [h, T, d] padded so T % block == 0."""
    heads, t, d = q.shape
    grid = (heads, t // block_q, t // block_k)
    return pl.pallas_call(
        partial(_kernel, block_q=block_q, block_k=block_k,
                t_real=t_real, causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, i, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
        # Inside a shard_map (parallel/ulysses.py) the output varies
        # over the same mesh axes as the operands; the replication check
        # refuses an out_shape that does not say so.
        out_shape=jax.ShapeDtypeStruct((heads, t, d), q.dtype,
                                       vma=jax.typeof(q).vma),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),       # running max
            pltpu.VMEM((block_q,), jnp.float32),       # running sum
            pltpu.VMEM((block_q, d), jnp.float32),     # V accumulator
        ],
        interpret=interpret,
    )(q, k, v)


def _pad_to(t: int, block_q: int, block_k: int) -> int:
    """Pad T to a common multiple of BOTH blocks — the grid uses floor
    divisions for each axis, so a T divisible by only one block size
    would silently drop the other axis's tail blocks."""
    import math

    lcm = block_q * block_k // math.gcd(block_q, block_k)
    return ((t + lcm - 1) // lcm) * lcm


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=False, block_q=128, block_k=128,
                    interpret=False):
    """Softmax attention over [T, heads, head_dim] tensors.

    Pallas kernel on TPU (or anywhere with ``interpret=True``); dense
    XLA otherwise. Pads T up to the block size internally; padded keys
    are masked out, padded query rows are dropped on return.
    """
    out, _ = _fwd(q, k, v, causal, block_q, block_k, interpret)
    return out


def _fwd(q, k, v, causal, block_q, block_k, interpret):
    t_real = q.shape[0]
    on_tpu = jax.devices()[0].platform == "tpu"
    if not (on_tpu or interpret):
        return _dense_reference(q, k, v, causal, t_real), (q, k, v)
    t_pad = _pad_to(t_real, block_q, block_k)
    pad = [(0, t_pad - t_real), (0, 0), (0, 0)]
    qp, kp, vp = (jnp.pad(a, pad) for a in (q, k, v))
    # [T, h, d] -> [h, T, d] for contiguous (head, block) tiles.
    qp, kp, vp = (jnp.moveaxis(a, 1, 0) for a in (qp, kp, vp))
    out = _pallas_forward(qp, kp, vp, causal, t_real, block_q, block_k,
                          interpret)
    return jnp.moveaxis(out, 0, 1)[:t_real], (q, k, v)


def _bwd(causal, block_q, block_k, interpret, residuals, g):
    """Recompute through the chunked online-softmax scan — O(T·block)
    residents, so differentiating the kernel at training-scale T stays
    in the flash memory class instead of materializing the dense [T, T]
    score matrix the forward exists to avoid."""
    q, k, v = residuals
    _, vjp = jax.vjp(
        lambda q, k, v: chunked_attention(
            q, k, v, causal, block=max(block_k, 512)),
        q, k, v)
    return vjp(g)


flash_attention.defvjp(_fwd, _bwd)


# ----------------------------------------------------------------------
# Graph-biased flash attention (the GraphTransformer "blocks" hot op)
# ----------------------------------------------------------------------


# Query rows per step of the in-kernel bias scatter: one f32 sublane
# tile, so a step's [rows, block_k] bias stays in vector registers
# across the K statically unrolled slot selects.
_SCATTER_ROWS = 8


def _graph_kernel(q_ref, k_ref, v_ref, nbr_ref, val_ref, o_ref,
                  m_ref, l_ref, acc_ref, bias_ref, *, block_k: int):
    """One (head, q-block, k-block) tile: scatter this tile's bias/mask
    from the q-rows' neighbor lists, then the online-softmax update.

    The scatter fills ``bias_ref`` [block_q, block_k] eight query rows
    at a time: per row group, one [8, block_k] select per neighbor slot,
    the K slots unrolled statically (Mosaic lowers no dynamic slice of a
    loaded value, and a whole-tile unroll spills K live tiles). Slots are
    deduped host-side (build_neighbor_lists), so select is exact; PAD_ID
    slots match no column of any block. Untouched columns keep NEG_INF,
    which doubles as the mask (a listed bias is a finite -log1p(rtt)).
    """
    j = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(j == 0)
    def _reset():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k_start = j * block_k
    cols_iota = jax.lax.broadcasted_iota(
        jnp.int32, (_SCATTER_ROWS, block_k), 1)

    def scatter_rows(r, carry):
        rows = pl.ds(pl.multiple_of(r * _SCATTER_ROWS, _SCATTER_ROWS),
                     _SCATTER_ROWS)
        col = nbr_ref[rows, :] - k_start               # [8, K] int32
        valb = val_ref[rows, :]                        # [8, K] f32
        bias = jnp.full(cols_iota.shape, NEG_INF, jnp.float32)
        for kk in range(col.shape[1]):
            bias = jnp.where(cols_iota == col[:, kk:kk + 1],
                             valb[:, kk:kk + 1], bias)
        bias_ref[rows, :] = bias
        return carry

    jax.lax.fori_loop(0, bias_ref.shape[0] // _SCATTER_ROWS,
                      scatter_rows, 0)

    q = q_ref[0]                                       # [bq, d]
    kb = k_ref[0]                                      # [bk, d]
    vb = v_ref[0]
    bias = bias_ref[...]                               # [bq, bk]
    hit = bias > NEG_INF
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jax.lax.dot_general(
        q, kb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # [bq, bk]
    s = jnp.where(hit, s + bias, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None]) * hit
    fold = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * fold + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * fold[:, None] + jax.lax.dot_general(
        p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == n_k - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-20)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def graph_flash_attention(q, k, v, nbr, val, block_q=128, block_k=128,
                          interpret=False):
    """Neighbor-masked attention with in-kernel bias scatter.

    Same semantics as ``models.graph_transformer.sparse_graph_attention``
    (scores + RTT bias on listed neighbors, NEG_INF elsewhere, rows with
    no in-range neighbor produce 0): q ``[Nq, h, d]``, k/v ``[Nk, h, d]``
    full-width, nbr/val ``[Nq, K]`` with ids in k's GLOBAL index space.
    Row counts are padded internally to the block grid; padded query
    rows return 0 and are dropped, padded key columns are unreachable
    (no neighbor id points at them).
    """
    out, _ = _graph_fwd(q, k, v, nbr, val, block_q, block_k, interpret)
    return out


def _graph_vmem_limit(block_q: int, block_k: int, kw: int) -> int:
    """Scoped-VMEM request for one graph tile. The default scoped limit
    (16 MiB on v5e, of 128 MiB physical) is what the compiler's own
    [block_q, block_k] f32 temporaries (scores, probabilities, mask)
    overrun at block 1024 once K reaches 128: bound them as six tiles
    beside the bias scratch's one, plus the double-buffered neighbor
    blocks (lane-padded to 128) and slack for the q/k/v/out blocks."""
    tile = block_q * block_k * 4
    lists = 2 * 2 * block_q * max(kw, 128) * 4
    return max(16 << 20, 7 * tile + lists + (4 << 20))


def _graph_fwd(q, k, v, nbr, val, block_q, block_k, interpret):
    n_q, heads, d = q.shape
    n_k = k.shape[0]
    assert block_q % _SCATTER_ROWS == 0, block_q
    on_tpu = jax.devices()[0].platform == "tpu"
    if not (on_tpu or interpret):
        from dragonfly2_tpu.models.graph_transformer import (
            _divisor_block,
            sparse_graph_attention,
        )

        return (sparse_graph_attention(q, k, v, nbr, val,
                                       _divisor_block(n_q, block_k)),
                (q, k, v, nbr, val))
    q_pad = ((n_q + block_q - 1) // block_q) * block_q - n_q
    k_pad = ((n_k + block_k - 1) // block_k) * block_k - n_k
    qp = jnp.pad(q, [(0, q_pad), (0, 0), (0, 0)])
    kp = jnp.pad(k, [(0, k_pad), (0, 0), (0, 0)])
    vp = jnp.pad(v, [(0, k_pad), (0, 0), (0, 0)])
    # Padded query rows must scatter nothing: PAD_ID is out of range of
    # every key block (same invariant as the host-side pad rows).
    from dragonfly2_tpu.models.graph_transformer import PAD_ID

    nbrp = jnp.pad(nbr, [(0, q_pad), (0, 0)], constant_values=PAD_ID)
    valp = jnp.pad(val, [(0, q_pad), (0, 0)])
    qp, kp, vp = (jnp.moveaxis(a, 1, 0) for a in (qp, kp, vp))
    t_q, t_k = qp.shape[1], kp.shape[1]
    kw = nbr.shape[1]
    grid = (heads, t_q // block_q, t_k // block_k)
    out = pl.pallas_call(
        partial(_graph_kernel, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((block_q, kw), lambda h, i, j: (i, 0)),
            pl.BlockSpec((block_q, kw), lambda h, i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((heads, t_q, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, block_k), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_graph_vmem_limit(block_q, block_k, kw)),
        interpret=interpret,
    )(qp, kp, vp, nbrp, valp)
    return jnp.moveaxis(out, 0, 1)[:n_q], (q, k, v, nbr, val)


def _graph_bwd(block_q, block_k, interpret, residuals, g):
    """Recompute through the XLA chunked scan — same memory class as the
    training default, and numerically the same algebra as the kernel."""
    q, k, v, nbr, val = residuals
    from dragonfly2_tpu.models.graph_transformer import (
        _divisor_block,
        sparse_graph_attention,
    )

    chunk = _divisor_block(q.shape[0], block_k)
    _, vjp = jax.vjp(
        lambda q, k, v, val: sparse_graph_attention(
            q, k, v, nbr, val, chunk), q, k, v, val)
    dq, dk, dv, dval = vjp(g)
    return dq, dk, dv, None, dval


graph_flash_attention.defvjp(_graph_fwd, _graph_bwd)
