"""Ring attention — sequence/context parallelism over the device mesh.

Long-context attention where the sequence axis is sharded across
devices: each device owns T/d query rows, and K/V blocks rotate around
the ring via ``lax.ppermute`` (one ICI hop per step, d steps total)
while an online (flash-style) softmax folds each visiting block into
running (max, sum, weighted-V) accumulators. Peak memory per device is
O(T/d · heads · T/d) for the score block — never the full [T, T]
matrix — and the collective traffic is the K/V bytes once around the
ring, overlapping compute on TPU (XLA schedules the ppermute DMA
alongside the einsums).

This is the "first-class long-context" primitive of the framework (the
reference has no counterpart — its data plane distributes files, not
activations; SURVEY §2.7). The GraphTransformer's chunked path
(`models/graph_transformer.py`) is the graph-shaped sibling: same
online-softmax algebra, neighbor-list bias instead of causal masks.

Differentiable end-to-end: ppermute transposes to the inverse ring
permutation, so ``jax.grad`` through a training step works without a
custom VJP (the python-level ring loop is unrolled — d is a mesh
constant). Causal masking uses each block's global row offset, which
rotates with the ring. The zigzag/striped causal load-balancing trick
is intentionally not implemented — at the block sizes TPU cares about,
XLA's overlap already hides most of the idle triangle.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


NEG_INF = -1e9


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "data",
    causal: bool = False,
    kv_valid: Optional[jax.Array] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Softmax attention with the sequence axis sharded over ``axis``.

    q/k/v: ``[T, heads, head_dim]`` or ``[B, T, heads, head_dim]`` with
    T sharded over the mesh axis (B and heads replicated). ``kv_valid``
    is an optional ``[T]`` (or ``[B, T]``) bool mask of real (non-pad)
    key positions, sharded like T. Accumulation runs in f32; the P·V
    contraction runs in the input dtype (bf16 on TPU → MXU).

    Returns attention output shaped and sharded like ``q``.
    """
    if q.ndim not in (3, 4):
        raise ValueError(f"expected [T,h,d] or [B,T,h,d], got {q.shape}")
    batched = q.ndim == 4
    n_dev = mesh.shape[axis]
    seq_spec = (P(None, axis, None, None) if batched
                else P(axis, None, None))
    valid_spec = (P(None, axis) if batched else P(axis))
    head_dim = q.shape[-1]
    inv_scale = scale if scale is not None else 1.0 / np.sqrt(head_dim)
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    if kv_valid is None:
        kv_valid = jnp.ones(q.shape[:-2], dtype=bool)

    qk = "bnhd,bmhd->bhnm" if batched else "nhd,mhd->hnm"
    pv = "bhnm,bmhd->bnhd" if batched else "hnm,mhd->nhd"

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(seq_spec, seq_spec, seq_spec, valid_spec),
             out_specs=seq_spec)
    def run(ql, kl, vl, validl):
        t_loc = ql.shape[-3]
        my_idx = jax.lax.axis_index(axis)
        q_pos = my_idx * t_loc + jnp.arange(t_loc)          # global rows

        # running max/sum indexed [(B,) heads, n] to match the score
        # blocks; the V accumulator stays q-shaped [(B,) n, heads, d]
        m = jnp.swapaxes(
            jnp.full(ql.shape[:-1], NEG_INF, jnp.float32), -1, -2)
        l = jnp.zeros_like(m)
        acc = jnp.zeros(ql.shape, jnp.float32)               # [(B,)n,h,d]
        kb, vb, validb = kl, vl, validl

        for step in range(n_dev):
            src_idx = (my_idx - step) % n_dev                # block owner
            k_pos = src_idx * t_loc + jnp.arange(t_loc)      # global cols
            s = jnp.einsum(qk, ql, kb,
                           preferred_element_type=jnp.float32) * inv_scale
            # mask shape [(B,)1?,n,m] matching s [(B,)h,n,m]
            block_mask = validb[..., None, None, :] if s.ndim == 4 \
                else validb[None, None, :]
            if causal:
                tri = (q_pos[:, None] >= k_pos[None, :])
                block_mask = block_mask & tri[None, ...] if s.ndim == 3 \
                    else block_mask & tri[None, None, ...]
            s = jnp.where(block_mask, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            # multiply by the mask so fully-masked blocks contribute 0
            # (exp(NEG_INF - NEG_INF) = 1 would otherwise pollute l)
            p = jnp.exp(s - m_new[..., None]) * block_mask
            fold = jnp.exp(m - m_new)
            l = l * fold + p.sum(-1)
            acc = acc * jnp.swapaxes(fold, -1, -2)[..., None] + jnp.einsum(
                pv, p.astype(ql.dtype), vb).astype(jnp.float32)
            m = m_new
            kb = jax.lax.ppermute(kb, axis, perm)
            vb = jax.lax.ppermute(vb, axis, perm)
            validb = jax.lax.ppermute(validb, axis, perm)

        denom = jnp.swapaxes(jnp.maximum(l, 1e-20), -1, -2)[..., None]
        return (acc / denom).astype(ql.dtype)

    return run(q, k, v, kv_valid)
