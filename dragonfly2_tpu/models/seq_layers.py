"""What the sequence-model families (``lfm2_moe``, ``laguna``) share:
parameters drawn from a list of shapes, the RMS norm, rotate-half RoPE,
the embedding lookup with its one-hot backward, causal same-document
attention with or without a window (plain, and through JAX's
splash-attention kernel on a TPU), the gated FFN, the summed next-token
loss, and the per-sequence recomputed loss of a batch.

A family is a module with ``param_shapes(cfg)`` and ``block(p, x,
router_bias, segments, positions, *, cfg, layer)``, and a config that
says which published layers run and what of a layer is held here
(``kept_layers``, ``expert_layers``, ``held_experts``, ``held_vocab``,
``num_experts``, ``norm_eps``, ``compute_dtype``, ``attention_window``).

The job, not the model, packs documents: ``segments`` (a document id per
position) keeps an attention score from crossing a document boundary,
and ``positions`` restart in each document. Parameters are float32 in a
plain nested dict; products run in ``compute_dtype`` (bfloat16), norms,
softmax, router and loss in float32.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02


class HeldShare:
    """What a family's config answers about the share it states in
    ``layers`` (indices into ``layer_types``; None: all), ``experts_held``
    and ``vocab_held`` (pairs of first and count; None: all)."""

    @property
    def kept_layers(self) -> tuple:
        return (tuple(range(len(self.layer_types)))
                if self.layers is None else tuple(self.layers))

    @property
    def held_experts(self) -> tuple:
        return self.experts_held or (0, self.num_experts)

    @property
    def held_vocab(self) -> tuple:
        return self.vocab_held or (0, self.vocab_size)


def init_params(key, shapes: list) -> dict:
    """Parameters drawn operation by operation (a compiled init rounds
    differently on a v5e; PERF.md, PR 25): leaf ``n`` of ``shapes``
    (``[(path, shape, "normal" | "ones")]``, a family's
    ``param_shapes``) is ``normal(fold_in(key, n)) · 0.02``, a norm's
    weight is ones."""
    params: dict = {}
    for n, (path, shape, kind) in enumerate(shapes):
        node = params
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = (
            jnp.ones(shape, jnp.float32) if kind == "ones" else
            jax.random.normal(jax.random.fold_in(key, n), shape, jnp.float32)
            * jnp.float32(INIT_STD))
    return params


def rms_norm(x, weight, eps: float):
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (x32 * scale * weight).astype(x.dtype)


def rope_frequencies(theta: float, lanes: int):
    """The ``lanes // 2`` frequencies of plain RoPE over ``lanes``
    rotated lanes."""
    half = lanes // 2
    return 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)


def rope(x, positions, inv_freq, scale: float | None = None):
    """Rotate-half rotary embedding of ``x`` [S, heads, head] at
    ``positions`` [S], in float32: the first ``2 · len(inv_freq)`` lanes
    of each head are rotated (lane ``i`` with lane ``i + len(inv_freq)``),
    the rest pass; ``scale`` multiplies cos and sin."""
    half = inv_freq.shape[0]
    angle = positions.astype(jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None, :]
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    x32 = x.astype(jnp.float32)
    if 2 * half == x.shape[-1]:
        turn, rest = x32, None
    else:
        turn, rest = x32[..., :2 * half], x32[..., 2 * half:]
    turned = jnp.concatenate([-turn[..., half:], turn[..., :half]], -1)
    out = turn * cos + turned * sin
    if rest is not None:
        out = jnp.concatenate([out, rest], -1)
    return out.astype(x.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def embedding_rows(table, ids, dtype):
    """``table[ids]`` in ``dtype``. Backward: the table's gradient as one
    product with the ids' one-hot matrix, not a scatter-add (token ids
    repeat, and duplicate indices serialize on a TPU)."""
    return table[ids].astype(dtype)


def _embedding_rows_fwd(table, ids, dtype):
    return table[ids].astype(dtype), (ids, table.shape[0])


def _embedding_rows_bwd(dtype, saved, g):
    ids, rows = saved
    with jax.named_scope("df2.seq.embed"):
        one_hot = (ids[None, :] == jnp.arange(rows)[:, None]).astype(dtype)
        return jnp.matmul(one_hot, g.astype(dtype),
                          preferred_element_type=jnp.float32), None


embedding_rows.defvjp(_embedding_rows_fwd, _embedding_rows_bwd)


def dense_attention(q, k, v, segments, window: int | None = None):
    """Causal same-document attention, scores held whole: q [S, H, hd]
    already scaled, k and v [S, KV, hd]; query ``t`` sees key ``s`` where
    ``s <= t`` in the same document and, with a ``window``, ``t - s <
    window``. The plain form, for sizes at which ``[H, S, S]`` fits."""
    s, h, hd = q.shape
    group = h // k.shape[1]
    q = q.reshape(s, k.shape[1], group, hd)
    scores = jnp.einsum("sjgd,tjd->jgst", q, k,
                        preferred_element_type=jnp.float32)
    at = jnp.arange(s)
    seen = (at[:, None] >= at[None, :]) & (
        segments[:, None] == segments[None, :])
    if window is not None:
        seen = seen & (at[:, None] - at[None, :] < window)
    probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
    out = jnp.einsum("jgst,tjd->sjgd", probs.astype(v.dtype), v)
    return out.reshape(s, h, hd)


# Rows and columns of a score tile of the TPU kernel: all causal pairs,
# and pairs within a window (tiles that lie wholly outside the window
# are skipped, so a tile is no wider than the window is long). Read on a
# v5e at the benchmark cells' shapes, one 8k sequence, forward alone and
# backward with its forward (PERF.md, PR 31). 48 heads of 128, causal:
# 8.33 and 23.9 ms at 1,024 (10.3 and 31.0 at 512; 2,048 does not fit
# the kernel's fast memory). 64 heads of 128, window 512: 3.37 and 17.2
# ms at 512 (5.10 and 36.8 at 256, 4.96 and 16.7 at 1,024), and 11.1 ms
# with the backward's dq in a kernel of its own: the fused backward
# writes a partial dq for every key block and sums them, which a window
# that leaves most blocks empty does not repay (without a window it
# does: 23.9 against 28.5 ms).
# Without a window a tile that no document reaches (`document_tiles`)
# is skipped as the tiles above the diagonal are. On the benchmark's
# corpus (documents of median 700 tokens packed into 8k rows) a document
# reaches 59.8% of a row's 36 causal tiles of 1,024 (59.1-60.5% by
# seed) and 48.2% of its 136 of 512, where the same-document pairs are
# 35.5% of the causal ones; of the sliding layers' 31 tiles 99.96% are
# reached, so nothing is skipped there. Read on eight rows of that
# corpus (PERF.md, PR 32; 48 heads of 128): 5.73 and 16.3 ms at 1,024
# with 55.9% of the tiles kept (8.47 and 23.9 with none skipped), 7.14
# and 21.5 at 512 with 44.1% kept (10.3 and 30.9): a grid step costs
# some 0.7 us whether it computes or not, so the kernel's time is about
# 0.27 + 0.73 x the kept share of what it was, and the smaller tile's
# four times as many steps cost more than its emptier grid saves.
ATTENTION_BLOCK, WINDOW_BLOCK = 1024, 512


def document_tiles(segments, block: int):
    """Which ``block`` x ``block`` score tiles on or under the diagonal
    may hold a pair of one document: ``keep[..., i, j]`` for query block
    ``i`` and key block ``j`` of ``segments`` ``[..., S]``, where the two
    blocks' ranges of document ids overlap. Blocks with disjoint ranges
    share no id, so no tile that holds a pair is dropped, whatever the
    ids' order; for a packer's non-decreasing ids the rule is exact.
    ``numpy`` in, ``numpy`` out; a ``jax`` array, traced or not, ``jax``
    out."""
    xp = jnp if isinstance(segments, jax.Array) else np
    blocks = segments.reshape(*segments.shape[:-1], -1, block)
    lo, hi = blocks.min(-1), blocks.max(-1)
    at = xp.arange(lo.shape[-1])
    return ((at[:, None] >= at[None, :])
            & (hi[..., None, :] >= lo[..., :, None])
            & (lo[..., None, :] <= hi[..., :, None]))


def _next_kept(keep):
    """For each tile of ``keep`` ``[rows, columns]``, in row-major
    order, the column of the next kept tile at or after it; 0 past the
    last."""
    at = jnp.arange(keep.size)
    following = jax.lax.cummin(
        jnp.where(keep.ravel(), at, keep.size), reverse=True)
    column = jnp.where(following < keep.size, following % keep.shape[1], 0)
    return column.reshape(keep.shape)


def document_tile_tables(segments, block: int):
    """The splash kernel's run-time tables for a causal mask over this
    sequence's documents, ``[n, n]`` over ``[query block, key block]``:
    ``keep`` (:func:`document_tiles`) and the two ``data_next`` tables,
    laid out as ``splash_attention_mask_info.process_mask`` and
    ``process_mask_dkv`` (unshrunk, as the fused backward takes it) lay
    out theirs. Forward: the key block of the next kept tile along the
    forward grid (query blocks outer, key blocks inner), which the
    kernel fetches while a dropped tile passes. Backward: the query
    block of the next kept tile along the dkv grid (key blocks outer,
    query blocks inner)."""
    keep = document_tiles(segments, block)
    return keep, _next_kept(keep), _next_kept(keep.T).T


def kernel_attention(q, k, v, segments, window: int | None = None,
                     interpret: bool = False):
    """The same attention through JAX's splash-attention kernel (TPU):
    no score matrix in HBM, tiles above the diagonal, tiles wholly
    before the window and (without a window) tiles that no document
    reaches skipped, one key-value head shared by its group of query
    heads."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel,
        splash_attention_mask as masks,
    )

    s, h, hd = q.shape
    kv = k.shape[1]
    group = h // kv
    if window is None:
        block, mask = min(ATTENTION_BLOCK, s), masks.CausalMask((s, s))
    else:
        # Keys t - window + 1 .. t: causal, and the token itself counts.
        block = min(WINDOW_BLOCK, s)
        mask = masks.LocalMask((s, s), (window - 1, 0), 0)
    # dq with dk and dv in one kernel, or (with a window) in its own.
    backward = (dict(use_fused_bwd_kernel=True) if window is None else
                dict(block_q_dq=block, block_kv_dq=block))
    sizes = kernel.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        **backward)
    attend = kernel.make_splash_mqa_single_device(
        masks.MultiHeadMask([mask] * group),
        block_sizes=sizes, interpret=interpret)
    if window is None:
        # The same kernel with the tiles that none of this sequence's
        # documents reaches switched off in its run-time tables: a kept
        # tile is computed as before, a dropped one held masked scores
        # alone, which add exact zeros.
        keep, forward_next, dkv_next = document_tile_tables(segments, block)

        def kept(info, data_next):
            return info._replace(
                block_mask=jnp.where(keep, info.block_mask, 0),
                data_next=data_next[None].astype(info.data_next.dtype))

        attend = kernel.SplashAttentionKernel(
            kept(attend.fwd_mask_info, forward_next), None,
            kept(attend.dkv_mask_info, dkv_next), **attend.kwargs)
    ids = kernel.SegmentIds(q=segments, kv=segments)
    out = jax.vmap(lambda q_, k_, v_: attend(q_, k_, v_, segment_ids=ids))(
        q.reshape(s, kv, group, hd).transpose(1, 2, 0, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2))
    return out.transpose(2, 0, 1, 3).reshape(s, h, hd)


def attention(q, k, v, segments, window: int | None = None):
    """One of the two forms above, by platform and length, under its
    device scope: ``df2.seq.attn``, or ``df2.seq.attn_window`` with a
    window."""
    scope = "df2.seq.attn" if window is None else "df2.seq.attn_window"
    with jax.named_scope(scope):
        # The kernel needs whole 128-wide tiles; below that, and off the
        # TPU, the plain form.
        if jax.devices()[0].platform == "tpu" and q.shape[0] % 128 == 0:
            return kernel_attention(q, k, v, segments, window)
        return dense_attention(q, k, v, segments, window)


def gated_ffn(p, a):
    dt = a.dtype
    return (jax.nn.silu(a @ p["w1"].astype(dt)) * (a @ p["w3"].astype(dt))
            ) @ p["w2"].astype(dt)


def head_loss(head, final_norm, x, local, segments, *, cfg):
    """The summed cross-entropy of one sequence's next tokens, over the
    positions whose next token is in the same document. ``head``: the
    output rows held ``[rows, hidden]``; ``local``: token ids as rows of
    it."""
    dt = x.dtype
    x = rms_norm(x, final_norm, cfg.norm_eps)
    logits = jnp.matmul(x, head.astype(dt).T,
                        preferred_element_type=jnp.float32)
    target = jnp.roll(local, -1)
    hit = jnp.arange(head.shape[0])[None, :] == target[:, None]
    nll = jax.nn.logsumexp(logits, -1) - jnp.where(hit, logits, 0).sum(-1)
    return jnp.where(target_positions(segments), nll, 0).sum()


def target_positions(segments):
    """Where a position's next token is in the same document (last
    axis: the sequence)."""
    same = jnp.roll(segments, -1, axis=-1) == segments
    return same & (jnp.arange(segments.shape[-1]) < segments.shape[-1] - 1)


def sequence_loss(params, router_bias, tokens, segments, positions, *,
                  cfg, block):
    """One packed sequence ``[S]`` through a family's ``block``s: the
    summed cross-entropy over :func:`target_positions` and each expert
    layer's assignment counts ``[expert layers, E]``. ``router_bias``:
    ``[expert layers, E]``. The logits are against ``lm_head`` where the
    family has one, else against the embedding rows (tied). Each block,
    and the head with the loss, keeps its input alone for the backward
    pass and is computed again there."""
    local = tokens - cfg.held_vocab[0]
    with jax.named_scope("df2.seq.embed"):
        x = embedding_rows(params["embed"], local,
                           jnp.dtype(cfg.compute_dtype))
    counts = []
    for i in cfg.kept_layers:
        routed = i in cfg.expert_layers
        bias = router_bias[cfg.expert_layers.index(i)] if routed else None
        x, assigned = jax.checkpoint(partial(block, cfg=cfg, layer=i))(
            params[f"layer_{i}"], x, bias, segments, positions)
        if routed:
            counts.append(assigned)
    with jax.named_scope("df2.loss"):
        loss = jax.checkpoint(partial(head_loss, cfg=cfg))(
            params.get("lm_head", params["embed"]), params["final_norm"], x,
            local, segments)
    return loss, (jnp.stack(counts) if counts else jnp.zeros(
        (0, cfg.num_experts), jnp.int32))


def batch_loss(params, router_bias, tokens, segments, positions, *,
               cfg, block):
    """:func:`sequence_loss` over a batch ``[B, S]``, one sequence at a
    time (a sequence is the unit of memory: the batch costs residuals of
    ``B`` block inputs a layer and no more). Returns the two sums."""
    def one(args):
        return sequence_loss(params, router_bias, *args, cfg=cfg, block=block)
    loss, counts = jax.lax.map(one, (tokens, segments, positions))
    return loss.sum(), counts.sum(0)
