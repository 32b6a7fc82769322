"""What the sequence-model families (``lfm2_moe``, ``laguna``,
``keye_vl2``, ``ouro``) share: parameters drawn from a list of shapes,
the RMS norm, rotate-half RoPE over one position stream or several, the
embedding lookup with its one-hot backward, causal same-document
attention with or without a window (plain, and through JAX's
splash-attention kernel on a TPU), attention over a learned selection of
keys (an indexer's scores, an exact top-k a query, the softmax over the
kept keys alone), the gated FFN, the next-token loss weighted by
position with its gradient formed in its forward pass, a stack of
layers run several times with an exit after each pass, and a batch's
loss: its layers recomputed a sequence at a time, its head in one call.

A family is a module with ``param_shapes(cfg)`` and ``block(p, x,
router_bias, segments, positions, *, cfg, layer)`` (a looped one also
with ``exit_gate(p, h)``), and a config that says which published
layers run and what of a layer is held here (``kept_layers``,
``expert_layers``, ``held_experts``, ``held_vocab``, ``num_experts``,
``norm_eps``, ``compute_dtype``, ``attention_window``; a looped one also
``total_ut_steps`` and ``exit_entropy``).

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
    (``[(path, shape, "normal" | "ones" | "zeros" | std)]``, a family's
    ``param_shapes``) is ``normal(fold_in(key, n)) · 0.02`` (or times
    the ``std`` a family gives in the kind's place), a norm's weight is
    ones and its bias zeros."""
    params: dict = {}
    for n, (path, shape, kind) in enumerate(shapes):
        node = params
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = (
            jnp.ones(shape, jnp.float32) if kind == "ones" else
            jnp.zeros(shape, jnp.float32) if kind == "zeros" else
            jax.random.normal(jax.random.fold_in(key, n), shape, jnp.float32)
            * jnp.float32(INIT_STD if kind == "normal" else kind))
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


def rope(x, positions, inv_freq, scale: float | None = None,
         sections: tuple | None = None):
    """Rotate-half rotary embedding of ``x`` [S, heads, head] at
    ``positions`` [S], in float32: the first ``2 · len(inv_freq)`` lanes
    of each head are rotated (lane ``i`` with lane ``i + len(inv_freq)``),
    the rest pass; ``scale`` multiplies cos and sin. With ``sections``
    (how many frequency pairs each position stream turns, in chunks:
    the first ``sections[0]`` pairs by stream 0, and so on)
    ``positions`` is ``[streams, S]``."""
    half = inv_freq.shape[0]
    if sections is not None:
        if sum(sections) != half or len(sections) != positions.shape[0]:
            raise ValueError(f"sections {sections} over {half} frequency "
                             f"pairs and {positions.shape[0]} streams")
        positions = positions[np.repeat(np.arange(len(sections)),
                                        sections)].T         # [S, half]
        angle = positions.astype(jnp.float32) * inv_freq
    else:
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


def dense_attention(q, k, v, segments, window: int | None = None,
                    seen=None):
    """Causal same-document attention, scores held whole: q [S, H, hd]
    already scaled, k and v [S, KV, hd]; query ``t`` sees key ``s`` where
    ``s <= t`` in the same document and, with a ``window``, ``t - s <
    window``; or where ``seen`` ``[S, S]`` says so, if that is given.
    The plain form, for sizes at which ``[H, S, S]`` fits."""
    s, h, hd = q.shape
    group = h // k.shape[1]
    q = q.reshape(s, k.shape[1], group, hd)
    scores = jnp.einsum("sjgd,tjd->jgst", q, k,
                        preferred_element_type=jnp.float32)
    if seen is None:
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


# -- attention over a learned selection of keys ------------------------------
#
# An indexer scores every earlier token of a query's document, the
# ``top_k`` best are kept, and the softmax runs over those alone. The
# selection is a function of the data, exact on the scores computed, and
# piecewise constant: nothing differentiates through it.

# Queries ranked at a time: a panel's scores ``[panel, heads, keys]``
# are the unit of memory of the indexer, and the keys a panel reads are
# the shortest of a few static widths (twice the panel, doubling up to
# the sequence) that reaches back to where its documents start. Read on
# a v5e at the benchmark cell's shapes (16 heads of 64, 2,048 kept, 32k
# rows of six documents of 16k to 1k; PERF.md, PR 33): scores 7.8 ms and
# ranking 20.4 ms a sequence and layer.
SELECT_PANEL = 512
# The selection is held one bit a pair, packed block by block of this
# many keys (``models/selected_attention.py``: the TPU kernels' tile).
SELECT_BLOCK = 1024
# The name under which a block's selection is kept for the backward pass
# (the ``saved`` of ``head_inputs``).
SELECTION = "df2_selection"


def select_block(length: int) -> int:
    """The packing block of a sequence of ``length``: :data:`SELECT_BLOCK`
    where that divides it, else the whole sequence (a multiple of 8)."""
    return SELECT_BLOCK if length % SELECT_BLOCK == 0 else length


def index_scores(q, k, w):
    """``I[t, s] = Σ_j w[t, j] · relu(q[t, j] · k[s])``, accumulated in
    float32: ``q`` [T, heads, d] the indexer's queries, ``k`` [K, d] its
    one shared key head, ``w`` [T, heads] float32."""
    per_head = jnp.einsum("thd,kd->thk", q, k,
                          preferred_element_type=jnp.float32)
    return (w[:, :, None] * jax.nn.relu(per_head)).sum(1)


def top_k_mask(scores, candidates, top_k: int):
    """For each row of ``scores`` [T, K] (float32) the ``min(c, top_k)``
    ``candidates`` [T, K] with the largest score, ``c`` the row's number
    of candidates, as a mask; ties at the last place go to the lower
    position (the rule of ``jax.lax.top_k``). Exact: the ``top_k``-th
    largest score is found bit by bit (32 counts over the row), not by
    a sort, and nothing is approximated."""
    # Scores as unsigned integers in the same order; -0.0 is 0.0, and a
    # key that is no candidate is 0, under every candidate's.
    bits = jax.lax.bitcast_convert_type(
        jnp.where(scores == 0, 0.0, scores), jnp.uint32)
    negative = bits >> 31 == 1
    key = jnp.where(negative, ~bits, bits | jnp.uint32(1 << 31))
    key = jnp.where(candidates, jnp.maximum(key, 1), 0)

    def larger_bit(i, least):
        tried = least | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        enough = (key >= tried[:, None]).sum(-1, dtype=jnp.int32) >= top_k
        return jnp.where(enough, tried, least)

    # The largest value that ``top_k`` keys reach: the last kept score.
    least = jax.lax.fori_loop(0, 32, larger_bit,
                              jnp.zeros(key.shape[0], jnp.uint32))
    reach = key >= least[:, None]
    few = candidates.sum(-1, dtype=jnp.int32) <= top_k

    def with_ties():
        # More keys reach the last kept score than there are places:
        # those above it, and of its equals the first by position.
        above = key > least[:, None]
        equal = key == least[:, None]
        left = top_k - above.sum(-1, dtype=jnp.int32)
        return above | (equal & (jnp.cumsum(equal, -1, dtype=jnp.int32)
                                 <= left[:, None]))

    tied = (~few & (reach.sum(-1, dtype=jnp.int32) > top_k)).any()
    kept = jax.lax.cond(tied, with_ties, lambda: reach)
    return candidates & (few[:, None] | kept)


def _panel_widths(length: int, panel: int) -> list:
    widths, w = [], 2 * panel
    while w < length:
        widths.append(w)
        w *= 2
    return widths + [length]


def select_keys(q, k, w, segments, top_k: int):
    """The selection of every query of one sequence, as a mask over
    ``[query, key]`` held one bit a pair (``[S, S / 8]`` uint8,
    ``selected_attention.pack_mask`` at :func:`select_block`), and how
    many candidates and members it has (two int32 ``[panels]`` vectors:
    a panel's counts fit, a sequence's need not). Query ``t``'s
    candidates are the ``s <= t`` of its document (equal ``segments``),
    itself among them; its members are the ``min(c_t, top_k)``
    candidates of the largest :func:`index_scores`.

    Scores are made, ranked and dropped a panel of queries at a time,
    against the keys from where the panel's documents start (for a
    packer's non-decreasing ids; for any ids, from the first position
    whose id lies within the panel's range, which is no later) to the
    panel's end, in a buffer of the shortest static width that holds
    them: work follows the documents' lengths, not the sequence's."""
    from dragonfly2_tpu.models.selected_attention import pack_mask

    length = q.shape[0]
    panel, block = min(SELECT_PANEL, length), select_block(length)
    if length % panel or length % 8:
        raise ValueError(f"{length} positions in panels of {panel}")
    q, k, w, segments = jax.lax.stop_gradient((q, k, w, segments))
    widths = _panel_widths(length, panel)
    rows = segments.reshape(-1, panel)
    within = ((segments >= rows.min(-1, keepdims=True))
              & (segments <= rows.max(-1, keepdims=True)))    # [panels, S]
    ends = (jnp.arange(rows.shape[0], dtype=jnp.int32) + 1) * panel
    reach = ends - jnp.argmax(within, -1).astype(jnp.int32)

    def ranked(width, end):
        first = jnp.maximum(end - width, 0)
        at_q = end - panel + jnp.arange(panel, dtype=jnp.int32)
        at_k = first + jnp.arange(width, dtype=jnp.int32)
        seg_q = jax.lax.dynamic_slice(segments, (end - panel,), (panel,))
        seg_k = jax.lax.dynamic_slice(segments, (first,), (width,))
        candidates = ((at_k[None, :] <= at_q[:, None])
                      & (seg_k[None, :] == seg_q[:, None]))
        with jax.named_scope("df2.seq.index"):
            scores = index_scores(
                jax.lax.dynamic_slice_in_dim(q, end - panel, panel),
                jax.lax.dynamic_slice_in_dim(k, first, width),
                jax.lax.dynamic_slice_in_dim(w, end - panel, panel))
        with jax.named_scope("df2.seq.select"):
            kept = top_k_mask(scores, candidates, top_k)
            row = jax.lax.dynamic_update_slice(
                jnp.zeros((panel, length), bool), kept, (0, first))
            return (pack_mask(row, block), candidates.sum(dtype=jnp.int32),
                    kept.sum(dtype=jnp.int32))

    def one(args):
        end, needed = args
        if len(widths) == 1:
            return ranked(widths[0], end)
        rung = sum((needed > w).astype(jnp.int32) for w in widths[:-1])
        return jax.lax.switch(rung, [partial(ranked, w) for w in widths],
                              end)

    packed, candidates, members = jax.lax.map(one, (ends, reach))
    return packed.reshape(length, length // 8), candidates, members


def count_limbs(counts):
    """Non-negative int32 counts as three 16-bit limbs (``[..., 3]``
    uint32, least first): sums of up to 65,536 of them limb by limb
    cannot overflow, and :func:`carry_limbs` brings a sum back to 16
    bits a limb (48 bits in all), where a plain uint32 would wrap
    within a dozen steps of 32k-token sequences."""
    counts = counts.astype(jnp.uint32)
    return jnp.stack([counts & 0xFFFF, counts >> 16,
                      jnp.zeros_like(counts)], -1)


def carry_limbs(limbs):
    low, mid, high = limbs[..., 0], limbs[..., 1], limbs[..., 2]
    mid = mid + (low >> 16)
    return jnp.stack([low & 0xFFFF, mid & 0xFFFF, high + (mid >> 16)], -1)


def limbs_value(limbs) -> np.ndarray:
    """What :func:`count_limbs` limbs hold, as Python-sized integers
    (host)."""
    limbs = np.asarray(limbs).astype(object)
    return limbs[..., 0] + (limbs[..., 1] << 16) + (limbs[..., 2] << 32)


def selected_attention(q, k, v, packed):
    """Softmax attention of q [S, H, hd] (already scaled) over the keys
    the selection ``packed`` (:func:`select_keys`) keeps for each query,
    one selection for all heads, under ``df2.seq.attn_sparse``: the
    kernels of ``models/selected_attention.py`` on a TPU, which read the
    packed bits; elsewhere, and for a sequence that is no whole number
    of their tiles, the plain form. Returns the attention and how many
    ``[block, block]`` tiles of the selection hold a member (int32),
    from the kernels' own tile table."""
    from dragonfly2_tpu.models import selected_attention as kernels

    s, h, hd = q.shape
    block = select_block(s)
    with jax.named_scope("df2.seq.attn_sparse"):
        tables = kernels.tile_tables(packed, block)
        held = tables[0].sum(dtype=jnp.int32)
        if jax.devices()[0].platform == "tpu" and block == SELECT_BLOCK:
            out = kernels.packed_attention(
                q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                v.transpose(1, 0, 2), packed, block, tables=tables)
            return out.transpose(1, 0, 2), held
        return dense_attention(q, k, v, None,
                               seen=kernels.unpack_mask(packed, block)), held


def gated_ffn(p, a):
    dt = a.dtype
    return (jax.nn.silu(a @ p["w1"].astype(dt)) * (a @ p["w3"].astype(dt))
            ) @ p["w2"].astype(dt)


# Positions whose logits are held at a time where a sequence has more:
# at 32,768 positions against 18,992 rows one sequence's float32 logits
# are 2.5 GB. A sequence no longer than this is one block.
HEAD_BLOCK = 8192


def head_blocks(length: int) -> int:
    """How many blocks of positions :func:`head_loss` takes ``length``
    positions in: one up to :data:`HEAD_BLOCK`, else whole blocks of
    that many (a length that is neither is refused)."""
    if length <= HEAD_BLOCK:
        return 1
    if length % HEAD_BLOCK:
        raise ValueError(f"{length} positions are neither one block of the "
                         f"head's loss nor whole blocks of {HEAD_BLOCK}")
    return length // HEAD_BLOCK


def _head_block(head, x, targets, weights, with_gradient: bool):
    """One block's cross-entropies ``[P]`` (``x`` ``[P, hidden]`` against
    every row of ``head``: products in ``x``'s dtype, accumulated and
    normalised in float32) and, ``with_gradient``, the gradients of
    ``Σ weights·nll`` with respect to ``x`` (in its dtype) and to the
    head's rows in that dtype (as float32): the logits' gradient
    ``weights ⊙ (softmax - onehot)`` is rounded to the products' dtype,
    where the MXU would round it."""
    dt = x.dtype
    rows = head.astype(dt)
    logits = jnp.matmul(x, rows.T, preferred_element_type=jnp.float32)
    hit = jnp.arange(head.shape[0])[None, :] == targets[:, None]
    lse = jax.nn.logsumexp(logits, -1)
    nll = lse - jnp.where(hit, logits, 0).sum(-1)
    if not with_gradient:
        return nll
    g = (weights[:, None] * (jnp.exp(logits - lse[:, None]) - hit)).astype(dt)
    dx = jnp.matmul(g, rows, preferred_element_type=jnp.float32).astype(dt)
    d_rows = jnp.matmul(g.T, x, preferred_element_type=jnp.float32)
    return nll, dx, d_rows.astype(dt).astype(jnp.float32)


def _head_pass(head, x, targets, weights, with_gradient: bool):
    """:func:`_head_block` over the call's blocks, one at a time: each
    sequence's positions (the last axis but one of ``x``) in
    :func:`head_blocks` of them; the rows' gradient summed over all the
    blocks in float32."""
    shape = x.shape
    size = shape[-2] // head_blocks(shape[-2])
    blocks = (x.reshape(-1, size, shape[-1]), targets.reshape(-1, size),
              weights.reshape(-1, size))
    if len(blocks[0]) == 1:
        out = _head_block(head, *(b[0] for b in blocks), with_gradient)
    elif not with_gradient:
        out = jax.lax.map(lambda b: _head_block(head, *b, False), blocks)
    else:
        def one(d_rows, block):
            nll, dx, d = _head_block(head, *block, True)
            return d_rows + d, (nll, dx)

        d_rows, (nll, dx) = jax.lax.scan(
            one, jnp.zeros(head.shape, jnp.float32), blocks)
        out = nll, dx, d_rows
    if not with_gradient:
        return out.reshape(shape[:-1])
    nll, dx, d_rows = out
    return nll.reshape(shape[:-1]), dx.reshape(shape), d_rows


@jax.custom_vjp
def head_loss(head, x, targets, weights):
    """``Σ weights·nll``: the cross-entropy of each position of ``x``
    ``[..., P, hidden]`` (normed already; leading axes, if any, are
    sequences) against the output rows ``head`` ``[rows, hidden]`` with
    ``targets`` ``[..., P]`` (row ids), weighted by ``weights`` ``[...,
    P]`` float32 (a mask of the counted positions, or a looped family's
    exit distribution on them). A sequence's positions are one block,
    or past :data:`HEAD_BLOCK` a whole number of blocks of that many;
    the blocks are taken one at a time.

    Differentiated, the pass that makes a block's logits also forms the
    gradients (:func:`_head_block`): three products a block, and
    nothing of the logits kept or made again; the backward pass scales
    what it kept (the input's gradient, the rows' gradient summed over
    the blocks, and each position's cross-entropy, which is the
    weights' gradient) by the loss's cotangent: one rows' gradient for
    the whole call. Undifferentiated, one product a block."""
    return (weights * _head_pass(head, x, targets, weights, False)).sum()


def _head_loss_fwd(head, x, targets, weights):
    nll, dx, d_rows = _head_pass(head, x, targets, weights, True)
    return (weights * nll).sum(), (nll, dx, d_rows)


def _head_loss_bwd(saved, ct):
    nll, dx, d_rows = saved
    return d_rows * ct, (dx * ct).astype(dx.dtype), None, nll * ct


head_loss.defvjp(_head_loss_fwd, _head_loss_bwd)


def target_positions(segments):
    """Where a position's next token is in the same document (last
    axis: the sequence)."""
    same = jnp.roll(segments, -1, axis=-1) == segments
    return same & (jnp.arange(segments.shape[-1]) < segments.shape[-1] - 1)


def sequence_loss(params, router_bias, tokens, segments, positions, *,
                  cfg, block, saved=None, exit_gate=None):
    """:func:`batch_loss` of one packed sequence ``[S]``."""
    return batch_loss(params, router_bias, tokens[None], segments[None],
                      positions[None], cfg=cfg, block=block, saved=saved,
                      exit_gate=exit_gate)


def head_inputs(params, router_bias, tokens, segments, positions, *,
                cfg, block, saved=None, exit_gate=None):
    """One packed sequence ``[S]`` through a family's ``block``s up to
    the head: what :func:`head_loss` takes of it (the states ``[P,
    hidden]``, normed, their targets and weights ``[P]``), the part of
    the loss outside the head (0, or a looped family's entropy bonus)
    and what each expert layer's block counted, stacked over the expert
    layers: the assignment counts ``[expert layers, E]`` (with whatever
    else the family's block counts beside them, as a tuple of such
    stacks). ``router_bias``: ``[expert layers, E]``. Each block, and the
    final norm, keeps its input alone for the backward pass and is
    computed again there; ``saved`` (names of
    ``jax.ad_checkpoint.checkpoint_name``) is what a family's blocks
    keep beside it.

    A plain family's ``P`` positions are the sequence's, weighted by
    :func:`target_positions`. A looped family gives ``exit_gate``
    (``(params["exit_gate"], h) -> z``, float32 ``[S]``): its kept
    layers run ``cfg.total_ut_steps`` times with the same weights, and
    after each pass ``t`` the final norm gives ``h_t``, which feeds pass
    ``t + 1``, and the gate ``λ_t = σ(z_t)``; the head then takes every
    exit's positions, ``P = T·S`` (no second norm), each exit's weighted
    by the exit distribution :func:`exit_mixture` gives. The assignment
    counts are then summed over the passes, and beside them stands the
    exit distribution's mass ``[T, 3]`` (:func:`count_limbs` of ``p(t)``
    in units of ``2^-EXIT_MASS_BITS`` over the counted positions)."""
    keep = (None if saved is None else
            jax.checkpoint_policies.save_only_these_names(*saved))
    local = tokens - cfg.held_vocab[0]
    with jax.named_scope("df2.seq.embed"):
        x = embedding_rows(params["embed"], local,
                           jnp.dtype(cfg.compute_dtype))

    def layers(x):
        counts = []
        for i in cfg.kept_layers:
            routed = i in cfg.expert_layers
            bias = router_bias[cfg.expert_layers.index(i)] if routed else None
            x, assigned = jax.checkpoint(
                partial(block, cfg=cfg, layer=i), policy=keep)(
                params[f"layer_{i}"], x, bias, segments, positions)
            if routed:
                counts.append(assigned)
        return x, counts

    def stacked(counts):
        return (jax.tree.map(lambda *c: jnp.stack(c), *counts) if counts
                else jnp.zeros((0, cfg.num_experts), jnp.int32))

    targets, counted = jnp.roll(local, -1), target_positions(segments)
    if exit_gate is None:
        x, counts = layers(x)
        with jax.named_scope("df2.loss"):
            h = jax.checkpoint(rms_norm, static_argnums=2)(
                x, params["final_norm"], cfg.norm_eps)
        return (h, targets, counted.astype(jnp.float32),
                jnp.zeros((), jnp.float32), stacked(counts))

    def exit_of(final_norm, gate, x):
        h = rms_norm(x, final_norm, cfg.norm_eps)
        return h, exit_gate(gate, h)

    def one_pass(x, _):
        x, counts = layers(x)
        with jax.named_scope("df2.seq.exit"):
            h, z = jax.checkpoint(exit_of)(
                params["final_norm"], params["exit_gate"], x)
        return h, (h, z, stacked(counts))

    # One pass traced once, its weights the same at every step: their
    # gradients add into one a leaf.
    _, (h, z, counts) = jax.lax.scan(one_pass, x,
                                     length=cfg.total_ut_steps)
    with jax.named_scope("df2.seq.exit"):
        weights, bonus = exit_mixture(z, counted, cfg.exit_entropy)
        mass = count_limbs(jnp.round(
            weights * 2 ** EXIT_MASS_BITS).astype(jnp.int32)).sum(-2)
    return (h.reshape(-1, h.shape[-1]), jnp.tile(targets, len(z)),
            weights.reshape(-1), bonus,
            (jax.tree.map(lambda c: c.sum(0), counts), mass))


# A looped family's exit distribution is counted in units of 2^-16 of a
# position (one position's p(t) is at most 65,536 units).
EXIT_MASS_BITS = 16


def exit_mixture(z, counted, entropy_weight: float):
    """The looped objective of one sequence, ``Σ_t p(t)·CE_t - β·H(p)``
    summed over the ``counted`` positions ``[S]``, in two parts: the
    weights ``[T, S]`` float32 of the exits' cross-entropies (the exit
    distribution ``p(t) = λ_t Π_{j<t} (1 - λ_j)`` for ``t < T`` and
    ``p(T) = Π_{j<T} (1 - λ_j)``, ``λ_t = σ(z_t)``, on the counted
    positions, 0 elsewhere) and ``-β·H(p)`` summed. ``z`` ``[T, S]``
    float32; ``β`` ``entropy_weight``; the last step's gate is not
    read. In logs, as ``log σ``, so that a gate near 0 or 1 stays
    finite."""
    leave = jax.nn.log_sigmoid(z[:-1])
    stayed = jnp.cumsum(jax.nn.log_sigmoid(-z[:-1]), 0)
    none = jnp.zeros_like(z[:1])
    log_p = (jnp.concatenate([none, stayed])
             + jnp.concatenate([leave, none]))
    weights = jnp.where(counted, jnp.exp(log_p), 0)
    return weights, entropy_weight * (weights * log_p).sum()


def batch_loss(params, router_bias, tokens, segments, positions, *,
               cfg, block, saved=None, exit_gate=None):
    """A batch ``[B, S]`` of packed sequences: the summed cross-entropy
    over :func:`target_positions` (for a looped family, the summed
    :func:`exit_mixture` objective) and what :func:`head_inputs` counted,
    summed over the sequences. The layers take one sequence at a time (a
    sequence is the unit of memory: the batch costs residuals of ``B``
    block inputs a layer and no more); the head then takes every
    sequence's positions in one call (:func:`head_loss`, under
    ``df2.loss``, or ``df2.seq.exit`` for a looped family), which forms
    its gradients where it makes its logits, under no recomputation, and
    keeps one rows' gradient for the batch. The logits are against
    ``lm_head`` where the family has one, else against the embedding
    rows (tied)."""
    def one(args):
        return head_inputs(params, router_bias, *args, cfg=cfg, block=block,
                           saved=saved, exit_gate=exit_gate)

    h, targets, weights, outside, counts = jax.lax.map(
        one, (tokens, segments, positions))
    head = params.get("lm_head", params["embed"])
    with jax.named_scope("df2.loss" if exit_gate is None else "df2.seq.exit"):
        loss = outside.sum() + head_loss(head, h, targets, weights)
    return loss, jax.tree.map(lambda c: c.sum(0), counts)
