"""Plain reference for the ``keye_vl2`` kind: the language model of
huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B (``config.json``,
``model_type`` ``KeyeVL2``) in ``jax.numpy`` and float32; no kernel, no
packed selection, no grouping of rows by expert (every held expert is
applied to every token and masked by the routing), no online softmax.
The index scores of a block of queries are held dense against the keys
they may see, ranked by a plain ``jax.lax.top_k``, and the attention is
a dense softmax under the mask that selection gives. Its own weights
from the seed, its own masks from the packed arrays; the batch order and
AdamW (its one-program form, ``corrections="device"``) are
``references/common.py``'s. Imports nothing of the program.

With ``n(x; w) = x / sqrt(mean(x²) + rms_norm_eps) · w`` and ``x`` one
packed sequence ``[S, hidden]``: block ``l`` is ``h = x + Attn(n(x;
w_in))``, ``y = h + MoE(n(h; w_post))``. The projections, QK-norm, the
three-stream RoPE, the indexer (the DeepSeek-V3.2-Exp report's
"lightning indexer" at ``sa_config``'s sizes), the selection, the
attention over it, the softmax router, the experts and the head are
written out below; what the published config is silent on has a comment
at its line (the configuration's file lists each under ``assumed``).

Two evaluations of the same equations. :func:`forward_sums` is the
definition: one packed sequence whole, every query against every key
under the document mask, for plain autodiff (the CPU tests). At the
cell's 32,768 positions that costs minutes of chip time a run, and a
run has 360 s in all, so :func:`sequence_gradient` evaluates the same
function document by document: a packed row is its documents side by
side, attention never leaves a document, and everything else is
token-wise. A document's queries are taken in groups (:func:`groups`),
each against the document's tokens up to the group's end and no others;
the selection a group's forward pass ranked is kept for its backward
pass (it is a mask); the gradient is chained by hand out of plain
``jax.vjp`` of the pieces (a block of queries, the keys' projections,
the rest of a layer, the head), so that nothing is computed a second
time but a block's scores. A test holds it to
``jax.value_and_grad(forward_sums)``.

Precision: the products that decide a discrete choice (the index
scores, the router's logits) at ``precision="highest"``; every other
product at :data:`PRODUCT`.
"""

from __future__ import annotations

import math
from functools import partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references import common
# The compiler's effort on the programs below, least: they run a few
# dozen times a run, and at its usual effort a run's first reference
# compiled for two minutes (PERF.md section 6).
from benchmarks.references.common import QUICKLY

HIGHEST = "highest"
# Every product but the index scores and the router's logits: float32
# operands and sums at three bfloat16 passes a product (2^-16 a term,
# 256 times finer than the bfloat16 the configuration states), half the
# six passes of "highest". ISSUE 33 wrote "highest" throughout; at that
# the reference took 320 s of a run that has 360 (PERF.md, PR 33).
PRODUCT = "high"
INIT_STD = 0.02
# Queries whose scores are held at a time: against 16,384 keys a block's
# scores and probabilities are 0.5 GB each for all heads, and a block's
# backward pass holds some five such arrays beside the reference's
# parameters, gradients, Adam's moments and a sequence's kept layers.
QUERY_BLOCK = 256
# A document longer than this is asked in groups: its second half
# against all of it, then its first half the same way.
GROUP_FLOOR = 1024
HEAD_BLOCK = 8192
# The leaves of a layer that its attention reads; the rest are the
# output projection's and the expert layer's.
ATTENDING = ("in_norm", "attn/q", "attn/k", "attn/v", "attn/q_norm",
             "attn/k_norm", "indexer/q", "indexer/k", "indexer/w",
             "indexer/k_norm", "indexer/k_norm_bias")


def sizes(spec: dict) -> dict:
    """What the equations need, from the configuration's file: the
    published keys, the published layers that are kept, the experts and
    the vocabulary rows held here, the router's published width."""
    sparse = spec["sa_config"]
    return {
        "kept": spec["deployment"]["layers_kept"],
        "d": spec["hidden_size"], "f_expert": spec["moe_intermediate_size"],
        "experts": spec["published"]["num_experts"],
        "held": tuple(spec["deployment"]["experts_held"]),
        "top_k": spec["num_experts_per_tok"],
        "heads": spec["num_attention_heads"],
        "kv_heads": spec["num_key_value_heads"], "head": spec["head_dim"],
        "eps": spec["rms_norm_eps"], "theta": float(spec["rope_theta"]),
        "sections": tuple(spec["rope_scaling"]["mrope_section"]),
        "index_heads": sparse["indexer_num_heads"],
        "index_head": sparse["indexer_head_dim"],
        "keep": sparse["topk"],
        "vocab": tuple(spec["deployment"]["vocab_rows_held"]),
        "emb_std": spec.get("emb_init_std", INIT_STD),
    }


def leaf_shapes(s: dict) -> list:
    """``[(name, shape, drawn)]`` in the order the parameters are drawn
    (``drawn``: True a matrix; 1.0 or 0.0 a norm's weight or bias)."""
    d, hd = s["d"], s["head"]
    q, kv, rows = s["heads"] * hd, s["kv_heads"] * hd, s["vocab"][1]
    ih, ihd = s["index_heads"], s["index_head"]
    e, f = s["held"][1], s["f_expert"]
    out = [("embed", (rows, d), True)]
    for i in s["kept"]:
        at = f"layer_{i}"
        out += [(f"{at}/in_norm", (d,), 1.0),
                (f"{at}/attn/q", (d, q), True),
                (f"{at}/attn/k", (d, kv), True),
                (f"{at}/attn/v", (d, kv), True),
                (f"{at}/attn/o", (q, d), True),
                # QK-norm (assumed: the Qwen3-MoE text family's, whose
                # keys these are and which names no key for it).
                (f"{at}/attn/q_norm", (hd,), 1.0),
                (f"{at}/attn/k_norm", (hd,), 1.0),
                (f"{at}/indexer/q", (d, ih * ihd), True),
                (f"{at}/indexer/k", (d, ihd), True),
                (f"{at}/indexer/w", (d, ih), True),
                (f"{at}/indexer/k_norm", (ihd,), 1.0),
                (f"{at}/indexer/k_norm_bias", (ihd,), 0.0),
                (f"{at}/post_norm", (d,), 1.0),
                (f"{at}/moe/router", (d, s["experts"]), True),
                (f"{at}/moe/w1", (e, d, f), True),
                (f"{at}/moe/w3", (e, d, f), True),
                (f"{at}/moe/w2", (e, f, d), True)]
    # Untied (``tie_word_embeddings`` false): the output head is a leaf
    # of its own over the rows held.
    out += [("final_norm", (d,), 1.0), ("lm_head", (rows, d), True)]
    return out


def init_params(seed: int, s: dict) -> dict:
    """Assumed (the config gives no initialisation): normal(0, 0.02) for
    every matrix but the embedding, whose rows are normal(0,
    ``emb_init_std``) where the file has that key; ones for norm
    weights, zero for the layer norm's bias; leaf ``n`` drawn from
    ``fold_in(key(seed), n)``, operation by operation."""
    root = jax.random.key(seed)
    return {name: (jax.random.normal(jax.random.fold_in(root, n), shape,
                                     jnp.float32)
                   * jnp.float32(s.get("emb_std", INIT_STD)
                                 if name == "embed" else INIT_STD)
                   if drawn is True
                   else jnp.asarray(np.full(shape, drawn, np.float32)))
            for n, (name, shape, drawn) in enumerate(leaf_shapes(s))}


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def layer_norm(x, w, b, eps):
    centred = x - x.mean(-1, keepdims=True)
    return centred / jnp.sqrt(
        jnp.mean(centred * centred, -1, keepdims=True) + eps) * w + b


def rope_tables(theta: float, lanes: int, positions, sections=None):
    """cos and sin ``[S, lanes]`` of rotate-half RoPE over ``lanes``
    lanes, ``inv_freq_i = theta^(-i / (lanes / 2))``. ``positions``
    ``[S]``, or with ``sections`` ``[streams, S]``: frequency pair ``i``
    is turned by the stream whose chunk of ``sections`` holds it (the
    first ``sections[0]`` pairs by stream 0, and so on: ``mrope_section``
    as the key's public use has it; assumed)."""
    half = lanes // 2
    f = jnp.asarray(theta ** -(np.arange(half, dtype=np.float64) / half),
                    jnp.float32)
    if sections is None:
        at = positions.astype(jnp.float32)[:, None]
    else:
        stream = np.repeat(np.arange(len(sections)), sections)
        at = positions.astype(jnp.float32)[stream].T          # [S, half]
    angle = at * f
    return (jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1),
            jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1))


def rope(x, cos, sin):
    """Rotate-half RoPE on every lane of each head of ``x`` [S, heads,
    head] (lane ``i`` with lane ``i + head / 2``)."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos[:, None] + turned * sin[:, None]


def ffn(mm, a, w1, w3, w2):
    """The gated FFN; ``mm``: the product (precision, rounding)."""
    return mm(jax.nn.silu(mm(a, w1)) * mm(a, w3), w2)


def routed_experts(mm, q, a, s):
    """The part of the expert layer's result that the experts
    ``s["held"]`` give: selection and weights over all ``s["experts"]``,
    the sum over the held ones (all of them: the uncut layer)."""
    # The router in float32 whatever ``mm`` rounds: the configuration
    # computes it in float32, so the control does too. Softmax over all
    # experts, the ``top_k`` largest, their shares divided by their sum
    # (``norm_topk_prob``); no selection bias.
    shares = jax.nn.softmax(jnp.matmul(a, q["router"], precision=HIGHEST), -1)
    _, chosen = jax.lax.top_k(shares, s["top_k"])
    # As a mask over the experts (a gather of the chosen shares would
    # have a scatter for its backward pass).
    chosen = (chosen[:, :, None] == jnp.arange(s["experts"])).any(1)
    weights = jnp.where(chosen, shares, 0.0)
    weights = weights / weights.sum(-1, keepdims=True)
    first, count = s["held"]
    # An expert's rows are made again in the backward pass, not kept
    # (four float32 [tokens, width] arrays an expert).
    @jax.checkpoint
    def weighted(a, w_e, w1, w3, w2):
        return w_e[:, None] * ffn(mm, a, w1, w3, w2)

    def add_expert(out, expert):
        return out + weighted(a, *expert), None

    return jax.lax.scan(add_expert, jnp.zeros_like(a), (
        weights[:, first:first + count].T, q["w1"][:count], q["w3"][:count],
        q["w2"][:count]))[0]


def _product(rnd, precision=None):
    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b), precision=precision or PRODUCT)
    return mm


def layer_leaves(p: dict, i: int) -> dict:
    """Layer ``i``'s leaves of the flat tree, without its prefix."""
    prefix = f"layer_{i}/"
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


def index_scores(mm, qi, ki, w):
    """``I[t, s] = Σ_j w[t, j] · relu(qI[t, j] · kI[s])`` for a block of
    queries ``qi`` [T, heads, d] against the keys ``ki`` [S, d]."""
    return jnp.einsum("tj,tjs->ts", w, jax.nn.relu(mm(qi, ki.T)),
                      precision=HIGHEST)


def selection(scores, candidates, keep: int):
    """The ``min(c, keep)`` candidates of the largest score in each row,
    as a mask: a plain ``top_k`` over the scores with every other key
    at ``-inf`` gives the score at the last place, and ties there go to
    the lower position (``top_k``'s own rule); where a row has fewer
    candidates than places, the places ``-inf`` took are dropped."""
    # -0.0 ranks as 0.0 (a sum of relu's zeros under negative weights).
    scores = jnp.where(candidates, jnp.where(scores == 0, 0.0, scores),
                       -jnp.inf)
    places = min(keep, scores.shape[-1])
    last = jax.lax.top_k(scores, places)[0][:, -1:]
    # Every key above the last place's score, and of those at it the
    # first that fit.
    above, ties = scores > last, scores == last
    room = places - above.sum(-1, keepdims=True)
    return (above | (ties & (jnp.cumsum(ties, -1) <= room))) & candidates


def keys_of(q, a, positions, s, rnd):
    """Of the normed tokens ``a`` [S, hidden] at ``positions`` [3, S]:
    the attention's keys and values ``[S, kv_heads, head]`` and the
    indexer's one key head ``[S, d]``."""
    mm = _product(rnd)
    length, hd, kvh = a.shape[0], s["head"], s["kv_heads"]
    cos, sin = rope_tables(s["theta"], hd, positions, s["sections"])
    k = rope(rms_norm(mm(a, q["attn/k"]).reshape(length, kvh, hd),
                      q["attn/k_norm"], s["eps"]), cos, sin)
    v = mm(a, q["attn/v"]).reshape(length, kvh, hd)
    # The indexer's one key head under a layer norm with weight and bias
    # (assumed, as DeepSeek-V3.2-Exp's public inference code); one-stream
    # RoPE over all its lanes from stream 0 at the same theta (assumed).
    cos_i, sin_i = rope_tables(s["theta"], s["index_head"], positions[0])
    ki = rope(layer_norm(mm(a, q["indexer/k"]), q["indexer/k_norm"],
                         q["indexer/k_norm_bias"], s["eps"])[:, None],
              cos_i, sin_i)[:, 0]
    return k, v, ki


def block_selection(q, a, positions, segments, ki, key_segments, first, s,
                    rnd):
    """Which of the keys (``ki`` [S, d], of ``key_segments``, the first
    of them at place 0) each query of a block keeps; the block's normed
    tokens ``a`` [T, hidden] stand at places ``first ..`` of the keys."""
    mm = _product(rnd, HIGHEST)
    block, keys = a.shape[0], ki.shape[0]
    ih, ihd = s["index_heads"], s["index_head"]
    # The indexer's queries from the layer's normed input (assumed: this
    # model has no query latent to take them from).
    cos_i, sin_i = rope_tables(s["theta"], ihd, positions[0])
    qi = rope(mm(a, q["indexer/q"]).reshape(block, ih, ihd), cos_i, sin_i)
    w = mm(a, q["indexer/w"]) / math.sqrt(ih * ihd)
    # Candidates: earlier in the same document, the token itself among
    # them.
    at = jnp.arange(keys)
    candidates = ((at[None] <= first + jnp.arange(block)[:, None])
                  & (key_segments[None] == segments[:, None]))
    if keys <= s["keep"]:                # a place for every candidate
        return candidates
    return selection(index_scores(mm, qi, ki, w), candidates, s["keep"])


def block_rows(q, a, positions, k, v, kept, s, rnd):
    """The heads' outputs ``[T, heads · head]`` of a block of queries
    (normed tokens ``a`` [T, hidden] at ``positions`` [3, T]) over the
    keys ``kept`` [T, S] keeps: scores / sqrt(head), a softmax over the
    selection alone, query heads ``g·j .. g·j + g - 1`` on key-value head
    ``j``."""
    mm = _product(rnd)
    block, hd, h, kvh = a.shape[0], s["head"], s["heads"], s["kv_heads"]
    cos, sin = rope_tables(s["theta"], hd, positions, s["sections"])
    qs = rope(rms_norm(mm(a, q["attn/q"]).reshape(block, h, hd),
                       q["attn/q_norm"], s["eps"]), cos, sin)
    scores = jnp.einsum(
        "tjgd,sjd->jgts", rnd(qs.reshape(block, kvh, h // kvh, hd)), rnd(k),
        precision=PRODUCT) / math.sqrt(hd)
    probs = jax.nn.softmax(jnp.where(kept, scores, -jnp.inf), -1)
    return jnp.einsum("jgts,sjd->tjgd", rnd(probs), rnd(v),
                      precision=PRODUCT).reshape(block, h * hd)


def _blocks(queries: int) -> tuple:
    """``queries`` as whole blocks: how many, and of how many queries."""
    block = QUERY_BLOCK if queries % QUERY_BLOCK == 0 else queries
    return queries // block, block


def attention(q, x, segments, positions, s, rnd, queries=None):
    """``Attn`` (without ``W_o``'s product: the heads' outputs
    ``[queries, heads · head]``) of the last ``queries`` of the tokens
    ``x`` [S, hidden] (all of them by default) over all of them, a block
    of queries at a time, and their selection ``[queries, S]``.
    ``positions`` [3, S]."""
    length = x.shape[0]
    queries = queries or length
    blocks, block = _blocks(queries)
    a = rms_norm(x, q["in_norm"], s["eps"])
    k, v, ki = keys_of(q, a, positions, s, rnd)

    @jax.checkpoint
    def some(start):
        mine = lambda x, axis=0: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            x, start, block, axis)
        # The selection is a set: nothing differentiates through it.
        kept = block_selection(
            q, jax.lax.stop_gradient(mine(a)), mine(positions, 1),
            mine(segments), jax.lax.stop_gradient(ki), segments, start, s,
            rnd)
        return block_rows(q, mine(a), mine(positions, 1), k, v, kept, s,
                          rnd), kept

    rows, kept = jax.lax.map(
        some, length - queries + jnp.arange(blocks) * block)
    return rows.reshape(queries, -1), kept.reshape(queries, length)


def attention_gradient(q, x, segments, positions, kept, ct, s, rnd):
    """The cotangents of ``q`` and ``x`` under :func:`attention` where
    it selected ``kept``, from the rows' cotangent ``ct`` [queries,
    heads · head]: plain ``jax.vjp`` of the norm, of the keys'
    projections and of each block of queries in turn, added up. What a
    block's pass holds is dropped before the next block's."""
    length, queries = x.shape[0], ct.shape[0]
    first, (blocks, block) = length - queries, _blocks(queries)
    norm = lambda w, x: rms_norm(x, w, s["eps"])  # noqa: E731
    a, pull_norm = jax.vjp(norm, q["in_norm"], x)
    (k, v), pull_keys = jax.vjp(
        lambda q, a: keys_of(q, a, positions, s, rnd)[:2], q, a)

    def some(total, args):
        start, given, ct = args
        mine = lambda x, axis=0: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            x, start, block, axis)
        _, pull = jax.vjp(lambda q, a, k, v: block_rows(
            q, a, mine(positions, 1), k, v, given, s, rnd), q, mine(a), k, v)
        d_q, d_a, d_k, d_v = pull(ct)
        return jax.tree.map(jnp.add, total, (d_q, d_k, d_v)), d_a

    zeros = jax.tree.map(jnp.zeros_like, (q, k, v))
    (d_q, d_k, d_v), d_rows = jax.lax.scan(some, zeros, (
        first + jnp.arange(blocks) * block,
        kept.reshape(blocks, block, length), ct.reshape(blocks, block, -1)))
    more_q, d_a = pull_keys((d_k, d_v))
    d_a = d_a.at[first:].add(d_rows.reshape(queries, -1))
    d_norm, d_x = pull_norm(d_a)
    d_q = jax.tree.map(jnp.add, d_q, more_q)
    return dict(d_q, in_norm=d_q["in_norm"] + d_norm), d_x


def rest_of_block(q, x, rows, s, rnd):
    """``y`` of a layer from its input ``x`` and its attention's heads'
    outputs ``rows``: ``h = x + rows W_o``, ``y = h + MoE(n(h;
    w_post))``."""
    mm = _product(rnd)
    x = x + mm(rows, q["attn/o"])
    a = rms_norm(x, q["post_norm"], s["eps"])
    moe = {k[len("moe/"):]: v for k, v in q.items() if k.startswith("moe/")}
    return x + routed_experts(mm, moe, a, s)


def block(q, x, segments, positions, s, rnd):
    """One published layer on one sequence ``x`` [S, hidden]; ``q``: the
    layer's leaves (:func:`layer_leaves`); ``positions`` [S] (text: the
    three streams are equal) or [3, S]."""
    if positions.ndim == 1:
        positions = jnp.broadcast_to(positions, (3,) + positions.shape)
    rows, _ = attention(q, x, segments, positions, s, rnd)
    return rest_of_block(q, x, rows, s, rnd)


def head_sums(final_norm, lm_head, x, local, segments, weight, s, rnd):
    """``weight`` · the summed cross-entropy over the positions whose
    next token is in the same document + (1 - ``weight``) · those
    positions' summed target logits, and how many they are; the logits
    of :data:`HEAD_BLOCK` positions at a time."""
    length = x.shape[0]
    at = jnp.arange(length)
    target = jnp.roll(local, -1)
    valid = (jnp.roll(segments, -1) == segments) & (at < length - 1)

    @jax.checkpoint
    def some(args):
        x, target, valid = args
        logits = _product(rnd)(rms_norm(x, final_norm, s["eps"]), lm_head.T)
        hit = jnp.take_along_axis(logits, target[:, None], -1)[:, 0]
        nll = jax.nn.logsumexp(logits, -1) - hit
        return jnp.where(valid, weight * nll + (1.0 - weight) * hit,
                         0.0).sum()

    size = HEAD_BLOCK if length % HEAD_BLOCK == 0 else length
    parts = jax.lax.map(some, tuple(
        a.reshape(-1, size, *a.shape[1:]) for a in (x, target, valid)))
    return parts.sum(), valid.sum()


def forward_sums(p, tokens, segments, positions, weight, s, rnd):
    """One sequence, whole: the embedding rows, the kept layers and
    :func:`head_sums`."""
    local = tokens - s["vocab"][0]
    x = p["embed"][local]
    for i in s["kept"]:
        x = block(layer_leaves(p, i), x, segments, positions, s, rnd)
    return head_sums(p["final_norm"], p["lm_head"], x, local, segments,
                     weight, s, rnd)


def groups(segments) -> list:
    """``[(first, tokens, queries)]`` that between them ask every query
    of a packed row once: the last ``queries`` of the ``tokens`` from
    ``first`` on, which lie in one document. A document of more than
    :data:`GROUP_FLOOR` tokens (an even number) is its second half
    against all of it, then its first half likewise, so that no query
    is held against many more keys than the earlier ones of its
    document, and documents of a power of two of tokens share their
    shapes."""
    segments = np.asarray(segments)
    edges = np.flatnonzero(np.diff(segments)) + 1
    out = []
    for first, end in zip(np.r_[0, edges], np.r_[edges, len(segments)]):
        tokens = int(end - first)
        while tokens > GROUP_FLOOR and tokens % 2 == 0:
            out.append((int(first), tokens, tokens // 2))
            tokens //= 2
        out.append((int(first), tokens, tokens))
    return out


def programs(s: dict, rnd) -> SimpleNamespace:
    """The compiled pieces of :func:`sequence_gradient`, one program for
    all layers and all groups of a shape."""
    jit = partial(jax.jit, compiler_options=QUICKLY)

    @jit
    def embed(table, local):
        return table[local]

    @jit
    def embed_back(table, local, ct):
        return jnp.zeros_like(table).at[local].add(ct)

    def prefix(first, tokens, x, segments, positions):
        take = lambda x, axis=0: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            x, first, tokens, axis)
        return take(x), take(segments), take(positions, 1)

    @partial(jit, static_argnums=(0, 1), donate_argnums=5)
    def attend(tokens, queries, first, q, x, rows, segments, positions):
        mine, kept = attention(
            q, *prefix(first, tokens, x, segments, positions), s, rnd,
            queries=queries)
        return jax.lax.dynamic_update_slice_in_dim(
            rows, mine, first + tokens - queries, 0), kept

    @partial(jit, static_argnums=0, donate_argnums=(7, 8))
    def attend_back(tokens, first, q, x, segments, positions, kept, d_q, d_x,
                    ct):
        queries = kept.shape[0]
        more_q, more_x = attention_gradient(
            q, *prefix(first, tokens, x, segments, positions), kept,
            jax.lax.dynamic_slice_in_dim(ct, first + tokens - queries,
                                         queries), s, rnd)
        mine = jax.lax.dynamic_slice_in_dim(d_x, first, tokens)
        return (jax.tree.map(jnp.add, d_q, more_q),
                jax.lax.dynamic_update_slice_in_dim(d_x, mine + more_x,
                                                    first, 0))

    @jit
    def rest(q, x, rows):
        return rest_of_block(q, x, rows, s, rnd)

    @jit
    def rest_back(q, x, rows, ct):
        return jax.vjp(lambda *a: rest_of_block(*a, s, rnd), q, x, rows)[1](ct)

    @jit
    def head(final_norm, lm_head, x, local, segments, weight):
        return jax.value_and_grad(
            lambda *a: head_sums(*a, local, segments, weight, s, rnd),
            argnums=(0, 1, 2), has_aux=True)(final_norm, lm_head, x)

    return SimpleNamespace(embed=embed, embed_back=embed_back, attend=attend,
                           attend_back=attend_back, rest=rest,
                           rest_back=rest_back, head=head)


@partial(jax.jit, compiler_options=QUICKLY)
def zeros_like(tree):
    return jax.tree.map(jnp.zeros_like, tree)


def _split(q: dict) -> tuple:
    """A layer's leaves: its attention's, and the rest."""
    return ({k: q[k] for k in ATTENDING},
            {k: v for k, v in q.items() if k not in ATTENDING})


def sequence_gradient(s: dict, rnd):
    """``(params, tokens, segments, positions, weight, into=None) ->
    ((sums, count), gradient)``: the value and gradient of
    :func:`forward_sums`, a layer and in it a group of queries
    (:func:`groups`) at a time, each a compiled program of its own
    (:func:`programs`); what the backward pass needs of the forward one
    is kept (each layer's input, its heads' outputs and its selections).
    With ``into`` (a running sum over sequences) each layer's gradient is
    added into it as it is made (``common.Sum``)."""
    run = programs(s, rnd)

    def gradient(p, tokens, segments, positions, weight, into=None):
        asked = groups(segments)
        local, segments, positions = (
            jnp.asarray(tokens) - s["vocab"][0], jnp.asarray(segments),
            jnp.asarray(positions))
        if positions.ndim == 1:
            positions = jnp.broadcast_to(positions, (3,) + positions.shape)
        x, kept = run.embed(p["embed"], local), []
        for i in s["kept"]:
            mine, others = _split(layer_leaves(p, i))
            rows = jnp.zeros((len(local), s["heads"] * s["head"]))
            selections = []
            for first, length, queries in asked:
                rows, chosen = run.attend(length, queries, first, mine, x,
                                          rows, segments, positions)
                selections.append(chosen)
            kept.append((x, rows, selections))
            x = run.rest(others, x, rows)
        out, (d_norm, d_head, ct) = run.head(
            p["final_norm"], p["lm_head"], x, local, segments, weight)
        grads = common.Sum(into)
        grads.add({"final_norm": d_norm, "lm_head": d_head})
        for i in reversed(s["kept"]):
            mine, others = _split(layer_leaves(p, i))
            x, rows, selections = kept.pop()
            d_rest, d_x, ct = run.rest_back(others, x, rows, ct)
            d_mine = zeros_like(mine)
            for (first, length, _), chosen in zip(asked, selections):
                d_mine, d_x = run.attend_back(
                    length, first, mine, x, segments, positions, chosen,
                    d_mine, d_x, ct)
            grads.add({f"layer_{i}/{k}": g
                       for k, g in {**d_rest, **d_mine}.items()})
            ct = d_x
        grads.add({"embed": run.embed_back(p["embed"], local, ct)})
        return out, grads.tree(p)
    return gradient


def readings(spec: dict, arrays: dict, seed: int, steps: int, sink,
             precision: str = "float32", keep_rows: float = 1.0,
             frozen: bool = False):
    """What the comparison reads, handed to ``sink`` as it is made
    (``common.sequence_readings``; AdamW in its one-program form)."""
    s = sizes(spec)
    return common.sequence_readings(
        spec, arrays, seed, steps, lambda: init_params(seed, s),
        sequence_gradient(s, common.rounder(precision)), sink,
        keep_rows=keep_rows, frozen=frozen, corrections="device")
