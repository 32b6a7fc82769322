"""The expert layer: top-k routing over all experts, computed for the
experts held here.

One device of an expert-parallel group holds ``count`` consecutive
experts of the layer's ``E`` (``held = (first, count)``). The router is
the whole layer's: every token is scored against all ``E`` experts, its
``top_k`` are selected and weighted over all ``E``, and this device adds
the part of the result that its own experts give. The parts of all the
shares sum to the whole layer's result (``tests/test_moe.py``). What the
other devices' experts would add is not computed, stood in for or
exchanged here: on one device the layer runs without its exchange, and
the exchange over an ``expert`` mesh axis arrives with the first
configuration that spans devices.

Nothing is dropped and there is no capacity factor. The ``T·top_k``
assignments are sorted by held expert (those of experts held elsewhere
last), the rows are gathered into that order, and three grouped products
apply each expert to its own rows: on a TPU JAX's megablox kernel
(``gmm``, which visits only the row tiles its groups fill and keeps the
caller's ``df2.*`` scope on its operations; XLA's own lowering of
``jax.lax.ragged_dot`` is the same kind of kernel but renames them),
elsewhere ``jax.lax.ragged_dot``. The products' work grows with the rows
that are there. The row buffers, and with them the gathers and the
elementwise work, are as long as twice the expected number of held
assignments in any ordinary step, and longer only in a step that needs
it: four times as long each time, up to the worst case (every token sent
to held experts, which is exact too); ``jax.lax.cond`` (two lengths) or
``jax.lax.switch`` takes the one that the count needs.

Rows move by gathers only, forward and backward: an assignment's place
in the sorted order and its inverse are both known, so the backward of
"gather rows into expert order" is a gather by the inverse and a sum
over a token's ``top_k`` places (autodiff's own would be a scatter-add
with duplicate indices, which serializes on a TPU).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


# The TPU kernel's tiles. 256 rows: a step's groups are a few hundred
# rows each, and a group's last tile is mostly empty at 512. Read on a
# v5e at the benchmark cell's shapes (8 uneven groups in 4,100 of 8,192
# rows, 2048 x 1536; forward and backward of the three products): 2.69
# ms against 3.17 at 512 x 512 x 512 and 2.93 at 128 rows (PERF.md,
# PR 27). 256 last, for a width that neither of the two divides (768).
ROW_TILE, WIDTH_TILES = 256, (1024, 512, 256)


def group_tiles(m: int, k: int, n: int):
    """Row, contraction and column tile for a product of those sizes:
    the widest of :data:`WIDTH_TILES` that divides each width."""
    def widest(width):
        return next((t for t in WIDTH_TILES if width % t == 0), None)
    return ROW_TILE, widest(k), widest(n)


def grouped_product(rows, weights, sizes):
    """``rows[start_g : start_g + sizes[g]] @ weights[g]`` for each group
    ``g`` of consecutive rows; rows past the groups are left undefined."""
    tiles = group_tiles(rows.shape[0], *weights.shape[1:])
    if (jax.devices()[0].platform == "tpu" and None not in tiles
            and rows.shape[0] % ROW_TILE == 0):
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        # ``group_tiles`` itself: the backward's products have other
        # sizes and ask it again.
        return gmm(rows, weights, sizes, rows.dtype, group_tiles)
    return jax.lax.ragged_dot(rows, weights, sizes)


def route(x, router_w, router_bias, *, top_k: int,
          norm_topk_prob: bool = True, scaling_factor: float = 1.0,
          scoring: str = "sigmoid"):
    """Selection and weights over all experts, in float32.

    ``s = sigmoid(x · W_g)``, or with ``scoring="softmax"`` the softmax
    of ``x · W_g`` over all experts; the ``top_k`` experts with the
    largest ``s + b`` are selected (``b``: the selection bias, a
    buffer); their weights are ``s`` without the bias, divided by their
    sum where ``norm_topk_prob`` (plus 1e-6 under sigmoid scores, which
    need not sum to anything; plus nothing under softmax shares), times
    ``scaling_factor``. Returns the selected experts ``[T, top_k]``
    (int32) and their weights (float32). Only the weights carry a
    gradient.
    """
    logits = jnp.matmul(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)
    if scoring == "sigmoid":
        scores, guard = jax.nn.sigmoid(logits), 1e-6
    elif scoring == "softmax":
        scores, guard = jax.nn.softmax(logits, axis=-1), 0.0
    else:
        raise ValueError(f"scoring {scoring!r}: sigmoid or softmax")
    _, chosen = jax.lax.top_k(
        jax.lax.stop_gradient(scores) + router_bias.astype(jnp.float32),
        top_k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if norm_topk_prob:
        weights = weights / (weights.sum(-1, keepdims=True) + guard)
    return chosen.astype(jnp.int32), weights * scaling_factor


def _rows_to_tokens(rows, place, tokens: int):
    """``[tokens, top_k, d]``: for each of a token's ``top_k``
    assignments its row of ``rows`` ``[R, d]``, or zeros where the
    assignment's ``place`` is ``R`` or beyond (it has no row)."""
    padded = jnp.concatenate([rows, jnp.zeros_like(rows[:1])])
    return padded[jnp.minimum(place, rows.shape[0]).reshape(tokens, -1)]


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _to_expert_order(x, order, place, top_k):
    """Rows of ``x`` [T, d] in sorted-assignment order: row ``i`` of the
    ``R = len(order)`` is the token of assignment ``order[i]``."""
    return x[order // top_k]


def _to_expert_order_fwd(x, order, place, top_k):
    return x[order // top_k], (place, x.shape[0])


def _to_expert_order_bwd(top_k, saved, g):
    place, tokens = saved
    # A token's cotangent: the sum over its top_k places (a gather).
    with jax.named_scope("df2.moe.dispatch"):
        return (_rows_to_tokens(g, place, tokens).sum(
            1, dtype=jnp.float32).astype(g.dtype), None, None)


_to_expert_order.defvjp(_to_expert_order_fwd, _to_expert_order_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _to_token_order(ys, weights, order, place, top_k):
    """``out[t] = Σ_k weights[t, k] · ys[place[t·top_k + k]]`` in
    float32: the weighted sum of a token's expert outputs (an
    assignment placed past ``ys``'s rows adds nothing)."""
    picked = _rows_to_tokens(ys, place, weights.shape[0])
    return jnp.einsum("tk,tkd->td", weights, picked.astype(jnp.float32))


def _to_token_order_fwd(ys, weights, order, place, top_k):
    return (_to_token_order(ys, weights, order, place, top_k),
            (ys, weights, order))


def _to_token_order_bwd(top_k, saved, g):
    ys, weights, order = saved
    with jax.named_scope("df2.moe.combine"):
        to_row = g[order // top_k]
        d_ys = (weights.reshape(-1)[order][:, None] * to_row).astype(ys.dtype)
        # Each row's weight is one assignment's: back to [T, top_k] by
        # a scatter of scalars to distinct places.
        d_weights = jnp.zeros(weights.size, jnp.float32).at[order].set(
            (to_row * ys.astype(jnp.float32)).sum(-1), unique_indices=True)
    return d_ys, d_weights.reshape(weights.shape), None, None


_to_token_order.defvjp(_to_token_order_fwd, _to_token_order_bwd)


def _held_experts_part(x, weights, w1, w3, w2, order, place, sizes,
                       rows: int, top_k: int):
    """The held experts applied to the first ``rows`` rows of the sorted
    order (which hold every held assignment) and summed per token."""
    order = order[:rows]
    with jax.named_scope("df2.moe.dispatch"):
        filled = (jnp.arange(rows) < sizes.sum())[:, None]
        xs = _to_expert_order(x, order, place, top_k)
    with jax.named_scope("df2.moe.experts"):
        # Rows past the held assignments belong to no group, and a
        # grouped product leaves such rows unwritten, forward and
        # backward: every product's rows are set to 0 there on the way
        # out, which sets its cotangent's to 0 on the way back.
        def product(rows_, w):
            return jnp.where(filled, grouped_product(
                rows_, w.astype(x.dtype), sizes), 0)

        xs = jnp.where(filled, xs, 0)
        ys = product(jax.nn.silu(product(xs, w1)) * product(xs, w3), w2)
    with jax.named_scope("df2.moe.combine"):
        return _to_token_order(ys, weights, order, place, top_k)


def expert_layer(x, router_w, router_bias, w1, w3, w2, held, *,
                 top_k: int, norm_topk_prob: bool = True,
                 scaling_factor: float = 1.0, scoring: str = "sigmoid"):
    """The held experts' part of a gated-FFN expert layer.

    ``x``: tokens ``[T, d]``. ``router_w``: ``[d, E]``, ``router_bias``:
    ``[E]``. ``w1``, ``w3``: ``[count, d, f]`` and ``w2``: ``[count, f,
    d]``, the stacked weights of experts ``first .. first + count - 1``
    (``held = (first, count)``, static). Returns

    - ``Σ over a token's selected experts e held here of
      w_e · W2_e (silu(W1_e x) * W3_e x)``, float32 ``[T, d]``;
    - how many assignments each of the ``E`` experts got, int32 ``[E]``.

    ``scoring``: the router's scores, :func:`route`'s. The products run
    in ``x``'s dtype (the weights are cast to it).
    """
    first, count = held
    n_experts = router_w.shape[1]
    if w1.shape[0] != count or not 0 <= first <= n_experts - count:
        raise ValueError(f"held={held} against {w1.shape[0]} stacked "
                         f"experts of {n_experts}")
    with jax.named_scope("df2.moe.route"):
        chosen, weights = route(
            x, router_w, router_bias, top_k=top_k,
            norm_topk_prob=norm_topk_prob, scaling_factor=scaling_factor,
            scoring=scoring)
        # A count by comparison: a scatter-add of T·top_k ones into E
        # bins is all duplicate indices.
        assigned = (chosen[..., None] == jnp.arange(n_experts)).sum(
            (0, 1), dtype=jnp.int32)
    with jax.named_scope("df2.moe.dispatch"):
        local = chosen.reshape(-1) - first
        here = (local >= 0) & (local < count)
        # Sorted by held expert; assignments of experts held elsewhere
        # last. ``place`` is the inverse: where each assignment went.
        order = jnp.argsort(jnp.where(here, local, count), stable=True)
        every = order.shape[0]
        place = jnp.zeros(every, jnp.int32).at[order].set(
            jnp.arange(every, dtype=jnp.int32), unique_indices=True)
        sizes = assigned[first:first + count]
        weights = jnp.where(here.reshape(weights.shape), weights, 0.0)

    def part(rows):
        return _held_experts_part(x, weights, w1, w3, w2, order, place,
                                  sizes, rows, top_k)

    # The held assignments come first in the sorted order. Twice their
    # expected number (a share count / E of all) is where they end in
    # any ordinary step: the row buffers are that long, and longer (four
    # times each, up to the worst case, every assignment held here) only
    # in a step whose held assignments pass that.
    lengths = [-(-2 * every * count // n_experts // 8) * 8]
    while lengths[-1] * 4 < every:
        lengths.append(lengths[-1] * 4)
    if lengths[-1] >= every:
        return part(every), assigned
    lengths.append(every)
    if len(lengths) == 2:
        return jax.lax.cond(sizes.sum() <= lengths[0],
                            lambda: part(lengths[0]),
                            lambda: part(every)), assigned
    passed = sum((sizes.sum() > n).astype(jnp.int32) for n in lengths[:-1])
    return jax.lax.switch(passed, [partial(part, n) for n in lengths]
                          ), assigned
