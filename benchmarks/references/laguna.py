"""Plain reference for the ``laguna`` kind: the published layer
equations (huggingface.co/poolside/Laguna-XS.2 ``config.json``,
``model_type`` ``laguna``) in ``jax.numpy`` and float32, every product
at ``precision="highest"``; no kernel, no grouping of rows by expert
(every held expert is applied to every token and masked by the
selection), no online softmax and no skipped tile (a head's ``[S, S]``
scores are held whole, the masks written as comparisons). One sequence
at a time and one head at a time, so that the cell's own size fits the
chip, and the gradient taken layer by layer (plain autodiff of each
layer from its kept input), so that no compiled program holds the whole
model and the benchmark's host has room for it. Its own weights from the
seed, its own masks from the packed
arrays; the batch order and AdamW are ``references/common.py``'s.
Imports nothing of the program.

With ``n(x; w) = x / sqrt(mean(x²) + rms_norm_eps) · w`` and ``x`` one
packed sequence ``[S, hidden]``: block ``l`` is ``h = x + Attn_l(n(x;
w_in))``, ``y = h + FF_l(n(h; w_post))``, no biases. Attention, the two
RoPEs, the gate, the FFNs, the router and the head are written out
below; what the published config is silent on has a comment at its line
(the configuration's file lists each under ``assumed``).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references import common

HIGHEST = "highest"
INIT_STD = 0.02


def sizes(spec: dict) -> dict:
    """What the equations need, from the configuration's file: the
    published keys, the published layers that are kept, the experts and
    the vocabulary rows held here, the router's published width."""
    kept = spec["deployment"]["layers_kept"]
    return {
        "kept": kept,
        "types": [spec["layer_types"][i] for i in kept],
        "sparse": [spec["mlp_layer_types"][i] == "sparse" for i in kept],
        "heads": [spec["num_attention_heads_per_layer"][i] for i in kept],
        "d": spec["hidden_size"], "f_dense": spec["intermediate_size"],
        "f_expert": spec["moe_intermediate_size"],
        "f_shared": spec["shared_expert_intermediate_size"],
        "experts": spec["published"]["num_experts"],
        "held": tuple(spec["deployment"]["experts_held"]),
        "top_k": spec["num_experts_per_tok"],
        "kv_heads": spec["num_key_value_heads"], "head": spec["head_dim"],
        "eps": spec["rms_norm_eps"], "window": spec["sliding_window"],
        "rope": {kind: given for kind, given in
                 spec["rope_parameters"].items() if isinstance(given, dict)},
        "scaling": float(spec["moe_routed_scaling_factor"]),
        "vocab": tuple(spec["deployment"]["vocab_rows_held"]),
    }


def leaf_shapes(s: dict) -> list:
    """``[(name, shape, drawn)]`` in the order the parameters are drawn
    (``drawn`` False: a norm's weight, ones)."""
    d, hd = s["d"], s["head"]
    kv, rows = s["kv_heads"] * hd, s["vocab"][1]
    out = [("embed", (rows, d), True)]
    for i, heads, sparse in zip(s["kept"], s["heads"], s["sparse"]):
        at, q = f"layer_{i}", heads * hd
        out += [(f"{at}/in_norm", (d,), False),
                (f"{at}/attn/q", (d, q), True),
                (f"{at}/attn/k", (d, kv), True),
                (f"{at}/attn/v", (d, kv), True),
                (f"{at}/attn/o", (q, d), True),
                (f"{at}/attn/gate", (d, q), True),
                (f"{at}/post_norm", (d,), False)]
        if sparse:
            e, f, sh = s["held"][1], s["f_expert"], s["f_shared"]
            out += [(f"{at}/moe/router", (d, s["experts"]), True),
                    (f"{at}/moe/w1", (e, d, f), True),
                    (f"{at}/moe/w3", (e, d, f), True),
                    (f"{at}/moe/w2", (e, f, d), True),
                    (f"{at}/shared/w1", (d, sh), True),
                    (f"{at}/shared/w3", (d, sh), True),
                    (f"{at}/shared/w2", (sh, d), True)]
        else:
            f = s["f_dense"]
            out += [(f"{at}/ff/w1", (d, f), True),
                    (f"{at}/ff/w3", (d, f), True),
                    (f"{at}/ff/w2", (f, d), True)]
    # Untied (``tie_word_embeddings`` false): the output head is a leaf
    # of its own over the rows held.
    out += [("final_norm", (d,), False), ("lm_head", (rows, d), True)]
    return out


def init_params(seed: int, s: dict) -> dict:
    """Assumed (the config gives no initialisation): normal(0, 0.02) for
    every matrix, ones for norm weights; leaf ``n`` drawn from
    ``fold_in(key(seed), n)``, operation by operation."""
    root = jax.random.key(seed)
    return {name: (jax.random.normal(jax.random.fold_in(root, n), shape,
                                     jnp.float32) * jnp.float32(INIT_STD)
                   if drawn else jnp.ones(shape, jnp.float32))
            for n, (name, shape, drawn) in enumerate(leaf_shapes(s))}


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope_tables(given: dict, head: int, positions):
    """cos and sin ``[S, rotated lanes]`` of one layer type's
    ``rope_parameters`` at each token's position within its document
    (the restart at a document's start is the job's, not the model's),
    as the public ``rope_type`` rules compute them.

    ``default``: frequencies ``theta^(-2i / lanes)``. ``yarn``: each the
    blend of that (``f``) and ``f / factor`` by a linear ramp over the
    frequency's index between the two correction dimensions ``lanes ·
    ln(original_max_position_embeddings / (2π·β)) / (2 ln theta)`` of
    ``beta_fast`` (rounded down; below it ``f`` stays) and ``beta_slow``
    (rounded up; above it ``f / factor``), and cos and sin times
    ``attention_factor``."""
    lanes = int(head * given.get("partial_rotary_factor", 1.0))
    theta = float(given["rope_theta"])
    f = theta ** -(np.arange(0, lanes, 2, dtype=np.float64) / lanes)
    scale = 1.0
    if given["rope_type"] == "yarn":
        def dimension(beta):
            return lanes * math.log(given["original_max_position_embeddings"]
                                    / (beta * 2 * math.pi)) / (
                2 * math.log(theta))
        low = max(math.floor(dimension(given["beta_fast"])), 0)
        high = min(math.ceil(dimension(given["beta_slow"])), lanes - 1)
        ramp = np.clip((np.arange(lanes // 2) - low) / max(high - low, 0.001),
                       0, 1)
        f = f * (1 - ramp) + f / given["factor"] * ramp
        scale = given["attention_factor"]
    elif given["rope_type"] != "default":
        raise ValueError(given["rope_type"])
    angle = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        f, jnp.float32)
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1) * scale
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1) * scale
    return cos, sin


def rope(x, cos, sin):
    """Rotate-half RoPE on the first ``cos.shape[-1]`` lanes of each
    head of ``x`` [S, heads, head] (assumed: the rotated lanes come
    first), the rest passed through."""
    lanes = cos.shape[-1]
    turn, rest = x[..., :lanes], x[..., lanes:]
    turned = jnp.concatenate([-turn[..., lanes // 2:],
                              turn[..., :lanes // 2]], -1)
    return jnp.concatenate(
        [turn * cos[:, None] + turned * sin[:, None], rest], -1)


def ffn(mm, a, w1, w3, w2):
    """The gated FFN; ``mm``: the product (precision, rounding)."""
    return mm(jax.nn.silu(mm(a, w1)) * mm(a, w3), w2)


def routed_experts(mm, q, a, s):
    """The part of a sparse layer's routed experts that the experts
    ``s["held"]`` give: selection and weights over all ``s["experts"]``,
    the sum over the held ones (all of them: the uncut layer)."""
    # The router in float32 whatever ``mm`` rounds: the configuration
    # computes it in float32, so the control does too. Assumed: sigmoid
    # scores, no selection bias.
    scores = jax.nn.sigmoid(jnp.matmul(a, q["router"], precision=HIGHEST))
    _, chosen = jax.lax.top_k(scores, s["top_k"])
    weights = jnp.take_along_axis(scores, chosen, -1)
    # Normalised over the selected (the 1e-6 is assumed), then the
    # published scaling factor; the weights are on the outputs.
    weights = weights / (weights.sum(-1, keepdims=True) + 1e-6)
    weights = weights * s["scaling"]
    out = 0.0
    first, count = s["held"]
    for e in range(count):
        w_e = jnp.where(chosen == first + e, weights, 0.0).sum(-1)
        out = out + w_e[:, None] * ffn(mm, a, q["w1"][e], q["w3"][e],
                                       q["w2"][e])
    return out


def _product(rnd):
    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)
    return mm


def layer_leaves(p: dict, i: int) -> dict:
    """Layer ``i``'s leaves of the flat tree, without its prefix."""
    prefix = f"layer_{i}/"
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


def block(q, x, segments, positions, kind, h, sparse, s, rnd):
    """One published layer on one sequence ``x`` [S, hidden]; ``q``: the
    layer's leaves (:func:`layer_leaves`), ``kind`` its attention type,
    ``h`` its query heads, ``sparse`` whether its FFN is the expert
    one."""
    mm = _product(rnd)
    length = x.shape[0]
    at = jnp.arange(length)

    def attention(a):
        kvh, hd = s["kv_heads"], s["head"]
        cos, sin = rope_tables(s["rope"][kind], hd, positions)
        qs = rope(mm(a, q["attn/q"]).reshape(length, h, hd), cos, sin)
        k = rope(mm(a, q["attn/k"]).reshape(length, kvh, hd), cos, sin)
        v = mm(a, q["attn/v"]).reshape(length, kvh, hd)
        # Causal within the document; a sliding layer sees the window's
        # last ``window`` keys, the token itself among them (assumed).
        seen = (at[:, None] >= at[None, :]) & (
            segments[:, None] == segments[None, :])
        if kind == "sliding_attention":
            seen = seen & (at[:, None] - at[None, :] < s["window"])

        # One head at a time, its inputs alone kept for the backward
        # pass: 64 heads' [S, S] scores at once do not fit. Query head i
        # reads key-value head i // (heads / kv_heads).
        @jax.checkpoint
        def one_head(i):
            j = i // (h // kvh)
            scores = jnp.matmul(rnd(qs[:, i]), rnd(k[:, j]).T,
                                precision=HIGHEST) / math.sqrt(hd)
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return jnp.matmul(rnd(probs), rnd(v[:, j]), precision=HIGHEST)

        heads = jax.lax.map(one_head, jnp.arange(h))         # [h, S, hd]
        out = heads.transpose(1, 0, 2).reshape(length, h * hd)
        # The output gate (assumed form: elementwise sigmoid of a
        # projection of the layer's normed input, before W_o).
        return mm(jax.nn.sigmoid(mm(a, q["attn/gate"])) * out, q["attn/o"])

    x = x + attention(rms_norm(x, q["in_norm"], s["eps"]))
    a = rms_norm(x, q["post_norm"], s["eps"])
    if not sparse:
        return x + ffn(mm, a, q["ff/w1"], q["ff/w3"], q["ff/w2"])
    moe = {k[len("moe/"):]: v for k, v in q.items() if k.startswith("moe/")}
    return (x + ffn(mm, a, q["shared/w1"], q["shared/w3"], q["shared/w2"])
            + routed_experts(mm, moe, a, s))


def head_sums(final_norm, lm_head, x, local, segments, weight, s, rnd):
    """``weight`` · the summed cross-entropy over the positions whose
    next token is in the same document + (1 - ``weight``) · those
    positions' summed target logits, and how many they are."""
    at = jnp.arange(x.shape[0])
    logits = _product(rnd)(rms_norm(x, final_norm, s["eps"]), lm_head.T)
    target = jnp.roll(local, -1)
    valid = (jnp.roll(segments, -1) == segments) & (at < x.shape[0] - 1)
    hit = jnp.take_along_axis(logits, target[:, None], -1)[:, 0]
    nll = jax.nn.logsumexp(logits, -1) - hit
    per_position = weight * nll + (1.0 - weight) * hit
    return jnp.where(valid, per_position, 0.0).sum(), valid.sum()


def layers(s: dict):
    return zip(s["kept"], s["types"], s["heads"], s["sparse"])


def forward_sums(p, tokens, segments, positions, weight, s, rnd):
    """One sequence, whole: the embedding rows, the kept layers and
    :func:`head_sums`."""
    local = tokens - s["vocab"][0]
    x = p["embed"][local]
    for i, kind, h, sparse in layers(s):
        x = block(layer_leaves(p, i), x, segments, positions, kind, h,
                  sparse, s, rnd)
    return head_sums(p["final_norm"], p["lm_head"], x, local, segments,
                     weight, s, rnd)


def sequence_gradient(s: dict, rnd):
    """``(params, tokens, segments, positions, weight, into=None) ->
    ((sums, count), gradient)``: plain autodiff of :func:`forward_sums`,
    taken layer by layer (each layer's input kept, its vector-Jacobian
    product a compiled program of its own, one for the layers of a kind)
    so that no one program holds the whole model: the numbers are those
    of ``jax.value_and_grad(forward_sums)``, the host's memory is not.
    With ``into`` (a running sum over sequences) each layer's gradient
    is added into it as it is made (``common.Sum``)."""
    @jax.jit
    def embed(table, local):
        return table[local]

    @jax.jit
    def embed_back(table, local, ct):
        return jnp.zeros_like(table).at[local].add(ct)

    @partial(jax.jit, static_argnums=(4, 5, 6))
    def forward(q, x, segments, positions, kind, h, sparse):
        return block(q, x, segments, positions, kind, h, sparse, s, rnd)

    @partial(jax.jit, static_argnums=(4, 5, 6))
    def backward(q, x, segments, positions, kind, h, sparse, ct):
        _, pull = jax.vjp(lambda q_, x_: block(
            q_, x_, segments, positions, kind, h, sparse, s, rnd), q, x)
        return pull(ct)

    @jax.jit
    def head(final_norm, lm_head, x, local, segments, weight):
        return jax.value_and_grad(
            lambda *a: head_sums(*a, local, segments, weight, s, rnd),
            argnums=(0, 1, 2), has_aux=True)(final_norm, lm_head, x)

    def gradient(p, tokens, segments, positions, weight, into=None):
        local = tokens - s["vocab"][0]
        x, inputs = embed(p["embed"], local), []
        for i, kind, h, sparse in layers(s):
            inputs.append(x)
            x = forward(layer_leaves(p, i), x, segments, positions, kind, h,
                        sparse)
        out, (d_norm, d_head, ct) = head(
            p["final_norm"], p["lm_head"], x, local, segments, weight)
        grads = common.Sum(into)
        grads.add({"final_norm": d_norm, "lm_head": d_head})
        for (i, kind, h, sparse), x in reversed(list(zip(layers(s), inputs))):
            d_layer, ct = backward(layer_leaves(p, i), x, segments, positions,
                                   kind, h, sparse, ct)
            grads.add({f"layer_{i}/{k}": g for k, g in d_layer.items()})
        grads.add({"embed": embed_back(p["embed"], local, ct)})
        return out, grads.tree(p)
    return gradient


def readings(spec: dict, arrays: dict, seed: int, steps: int, sink,
             precision: str = "float32", keep_rows: float = 1.0,
             frozen: bool = False):
    """What the comparison reads, handed to ``sink`` as it is made
    (``common.sequence_readings``)."""
    s = sizes(spec)
    return common.sequence_readings(
        spec, arrays, seed, steps, lambda: init_params(seed, s),
        sequence_gradient(s, common.rounder(precision)), sink,
        keep_rows=keep_rows, frozen=frozen)
