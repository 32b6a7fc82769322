"""Plain reference for the ``ouro`` kind: the looped decoder of
huggingface.co/ByteDance/Ouro-2.6B (``config.json``, ``model_type``
``ouro``; the Ouro LoopLM, arXiv:2510.25741) in ``jax.numpy`` and
float32, every product at ``precision="highest"``; no kernel, no online
softmax and no skipped tile (a head's ``[S, S]`` scores are held whole,
the document mask written as comparisons), no loop construct (the
passes are written out one after another). Its own weights from the
seed, its own masks from the packed arrays; the batch order and AdamW
(its one-program form, ``corrections="device"``) are
``references/common.py``'s. Imports nothing of the program.

With ``n(x; w) = x / sqrt(mean(x²) + rms_norm_eps) · w`` and ``x`` one
packed sequence ``[S, hidden]``: block ``l`` is ``h = x + n(Attn(n(x;
w_in)); w_attn_out)``, ``y = h + n(FFN(n(h; w_ff)); w_ff_out)``, no
biases. The kept layers run ``total_ut_steps`` times; after pass ``t``
``h_t = n(x; w_final)`` is the next pass's input, ``λ_t = σ(h_t · w_g +
b_g)`` and ``CE_t`` the cross-entropy of ``h_t W_head^T``; the loss is
``Σ_t p(t)·CE_t - β·H(p)`` per target position, ``p(t) = λ_t Π_{j<t}
(1 - λ_j)`` and ``p(T) = Π_{j<T} (1 - λ_j)``. What the published config
is silent on has a comment at its line (the configuration's file lists
each under ``assumed``).

Two evaluations of the same equations. :func:`forward_sums` is the
definition: one packed sequence whole, for plain autodiff (the CPU
tests). :func:`sequence_gradient` is what a run evaluates: the same
function a piece at a time (a layer application, a pass's exit, the
mixture of the exits), each piece a compiled program of its own and its
gradient plain ``jax.vjp`` of it, chained by hand; so no one program
holds the whole model (the benchmark's host has to compile it) and
nothing is computed a second time. A test holds it to
``jax.value_and_grad(forward_sums)``.
"""

from __future__ import annotations

import math
from functools import partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references import common
from benchmarks.references.common import QUICKLY

HIGHEST = "highest"
INIT_STD = 0.02
# Positions whose logits are held at a time: against 49,152 rows a
# block's float32 logits are 0.4 GB, and its backward pass holds some
# four such arrays beside the reference's parameters, Adam's moments and
# two gradients.
HEAD_BLOCK = 2048


def sizes(spec: dict) -> dict:
    """What the equations need, from the configuration's file: the
    published keys, the published layers that are kept, the vocabulary
    rows held here, the loop and the objective's entropy weight."""
    return {
        "kept": spec["deployment"]["layers_kept"],
        "d": spec["hidden_size"], "f": spec["intermediate_size"],
        "heads": spec["num_attention_heads"],
        "kv_heads": spec["num_key_value_heads"], "head": spec["head_dim"],
        "eps": spec["rms_norm_eps"], "theta": float(spec["rope_theta"]),
        "loops": spec["total_ut_steps"],
        # Assumed: the Ouro report's first-stage weight of the entropy
        # bonus (no published key).
        "beta": spec.get("exit_entropy", 0.1),
        "vocab": tuple(spec["deployment"]["vocab_rows_held"]),
    }


def leaf_shapes(s: dict) -> list:
    """``[(name, shape, drawn)]`` in the order the parameters are drawn
    (``drawn``: True a matrix; 1.0 or 0.0 a norm's weight or a bias)."""
    d, hd, f = s["d"], s["head"], s["f"]
    q, kv, rows = s["heads"] * hd, s["kv_heads"] * hd, s["vocab"][1]
    out = [("embed", (rows, d), True)]
    for i in s["kept"]:
        at = f"layer_{i}"
        # Four norms a layer, before and after each sub-layer (assumed:
        # the family's public modelling code, which the config does not
        # describe).
        out += [(f"{at}/in_norm", (d,), 1.0),
                (f"{at}/attn/q", (d, q), True),
                (f"{at}/attn/k", (d, kv), True),
                (f"{at}/attn/v", (d, kv), True),
                (f"{at}/attn/o", (q, d), True),
                (f"{at}/attn_out_norm", (d,), 1.0),
                (f"{at}/ff_norm", (d,), 1.0),
                (f"{at}/ff/w1", (d, f), True),
                (f"{at}/ff/w3", (d, f), True),
                (f"{at}/ff/w2", (f, d), True),
                (f"{at}/ff_out_norm", (d,), 1.0)]
    # The exit gate (assumed: one linear unit of the normed state with a
    # bias); untied (``tie_word_embeddings`` false): the output head is
    # a leaf of its own over the rows held.
    out += [("final_norm", (d,), 1.0), ("exit_gate/w", (d,), True),
            ("exit_gate/b", (), 0.0), ("lm_head", (rows, d), True)]
    return out


def init_params(seed: int, s: dict) -> dict:
    """Assumed (the config gives no initialisation): normal(0, 0.02) for
    every matrix and the gate's weight, ones for norm weights, zero for
    the gate's bias; leaf ``n`` drawn from ``fold_in(key(seed), n)``,
    operation by operation."""
    root = jax.random.key(seed)
    return {name: (jax.random.normal(jax.random.fold_in(root, n), shape,
                                     jnp.float32) * jnp.float32(INIT_STD)
                   if drawn is True
                   else jnp.asarray(np.full(shape, drawn, np.float32)))
            for n, (name, shape, drawn) in enumerate(leaf_shapes(s))}


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """Rotate-half RoPE over every lane of each head of ``x`` [S, heads,
    head] at each token's position within its document (the restart at
    a document's start is the job's, not the model's): lane ``i`` with
    lane ``i + head / 2``, ``inv_freq_i = theta^(-i / (head / 2))``."""
    half = x.shape[-1] // 2
    f = jnp.asarray(theta ** -(np.arange(half, dtype=np.float64) / half),
                    jnp.float32)
    angle = positions.astype(jnp.float32)[:, None] * f
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None]
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + turned * sin


def _product(rnd):
    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)
    return mm


def layer_leaves(p: dict, i: int) -> dict:
    """Layer ``i``'s leaves of the flat tree, without its prefix."""
    prefix = f"layer_{i}/"
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


def block(q, x, segments, positions, s, rnd):
    """One published layer on one sequence ``x`` [S, hidden]; ``q``: the
    layer's leaves (:func:`layer_leaves`)."""
    mm = _product(rnd)
    length, h, kvh, hd = x.shape[0], s["heads"], s["kv_heads"], s["head"]
    at = jnp.arange(length)

    def attention(a):
        qs = rope(mm(a, q["attn/q"]).reshape(length, h, hd), positions,
                  s["theta"])
        k = rope(mm(a, q["attn/k"]).reshape(length, kvh, hd), positions,
                 s["theta"])
        v = mm(a, q["attn/v"]).reshape(length, kvh, hd)
        # Causal within the document. No attention bias.
        seen = (at[:, None] >= at[None, :]) & (
            segments[:, None] == segments[None, :])

        # One head at a time, its inputs alone kept for the backward
        # pass. Query head i reads key-value head i // (heads / kv_heads).
        @jax.checkpoint
        def one_head(i):
            j = i // (h // kvh)
            scores = mm(qs[:, i], k[:, j].T) / math.sqrt(hd)
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return mm(probs, v[:, j])

        heads = jax.lax.map(one_head, jnp.arange(h))         # [h, S, hd]
        return mm(heads.transpose(1, 0, 2).reshape(length, h * hd),
                  q["attn/o"])

    x = x + rms_norm(attention(rms_norm(x, q["in_norm"], s["eps"])),
                     q["attn_out_norm"], s["eps"])
    a = rms_norm(x, q["ff_norm"], s["eps"])
    out = mm(jax.nn.silu(mm(a, q["ff/w1"])) * mm(a, q["ff/w3"]), q["ff/w2"])
    return x + rms_norm(out, q["ff_out_norm"], s["eps"])


def exit_of(final_norm, gate_w, gate_b, lm_head, x, local, segments, weight,
            s, rnd):
    """One pass's exit from its last layer's output ``x``: the normed
    state ``h`` (the next pass's input), the gate's logit ``z`` [S] and
    each position's term [S]: ``weight`` · the cross-entropy of its next
    token + (1 - ``weight``) · that token's logit, 0 where the next token
    lies in another document; the logits of :data:`HEAD_BLOCK` positions
    at a time, against ``h`` as it is (no second norm)."""
    length = x.shape[0]
    h = rms_norm(x, final_norm, s["eps"])
    z = jnp.matmul(h, gate_w, precision=HIGHEST) + gate_b
    target = jnp.roll(local, -1)
    valid = (jnp.roll(segments, -1) == segments) & (
        jnp.arange(length) < length - 1)

    @jax.checkpoint
    def some(args):
        h, target, valid = args
        logits = _product(rnd)(h, lm_head.T)
        hit = jnp.take_along_axis(logits, target[:, None], -1)[:, 0]
        nll = jax.nn.logsumexp(logits, -1) - hit
        return jnp.where(valid, weight * nll + (1.0 - weight) * hit, 0.0)

    size = HEAD_BLOCK if length % HEAD_BLOCK == 0 else length
    terms = jax.lax.map(some, tuple(
        a.reshape(-1, size, *a.shape[1:]) for a in (h, target, valid)))
    return h, z, terms.reshape(length)


def exit_distribution(z):
    """``p`` ``[T, S]`` from the gates' logits ``z`` ``[T, S]`` (the last
    row is not read): ``p(t) = λ_t Π_{j<t} (1 - λ_j)`` for ``t < T``,
    ``p(T) = Π_{j<T} (1 - λ_j)``, ``λ = σ(z)``."""
    lam = jax.nn.sigmoid(z[:-1])
    stayed = jnp.cumprod(1.0 - lam, 0)
    before = jnp.concatenate([jnp.ones_like(z[:1]), stayed])
    return before * jnp.concatenate([lam, jnp.ones_like(z[:1])])


def mixture(z, terms, segments, beta):
    """The sum over the target positions of ``Σ_t p(t)·term_t - β·H(p)``
    (``H = -Σ_t p(t) log p(t)``), and how many they are."""
    length = segments.shape[0]
    valid = (jnp.roll(segments, -1) == segments) & (
        jnp.arange(length) < length - 1)
    p = exit_distribution(z)
    entropy = -(p * jnp.log(p)).sum(0)
    per_position = (p * terms).sum(0) - beta * entropy
    return jnp.where(valid, per_position, 0.0).sum(), valid.sum()


def forward_sums(p, tokens, segments, positions, weight, s, rnd):
    """One sequence, whole: the embedding rows, the kept layers
    ``loops`` times with an exit after each pass, and :func:`mixture`
    (its entropy weighed by ``weight`` too: at ``weight`` 0 the sum is of
    the exits' target logits under ``p``)."""
    local = tokens - s["vocab"][0]
    x, zs, terms = p["embed"][local], [], []
    for _ in range(s["loops"]):
        for i in s["kept"]:
            x = block(layer_leaves(p, i), x, segments, positions, s, rnd)
        x, z, term = exit_of(p["final_norm"], p["exit_gate/w"],
                             p["exit_gate/b"], p["lm_head"], x, local,
                             segments, weight, s, rnd)
        zs.append(z)
        terms.append(term)
    return mixture(jnp.stack(zs), jnp.stack(terms), segments,
                   weight * s["beta"])


EXIT_LEAVES = ("final_norm", "exit_gate/w", "exit_gate/b", "lm_head")


def programs(s: dict, rnd) -> SimpleNamespace:
    """The compiled pieces of :func:`sequence_gradient`: one program for
    every layer application, one for every exit, one for the mixture."""
    jit = partial(jax.jit, compiler_options=QUICKLY)

    @jit
    def embed(table, local):
        return table[local]

    @jit
    def embed_back(table, local, ct):
        return jnp.zeros_like(table).at[local].add(ct)

    @jit
    def layer(q, x, segments, positions):
        return block(q, x, segments, positions, s, rnd)

    @jit
    def layer_back(q, x, segments, positions, ct):
        return jax.vjp(lambda q, x: block(q, x, segments, positions, s, rnd),
                       q, x)[1](ct)

    def one_exit(leaves, x, local, segments, weight):
        return exit_of(*(leaves[k] for k in EXIT_LEAVES), x, local, segments,
                       weight, s, rnd)

    @jit
    def exit_(leaves, x, local, segments, weight):
        return one_exit(leaves, x, local, segments, weight)

    @jit
    def exit_back(leaves, x, local, segments, weight, d_h, d_z, d_terms):
        return jax.vjp(lambda e, x: one_exit(e, x, local, segments, weight),
                       leaves, x)[1]((d_h, d_z, d_terms))

    @jit
    def mix(z, terms, segments, beta):
        return jax.value_and_grad(mixture, argnums=(0, 1), has_aux=True)(
            z, terms, segments, beta)

    add = jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
    return SimpleNamespace(embed=embed, embed_back=embed_back, layer=layer,
                           layer_back=layer_back, exit=exit_,
                           exit_back=exit_back, mix=mix, add=add)


def sequence_gradient(s: dict, rnd):
    """``(params, tokens, segments, positions, weight, into=None) ->
    ((sums, count), gradient)``: the value and gradient of
    :func:`forward_sums`, a piece at a time (:func:`programs`); what the
    backward pass needs of the forward one is kept (each layer
    application's input, each exit's). With ``into`` (a running sum over
    sequences) the exits' leaves and each layer's are added into it once
    the last pass's backward has made them (``common.Sum``)."""
    run = programs(s, rnd)

    def gradient(p, tokens, segments, positions, weight, into=None):
        local, segments, positions = (
            jnp.asarray(tokens) - s["vocab"][0], jnp.asarray(segments),
            jnp.asarray(positions))
        leaves = {k: p[k] for k in EXIT_LEAVES}
        x, inputs, exits, zs, terms = run.embed(p["embed"], local), [], [], [], []
        for _ in range(s["loops"]):
            for i in s["kept"]:
                inputs.append(x)
                x = run.layer(layer_leaves(p, i), x, segments, positions)
            exits.append(x)
            x, z, term = run.exit(leaves, x, local, segments, weight)
            zs.append(z)
            terms.append(term)
        out, (d_z, d_terms) = run.mix(jnp.stack(zs), jnp.stack(terms),
                                      segments, weight * s["beta"])
        # The last pass's state feeds nothing.
        ct, d_exit, d_layers = jnp.zeros_like(x), None, {}
        grads = common.Sum(into)
        for t in reversed(range(s["loops"])):
            more, ct = run.exit_back(leaves, exits[t], local, segments,
                                     weight, ct, d_z[t], d_terms[t])
            d_exit = more if d_exit is None else run.add(d_exit, more)
            if t == 0:
                grads.add(d_exit)
            for i in reversed(s["kept"]):
                more, ct = run.layer_back(layer_leaves(p, i), inputs.pop(),
                                          segments, positions, ct)
                d_layers[i] = (more if i not in d_layers
                               else run.add(d_layers[i], more))
                if t == 0:
                    grads.add({f"layer_{i}/{k}": g
                               for k, g in d_layers.pop(i).items()})
        grads.add({"embed": run.embed_back(p["embed"], local, ct)})
        return out, grads.tree(p)
    return gradient


def readings(spec: dict, arrays: dict, seed: int, steps: int, sink,
             precision: str = "float32", keep_rows: float = 1.0,
             frozen: bool = False):
    """What the comparison reads, handed to ``sink`` as it is made
    (``common.sequence_readings``)."""
    s = sizes(spec)
    with jax.default_matmul_precision(HIGHEST):
        return common.sequence_readings(
            spec, arrays, seed, steps, lambda: init_params(seed, s),
            sequence_gradient(s, common.rounder(precision)), sink,
            keep_rows=keep_rows, frozen=frozen, corrections="device")
