"""Plain reference for the ``lfm2_moe`` kind: the published layer
equations (huggingface.co/LiquidAI/LFM2-24B-A2B ``config.json``,
``model_type`` ``lfm2_moe``) in ``jax.numpy`` and float32, every product
at ``precision="highest"``; no kernel, no grouping of rows by expert
(every held expert is applied to every token and masked by the
selection), no online softmax (a head's ``[S, S]`` scores are held
whole). One sequence at a time, so that the cell's own size fits the
chip. Its own weights from the seed, its own masks from the packed
arrays; the batch order and AdamW are ``references/common.py``'s.
Imports nothing of the program.

With ``n(x; w) = x / sqrt(mean(x²) + norm_eps) · w`` and ``x`` one packed
sequence ``[S, hidden]``: block ``l`` is ``h = x + Op_l(n(x; w_op))``,
``y = h + FF_l(n(h; w_ff))``, no biases. The operators, the FFNs, the
router and the head are written out below; a departure from the
published description has a comment at its line that says so.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references import common

HIGHEST = "highest"
INIT_STD = 0.02


def sizes(spec: dict) -> dict:
    """What the equations need, from the configuration's file: the
    published keys, the published layers that are kept, the experts and
    the embedding rows held here, the router's published width."""
    kept = spec["deployment"]["layers_kept"]
    return {
        "kept": kept,
        "types": [spec["layer_types"][i] for i in kept],
        "dense": [i < spec["num_dense_layers"] for i in kept],
        "d": spec["hidden_size"], "f_dense": spec["intermediate_size"],
        "f_expert": spec["moe_intermediate_size"],
        "experts": spec["published"]["num_experts"],
        "held": tuple(spec["deployment"]["experts_held"]),
        "top_k": spec["num_experts_per_tok"],
        "heads": spec["num_attention_heads"],
        "kv_heads": spec["num_key_value_heads"],
        "head": spec["hidden_size"] // spec["num_attention_heads"],
        "taps": spec["conv_L_cache"], "eps": spec["norm_eps"],
        "theta": float(spec["rope_parameters"]["rope_theta"]),
        "norm_topk": spec["norm_topk_prob"],
        "scaling": float(spec["routed_scaling_factor"]),
        "vocab": tuple(spec["deployment"]["vocab_rows_held"]),
    }


def leaf_shapes(s: dict) -> list:
    """``[(name, shape, drawn)]`` in the order the parameters are drawn
    (``drawn`` False: a norm's weight, ones)."""
    d, hd = s["d"], s["head"]
    out = [("embed", (s["vocab"][1], d), True)]
    for i, kind, dense in zip(s["kept"], s["types"], s["dense"]):
        at = f"layer_{i}"
        out.append((f"{at}/op_norm", (d,), False))
        if kind == "conv":
            out += [(f"{at}/conv/in_proj", (d, 3 * d), True),
                    (f"{at}/conv/kernel", (s["taps"], d), True),
                    (f"{at}/conv/out_proj", (d, d), True)]
        else:
            kv = s["kv_heads"] * hd
            out += [(f"{at}/attn/q", (d, d), True),
                    (f"{at}/attn/k", (d, kv), True),
                    (f"{at}/attn/v", (d, kv), True),
                    (f"{at}/attn/o", (d, d), True),
                    (f"{at}/attn/q_norm", (hd,), False),
                    (f"{at}/attn/k_norm", (hd,), False)]
        out.append((f"{at}/ff_norm", (d,), False))
        if dense:
            f = s["f_dense"]
            out += [(f"{at}/ff/w1", (d, f), True),
                    (f"{at}/ff/w3", (d, f), True),
                    (f"{at}/ff/w2", (f, d), True)]
        else:
            e, f = s["held"][1], s["f_expert"]
            out += [(f"{at}/moe/router", (d, s["experts"]), True),
                    (f"{at}/moe/w1", (e, d, f), True),
                    (f"{at}/moe/w3", (e, d, f), True),
                    (f"{at}/moe/w2", (e, f, d), True)]
    out.append(("final_norm", (d,), False))
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


def selection_bias(spec: dict) -> np.ndarray:
    """``b_e = beta · ((e mod period) - (period - 1) / 2) / ((period - 1) /
    2)``: the same ramp on every chip of the group. Not in the published
    config (which only says ``use_expert_bias``); fixed for the run."""
    ramp, e = spec["router_bias"], np.arange(spec["published"]["num_experts"])
    mid = (ramp["period"] - 1) / 2
    return (ramp["beta"] * ((e % ramp["period"]) - mid) / mid).astype(
        np.float32)


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """Rotate-half RoPE (assumed from the family's modelling code) on
    ``x`` [S, heads, head] at each token's position within its document
    (the restart at a document's start is the job's, not the model's)."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1)[:, None]
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + turned * sin


def forward_sums(p, tokens, segments, positions, bias, weight, s, rnd):
    """One sequence. Returns ``weight`` · the summed cross-entropy over
    the positions whose next token is in the same document + (1 -
    ``weight``) · those positions' summed target logits, and how many
    they are."""
    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)

    def lay(prefix):
        return common.layer(p, prefix)

    length = tokens.shape[0]
    at = jnp.arange(length)
    same_doc = segments[:, None] == segments[None, :]
    local = tokens - s["vocab"][0]
    x = p["embed"][local]

    def conv(q, a):
        b, c, u = jnp.split(mm(a, q["in_proj"]), 3, axis=-1)
        z = b * u
        out = 0.0
        for lag in range(s["taps"]):
            # Tap j multiplies z_{t-j}; a tap that would reach before
            # the sequence or into another document is zero (the job's
            # masking, not the published model's).
            shifted = jnp.roll(z, lag, axis=0)
            seen = (at >= lag) & (jnp.roll(segments, lag) == segments)
            out = out + jnp.where(seen[:, None], shifted, 0.0) * q["kernel"][lag]
        return mm(c * out, q["out_proj"])

    def attention(q_, a):
        h, kvh, hd = s["heads"], s["kv_heads"], s["head"]
        q = rms_norm(mm(a, q_["q"]).reshape(length, h, hd), q_["q_norm"],
                     s["eps"])
        k = rms_norm(mm(a, q_["k"]).reshape(length, kvh, hd), q_["k_norm"],
                     s["eps"])
        v = mm(a, q_["v"]).reshape(length, kvh, hd)
        q, k = rope(q, positions, s["theta"]), rope(k, positions, s["theta"])
        seen = (at[:, None] >= at[None, :]) & same_doc

        # One head at a time, its inputs alone kept for the backward
        # pass: 32 heads' [S, S] scores at once do not fit. Query head i
        # reads key-value head i // (heads / kv_heads).
        @jax.checkpoint
        def one_head(i):
            j = i // (h // kvh)
            scores = jnp.matmul(rnd(q[:, i]), rnd(k[:, j]).T,
                                precision=HIGHEST) / math.sqrt(hd)
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return jnp.matmul(rnd(probs), rnd(v[:, j]), precision=HIGHEST)

        heads = jax.lax.map(one_head, jnp.arange(h))         # [h, S, hd]
        return mm(heads.transpose(1, 0, 2).reshape(length, h * hd), q_["o"])

    def ffn(a, w1, w3, w2):
        return mm(jax.nn.silu(mm(a, w1)) * mm(a, w3), w2)

    def experts(q, a):
        # The router in float32 whatever ``rnd``: the configuration
        # computes it in float32, so the control does too.
        scores = jax.nn.sigmoid(jnp.matmul(a, q["router"], precision=HIGHEST))
        _, chosen = jax.lax.top_k(scores + bias, s["top_k"])
        weights = jnp.take_along_axis(scores, chosen, -1)
        if s["norm_topk"]:
            # The 1e-6 is assumed from the family's modelling code.
            weights = weights / (weights.sum(-1, keepdims=True) + 1e-6)
        weights = weights * s["scaling"]
        out = 0.0
        first, count = s["held"]
        # The chip's share: the sum runs over the experts held here;
        # selection and weights are over all experts.
        for e in range(count):
            w_e = jnp.where(chosen == first + e, weights, 0.0).sum(-1)
            out = out + w_e[:, None] * ffn(a, q["w1"][e], q["w3"][e],
                                           q["w2"][e])
        return out

    def block(x, i, kind, dense):
        name = f"layer_{i}"
        a = rms_norm(x, p[f"{name}/op_norm"], s["eps"])
        h = x + (conv(lay(f"{name}/conv"), a) if kind == "conv"
                 else attention(lay(f"{name}/attn"), a))
        a = rms_norm(h, p[f"{name}/ff_norm"], s["eps"])
        if dense:
            q = lay(f"{name}/ff")
            return h + ffn(a, q["w1"], q["w3"], q["w2"])
        return h + experts(lay(f"{name}/moe"), a)

    for i, kind, dense in zip(s["kept"], s["types"], s["dense"]):
        # A layer's input alone is kept for the backward pass (memory;
        # the numbers are the same).
        x = jax.checkpoint(block, static_argnums=(1, 2, 3))(x, i, kind, dense)

    # Tied embeddings (assumed): the logits are against the rows held.
    logits = mm(rms_norm(x, p["final_norm"], s["eps"]), p["embed"].T)
    target = jnp.roll(local, -1)
    valid = (jnp.roll(segments, -1) == segments) & (at < length - 1)
    hit = jnp.take_along_axis(logits, target[:, None], -1)[:, 0]
    nll = jax.nn.logsumexp(logits, -1) - hit
    per_position = weight * nll + (1.0 - weight) * hit
    return jnp.where(valid, per_position, 0.0).sum(), valid.sum()


def sequence_gradient(s: dict, rnd, bias):
    """``(params, tokens, segments, positions, weight, into=None) ->
    ((sums, count), gradient)``: ``jax.value_and_grad`` of
    :func:`forward_sums`, one compiled program for a whole sequence;
    with ``into`` (a running sum over sequences) the gradient is added
    into it (``common.Sum``). ``bias``: the routers' selection bias, a
    host array (a device array closed over by a jitted function lives as
    long as JAX's cache of that function)."""
    @jax.jit
    def one_sequence(params, tok, seg, pos, weight):
        def summed(p):
            return forward_sums(p, tok, seg, pos, bias, weight, s, rnd)
        return jax.value_and_grad(summed, has_aux=True)(params)

    def gradient(p, tokens, segments, positions, weight, into=None):
        out, grads = one_sequence(p, tokens, segments, positions, weight)
        total = common.Sum(into)
        total.add(grads)
        return out, total.tree(p)
    return gradient


def readings(spec: dict, arrays: dict, seed: int, steps: int, sink,
             precision: str = "float32", keep_rows: float = 1.0,
             frozen: bool = False):
    """What the comparison reads, handed to ``sink`` as it is made
    (``common.sequence_readings``)."""
    s = sizes(spec)
    return common.sequence_readings(
        spec, arrays, seed, steps, lambda: init_params(seed, s),
        sequence_gradient(s, common.rounder(precision),
                          selection_bias(spec)), sink,
        keep_rows=keep_rows, frozen=frozen)
