"""The ``lfm2_moe`` sequence-model family: gated short convolutions and
grouped-query attention in a published pattern, a dense gated FFN in the
leading layers and a top-k-of-E expert FFN (``parallel/moe.py``) in the
rest, configured by the keys of the public ``config.json``
(huggingface.co/LiquidAI/LFM2-24B-A2B, ``model_type`` ``lfm2_moe``).

With ``n(x; w) = x / sqrt(mean(x²) + norm_eps) · w`` and ``x`` one packed
sequence ``[S, hidden]``, block ``l`` is ``h = x + Op_l(n(x; w_op))``,
``y = h + FF_l(n(h; w_ff))``; no biases anywhere.

- ``conv``: ``[B, C, u] = split3(W_in a)``; ``z = B·u``; ``c_t = Σ_j k_j ·
  z_{t-j}`` per channel over ``conv_L_cache`` taps, causal; ``Op = W_out
  (C·c)``.
- ``full_attention``: q as ``num_attention_heads`` heads, k and v as
  ``num_key_value_heads``; q and k RMS-normed over the head (a learned
  weight each), rotate-half RoPE, scores ``q·k / sqrt(head)``, causal,
  softmax in float32; query heads ``g·j .. g·j + g - 1`` share key-value
  head ``j``.
- FFN: ``W_2 (silu(W_1 a) · W_3 a)``, dense below ``num_dense_layers``
  and per selected expert above (router: sigmoid scores, selection by
  score + bias, weights without the bias; ``parallel/moe.py``).
- head: a final norm and logits against the tied embedding in float32;
  the loss is the cross-entropy of the next token.

The job, not the model, packs documents: ``segments`` (a document id per
position) keeps a convolution tap and an attention score from crossing a
document boundary, and ``positions`` restart in each document.

What a device holds: all of every operator, router and dense FFN, the
experts ``experts_held = (first, count)`` of each expert layer, and the
embedding rows ``vocab_held = (first, count)`` (token ids outside them
have no row here; the logits and the loss are over the rows held).
Parameters are float32 in a plain nested dict; products run in
``compute_dtype`` (bfloat16), norms, softmax, router and loss in float32.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from dragonfly2_tpu.parallel.moe import expert_layer

INIT_STD = 0.02


@dataclass(frozen=True)
class Lfm2MoeConfig:
    """The published keys, plus which published layers run and what of a
    layer is held here."""

    layer_types: tuple
    num_dense_layers: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    num_attention_heads: int
    num_key_value_heads: int
    vocab_size: int
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    rope_theta: float = 1_000_000.0
    # Indices into ``layer_types`` of the layers that run (None: all).
    layers: tuple | None = None
    experts_held: tuple | None = None
    vocab_held: tuple | None = None
    compute_dtype: str = "bfloat16"

    @classmethod
    def from_published(cls, config: dict, *, num_experts: int | None = None,
                       vocab_size: int | None = None, **held):
        """From a ``config.json``'s keys. ``num_experts`` and
        ``vocab_size`` override the file's where the file states what is
        held and not what is published."""
        if config.get("conv_bias"):
            raise ValueError("conv_bias is not supported: the published "
                             "lfm2_moe models have none")
        keys = ("num_dense_layers", "hidden_size", "intermediate_size",
                "moe_intermediate_size", "num_experts_per_tok",
                "num_attention_heads", "num_key_value_heads", "conv_L_cache",
                "norm_eps", "norm_topk_prob", "use_expert_bias",
                "routed_scaling_factor")
        return cls(
            layer_types=tuple(config["layer_types"]),
            num_experts=num_experts or config["num_experts"],
            vocab_size=vocab_size or config["vocab_size"],
            rope_theta=float(config["rope_parameters"]["rope_theta"]),
            **{k: config[k] for k in keys if k in config}, **held)

    @property
    def kept_layers(self) -> tuple:
        return (tuple(range(len(self.layer_types)))
                if self.layers is None else tuple(self.layers))

    @property
    def expert_layers(self) -> tuple:
        return tuple(i for i in self.kept_layers
                     if i >= self.num_dense_layers)

    @property
    def held_experts(self) -> tuple:
        return self.experts_held or (0, self.num_experts)

    @property
    def held_vocab(self) -> tuple:
        return self.vocab_held or (0, self.vocab_size)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


def param_shapes(cfg: Lfm2MoeConfig) -> list:
    """``[(path, shape, "normal" | "ones")]`` in the order the parameters
    are drawn."""
    d, hd = cfg.hidden_size, cfg.head_dim
    out = [(("embed",), (cfg.held_vocab[1], d), "normal")]
    for i in cfg.kept_layers:
        at = (f"layer_{i}",)
        out.append((at + ("op_norm",), (d,), "ones"))
        if cfg.layer_types[i] == "conv":
            out += [(at + ("conv", "in_proj"), (d, 3 * d), "normal"),
                    (at + ("conv", "kernel"), (cfg.conv_L_cache, d), "normal"),
                    (at + ("conv", "out_proj"), (d, d), "normal")]
        elif cfg.layer_types[i] == "full_attention":
            kv = cfg.num_key_value_heads * hd
            out += [(at + ("attn", "q"), (d, d), "normal"),
                    (at + ("attn", "k"), (d, kv), "normal"),
                    (at + ("attn", "v"), (d, kv), "normal"),
                    (at + ("attn", "o"), (d, d), "normal"),
                    (at + ("attn", "q_norm"), (hd,), "ones"),
                    (at + ("attn", "k_norm"), (hd,), "ones")]
        else:
            raise ValueError(f"layer type {cfg.layer_types[i]!r}")
        out.append((at + ("ff_norm",), (d,), "ones"))
        if i < cfg.num_dense_layers:
            f = cfg.intermediate_size
            out += [(at + ("ff", "w1"), (d, f), "normal"),
                    (at + ("ff", "w3"), (d, f), "normal"),
                    (at + ("ff", "w2"), (f, d), "normal")]
        else:
            e, f = cfg.held_experts[1], cfg.moe_intermediate_size
            out += [(at + ("moe", "router"), (d, cfg.num_experts), "normal"),
                    (at + ("moe", "w1"), (e, d, f), "normal"),
                    (at + ("moe", "w3"), (e, d, f), "normal"),
                    (at + ("moe", "w2"), (e, f, d), "normal")]
    out.append((("final_norm",), (d,), "ones"))
    return out


def init_params(key, cfg: Lfm2MoeConfig) -> dict:
    """Parameters drawn operation by operation (a compiled init rounds
    differently on a v5e; PERF.md, PR 25): leaf ``n`` of
    :func:`param_shapes` is ``normal(fold_in(key, n)) · 0.02``, a norm's
    weight is ones."""
    params: dict = {}
    for n, (path, shape, kind) in enumerate(param_shapes(cfg)):
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


def rope(x, positions, theta: float):
    """Rotate-half rotary embedding of ``x`` [S, heads, head] at
    ``positions`` [S], in float32."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None, :]
    x32 = x.astype(jnp.float32)
    turned = jnp.concatenate([-x32[..., half:], x32[..., :half]], -1)
    return (x32 * cos + turned * sin).astype(x.dtype)


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


def conv_operator(p, a, segments, cfg: Lfm2MoeConfig):
    dt = a.dtype
    b, c, u = jnp.split(a @ p["in_proj"].astype(dt), 3, axis=-1)
    z = b * u
    taps = p["kernel"].astype(dt)
    out = z * taps[0]
    for lag in range(1, cfg.conv_L_cache):
        # A tap that would reach before the sequence or into another
        # document is zero.
        same = jnp.pad(segments[lag:] == segments[:-lag], (lag, 0))
        before = jnp.pad(z[:-lag], ((lag, 0), (0, 0)))
        out = out + jnp.where(same[:, None], before, 0) * taps[lag]
    return (c * out) @ p["out_proj"].astype(dt)


def dense_attention(q, k, v, segments):
    """Causal same-document attention, scores held whole: q [S, H, hd]
    already scaled, k and v [S, KV, hd]. The plain form, for sizes at
    which ``[H, S, S]`` fits."""
    s, h, hd = q.shape
    group = h // k.shape[1]
    q = q.reshape(s, k.shape[1], group, hd)
    scores = jnp.einsum("sjgd,tjd->jgst", q, k,
                        preferred_element_type=jnp.float32)
    at = jnp.arange(s)
    seen = (at[:, None] >= at[None, :]) & (
        segments[:, None] == segments[None, :])
    probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
    out = jnp.einsum("jgst,tjd->sjgd", probs.astype(v.dtype), v)
    return out.reshape(s, h, hd)


# Rows and columns of a score tile of the TPU kernel.
ATTENTION_BLOCK = 1024


def kernel_attention(q, k, v, segments, interpret: bool = False):
    """The same attention through JAX's splash-attention kernel (TPU):
    no score matrix in HBM, tiles above the diagonal skipped, one
    key-value head shared by its group of query heads."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel,
        splash_attention_mask as masks,
    )

    s, h, hd = q.shape
    kv = k.shape[1]
    group = h // kv
    block = min(ATTENTION_BLOCK, s)
    sizes = kernel.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        use_fused_bwd_kernel=True)
    attend = kernel.make_splash_mqa_single_device(
        masks.MultiHeadMask([masks.CausalMask((s, s))] * group),
        block_sizes=sizes, interpret=interpret)
    ids = kernel.SegmentIds(q=segments, kv=segments)
    out = jax.vmap(lambda q_, k_, v_: attend(q_, k_, v_, segment_ids=ids))(
        q.reshape(s, kv, group, hd).transpose(1, 2, 0, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2))
    return out.transpose(2, 0, 1, 3).reshape(s, h, hd)


def attention_operator(p, a, segments, positions, cfg: Lfm2MoeConfig):
    dt, s, hd = a.dtype, a.shape[0], cfg.head_dim
    with jax.named_scope("df2.seq.attn_proj"):
        q = (a @ p["q"].astype(dt)).reshape(s, cfg.num_attention_heads, hd)
        k = (a @ p["k"].astype(dt)).reshape(s, cfg.num_key_value_heads, hd)
        v = (a @ p["v"].astype(dt)).reshape(s, cfg.num_key_value_heads, hd)
        q = rope(rms_norm(q, p["q_norm"], cfg.norm_eps), positions,
                 cfg.rope_theta)
        k = rope(rms_norm(k, p["k_norm"], cfg.norm_eps), positions,
                 cfg.rope_theta)
        q = (q.astype(jnp.float32) / math.sqrt(hd)).astype(dt)
    with jax.named_scope("df2.seq.attn"):
        # The kernel needs whole 128-wide tiles; below that, and off the
        # TPU, the plain form.
        if jax.devices()[0].platform == "tpu" and s % 128 == 0:
            out = kernel_attention(q, k, v, segments)
        else:
            out = dense_attention(q, k, v, segments)
    with jax.named_scope("df2.seq.attn_proj"):
        return out.reshape(s, -1) @ p["o"].astype(dt)


def gated_ffn(p, a):
    dt = a.dtype
    return (jax.nn.silu(a @ p["w1"].astype(dt)) * (a @ p["w3"].astype(dt))
            ) @ p["w2"].astype(dt)


def block(p, x, router_bias, segments, positions, *, cfg: Lfm2MoeConfig,
          layer: int):
    """One published layer on one sequence. Returns the new ``x`` and the
    expert layer's assignment counts (zeros for a dense layer)."""
    a = rms_norm(x, p["op_norm"], cfg.norm_eps)
    if cfg.layer_types[layer] == "conv":
        with jax.named_scope("df2.seq.conv"):
            h = x + conv_operator(p["conv"], a, segments, cfg)
    else:
        h = x + attention_operator(p["attn"], a, segments, positions, cfg)
    a = rms_norm(h, p["ff_norm"], cfg.norm_eps)
    if layer < cfg.num_dense_layers:
        with jax.named_scope("df2.seq.dense_ff"):
            return h + gated_ffn(p["ff"], a), jnp.zeros(
                cfg.num_experts, jnp.int32)
    m = p["moe"]
    out, assigned = expert_layer(
        a, m["router"], router_bias, m["w1"], m["w3"], m["w2"],
        cfg.held_experts, top_k=cfg.num_experts_per_tok,
        norm_topk_prob=cfg.norm_topk_prob,
        scaling_factor=cfg.routed_scaling_factor)
    return h + out.astype(h.dtype), assigned


def head_loss(embed, final_norm, x, local, segments, *, cfg: Lfm2MoeConfig):
    """The summed cross-entropy of one sequence's next tokens, over the
    positions whose next token is in the same document. ``local``: token
    ids as rows of ``embed``."""
    dt = x.dtype
    x = rms_norm(x, final_norm, cfg.norm_eps)
    logits = jnp.matmul(x, embed.astype(dt).T,
                        preferred_element_type=jnp.float32)
    target = jnp.roll(local, -1)
    hit = jnp.arange(embed.shape[0])[None, :] == target[:, None]
    nll = jax.nn.logsumexp(logits, -1) - jnp.where(hit, logits, 0).sum(-1)
    return jnp.where(target_positions(segments), nll, 0).sum()


def target_positions(segments):
    """Where a position's next token is in the same document (last
    axis: the sequence)."""
    same = jnp.roll(segments, -1, axis=-1) == segments
    return same & (jnp.arange(segments.shape[-1]) < segments.shape[-1] - 1)


def sequence_loss(params, router_bias, tokens, segments, positions, *,
                  cfg: Lfm2MoeConfig):
    """One packed sequence ``[S]``: the summed cross-entropy over
    :func:`target_positions` and each expert layer's assignment counts
    ``[expert layers, E]``. ``router_bias``: ``[expert layers, E]``.
    Each block, and the head with the loss, keeps its input alone for
    the backward pass and is computed again there."""
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
            params["embed"], params["final_norm"], x, local, segments)
    return loss, (jnp.stack(counts) if counts else jnp.zeros(
        (0, cfg.num_experts), jnp.int32))


def batch_loss(params, router_bias, tokens, segments, positions, *,
               cfg: Lfm2MoeConfig):
    """:func:`sequence_loss` over a batch ``[B, S]``, one sequence at a
    time (a sequence is the unit of memory: the batch costs residuals of
    ``B`` block inputs a layer and no more). Returns the two sums."""
    def one(args):
        return sequence_loss(params, router_bias, *args, cfg=cfg)
    loss, counts = jax.lax.map(one, (tokens, segments, positions))
    return loss.sum(), counts.sum(0)
