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
What the family shares with the other sequence families (norm, RoPE,
attention, gated FFN, head, loss) is ``models/seq_layers.py``'s.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from dragonfly2_tpu.models.seq_layers import (
    HeldShare,
    attention,
    gated_ffn,
    rms_norm,
    rope,
    rope_frequencies,
)
from dragonfly2_tpu.parallel.moe import expert_layer


@dataclass(frozen=True)
class Lfm2MoeConfig(HeldShare):
    """The published keys, plus which published layers run and what of a
    layer is held here."""

    model_type = "lfm2_moe"
    attention_window = 0             # every attention layer sees all

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
    def expert_layers(self) -> tuple:
        return tuple(i for i in self.kept_layers
                     if i >= self.num_dense_layers)

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


def attention_operator(p, a, segments, positions, cfg: Lfm2MoeConfig):
    dt, s, hd = a.dtype, a.shape[0], cfg.head_dim
    with jax.named_scope("df2.seq.attn_proj"):
        q = (a @ p["q"].astype(dt)).reshape(s, cfg.num_attention_heads, hd)
        k = (a @ p["k"].astype(dt)).reshape(s, cfg.num_key_value_heads, hd)
        v = (a @ p["v"].astype(dt)).reshape(s, cfg.num_key_value_heads, hd)
        q = rope(rms_norm(q, p["q_norm"], cfg.norm_eps), positions,
                 rope_frequencies(cfg.rope_theta, hd))
        k = rope(rms_norm(k, p["k_norm"], cfg.norm_eps), positions,
                 rope_frequencies(cfg.rope_theta, hd))
        q = (q.astype(jnp.float32) / math.sqrt(hd)).astype(dt)
    out = attention(q, k, v, segments)
    with jax.named_scope("df2.seq.attn_proj"):
        return out.reshape(s, -1) @ p["o"].astype(dt)


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
