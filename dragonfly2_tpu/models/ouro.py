"""The ``ouro`` sequence-model family: a looped dense decoder (the Ouro
LoopLM, arXiv:2510.25741), whose whole stack of layers runs
``total_ut_steps`` times with one set of weights and is read out after
every pass, configured by the keys of the public ``config.json``
(huggingface.co/ByteDance/Ouro-2.6B, ``model_type`` ``ouro``).

With ``n(x; w) = x / sqrt(mean(x²) + rms_norm_eps) · w`` and ``x`` one
packed sequence ``[S, hidden]``, block ``l`` is the pre-and-post
("sandwich") norm form, four norms a layer, no biases:

- ``h = x + n(Attn(n(x; w_in)); w_attn_out)``, ``Attn``: q, k and v as
  ``num_attention_heads`` / ``num_key_value_heads`` heads of
  ``head_dim``; rotate-half RoPE over all lanes of q and k at
  ``rope_theta``; scores ``q·k / sqrt(head_dim)``, softmax in float32
  over ``s <= t`` in the same document; ``out W_o``.
- ``y = h + n(W_2 (silu(W_1 a) · W_3 a); w_ff_out)``, ``a = n(h; w_ff)``.

The loop (``seq_layers.head_inputs``): for ``t = 1 .. T`` the kept
layers run in order, then ``h_t = n(x; w_final)``, which is the next
pass's input; the exit gate ``λ_t = σ(h_t · w_g + b_g)``
(:func:`exit_gate`) and the logits ``h_t W_head^T`` (untied, no second
norm). The loss is the expected cross-entropy over the exit step minus
``exit_entropy`` times the exit distribution's entropy
(``seq_layers.exit_mixture``). ``early_exit_threshold`` is a serving
setting (1: never exit early) and changes nothing here.

What a device holds: every layer it runs whole, and the rows
``vocab_held = (first, count)`` of the embedding and of the output
head. What the family shares with the other sequence families is
``models/seq_layers.py``'s.
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


@dataclass(frozen=True)
class OuroConfig(HeldShare):
    """The published keys, plus which published layers run, what of the
    vocabulary is held here, and the objective's entropy weight."""

    model_type = "ouro"
    use_expert_bias = False          # no expert layer
    num_experts = 0
    experts_held = None

    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    vocab_size: int
    rope_theta: float
    total_ut_steps: int
    early_exit_threshold: float = 1.0
    # β of the objective's entropy bonus (no published key; the Ouro
    # report's first stage).
    exit_entropy: float = 0.1
    norm_eps: float = 1e-6
    layers: tuple | None = None
    vocab_held: tuple | None = None
    compute_dtype: str = "bfloat16"

    @classmethod
    def from_published(cls, config: dict, *, vocab_size: int | None = None,
                       num_hidden_layers: int | None = None, **held):
        """From a ``config.json``'s keys (and ``exit_entropy`` where the
        file gives one). ``vocab_size`` and ``num_hidden_layers``
        override the file's where the file states what is held and not
        what is published."""
        layer_types = set(config.get("layer_types", ["full_attention"]))
        for name, given, has in (
                ("attention_bias", config.get("attention_bias", False), False),
                ("tie_word_embeddings",
                 config.get("tie_word_embeddings", False), False),
                ("use_sliding_window",
                 config.get("use_sliding_window", False), False),
                ("hidden_act", config.get("hidden_act", "silu"), "silu"),
                ("rope_scaling", config.get("rope_scaling"), None),
                ("layer_types", layer_types, {"full_attention"})):
            if given != has:
                raise ValueError(f"{name}={given!r} is not supported: the "
                                 f"published ouro models have {name}={has!r}")
        keys = ("hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "rope_theta",
                "total_ut_steps")
        return cls(
            num_hidden_layers=num_hidden_layers or config["num_hidden_layers"],
            vocab_size=vocab_size or config["vocab_size"],
            early_exit_threshold=config.get("early_exit_threshold", 1.0),
            exit_entropy=config.get("exit_entropy", 0.1),
            norm_eps=config["rms_norm_eps"],
            **{k: config[k] for k in keys}, **held)

    @property
    def layer_types(self) -> tuple:
        return ("full_attention",) * self.num_hidden_layers

    @property
    def expert_layers(self) -> tuple:
        return ()

    @property
    def attention_window(self) -> int:
        return 0                         # no sliding layer


def param_shapes(cfg: OuroConfig) -> list:
    """``[(path, shape, "normal" | "ones" | "zeros")]`` in the order the
    parameters are drawn."""
    d, hd = cfg.hidden_size, cfg.head_dim
    q, kv = cfg.num_attention_heads * hd, cfg.num_key_value_heads * hd
    f, rows = cfg.intermediate_size, cfg.held_vocab[1]
    out = [(("embed",), (rows, d), "normal")]
    for i in cfg.kept_layers:
        at = (f"layer_{i}",)
        out += [(at + ("in_norm",), (d,), "ones"),
                (at + ("attn", "q"), (d, q), "normal"),
                (at + ("attn", "k"), (d, kv), "normal"),
                (at + ("attn", "v"), (d, kv), "normal"),
                (at + ("attn", "o"), (q, d), "normal"),
                (at + ("attn_out_norm",), (d,), "ones"),
                (at + ("ff_norm",), (d,), "ones"),
                (at + ("ff", "w1"), (d, f), "normal"),
                (at + ("ff", "w3"), (d, f), "normal"),
                (at + ("ff", "w2"), (f, d), "normal"),
                (at + ("ff_out_norm",), (d,), "ones")]
    out += [(("final_norm",), (d,), "ones"),
            (("exit_gate", "w"), (d,), "normal"),
            (("exit_gate", "b"), (), "zeros"),
            (("lm_head",), (rows, d), "normal")]
    return out


def attention_operator(p, a, segments, positions, cfg: OuroConfig):
    dt, s, hd = a.dtype, a.shape[0], cfg.head_dim
    with jax.named_scope("df2.seq.attn_proj"):
        q = (a @ p["q"].astype(dt)).reshape(s, cfg.num_attention_heads, hd)
        k = (a @ p["k"].astype(dt)).reshape(s, cfg.num_key_value_heads, hd)
        v = (a @ p["v"].astype(dt)).reshape(s, cfg.num_key_value_heads, hd)
        inv_freq = rope_frequencies(cfg.rope_theta, hd)
        q = rope(q, positions, inv_freq)
        k = rope(k, positions, inv_freq)
        q = (q.astype(jnp.float32) / math.sqrt(hd)).astype(dt)
    out = attention(q, k, v, segments)
    with jax.named_scope("df2.seq.attn_proj"):
        return out.reshape(s, -1) @ p["o"].astype(dt)


def block(p, x, router_bias, segments, positions, *, cfg: OuroConfig,
          layer: int):
    """One published layer on one sequence, in the sandwich form. Returns
    the new ``x`` and an empty count (no expert layer)."""
    del router_bias, layer               # every layer is the same form
    eps = cfg.norm_eps
    attended = attention_operator(p["attn"], rms_norm(x, p["in_norm"], eps),
                                  segments, positions, cfg)
    h = x + rms_norm(attended, p["attn_out_norm"], eps)
    with jax.named_scope("df2.seq.dense_ff"):
        out = gated_ffn(p["ff"], rms_norm(h, p["ff_norm"], eps))
    return h + rms_norm(out, p["ff_out_norm"], eps), jnp.zeros(0, jnp.int32)


def exit_gate(p, h):
    """The exit gate's logit ``h · w_g + b_g`` of each position of the
    normed state ``h`` ``[S, hidden]``, in float32 (``λ = σ`` of it)."""
    return jnp.dot(h.astype(jnp.float32), p["w"],
                   precision=jax.lax.Precision.HIGHEST) + p["b"]
