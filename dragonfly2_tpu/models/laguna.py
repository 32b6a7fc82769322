"""The ``laguna`` sequence-model family: attention in every layer, full
and sliding-window layers mixed in a published pattern with a head
count, a mask and a RoPE of their own, an output gate, a dense gated FFN
in the leading layer and a shared expert beside a top-k-of-E expert FFN
(``parallel/moe.py``) in the rest, configured by the keys of the public
``config.json`` (huggingface.co/poolside/Laguna-XS.2, ``model_type``
``laguna``).

With ``n(x; w) = x / sqrt(mean(x²) + rms_norm_eps) · w`` and ``x`` one
packed sequence ``[S, hidden]``, block ``l`` is ``h = x + Attn_l(n(x;
w_in))``, ``y = h + FF_l(n(h; w_post))``; no biases anywhere.

- ``Attn_l``: q as ``num_attention_heads_per_layer[l]`` heads, k and v
  as ``num_key_value_heads`` heads of ``head_dim``; rotate-half RoPE on
  q and k by the layer type's ``rope_parameters`` (the first
  ``partial_rotary_factor`` of each head's lanes rotated; ``rope_type``
  ``yarn``: each frequency the blend of ``f`` and ``f / factor`` by the
  linear ramp between the two correction dimensions, cos and sin times
  ``attention_factor``); scores ``q·k / sqrt(head_dim)``, softmax in
  float32 over ``s <= t`` in the same document and, in a
  ``sliding_attention`` layer, ``t - s < sliding_window``; query heads
  ``g·j .. g·j + g - 1`` share key-value head ``j``; ``Attn_l =
  (sigmoid(a W_g) · out) W_o`` (the output gate, from the layer's normed
  input).
- ``FF_l``: ``W_2 (silu(W_1 a) · W_3 a)`` where ``mlp_layer_types[l]``
  is ``dense``; where ``sparse``, a shared expert of that form plus the
  token's ``num_experts_per_tok`` selected experts, weighted ``w =
  moe_routed_scaling_factor · s / Σ selected s`` with ``s`` the sigmoid
  scores (``parallel/moe.py``; no selection bias).
- head: a final norm and logits in float32 against ``lm_head``
  (untied); the loss is the cross-entropy of the next token.

What a device holds: all of every attention, gate, router, shared
expert and dense FFN, the experts ``experts_held = (first, count)`` of
each sparse layer, and the rows ``vocab_held = (first, count)`` of the
embedding and of the output head. What the family shares with the other
sequence families is ``models/seq_layers.py``'s.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from dragonfly2_tpu.models.seq_layers import (
    HeldShare,
    attention,
    gated_ffn,
    rms_norm,
    rope,
)
from dragonfly2_tpu.parallel.moe import expert_layer

SLIDING = "sliding_attention"


@dataclass(frozen=True)
class Rope:
    """One layer type's ``rope_parameters``."""

    rope_theta: float
    rope_type: str = "default"
    partial_rotary_factor: float = 1.0
    # ``yarn`` only.
    factor: float = 1.0
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float | None = None

    def frequencies(self, head_dim: int) -> np.ndarray:
        """The rotated lanes' frequencies (half as many as lanes),
        float32, as the public ``default`` and ``yarn`` rules compute
        them."""
        lanes = int(head_dim * self.partial_rotary_factor)
        plain = self.rope_theta ** -(np.arange(0, lanes, 2) / lanes)
        if self.rope_type == "default":
            return plain.astype(np.float32)
        if self.rope_type != "yarn":
            raise ValueError(f"rope_type {self.rope_type!r}")

        def correction_dim(rotations):
            return lanes * math.log(self.original_max_position_embeddings / (
                rotations * 2 * math.pi)) / (2 * math.log(self.rope_theta))

        low = max(math.floor(correction_dim(self.beta_fast)), 0)
        high = min(math.ceil(correction_dim(self.beta_slow)), lanes - 1)
        ramp = np.clip((np.arange(lanes // 2) - low) / max(high - low, 1e-3),
                       0.0, 1.0)
        # ramp 0: the frequency as it is; 1: divided by ``factor``.
        return (plain * (1 - ramp) + plain / self.factor * ramp).astype(
            np.float32)

    @property
    def scale(self) -> float | None:
        """What multiplies cos and sin."""
        if self.rope_type != "yarn":
            return None
        return (self.attention_factor if self.attention_factor is not None
                else 0.1 * math.log(self.factor) + 1.0)


@dataclass(frozen=True)
class LagunaConfig(HeldShare):
    """The published keys, plus which published layers run and what of a
    layer is held here."""

    model_type = "laguna"
    use_expert_bias = False          # no selection bias in this family

    layer_types: tuple
    mlp_layer_types: tuple
    num_attention_heads_per_layer: tuple
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    num_key_value_heads: int
    head_dim: int
    vocab_size: int
    sliding_window: int
    # ``rope_parameters`` by layer type, as pairs (hashable).
    rope: tuple
    norm_eps: float = 1e-6
    moe_routed_scaling_factor: float = 1.0
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
        for key, has in (("attention_bias", False), ("gating", True),
                         ("tie_word_embeddings", False),
                         ("moe_apply_router_weight_on_input", False)):
            if config.get(key, has) != has:
                raise ValueError(f"{key}={config[key]!r} is not supported: "
                                 "the published laguna models have "
                                 f"{key}={has!r}")
        keys = ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "shared_expert_intermediate_size", "num_experts_per_tok",
                "num_key_value_heads", "head_dim", "sliding_window",
                "moe_routed_scaling_factor")
        fields = Rope.__dataclass_fields__
        ropes = tuple(
            (kind, Rope(**{k: v for k, v in given.items() if k in fields}))
            for kind, given in config["rope_parameters"].items()
            if isinstance(given, dict))
        return cls(
            layer_types=tuple(config["layer_types"]),
            mlp_layer_types=tuple(config["mlp_layer_types"]),
            num_attention_heads_per_layer=tuple(
                config["num_attention_heads_per_layer"]),
            num_experts=num_experts or config["num_experts"],
            vocab_size=vocab_size or config["vocab_size"],
            norm_eps=config["rms_norm_eps"], rope=ropes,
            **{k: config[k] for k in keys}, **held)

    @property
    def expert_layers(self) -> tuple:
        return tuple(i for i in self.kept_layers
                     if self.mlp_layer_types[i] == "sparse")

    @property
    def attention_window(self) -> int:
        """The window of the sliding layers that run (0: none runs)."""
        return self.sliding_window if any(
            self.layer_types[i] == SLIDING for i in self.kept_layers) else 0


def param_shapes(cfg: LagunaConfig) -> list:
    """``[(path, shape, "normal" | "ones")]`` in the order the parameters
    are drawn."""
    d, hd = cfg.hidden_size, cfg.head_dim
    kv = cfg.num_key_value_heads * hd
    rows = cfg.held_vocab[1]
    out = [(("embed",), (rows, d), "normal")]
    for i in cfg.kept_layers:
        at, q = (f"layer_{i}",), cfg.num_attention_heads_per_layer[i] * hd
        if cfg.layer_types[i] not in ("full_attention", SLIDING):
            raise ValueError(f"layer type {cfg.layer_types[i]!r}")
        out += [(at + ("in_norm",), (d,), "ones"),
                (at + ("attn", "q"), (d, q), "normal"),
                (at + ("attn", "k"), (d, kv), "normal"),
                (at + ("attn", "v"), (d, kv), "normal"),
                (at + ("attn", "o"), (q, d), "normal"),
                (at + ("attn", "gate"), (d, q), "normal"),
                (at + ("post_norm",), (d,), "ones")]
        if i in cfg.expert_layers:
            e, f = cfg.held_experts[1], cfg.moe_intermediate_size
            s = cfg.shared_expert_intermediate_size
            out += [(at + ("moe", "router"), (d, cfg.num_experts), "normal"),
                    (at + ("moe", "w1"), (e, d, f), "normal"),
                    (at + ("moe", "w3"), (e, d, f), "normal"),
                    (at + ("moe", "w2"), (e, f, d), "normal"),
                    (at + ("shared", "w1"), (d, s), "normal"),
                    (at + ("shared", "w3"), (d, s), "normal"),
                    (at + ("shared", "w2"), (s, d), "normal")]
        else:
            f = cfg.intermediate_size
            out += [(at + ("ff", "w1"), (d, f), "normal"),
                    (at + ("ff", "w3"), (d, f), "normal"),
                    (at + ("ff", "w2"), (f, d), "normal")]
    out += [(("final_norm",), (d,), "ones"),
            (("lm_head",), (rows, d), "normal")]
    return out


def attention_operator(p, a, segments, positions, cfg: LagunaConfig,
                       layer: int):
    dt, s, hd = a.dtype, a.shape[0], cfg.head_dim
    kind = cfg.layer_types[layer]
    turn = dict(cfg.rope)[kind]
    with jax.named_scope("df2.seq.attn_proj"):
        q = (a @ p["q"].astype(dt)).reshape(s, -1, hd)
        k = (a @ p["k"].astype(dt)).reshape(s, cfg.num_key_value_heads, hd)
        v = (a @ p["v"].astype(dt)).reshape(s, cfg.num_key_value_heads, hd)
        gate = jax.nn.sigmoid(
            (a @ p["gate"].astype(dt)).astype(jnp.float32)).astype(dt)
        inv_freq = jnp.asarray(turn.frequencies(hd))
        q = rope(q, positions, inv_freq, turn.scale)
        k = rope(k, positions, inv_freq, turn.scale)
        q = (q.astype(jnp.float32) / math.sqrt(hd)).astype(dt)
    out = attention(q, k, v, segments,
                    cfg.sliding_window if kind == SLIDING else None)
    with jax.named_scope("df2.seq.attn_proj"):
        return (gate * out.reshape(s, -1)) @ p["o"].astype(dt)


def feed_forward(p, a, router_bias, cfg: LagunaConfig, layer: int):
    """``FF_l`` of the normed ``a`` and the expert layer's assignment
    counts (zeros for a dense layer): the dense FFN, or the shared
    expert plus this device's part of the routed ones (in float32)."""
    if layer not in cfg.expert_layers:
        with jax.named_scope("df2.seq.dense_ff"):
            return gated_ffn(p["ff"], a), jnp.zeros(
                cfg.num_experts, jnp.int32)
    # Outside ``df2.moe.experts``: that scope is the routed products'.
    with jax.named_scope("df2.moe.shared"):
        shared = gated_ffn(p["shared"], a)
    m = p["moe"]
    routed, assigned = expert_layer(
        a, m["router"], router_bias, m["w1"], m["w3"], m["w2"],
        cfg.held_experts, top_k=cfg.num_experts_per_tok,
        scaling_factor=cfg.moe_routed_scaling_factor)
    return shared + routed, assigned


def block(p, x, router_bias, segments, positions, *, cfg: LagunaConfig,
          layer: int):
    """One published layer on one sequence. Returns the new ``x`` and the
    expert layer's assignment counts (zeros for a dense layer)."""
    a = rms_norm(x, p["in_norm"], cfg.norm_eps)
    h = x + attention_operator(p["attn"], a, segments, positions, cfg, layer)
    out, assigned = feed_forward(
        p, rms_norm(h, p["post_norm"], cfg.norm_eps), router_bias, cfg, layer)
    return h + out.astype(h.dtype), assigned
