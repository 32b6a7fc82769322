"""The ``KeyeVL2`` sequence-model family's language model: grouped-query
attention over a learned selection of keys in every layer (an indexer
scores every earlier token of a query's document, the ``topk`` best are
kept, the softmax runs over those alone), QK-norm, RoPE whose frequency
pairs are dealt to three position streams, and a top-k-of-E expert FFN
under a softmax router (``parallel/moe.py``), configured by the keys of
the public ``config.json`` (huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B,
``model_type`` ``KeyeVL2``). The vision tower is not here: the published
keys this family reads are the language model's.

With ``n(x; w) = x / sqrt(mean(x²) + rms_norm_eps) · w`` and ``x`` one
packed sequence ``[S, hidden]``, block ``l`` is ``h = x + Attn(n(x;
w_in))``, ``y = h + MoE(n(h; w_post))``; no biases but the indexer's
layer norm's.

- ``Attn``: from ``a = n(x; w_in)``, q as ``num_attention_heads`` heads
  and k, v as ``num_key_value_heads`` heads of ``head_dim``; q and k
  normed per head (an RMS norm with a weight of ``head_dim``), then
  rotate-half RoPE over all lanes at ``rope_theta``, frequency pair
  ``i`` turned by the position stream of its chunk of
  ``rope_scaling.mrope_section`` (for text the three streams are equal).
  The indexer (``sa_config``): ``qI = a W_qI`` as ``indexer_num_heads``
  heads of ``indexer_head_dim``, ``kI = LN(a W_kI)`` one shared head (a
  layer norm with weight and bias), both under one-stream RoPE from
  stream 0, ``w = a W_w / sqrt(heads · head_dim)``; the index score
  ``I[t, s] = Σ_j w[t, j] · relu(qI[t, j] · kI[s])`` in float32 over
  ``s <= t`` of ``t``'s document; ``S_t`` the ``min(c_t, topk)``
  candidates of the largest score (ties to the lower position). Then,
  for each head, ``softmax over s in S_t of q_t · k_s / sqrt(head_dim)``
  in float32 and the weighted sum of ``v_s``, query heads ``g·j .. g·j +
  g - 1`` on key-value head ``j``, one ``S_t`` for all; ``Attn = out
  W_o``. The selection is piecewise constant: the indexer's leaves get a
  gradient of exactly zero from the next-token loss.
- ``MoE``: ``p = softmax(a' W_r)`` over all experts in float32, the
  ``num_experts_per_tok`` largest selected, their ``p`` divided by their
  sum (``norm_topk_prob``); each expert ``W_2 (silu(W_1 a') · W_3 a')``;
  no shared expert, no selection bias.
- head: a final norm and logits in float32 against ``lm_head``
  (untied); the loss is the cross-entropy of the next token.

What a device holds: all of every attention, indexer and router, the
experts ``experts_held = (first, count)`` of each layer, and the rows
``vocab_held = (first, count)`` of the embedding and of the output head.
What the family shares with the other sequence families is
``models/seq_layers.py``'s.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from dragonfly2_tpu.models.seq_layers import (
    INIT_STD,
    SELECTION,
    HeldShare,
    count_limbs,
    rms_norm,
    rope,
    rope_frequencies,
    select_keys,
    selected_attention,
)
from dragonfly2_tpu.parallel.moe import expert_layer

# Tokens the expert layer takes at a time. Its row buffers' worst case is
# every assignment held here, which the backward pass of its
# ``lax.switch`` holds zero-filled whether taken or not: at 32,768
# tokens and top-8 that is 262,144 rows a buffer (1 GB each at 2,048
# wide, 6 GB in all), at 8,192 tokens what the other sequence families'
# steps hold. A part keeps its input alone and is routed again in the
# backward pass.
MOE_TOKENS = 8192
# What a block keeps for the backward pass beside its input
# (``seq_layers.head_inputs``): the selection, one bit a pair, in
# place of being scored and ranked again.
SAVED = (SELECTION,)


@dataclass(frozen=True)
class KeyeVL2Config(HeldShare):
    """The published keys, plus which published layers run and what of a
    layer is held here."""

    model_type = "KeyeVL2"
    use_expert_bias = False          # no selection bias in this family

    hidden_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    num_experts: int
    num_experts_per_tok: int
    vocab_size: int
    rope_theta: float
    mrope_section: tuple
    indexer_num_heads: int
    indexer_head_dim: int
    sparse_topk: int
    norm_eps: float = 1e-6
    norm_topk_prob: bool = True
    # The embedding's rows are drawn at this deviation, every other
    # matrix at ``INIT_STD`` (``emb_init_std`` in a config file; no
    # published key). At ``INIT_STD`` the first attention's output, a
    # mean over a document's values, is several times a token's
    # embedding, and every router of the untrained model sees all
    # tokens alike; at 1.0 it sees the token (PERF.md, PR 33).
    emb_init_std: float = INIT_STD
    layers: tuple | None = None
    experts_held: tuple | None = None
    vocab_held: tuple | None = None
    compute_dtype: str = "bfloat16"

    @classmethod
    def from_published(cls, config: dict, *, num_experts: int | None = None,
                       vocab_size: int | None = None,
                       num_hidden_layers: int | None = None, **held):
        """From a ``config.json``'s keys. ``num_experts``, ``vocab_size``
        and ``num_hidden_layers`` override the file's where the file
        states what is held and not what is published."""
        scaling, sparse = config["rope_scaling"], config["sa_config"]
        for name, given, has in (
                ("attention_bias", config.get("attention_bias", False), False),
                ("tie_word_embeddings",
                 config.get("tie_word_embeddings", False), False),
                ("use_sliding_window",
                 config.get("use_sliding_window", False), False),
                ("mlp_only_layers", list(config.get("mlp_only_layers", [])),
                 []),
                ("decoder_sparse_step", config.get("decoder_sparse_step", 1),
                 1),
                ("hidden_act", config.get("hidden_act", "silu"), "silu"),
                ("rope_scaling.rope_type",
                 scaling.get("rope_type", "default"), "default"),
                ("sa_config.indexer_num_kv_heads",
                 sparse.get("indexer_num_kv_heads", 1), 1)):
            if given != has:
                raise ValueError(f"{name}={given!r} is not supported: the "
                                 f"published KeyeVL2 model has {name}={has!r}")
        keys = ("hidden_size", "moe_intermediate_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "num_experts_per_tok",
                "rope_theta")
        return cls(
            num_hidden_layers=num_hidden_layers or config["num_hidden_layers"],
            num_experts=num_experts or config["num_experts"],
            vocab_size=vocab_size or config["vocab_size"],
            mrope_section=tuple(scaling["mrope_section"]),
            indexer_num_heads=sparse["indexer_num_heads"],
            indexer_head_dim=sparse["indexer_head_dim"],
            sparse_topk=sparse["topk"],
            norm_eps=config["rms_norm_eps"],
            norm_topk_prob=config.get("norm_topk_prob", True),
            emb_init_std=config.get("emb_init_std", INIT_STD),
            **{k: config[k] for k in keys}, **held)

    @property
    def layer_types(self) -> tuple:
        return ("sparse_attention",) * self.num_hidden_layers

    @property
    def expert_layers(self) -> tuple:
        return self.kept_layers          # every layer's FFN is the expert one

    @property
    def attention_window(self) -> int:
        return 0                         # no sliding layer


def param_shapes(cfg: KeyeVL2Config) -> list:
    """``[(path, shape, "normal" | "ones" | "zeros" | std)]`` in the
    order the parameters are drawn."""
    d, hd = cfg.hidden_size, cfg.head_dim
    q, kv = cfg.num_attention_heads * hd, cfg.num_key_value_heads * hd
    ih, ihd = cfg.indexer_num_heads, cfg.indexer_head_dim
    e, f = cfg.held_experts[1], cfg.moe_intermediate_size
    rows = cfg.held_vocab[1]
    if 2 * sum(cfg.mrope_section) != hd:
        raise ValueError(f"mrope_section {cfg.mrope_section} over a head of "
                         f"{hd}")
    out = [(("embed",), (rows, d), cfg.emb_init_std)]
    for i in cfg.kept_layers:
        at = (f"layer_{i}",)
        out += [(at + ("in_norm",), (d,), "ones"),
                (at + ("attn", "q"), (d, q), "normal"),
                (at + ("attn", "k"), (d, kv), "normal"),
                (at + ("attn", "v"), (d, kv), "normal"),
                (at + ("attn", "o"), (q, d), "normal"),
                (at + ("attn", "q_norm"), (hd,), "ones"),
                (at + ("attn", "k_norm"), (hd,), "ones"),
                (at + ("indexer", "q"), (d, ih * ihd), "normal"),
                (at + ("indexer", "k"), (d, ihd), "normal"),
                (at + ("indexer", "w"), (d, ih), "normal"),
                (at + ("indexer", "k_norm"), (ihd,), "ones"),
                (at + ("indexer", "k_norm_bias"), (ihd,), "zeros"),
                (at + ("post_norm",), (d,), "ones"),
                (at + ("moe", "router"), (d, cfg.num_experts), "normal"),
                (at + ("moe", "w1"), (e, d, f), "normal"),
                (at + ("moe", "w3"), (e, d, f), "normal"),
                (at + ("moe", "w2"), (e, f, d), "normal")]
    out += [(("final_norm",), (d,), "ones"),
            (("lm_head",), (rows, d), "normal")]
    return out


def layer_norm(x, weight, bias, eps: float):
    x32 = x.astype(jnp.float32)
    centred = x32 - x32.mean(-1, keepdims=True)
    scale = jax.lax.rsqrt(jnp.mean(centred * centred, -1, keepdims=True) + eps)
    return (centred * scale * weight + bias).astype(x.dtype)


def indexer(p, a, positions, cfg: KeyeVL2Config):
    """The indexer's queries ``[S, heads, d]``, its shared keys ``[S, d]``
    and the head weights ``[S, heads]`` (float32) of the normed ``a``."""
    dt, s = a.dtype, a.shape[0]
    heads, hd = cfg.indexer_num_heads, cfg.indexer_head_dim
    inv_freq = rope_frequencies(cfg.rope_theta, hd)
    q = rope((a @ p["q"].astype(dt)).reshape(s, heads, hd), positions,
             inv_freq)
    k = layer_norm(a @ p["k"].astype(dt), p["k_norm"], p["k_norm_bias"],
                   cfg.norm_eps)
    k = rope(k[:, None, :], positions, inv_freq)[:, 0]
    w = (a @ p["w"].astype(dt)).astype(jnp.float32) / math.sqrt(heads * hd)
    return q, k, w


def attention_operator(p, a, segments, positions, cfg: KeyeVL2Config):
    """``Attn`` of the normed ``a`` at ``positions`` ``[3, S]``, and the
    selection's candidates, members and the attention tiles that hold a
    member as limbs (``seq_layers.count_limbs``, ``[3, 3]``)."""
    dt, s, hd = a.dtype, a.shape[0], cfg.head_dim
    kvh = cfg.num_key_value_heads
    with jax.named_scope("df2.seq.attn_proj"):
        q = (a @ p["attn"]["q"].astype(dt)).reshape(s, -1, hd)
        k = (a @ p["attn"]["k"].astype(dt)).reshape(s, kvh, hd)
        v = (a @ p["attn"]["v"].astype(dt)).reshape(s, kvh, hd)
        inv_freq = rope_frequencies(cfg.rope_theta, hd)
        q = rope(rms_norm(q, p["attn"]["q_norm"], cfg.norm_eps), positions,
                 inv_freq, sections=cfg.mrope_section)
        k = rope(rms_norm(k, p["attn"]["k_norm"], cfg.norm_eps), positions,
                 inv_freq, sections=cfg.mrope_section)
        q = (q.astype(jnp.float32) / math.sqrt(hd)).astype(dt)
        scored = indexer(p["indexer"], a, positions[0], cfg)
    # The scopes of the scores and of the ranking are the selection's own.
    packed, candidates, members = select_keys(*scored, segments,
                                              cfg.sparse_topk)
    out, held = selected_attention(q, k, v,
                                   checkpoint_name(packed, SELECTION))
    counted = jnp.stack([count_limbs(candidates).sum(0),
                         count_limbs(members).sum(0), count_limbs(held)])
    with jax.named_scope("df2.seq.attn_proj"):
        return out.reshape(s, -1) @ p["attn"]["o"].astype(dt), counted


def expert_ffn(m, a, router_bias, cfg: KeyeVL2Config):
    """``MoE`` of the normed ``a`` (this device's part, float32) and the
    assignment counts ``[E]``, :data:`MOE_TOKENS` tokens at a time."""
    def some(rows):
        return expert_layer(
            rows, m["router"], router_bias, m["w1"], m["w3"], m["w2"],
            cfg.held_experts, top_k=cfg.num_experts_per_tok,
            norm_topk_prob=cfg.norm_topk_prob, scoring="softmax")

    tokens, d = a.shape
    if tokens <= MOE_TOKENS:
        return some(a)
    if tokens % MOE_TOKENS:
        raise ValueError(f"{tokens} tokens are neither one part of the "
                         f"expert layer nor whole parts of {MOE_TOKENS}")
    routed, assigned = jax.lax.map(jax.checkpoint(some),
                                   a.reshape(-1, MOE_TOKENS, d))
    return routed.reshape(tokens, d), assigned.sum(0)


def block(p, x, router_bias, segments, positions, *, cfg: KeyeVL2Config,
          layer: int):
    """One published layer on one sequence; ``positions`` ``[S]`` (text:
    the three streams are equal) or ``[3, S]``. Returns the new ``x``
    and what the layer counted: the expert layer's assignment counts
    ``[E]`` and the selection's candidates, members and held tiles
    ``[3, 3]``."""
    del layer                            # every layer is the same
    if positions.ndim == 1:
        positions = jnp.broadcast_to(positions, (3,) + positions.shape)
    a = rms_norm(x, p["in_norm"], cfg.norm_eps)
    attended, selected = attention_operator(p, a, segments, positions, cfg)
    h = x + attended
    routed, assigned = expert_ffn(
        p["moe"], rms_norm(h, p["post_norm"], cfg.norm_eps), router_bias, cfg)
    return h + routed.astype(h.dtype), (assigned, selected)
