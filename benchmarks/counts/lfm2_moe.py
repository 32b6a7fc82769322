"""Work one optimizer step of the ``lfm2_moe`` kind requires, from the
configuration's file alone (never from the HLO): the products the layer
equations name, at the expected number of assignments to the experts
held here, and attention over the same-document causal pairs of the
corpus's fixed length sequence. Recomputation is not counted, nor is
anything an implementation adds (worst-case buffers, one-hot products,
scores of pairs that the masks then drop)."""

from __future__ import annotations

import numpy as np

from benchmarks.runners.lfm2_moe import document_lengths

BF16 = 2


def shapes(spec: dict) -> dict:
    kept = spec["deployment"]["layers_kept"]
    types = [spec["layer_types"][i] for i in kept]
    dense = sum(i < spec["num_dense_layers"] for i in kept)
    return {"tokens": spec["batch"] * spec["seq_len"],
            "conv": types.count("conv"),
            "attention": types.count("full_attention"),
            "dense": dense, "expert": len(kept) - dense,
            # Expected assignments a token to the experts held here, a
            # layer: the selection bias is the same ramp on every chip
            # of the group, so a chip's share is held / published.
            "held_per_token": spec["num_experts_per_tok"]
            * spec["deployment"]["experts_held"][1]
            / spec["published"]["num_experts"]}


def forward_flops_per_token(spec: dict) -> dict:
    """The forward products of one token, by part."""
    s, d = shapes(spec), spec["hidden_size"]
    kv = (d // spec["num_attention_heads"]) * spec["num_key_value_heads"]
    return {
        "conv": s["conv"] * 2 * (d * 3 * d + d * d),
        "attention_projections": s["attention"] * 2 * (2 * d * d + 2 * d * kv),
        "dense_ff": s["dense"] * 2 * 3 * d * spec["intermediate_size"],
        "routers": s["expert"] * 2 * d * spec["published"]["num_experts"],
        "experts": s["expert"] * s["held_per_token"]
        * expert_forward_flops_per_assignment(spec),
        "head": 2 * d * spec["deployment"]["vocab_rows_held"][1],
    }


def expert_forward_flops_per_assignment(spec: dict) -> int:
    """One token through one expert: three products of hidden x expert
    width."""
    return 3 * 2 * spec["hidden_size"] * spec["moe_intermediate_size"]


def attention_pairs_per_step(spec: dict) -> float:
    """Same-document causal (query, key) pairs a step, expected over the
    seed's order: a document of L tokens has L(L+1)/2; each of the
    corpus's R - 1 row ends falls in a document with probability L /
    tokens and cuts it at a uniform place, which takes (L² - 1) / 6
    pairs away."""
    corpus = spec["corpus"]
    lengths = document_lengths(corpus).astype(np.float64)
    rows = corpus["tokens"] // spec["seq_len"]
    pairs = (lengths * (lengths + 1) / 2).sum() - (rows - 1) * (
        lengths * (lengths ** 2 - 1) / 6).sum() / corpus["tokens"]
    return pairs * shapes(spec)["tokens"] / corpus["tokens"]


def attention_forward_flops_per_step(spec: dict) -> float:
    """Scores and weighted sums of those pairs, all heads: 2 products of
    head size a pair and head."""
    s = shapes(spec)
    return (s["attention"] * attention_pairs_per_step(spec)
            * 2 * 2 * spec["hidden_size"])


def flops_per_step(spec: dict) -> float:
    """Forward and backward: a product is 2·m·n·k forward and twice that
    backward (its weight's and its input's gradient)."""
    per_token = sum(forward_flops_per_token(spec).values())
    return 3.0 * (per_token * shapes(spec)["tokens"]
                  + attention_forward_flops_per_step(spec))


def gather_bytes_per_step(spec: dict) -> float:
    """Bytes of the rows that must move by index, at the least: one
    embedding row (bfloat16) a token forward and its cotangent backward;
    for each expert layer, each held assignment's row into expert order
    and back into token order, forward and backward."""
    s = shapes(spec)
    row = spec["hidden_size"] * BF16
    embedding = 2 * s["tokens"] * row
    experts = s["expert"] * 4 * s["held_per_token"] * s["tokens"] * row
    return float(embedding + experts)
