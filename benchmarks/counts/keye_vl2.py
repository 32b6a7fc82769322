"""Work one optimizer step of the ``keye_vl2`` kind requires, from the
configuration's file alone (never from the HLO): the products the layer
equations name, at the expected number of assignments to the experts
held here; the indexer's scores over the same-document causal pairs of
the corpus's fixed documents; attention over the pairs the selection
keeps (a query with ``c`` candidates keeps ``min(c, topk)``).
Recomputation is not counted, nor is anything an implementation adds
(worst-case buffers, one-hot products, scores of pairs a tile computes
and the masks then drop). The indexer has no backward pass under the
next-token loss (the selection is piecewise constant), so its
projections and scores count once; everything else three times."""

from __future__ import annotations

import numpy as np

BF16 = 2


def shapes(spec: dict) -> dict:
    return {"tokens": spec["batch"] * spec["seq_len"],
            "layers": len(spec["deployment"]["layers_kept"]),
            # Expected assignments a token to the experts held here, a
            # layer: no selection bias, so a chip's share is held /
            # published.
            "held_per_token": spec["num_experts_per_tok"]
            * spec["deployment"]["experts_held"][1]
            / spec["published"]["num_experts"]}


def forward_flops_per_token(spec: dict) -> dict:
    """The forward products of one token, by part."""
    s, d, hd = shapes(spec), spec["hidden_size"], spec["head_dim"]
    sparse = spec["sa_config"]
    q, kv = spec["num_attention_heads"] * hd, spec["num_key_value_heads"] * hd
    index = sparse["indexer_head_dim"] * (sparse["indexer_num_heads"] + 1) \
        + sparse["indexer_num_heads"]
    return {
        "attention_projections": s["layers"] * 2 * d * (2 * q + 2 * kv),
        "indexer_projections": s["layers"] * 2 * d * index,
        "routers": s["layers"] * 2 * d * spec["published"]["num_experts"],
        "experts": s["layers"] * s["held_per_token"]
        * expert_forward_flops_per_assignment(spec),
        "head": 2 * d * spec["deployment"]["vocab_rows_held"][1],
    }


def expert_forward_flops_per_assignment(spec: dict) -> int:
    """One token through one expert: three products of hidden x expert
    width."""
    return 3 * 2 * spec["hidden_size"] * spec["moe_intermediate_size"]


def pairs_per_row(spec: dict) -> tuple:
    """Of one row of the corpus (every row holds the same documents):
    the candidates ``Σ c_t`` (same-document causal pairs, the token
    itself among them) and the members ``Σ min(c_t, topk)`` of its
    queries' selections, a layer."""
    lengths = np.asarray(spec["corpus"]["document_lengths"], np.int64)
    keep = np.minimum(lengths, spec["sa_config"]["topk"])
    candidates = lengths * (lengths + 1) // 2
    members = keep * (keep + 1) // 2 + (lengths - keep) * keep
    return int(candidates.sum()), int(members.sum())


def index_forward_flops_per_step(spec: dict) -> float:
    """The index scores of every candidate pair: one product of the
    indexer's head size a pair and indexer head (the ReLU and the head
    sum are no products). Forward only: there is no backward."""
    sparse = spec["sa_config"]
    return float(pairs_per_row(spec)[0] * spec["batch"]
                 * shapes(spec)["layers"] * 2
                 * sparse["indexer_num_heads"] * sparse["indexer_head_dim"])


def sparse_attention_forward_flops_per_step(spec: dict) -> float:
    """Scores and weighted sums over the selected pairs, all heads: 2
    products of head size a pair and head."""
    return float(pairs_per_row(spec)[1] * spec["batch"]
                 * shapes(spec)["layers"] * 2 * 2 * spec["head_dim"]
                 * spec["num_attention_heads"])


def flops_per_step(spec: dict) -> float:
    """Forward and backward: a product is 2·m·n·k forward and twice that
    backward (its weight's and its input's gradient); the indexer's
    projections and scores forward alone."""
    per_token = forward_flops_per_token(spec)
    once = per_token.pop("indexer_projections")
    tokens = shapes(spec)["tokens"]
    return (3.0 * (sum(per_token.values()) * tokens
                   + sparse_attention_forward_flops_per_step(spec))
            + once * tokens + index_forward_flops_per_step(spec))


def gather_bytes_per_step(spec: dict) -> float:
    """Bytes of the rows that must move by index, at the least: one
    embedding row (bfloat16) a token forward and its cotangent backward;
    for each layer, each held assignment's row into expert order and
    back into token order, forward and backward. (The selection moves no
    rows: a query's kept keys are read where they lie.)"""
    s = shapes(spec)
    row = spec["hidden_size"] * BF16
    embedding = 2 * s["tokens"] * row
    experts = s["layers"] * 4 * s["held_per_token"] * s["tokens"] * row
    return float(embedding + experts)
