"""Work one optimizer step of the ``laguna`` kind requires, from the
configuration's file alone (never from the HLO): the products the layer
equations name, at the expected number of assignments to the experts
held here, attention over the same-document causal pairs of the
corpus's fixed length sequence in the full layers, and over those of
them within the window in the sliding layers. Recomputation is not
counted, nor is anything an implementation adds (worst-case buffers,
one-hot products, scores of pairs that the masks then drop)."""

from __future__ import annotations

import numpy as np

BF16 = 2


def shapes(spec: dict) -> dict:
    kept = spec["deployment"]["layers_kept"]
    heads = [spec["num_attention_heads_per_layer"][i] for i in kept]
    sliding = [spec["layer_types"][i] == "sliding_attention" for i in kept]
    sparse = sum(spec["mlp_layer_types"][i] == "sparse" for i in kept)
    return {"tokens": spec["batch"] * spec["seq_len"],
            "full_heads": [h for h, w in zip(heads, sliding) if not w],
            "sliding_heads": [h for h, w in zip(heads, sliding) if w],
            "dense": len(kept) - sparse, "sparse": sparse,
            # Expected assignments a token to the experts held here, a
            # layer: no selection bias, so a chip's share is held /
            # published.
            "held_per_token": spec["num_experts_per_tok"]
            * spec["deployment"]["experts_held"][1]
            / spec["published"]["num_experts"]}


def forward_flops_per_token(spec: dict) -> dict:
    """The forward products of one token, by part."""
    s, d = shapes(spec), spec["hidden_size"]
    kv = spec["head_dim"] * spec["num_key_value_heads"]
    q = spec["head_dim"] * sum(s["full_heads"] + s["sliding_heads"])
    layers = s["dense"] + s["sparse"]
    return {
        # q, o and the gate at the layer's own head count; k and v.
        "attention_projections": 2 * (3 * d * q + layers * 2 * d * kv),
        "dense_ff": s["dense"] * 2 * 3 * d * spec["intermediate_size"],
        "shared_experts": s["sparse"] * 2 * 3 * d
        * spec["shared_expert_intermediate_size"],
        "routers": s["sparse"] * 2 * d * spec["published"]["num_experts"],
        "experts": s["sparse"] * s["held_per_token"]
        * expert_forward_flops_per_assignment(spec),
        "head": 2 * d * spec["deployment"]["vocab_rows_held"][1],
    }


def expert_forward_flops_per_assignment(spec: dict) -> int:
    """One token through one expert: three products of hidden x expert
    width."""
    return 3 * 2 * spec["hidden_size"] * spec["moe_intermediate_size"]


def attention_pairs_per_step(spec: dict, window: int | None = None) -> int:
    """Same-document causal (query, key) pairs a step, with a ``window``
    those of them with ``t - s < window``: a document of L tokens has
    ``Σ_p min(p + 1, window)`` (L(L+1)/2 without one), and a step takes
    ``batch`` rows of the same documents."""
    lengths = np.asarray(spec["corpus"]["document_lengths"], np.int64)
    per_row = 0
    for n in lengths.tolist():
        if window is None or n <= window:
            per_row += n * (n + 1) // 2
        else:
            per_row += window * (window + 1) // 2 + (n - window) * window
    return per_row * spec["batch"]


def attention_forward_flops_per_step(spec: dict) -> float:
    """Scores and weighted sums of the full layers' pairs, all their
    heads: 2 products of head size a pair and head."""
    return (attention_pairs_per_step(spec) * 2 * 2 * spec["head_dim"]
            * sum(shapes(spec)["full_heads"]))


def window_attention_forward_flops_per_step(spec: dict) -> float:
    """The same of the sliding layers' pairs within the window."""
    return (attention_pairs_per_step(spec, spec["sliding_window"])
            * 2 * 2 * spec["head_dim"] * sum(shapes(spec)["sliding_heads"]))


def flops_per_step(spec: dict) -> float:
    """Forward and backward: a product is 2·m·n·k forward and twice that
    backward (its weight's and its input's gradient)."""
    per_token = sum(forward_flops_per_token(spec).values())
    return 3.0 * (per_token * shapes(spec)["tokens"]
                  + attention_forward_flops_per_step(spec)
                  + window_attention_forward_flops_per_step(spec))


def gather_bytes_per_step(spec: dict) -> float:
    """Bytes of the rows that must move by index, at the least: one
    embedding row (bfloat16) a token forward and its cotangent backward;
    for each sparse layer, each held assignment's row into expert order
    and back into token order, forward and backward."""
    s = shapes(spec)
    row = spec["hidden_size"] * BF16
    embedding = 2 * s["tokens"] * row
    experts = s["sparse"] * 4 * s["held_per_token"] * s["tokens"] * row
    return float(embedding + experts)
