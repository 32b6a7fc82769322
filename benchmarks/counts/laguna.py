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

from benchmarks.runners.lfm2_moe import document_lengths

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


def attention_pairs_per_step(spec: dict, window: int | None = None) -> float:
    """Same-document causal (query, key) pairs a step, with a ``window``
    those of them with ``t - s < window``, expected over the seed's
    order. A document of L tokens has ``f(L) = Σ_p min(p + 1, window)``
    (L(L+1)/2 without one); each of the corpus's R - 1 row ends falls at
    one of a document's L places with probability 1 / tokens each and
    cuts it there into a and L - a (a = 0: not at all), which takes
    ``f(L) - f(a) - f(L - a)`` pairs away (L(L² - 1)/6 over a document's
    places without a window)."""
    corpus = spec["corpus"]
    lengths = document_lengths(corpus)
    upto = np.arange(int(lengths.max()) + 1, dtype=np.float64)
    # f(n) for n = 0 .. the longest document, and its running sum.
    f = np.concatenate([[0.0], np.cumsum(
        upto[1:] if window is None else np.minimum(upto[1:], window))])
    running = np.cumsum(f)
    # Σ over a = 1 .. L - 1 of f(L) - f(a) - f(L - a).
    lost = (lengths - 1) * f[lengths] - 2 * running[lengths - 1]
    rows = corpus["tokens"] // spec["seq_len"]
    pairs = f[lengths].sum() - (rows - 1) * lost.sum() / corpus["tokens"]
    return pairs * shapes(spec)["tokens"] / corpus["tokens"]


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
