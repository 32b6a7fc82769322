"""Work one optimizer step of the ``ouro`` kind requires, from the
configuration's file alone (never from the HLO): the products the layer
equations name, for each of the ``total_ut_steps`` passes of the kept
layers (the same weights, applied again: each application is work), the
head's logits and the exit gate at each pass's exit, and attention over
the same-document causal pairs of the corpus's fixed documents (every
row holds the same ones). Forward and backward: everything three times
the forward. Recomputation is not counted, nor is anything an
implementation adds (one-hot products, scores of pairs a tile computes
and the mask then drops)."""

from __future__ import annotations

import numpy as np

BF16 = 2


def shapes(spec: dict) -> dict:
    return {"tokens": spec["batch"] * spec["seq_len"],
            "layers": len(spec["deployment"]["layers_kept"]),
            "loops": spec["total_ut_steps"]}


def forward_flops_per_token(spec: dict) -> dict:
    """The forward products of one token, by part, over all passes."""
    s, d, hd = shapes(spec), spec["hidden_size"], spec["head_dim"]
    q, kv = spec["num_attention_heads"] * hd, spec["num_key_value_heads"] * hd
    applications = s["layers"] * s["loops"]
    return {
        "attention_projections": applications * 2 * d * (2 * q + 2 * kv),
        "ffn": applications * 3 * 2 * d * spec["intermediate_size"],
        "head": s["loops"] * 2 * d * spec["deployment"]["vocab_rows_held"][1],
        "exit_gates": s["loops"] * 2 * d,
    }


def pairs_per_row(spec: dict) -> int:
    """Same-document causal pairs of one row of the corpus (every row
    holds the same documents), the token itself among them."""
    lengths = np.asarray(spec["corpus"]["document_lengths"], np.int64)
    return int((lengths * (lengths + 1) // 2).sum())


def attention_forward_flops_per_step(spec: dict) -> float:
    """Scores and weighted sums of the same-document causal pairs, all
    heads, in every layer application: 2 products of head size a pair
    and head."""
    s = shapes(spec)
    return float(pairs_per_row(spec) * spec["batch"] * s["layers"]
                 * s["loops"] * 2 * 2 * spec["head_dim"]
                 * spec["num_attention_heads"])


def exit_forward_flops_per_step(spec: dict) -> float:
    """The exits' forward products: at each pass, every token's logits
    against the rows held and its exit gate."""
    per_token = forward_flops_per_token(spec)
    return float((per_token["head"] + per_token["exit_gates"])
                 * shapes(spec)["tokens"])


def flops_per_step(spec: dict) -> float:
    """Forward and backward: a product is 2·m·n·k forward and twice that
    backward (its weight's and its input's gradient)."""
    tokens = shapes(spec)["tokens"]
    return 3.0 * (sum(forward_flops_per_token(spec).values()) * tokens
                  + attention_forward_flops_per_step(spec))


def gather_bytes_per_step(spec: dict) -> float:
    """Bytes of the rows that must move by index, at the least: one
    embedding row (bfloat16) a token forward and its cotangent backward.
    (No expert layer; the passes move no rows.)"""
    return float(2 * shapes(spec)["tokens"] * spec["hidden_size"] * BF16)
