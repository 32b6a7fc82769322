"""The ``lfm2_moe`` kind's count functions against numbers worked by
hand, and its traffic against "every seed the same shapes and mixes"."""

import json
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "lfm2-24b-a2b-ep8.json")) as fh:
        return json.load(fh)


def test_forward_flops_of_a_token_by_part(spec):
    from benchmarks.counts import lfm2_moe as counts

    per_token = counts.forward_flops_per_token(spec)
    d = 2048
    # Four convolution operators (layers 0, 3, 4, 5): 2048 -> 6144 and
    # 2048 -> 2048, 2 FLOPs a multiply-add.
    assert per_token["conv"] == 4 * 2 * (d * 6144 + d * d) == 134_217_728
    # One attention layer: q and o 2048 x 2048, k and v 2048 x 512.
    assert per_token["attention_projections"] == 2 * (
        2 * d * d + 2 * d * 512) == 20_971_520
    # Layer 0's dense FFN: three products of 2048 x 11776.
    assert per_token["dense_ff"] == 3 * 2 * d * 11776 == 144_703_488
    # Four routers of 64 outputs.
    assert per_token["routers"] == 4 * 2 * d * 64 == 1_048_576
    # Top-4 of 64 with 8 held: half an assignment a token a layer, three
    # products of 2048 x 1536 each.
    assert counts.expert_forward_flops_per_assignment(spec) == 18_874_368
    assert per_token["experts"] == 4 * 0.5 * 18_874_368 == 37_748_736
    # Logits against the 8,192 rows held.
    assert per_token["head"] == 2 * d * 8192 == 33_554_432
    # Layer 0 is 178.3 MFLOP, the three other conv operators 100.7.
    assert round((per_token["conv"] / 4 + per_token["dense_ff"]) / 1e6, 1
                 ) == 178.3
    assert round(3 * per_token["conv"] / 4 / 1e6, 1) == 100.7
    assert round(sum(per_token.values()) / 1e6, 1) == 372.2


def test_attention_pairs_and_the_steps_totals(spec):
    from benchmarks.counts import lfm2_moe as counts
    from benchmarks.runners.lfm2_moe import document_lengths

    tiny = dict(spec, batch=2, seq_len=8,
                corpus=dict(spec["corpus"], tokens=32, documents=4, median=8,
                            sigma=0.5, min=2, max=16))
    lengths = document_lengths(tiny["corpus"])
    assert lengths.sum() == 32 and len(lengths) == 4
    # Σ L(L+1)/2 less the 3 row ends' expected cuts, for half the corpus
    # (2 rows of 4).
    whole = sum(n * (n + 1) / 2 for n in lengths)
    cut = 3 * sum(n * (n * n - 1) / 6 for n in lengths) / 32
    assert counts.attention_pairs_per_step(tiny) == pytest.approx(
        (whole - cut) / 2)
    # The cell: 32,768 tokens a step; pairs under 10% of the step.
    assert counts.shapes(spec)["tokens"] == 32_768
    attention = 3 * counts.attention_forward_flops_per_step(spec)
    total = counts.flops_per_step(spec)
    assert total == pytest.approx(
        3 * 32_768 * 372_244_480 + attention)
    assert 0.01 < attention / total < 0.10
    # One attention layer, 4·hidden FLOPs a pair.
    assert counts.attention_forward_flops_per_step(spec) == pytest.approx(
        counts.attention_pairs_per_step(spec) * 4 * 2048)
    # Embedding rows each way, and for each of 4 expert layers half an
    # assignment a token to expert order and back, forward and backward,
    # in 4 KiB bfloat16 rows.
    assert counts.gather_bytes_per_step(spec) == 32_768 * 4096 * (
        2 + 4 * 4 * 0.5)


def test_the_attention_count_is_what_the_traffic_holds(spec):
    """The expected pairs of the count against the pairs counted in
    the arrays of three seeds."""
    from benchmarks.counts import lfm2_moe as counts
    from benchmarks.runners.lfm2_moe import traffic

    expected = counts.attention_pairs_per_step(spec) * (
        spec["corpus"]["tokens"] / counts.shapes(spec)["tokens"])
    for seed in (1, 2**31 + 7, 3000000019):
        positions = traffic(spec, seed)["positions"].astype(np.int64)
        # A token at position p of its document attends p + 1 keys.
        assert (positions + 1).sum() == pytest.approx(expected, rel=0.03)


def test_every_seed_the_same_shapes_and_mixes(spec):
    from benchmarks.runners.lfm2_moe import (
        document_lengths,
        token_table,
        traffic,
    )

    lengths = document_lengths(spec["corpus"])
    assert len(lengths) == 2960 and lengths.sum() == 4_194_304
    assert lengths.min() >= 16 and lengths.max() == 8192
    assert 650 <= np.median(lengths) <= 750
    a, b = traffic(spec, 2**31 + 7), traffic(spec, 2**31 + 7)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    shares = np.diff(token_table(spec["corpus"], 8192), prepend=0.0)
    for seed in (1, 2, 3000000019):
        arrays = traffic(spec, seed)
        for name in ("tokens", "segments", "positions"):
            assert arrays[name].shape == (512, 8192)
            assert arrays[name].dtype == np.int32
        # The same multiset of document lengths in another order
        # (before the rows cut them).
        flat = arrays["segments"].ravel()
        assert np.array_equal(np.sort(np.bincount(flat)), np.sort(lengths))
        assert not np.array_equal(np.bincount(flat), lengths)
        # The same token mix: ids of the held rows, the most frequent
        # 64 within 5% of their table shares (some 9,000 draws each).
        tokens = arrays["tokens"].ravel()
        assert tokens.min() >= 0 and tokens.max() < 8192
        got = np.bincount(tokens, minlength=8192) / tokens.size
        assert np.abs(got[:64] / shares[:64] - 1).max() < 0.05
        # Positions restart with each document and at each row's start.
        assert (arrays["positions"][:, 0] == 0).all()
        new = np.diff(arrays["segments"], axis=1) != 0
        assert (arrays["positions"][:, 1:][new] == 0).all()
        assert (np.diff(arrays["positions"], axis=1)[~new] == 1).all()


def test_the_selection_bias_is_the_same_ramp_on_every_chip(spec):
    from benchmarks.references.lfm2_moe import selection_bias

    bias = selection_bias(spec)
    assert bias.shape == (64,)
    np.testing.assert_allclose(bias[:8], bias[8:16])
    beta = spec["router_bias"]["beta"]
    np.testing.assert_allclose([bias[0], bias[7]], [-beta, beta])
    assert abs(bias.sum()) < 1e-6
