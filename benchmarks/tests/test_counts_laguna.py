"""The ``laguna`` kind's count functions against numbers worked by
hand and against three seeds' arrays, and its two new metrics' readers
on contexts that have and have not what they read."""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "laguna-xs2-ep32.json")) as fh:
        return json.load(fh)


def test_forward_flops_of_a_token_by_part(spec):
    from benchmarks.counts import laguna as counts

    per_token = counts.forward_flops_per_token(spec)
    d = 2048
    # q, o and gate at 48 heads of 128 in layers 0 and 4 and at 64 in
    # layers 1-3 (288 heads in all); k and v at 8 heads in all five.
    assert per_token["attention_projections"] == 2 * (
        3 * d * 288 * 128 + 5 * 2 * d * 1024) == 494_927_872
    # Layer 0's dense FFN: three products of 2048 x 8192.
    assert per_token["dense_ff"] == 3 * 2 * d * 8192 == 100_663_296
    # Four shared experts of width 512, four routers of 256 outputs.
    assert per_token["shared_experts"] == 4 * 3 * 2 * d * 512 == 25_165_824
    assert per_token["routers"] == 4 * 2 * d * 256 == 4_194_304
    # Top-8 of 256 with 8 held: a quarter of an assignment a token a
    # layer, three products of 2048 x 512 each.
    assert counts.expert_forward_flops_per_assignment(spec) == 6_291_456
    assert per_token["experts"] == 4 * 0.25 * 6_291_456 == 6_291_456
    # Logits against the 12,544 rows held.
    assert per_token["head"] == 2 * d * 12_544 == 51_380_224
    assert sum(per_token.values()) == 682_622_976
    shapes = counts.shapes(spec)
    assert shapes["full_heads"] == [48, 48]
    assert shapes["sliding_heads"] == [64, 64, 64]
    assert (shapes["dense"], shapes["sparse"], shapes["tokens"]) == (
        1, 4, 32_768)


def test_pairs_with_and_without_a_window_by_hand(spec):
    from benchmarks.counts import laguna as counts

    tiny = dict(spec, batch=2, seq_len=8, sliding_window=3,
                corpus=dict(spec["corpus"], tokens=32,
                            document_lengths=[1, 2, 5]))

    def f(n, window):
        return sum(min(p + 1, window) for p in range(n))

    for window in (3, 10**9):
        got = counts.attention_pairs_per_step(
            tiny, None if window > 100 else window)
        assert got == 2 * sum(f(n, window) for n in (1, 2, 5))
    # 1 + 3 + 15 pairs a row without the window; the five-token
    # document keeps 1 + 2 + 3 + 3 + 3 of its 15 within it.
    assert counts.attention_pairs_per_step(tiny) == 2 * 19
    assert counts.attention_pairs_per_step(tiny, 3) == 2 * (1 + 3 + 12)
    # The cell's row: six documents of 116 to 4,651 tokens.
    assert counts.attention_pairs_per_step(spec) == 4 * 12_848_339
    assert counts.attention_pairs_per_step(spec, 512) == 4 * 3_511_928


def test_the_steps_totals(spec):
    from benchmarks.counts import laguna as counts

    full = counts.attention_forward_flops_per_step(spec)
    window = counts.window_attention_forward_flops_per_step(spec)
    # Two full layers of 48 heads, three sliding ones of 64; 4·head
    # FLOPs a pair and head.
    assert full == pytest.approx(
        counts.attention_pairs_per_step(spec) * 4 * 128 * 96)
    assert window == pytest.approx(
        counts.attention_pairs_per_step(spec, 512) * 4 * 128 * 192)
    total = counts.flops_per_step(spec)
    assert total == pytest.approx(
        3 * (32_768 * 682_622_976 + full + window))
    # The mixed attention (projections, gate and pairs) does three
    # quarters of the required work; the window takes the sliding
    # layers' pairs to under a third of the full layers' a head.
    attention = 3 * (32_768 * 494_927_872 + full + window)
    assert 0.70 < attention / total < 0.80
    assert 0.25 < (window / 192) / (full / 96) < 0.33
    # Embedding rows each way, and for each of 4 sparse layers a quarter
    # of an assignment a token to expert order and back, forward and
    # backward, in 4 KiB bfloat16 rows.
    assert counts.gather_bytes_per_step(spec) == 32_768 * 4096 * (
        2 + 4 * 4 * 0.25)


@pytest.mark.parametrize("seed", [1, 2**31 + 7, 3000000019])
def test_the_pair_counts_are_what_the_traffic_holds(spec, seed):
    """The pairs of the counts against the pairs counted in a seed's
    arrays: equal, every step alike (a share of a roofline over 105% is
    refused)."""
    from benchmarks.counts import laguna as counts
    from benchmarks.runners.laguna import traffic

    batch = counts.shapes(spec)["tokens"] // spec["seq_len"]
    positions = traffic(spec, seed)["positions"].astype(np.int64)
    # A token at position p of its document attends p + 1 keys, and
    # min(p + 1, window) of them in a sliding layer.
    for window, held in ((None, positions + 1),
                         (512, np.minimum(positions + 1, 512))):
        by_step = held.reshape(-1, batch, spec["seq_len"]).sum((1, 2))
        assert (by_step == counts.attention_pairs_per_step(
            spec, window)).all()


def test_the_traffic_is_the_other_sequence_cells_over_the_rows_held(spec):
    """The same seed, the same arrays; every row the same six documents
    at the same places, ids from the other sequence cells' table, and so
    the same tiles of the full layers' attention kept in every row."""
    from benchmarks.runners.laguna import traffic
    from dragonfly2_tpu.models.seq_layers import (
        ATTENTION_BLOCK,
        document_tiles,
    )

    a, b = traffic(spec, 2**31 + 7), traffic(spec, 2**31 + 7)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    for name in ("tokens", "segments", "positions"):
        assert a[name].shape == (512, 8192) and a[name].dtype == np.int32
    assert a["tokens"].min() >= 0 and 8192 < a["tokens"].max() < 12_544
    assert not np.array_equal(a["tokens"], traffic(spec, 2)["tokens"])
    assert (a["positions"] == a["positions"][0]).all()
    starts = np.flatnonzero(a["positions"][0] == 0)
    assert np.diff(np.append(starts, 8192)).tolist() == [
        116, 291, 532, 920, 1682, 4651]
    # Ids differ by row, the layout does not.
    assert (np.diff(a["segments"], axis=1) == np.diff(
        a["segments"][:1], axis=1)).all()
    kept = document_tiles(a["segments"], ATTENTION_BLOCK).sum((-1, -2))
    assert (kept == 22).all()
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "lfm2-24b-a2b-ep8.json")) as fh:
        theirs = json.load(fh)["corpus"]
    assert {k: spec["corpus"][k] for k in ("tokens", "exponent", "offset")
            } == {k: theirs[k] for k in ("tokens", "exponent", "offset")}


def _ctx(spec, counts, under):
    return {"trace": SimpleNamespace(scope_seconds=under), "spec": spec,
            "counts": counts, "peaks": {"bf16_flops_per_s": 197e12},
            "run": {"steps": 8, "chips": 1}}


def test_the_new_readers_read_their_scopes_and_nothing_else(spec):
    from benchmarks.counts import laguna as counts
    from benchmarks.counts import lfm2_moe
    from benchmarks.metrics import moe_shared_ms, seq_window_attn_roofline

    under = {"df2.seq.attn_window": 0.4, "df2.moe.shared": 0.2,
             "df2.seq.attn": 9.0}
    share = seq_window_attn_roofline.read(_ctx(spec, counts, under))
    assert share == pytest.approx(
        100 * 3 * counts.window_attention_forward_flops_per_step(spec) * 8
        / 197e12 / 0.4)
    assert 0 < share < 100
    assert moe_shared_ms.read(_ctx(spec, counts, under)) == pytest.approx(25.0)
    # A program without the scopes (the parent), a kind without the
    # count, no trace: nothing to read, and nothing raised.
    for reader in (seq_window_attn_roofline, moe_shared_ms):
        assert reader.read(_ctx(spec, counts, {"df2.seq.attn": 9.0})) is None
        assert reader.read(dict(_ctx(spec, counts, under), trace=None)) is None
        assert reader.chip_only is True
    assert seq_window_attn_roofline.read(_ctx(spec, lfm2_moe, under)) is None
