"""The ``keye_vl2`` kind's count functions against numbers worked by
hand and against three seeds' arrays, its traffic, and its three new
metrics' readers on contexts that have and have not what they read."""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LENGTHS = [16_384, 8_192, 4_096, 2_048, 1_024, 1_024]


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "keye-vl2-30b-a3b-ep16.json")) as fh:
        return json.load(fh)


def test_forward_flops_of_a_token_by_part(spec):
    from benchmarks.counts import keye_vl2 as counts

    per_token = counts.forward_flops_per_token(spec)
    d = 2048
    # q and o at 32 heads of 128, k and v at 4, in four layers.
    assert per_token["attention_projections"] == 4 * 2 * d * (
        2 * 4096 + 2 * 512) == 4 * 2 * 18_874_368 == 150_994_944
    # The indexer's 16 query heads of 64, its one key head, 16 head
    # weights.
    assert per_token["indexer_projections"] == 4 * 2 * d * (
        1024 + 64 + 16) == 18_087_936
    assert per_token["routers"] == 4 * 2 * d * 128 == 2_097_152
    # Top-8 of 128 with 8 held: half an assignment a token a layer,
    # three products of 2048 x 768 each.
    assert counts.expert_forward_flops_per_assignment(spec) == 9_437_184
    assert per_token["experts"] == 4 * 0.5 * 9_437_184 == 18_874_368
    # Logits against the 18,992 rows held.
    assert per_token["head"] == 2 * d * 18_992 == 77_791_232
    assert counts.shapes(spec) == {"tokens": 65_536, "layers": 4,
                                   "held_per_token": 0.5}


def test_pairs_of_a_row_by_hand(spec):
    """A document of L tokens has L(L+1)/2 candidates; its first 2,048
    queries keep all of theirs and every later one 2,048."""
    from benchmarks.counts import keye_vl2 as counts

    assert spec["corpus"]["document_lengths"] == LENGTHS
    candidates = sum(n * (n + 1) // 2 for n in LENGTHS)
    members = sum(sum(min(c, 2048) for c in range(1, n + 1))
                  for n in LENGTHS)
    assert counts.pairs_per_row(spec) == (candidates, members) == (
        179_322_880, 55_579_648)
    # The selection binds on the queries past a document's 2,048th.
    bound = sum(max(n - 2048, 0) for n in LENGTHS)
    assert bound / 32_768 == 0.6875
    tiny = dict(spec, sa_config=dict(spec["sa_config"], topk=3),
                corpus={"document_lengths": [5, 2, 1]})
    assert counts.pairs_per_row(tiny) == (15 + 3 + 1, (1 + 2 + 3 + 3 + 3)
                                          + (1 + 2) + 1)


def test_the_steps_totals(spec):
    from benchmarks.counts import keye_vl2 as counts

    index = counts.index_forward_flops_per_step(spec)
    sparse = counts.sparse_attention_forward_flops_per_step(spec)
    # 2 sequences, 4 layers; a product of 64 a pair and indexer head, 2
    # products of 128 a kept pair and head.
    assert index == 179_322_880 * 2 * 4 * 2 * 16 * 64
    assert sparse == 55_579_648 * 2 * 4 * 4 * 128 * 32
    per_token = counts.forward_flops_per_token(spec)
    thrice = sum(v for k, v in per_token.items()
                 if k != "indexer_projections")
    total = counts.flops_per_step(spec)
    assert total == pytest.approx(
        3 * (65_536 * thrice + sparse)
        + 65_536 * per_token["indexer_projections"] + index)
    assert 74e12 < total < 76e12
    # Of a layer's forward products the indexer (projections, scores)
    # and the attention over the selection are half.
    layer = (thrice - per_token["head"]) * 65_536 \
        + per_token["indexer_projections"] * 65_536 + index + sparse
    new = per_token["indexer_projections"] * 65_536 + index + sparse
    assert 0.48 < new / layer < 0.53
    # Embedding rows each way, and for each of 4 layers half an
    # assignment a token to expert order and back, forward and backward,
    # in 4 KiB bfloat16 rows.
    assert counts.gather_bytes_per_step(spec) == 65_536 * 4096 * (
        2 + 4 * 4 * 0.5)


@pytest.mark.parametrize("seed", [1, 2**31 + 7, 3000000019])
def test_the_traffic_holds_the_same_documents_in_every_row(spec, seed):
    """Every row the same six lengths in an order of its own, ids over
    the rows held, positions restarting; the counts' pairs are the
    arrays' own, exactly (a share of a roofline over 105% is refused)."""
    from benchmarks.counts import keye_vl2 as counts
    from benchmarks.runners.keye_vl2 import traffic

    a, b = traffic(spec, seed), traffic(spec, seed)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    for name in ("tokens", "segments", "positions"):
        assert a[name].shape == (128, 32_768) and a[name].dtype == np.int32
    assert a["tokens"].min() >= 0 and 16_000 < a["tokens"].max() < 18_992
    orders = set()
    for row in range(128):
        starts = np.flatnonzero(a["positions"][row] == 0)
        lengths = np.diff(np.append(starts, 32_768))
        assert sorted(lengths.tolist(), reverse=True) == LENGTHS
        assert (np.diff(a["segments"][row]) >= 0).all()
        assert len(np.unique(a["segments"][row])) == 6
        orders.add(tuple(lengths.tolist()))
    assert len(orders) > 20
    positions = a["positions"].astype(np.int64)
    candidates, members = counts.pairs_per_row(spec)
    assert (positions + 1).sum() == 128 * candidates
    assert np.minimum(positions + 1, 2048).sum() == 128 * members


def test_rows_the_documents_do_not_fill_are_refused(spec):
    from benchmarks.runners.keye_vl2 import traffic

    with pytest.raises(ValueError, match="fill"):
        traffic(dict(spec, seq_len=16_384), 1)


def _ctx(spec, counts, under):
    return {"trace": SimpleNamespace(scope_seconds=under), "spec": spec,
            "counts": counts, "peaks": {"bf16_flops_per_s": 197e12},
            "run": {"steps": 4, "chips": 1}}


def test_the_new_readers_read_their_scopes_and_nothing_else(spec):
    from benchmarks.counts import keye_vl2 as counts
    from benchmarks.counts import laguna
    from benchmarks.metrics import (
        seq_index_roofline,
        seq_select_ms,
        seq_sparse_attn_roofline,
    )

    under = {"df2.seq.index": 0.8, "df2.seq.select": 1.2,
             "df2.seq.attn_sparse": 2.0, "df2.seq.attn": 9.0}
    ctx = _ctx(spec, counts, under)
    index = seq_index_roofline.read(ctx)
    assert index == pytest.approx(
        100 * counts.index_forward_flops_per_step(spec) * 4 / 197e12 / 0.8)
    sparse = seq_sparse_attn_roofline.read(ctx)
    assert sparse == pytest.approx(
        100 * 3 * counts.sparse_attention_forward_flops_per_step(spec) * 4
        / 197e12 / 2.0)
    assert 0 < index < 100 and 0 < sparse < 100
    assert seq_select_ms.read(ctx) == pytest.approx(300.0)
    # A program without the scopes (the parent), a kind without the
    # counts, no trace: nothing to read, and nothing raised.
    for reader in (seq_index_roofline, seq_sparse_attn_roofline,
                   seq_select_ms):
        assert reader.read(_ctx(spec, counts, {"df2.seq.attn": 9.0})) is None
        assert reader.read(dict(ctx, trace=None)) is None
        assert reader.chip_only is True
    for reader in (seq_index_roofline, seq_sparse_attn_roofline):
        assert reader.read(_ctx(spec, laguna, under)) is None
