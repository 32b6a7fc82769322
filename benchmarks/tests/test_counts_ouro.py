"""The ``ouro`` kind's count functions against numbers worked by hand
and against three seeds' arrays, and its new metric's reader on
contexts that have and have not what it reads."""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LENGTHS = [1_536, 1_024, 640, 384, 256, 128, 128]


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ouro-2.6b-pp12.json")) as fh:
        return json.load(fh)


def test_forward_flops_of_a_token_by_part(spec):
    from benchmarks.counts import ouro as counts

    per_token = counts.forward_flops_per_token(spec)
    d = 2048
    # 4 layers run 4 times: 16 applications of q, k, v, o at 16 heads of
    # 128 on 16 key-value heads, and of the gated FFN of 5,632.
    assert per_token["attention_projections"] == 16 * 2 * d * 4 * d \
        == 536_870_912
    assert per_token["ffn"] == 16 * 3 * 2 * d * 5_632 == 1_107_296_256
    # 4 exits: logits against all 49,152 rows, and the gate.
    assert per_token["head"] == 4 * 2 * d * 49_152 == 805_306_368
    assert per_token["exit_gates"] == 4 * 2 * d
    assert counts.shapes(spec) == {"tokens": 16_384, "layers": 4,
                                   "loops": 4}


def test_pairs_of_a_row_by_hand(spec):
    """Every row holds the same seven documents; a document of L tokens
    has L(L+1)/2 causal pairs, the token itself among them."""
    from benchmarks.counts import ouro as counts

    assert spec["corpus"]["document_lengths"] == LENGTHS
    assert sum(LENGTHS) == spec["seq_len"] == 4_096
    assert counts.pairs_per_row(spec) == sum(
        n * (n + 1) // 2 for n in LENGTHS) == 2_033_664
    assert counts.pairs_per_row(dict(spec, corpus={
        "document_lengths": [3, 1]})) == 6 + 1


def test_the_steps_totals(spec):
    """The count worked by hand: 2.514 GFLOP a token forward, 123.6 TFLOP a step
    with the backward; the loop's applications 65.4%, the exits' head
    products 32.0%, same-document attention pairs 2.6% (496.5 a
    token)."""
    from benchmarks.counts import ouro as counts

    attention = counts.attention_forward_flops_per_step(spec)
    # 4 sequences, 16 applications; 2 products of 128 a pair and head.
    assert attention == 2_033_664 * 4 * 16 * 2 * 2 * 128 * 16
    assert 2_033_664 / 4_096 == 496.5
    per_token = sum(counts.forward_flops_per_token(spec).values())
    assert per_token + attention / 16_384 == 2_514_567_168
    total = counts.flops_per_step(spec)
    assert total == 3 * 16_384 * 2_514_567_168 == pytest.approx(123.6e12,
                                                                rel=1e-3)
    exits = 3 * counts.exit_forward_flops_per_step(spec)
    assert exits == 3 * 16_384 * 4 * 2 * 2048 * (49_152 + 1)
    assert round(exits / total, 3) == 0.320
    assert round(3 * attention / total, 3) == 0.026
    layers = 3 * 16_384 * (536_870_912 + 1_107_296_256)
    assert round(layers / total, 3) == 0.654
    # An embedding row each way, 4 KiB in bfloat16; the passes move no
    # rows.
    assert counts.gather_bytes_per_step(spec) == 2 * 16_384 * 4_096


@pytest.mark.parametrize("seed", [1, 2**31 + 7, 3000000019])
def test_the_traffic_holds_the_same_documents_in_every_row(spec, seed):
    """Every row the same seven lengths in an order of its own, ids over
    all 49,152, positions restarting; the counts' pairs are the arrays'
    own, exactly (a share of a roofline over 105% is refused)."""
    from benchmarks.counts import ouro as counts
    from benchmarks.runners.ouro import traffic

    a, b = traffic(spec, seed), traffic(spec, seed)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    for name in ("tokens", "segments", "positions"):
        assert a[name].shape == (1_024, 4_096) and a[name].dtype == np.int32
    assert a["tokens"].min() >= 0 and 45_000 < a["tokens"].max() < 49_152
    orders = set()
    for row in range(1_024):
        starts = np.flatnonzero(a["positions"][row] == 0)
        lengths = np.diff(np.append(starts, 4_096))
        assert sorted(lengths.tolist(), reverse=True) == LENGTHS
        assert (np.diff(a["segments"][row]) >= 0).all()
        orders.add(tuple(lengths.tolist()))
    assert len(orders) > 500
    positions = a["positions"].astype(np.int64)
    assert ((positions + 1).sum(1) == counts.pairs_per_row(spec)).all()


def _ctx(spec, counts, under):
    return {"trace": SimpleNamespace(scope_seconds=under), "spec": spec,
            "counts": counts, "peaks": {"bf16_flops_per_s": 197e12},
            "run": {"steps": 5, "chips": 1}}


def test_the_exit_reader_reads_its_scope_and_nothing_else(spec):
    from benchmarks.counts import laguna
    from benchmarks.counts import ouro as counts
    from benchmarks.metrics import seq_attn_roofline, seq_exit_roofline

    under = {"df2.seq.exit": 2.5, "df2.seq.attn": 1.0}
    ctx = _ctx(spec, counts, under)
    share = seq_exit_roofline.read(ctx)
    assert share == pytest.approx(
        100 * 3 * counts.exit_forward_flops_per_step(spec) * 5 / 197e12
        / 2.5)
    assert 0 < share < 100
    # The attention reader takes the 16 applications' pairs from the
    # kind's count.
    assert seq_attn_roofline.read(ctx) == pytest.approx(
        100 * 3 * counts.attention_forward_flops_per_step(spec) * 5 / 197e12)
    # A program without the scope (the parent), a kind without the count,
    # no trace: nothing to read, and nothing raised.
    assert seq_exit_roofline.read(_ctx(spec, counts, {"df2.seq.attn": 1.0})
                                  ) is None
    assert seq_exit_roofline.read(dict(ctx, trace=None)) is None
    assert seq_exit_roofline.read(_ctx(spec, laguna, under)) is None
    assert seq_exit_roofline.chip_only is True
