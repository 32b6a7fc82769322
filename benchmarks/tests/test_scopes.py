"""The readers of the program's ``df2.*`` device scopes
(``trace.scopes``, ``metrics/sample_ms.py``, ``attn_gather_bwd_ms.py``,
``unscoped_share.py``) on traces recorded on a TPU v5e at the cells'
rehearsal sizes with the scopes in them (``data/tpu_v5e_scopes_*``, PR
26; the PR-23 recording beside them predates the scopes), and the
decoder they read with against the program's own."""

import os

import pytest

from benchmarks import trace, xplane
from benchmarks.metrics import attn_gather_bwd_ms, sample_ms, unscoped_share

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = {name: os.path.join(DATA, f"tpu_v5e_{name}.xplane.pb")
            for name in ("small", "scopes_gat", "scopes_sage")}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_the_decoder_is_the_programs(name):
    """``benchmarks/xplane.py`` is a copy (the yardstick lives under the
    benchmark's paths): the same events, stats and all."""
    from dragonfly2_tpu.utils import xplane as programs

    ours = xplane.read_xspace(RECORDED[name])
    theirs = programs.read_xspace(RECORDED[name])
    assert [p.name for p in ours] == [p.name for p in theirs]
    events = 0
    for mine, other in zip(ours, theirs):
        assert [ln.name for ln in mine.lines] == [
            ln.name for ln in other.lines]
        for a, b in zip(mine.lines, other.lines):
            assert [(e.name, e.start_ns, e.duration_ns, e.stats)
                    for e in a.events] == [
                (e.name, e.start_ns, e.duration_ns, e.stats)
                for e in b.events]
            events += len(a.events)
    assert events > 1000


def _ctx(reduced, steps):
    return {"trace": reduced, "run": {"steps": steps}}


def test_scopes_of_the_graphsage_step():
    reduced = trace.reduce(RECORDED["scopes_sage"])
    under = reduced.scope_seconds
    assert {"df2.batch", "df2.sample.hop1", "df2.sample.hop2",
            "df2.features", "df2.model"} <= set(under)
    assert all(0 < s <= reduced.scoped_s for s in under.values())
    # The two decoders cut times differently (ProfileData to whole ns).
    assert reduced.scoped_s + reduced.unscoped_s == pytest.approx(
        reduced.busy_s, rel=0.02)
    # The fused step's scopes do not nest: their times add up to the
    # scoped time.
    assert sum(under.values()) == pytest.approx(reduced.scoped_s, rel=1e-3)
    steps = 10
    assert sample_ms.read(_ctx(reduced, steps)) == pytest.approx(
        1e3 * (under["df2.sample.hop1"] + under["df2.sample.hop2"]) / steps)
    share = unscoped_share.read(_ctx(reduced, steps))
    assert 0 < share < 50
    # No attention in this step: nothing to read, not zero.
    assert attn_gather_bwd_ms.read(_ctx(reduced, steps)) is None


def test_scopes_of_the_graph_transformer_step():
    reduced = trace.reduce(RECORDED["scopes_gat"])
    under = reduced.scope_seconds
    assert {"df2.model", "df2.attn.gather", "df2.attn.gather_bwd"} <= set(
        under)
    # The attention's scopes lie inside the model's, and count for both.
    assert (under["df2.attn.gather"] + under["df2.attn.gather_bwd"]
            < under["df2.model"] <= reduced.scoped_s)
    assert attn_gather_bwd_ms.read(_ctx(reduced, 4)) == pytest.approx(
        250.0 * under["df2.attn.gather_bwd"])
    assert 0 < unscoped_share.read(_ctx(reduced, 4)) < 50
    assert sample_ms.read(_ctx(reduced, 4)) is None


def test_a_trace_without_scopes_reads_all_unscoped():
    """The PR-23 recording: a build without the scopes (or a cached
    executable of one) leaves the scope metrics silent and
    ``unscoped_share`` at 100."""
    reduced = trace.reduce(RECORDED["small"])
    assert reduced.scope_seconds == {} and reduced.scoped_s == 0.0
    assert sample_ms.read(_ctx(reduced, 4)) is None
    assert attn_gather_bwd_ms.read(_ctx(reduced, 4)) is None
    assert unscoped_share.read(_ctx(reduced, 4)) == 100.0


def test_scope_names_in_a_path():
    find = trace.SCOPE.findall
    assert find("jit(train_step)/jit(main)/transpose(jvp(df2.model))/"
                "df2.attn.gather_bwd/gather") == [
        "df2.model", "df2.attn.gather_bwd"]
    assert find("jit(f)/df2.sample.hop2/while/body/dynamic_slice") == [
        "df2.sample.hop2"]
    assert find("jit(f)/mydf2.model/add") == []
    assert find("") == []
