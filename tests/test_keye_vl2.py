"""The ``KeyeVL2`` sequence-model family (``models/keye_vl2.py``: an
indexer, an exact top-k of keys a query, attention over those alone,
QK-norm, three-stream RoPE, a softmax router) against the plain
reference the benchmark keeps (``benchmarks/references/keye_vl2.py``:
the equations in float32, importing nothing of the program), at a small
size: hidden 64, 4 query heads on 2 key-value heads of 16, an indexer of
2 heads of 8, 8 keys kept on 64-token sequences whose documents are both
shorter and longer than that, 16 experts top-4 with 4 held. The
selection is ranked in panels of 16 queries here (512 on the chip), so
that every width of its key buffer is met."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.compare import leaves
from benchmarks.references import keye_vl2 as reference
from dragonfly2_tpu.models import keye_vl2, selected_attention, seq_layers
from dragonfly2_tpu.models.keye_vl2 import KeyeVL2Config
from dragonfly2_tpu.parallel import moe

SPEC = {
    "model_type": "KeyeVL2", "hidden_size": 64, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_experts_per_tok": 4,
    "rms_norm_eps": 1e-6, "rope_theta": 10000000, "norm_topk_prob": True,
    "attention_bias": False, "tie_word_embeddings": False,
    "use_sliding_window": False, "mlp_only_layers": [],
    "decoder_sparse_step": 1, "hidden_act": "silu",
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default"},
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                  "indexer_num_kv_heads": 1, "topk": 8},
    "num_experts": 4, "vocab_size": 96,
    "published": {"num_hidden_layers": 6, "num_experts": 16,
                  "vocab_size": 768},
    "deployment": {"layers_kept": [0, 1, 2], "experts_held": [4, 4],
                   "vocab_rows_held": [96, 96]},
}
# Documents shorter than the 8 keys kept, and longer.
LENGTHS = [5, 30, 3, 19, 7]
S = sum(LENGTHS)
INDEXER = ("indexer/q", "indexer/k", "indexer/w", "indexer/k_norm",
           "indexer/k_norm_bias")


@pytest.fixture(autouse=True)
def small_panels(monkeypatch):
    monkeypatch.setattr(seq_layers, "SELECT_PANEL", 16)
    monkeypatch.setattr(reference, "QUERY_BLOCK", 16)


def config(dtype="float32", spec=SPEC, **over):
    held, published = spec["deployment"], spec["published"]
    return KeyeVL2Config.from_published(
        dict(spec, **over), num_experts=published["num_experts"],
        vocab_size=published["vocab_size"],
        num_hidden_layers=published["num_hidden_layers"],
        layers=tuple(held["layers_kept"]),
        experts_held=tuple(held["experts_held"]),
        vocab_held=tuple(held["vocab_rows_held"]), compute_dtype=dtype)


def init_params(seed, cfg):
    return seq_layers.init_params(jax.random.key(seed),
                                  keye_vl2.param_shapes(cfg))


def sequence(seed=0, lengths=LENGTHS):
    rng = np.random.default_rng(seed)
    first, rows = SPEC["deployment"]["vocab_rows_held"]
    tokens = first + rng.integers(0, rows, sum(lengths))
    segments = np.repeat(np.arange(len(lengths)), lengths)
    positions = np.concatenate([np.arange(n) for n in lengths])
    return tuple(jnp.asarray(a, jnp.int32)
                 for a in (tokens, segments, positions))


@functools.lru_cache(maxsize=None)
def sound_program_side(seed):
    return program_side(seed, config())


def program_side(seed, cfg=None):
    """Loss, what the blocks counted and gradient leaves of the program
    (the sound one's computed once a seed)."""
    if cfg is None:
        return sound_program_side(seed)
    tokens, segments, positions = sequence(seed)

    def ours(p):
        return seq_layers.sequence_loss(
            p, jnp.zeros((len(cfg.expert_layers), cfg.num_experts)), tokens,
            segments, positions, cfg=cfg, block=keye_vl2.block,
            saved=keye_vl2.SAVED)

    (loss, counts), grads = jax.value_and_grad(ours, has_aux=True)(
        init_params(seed, cfg))
    return loss, counts, leaves(grads)


@functools.lru_cache(maxsize=None)
def reference_side(seed):
    """The same of the reference (target positions in place of the
    counts), on its own weights from the same seed; computed once a
    seed (every test ranks in the same panels)."""
    tokens, segments, positions = sequence(seed)
    sizes = reference.sizes(SPEC)

    def theirs(p):
        return reference.forward_sums(p, tokens, segments, positions, 1.0,
                                      sizes, lambda x: x)

    (want, n), want_grads = jax.value_and_grad(theirs, has_aux=True)(
        reference.init_params(seed, sizes))
    return want, n, want_grads


def worst_gap(got, want) -> float:
    """Loss gap over the loss, or the worst trained leaf's largest
    gradient gap over that leaf's largest reference gradient."""
    (loss, _, grads), (ref_loss, _, ref_grads) = got, want
    gaps = [abs(float(loss) - float(ref_loss)) / abs(float(ref_loss))]
    for name, g in grads.items():
        scale = float(jnp.abs(ref_grads[name]).max())
        if scale:
            gaps.append(float(jnp.abs(g - ref_grads[name]).max()) / scale)
    return max(gaps)


def candidates_of(segments):
    at = np.arange(len(segments))
    seg = np.asarray(segments)
    return (at[None, :] <= at[:, None]) & (seg[None, :] == seg[:, None])


@pytest.mark.parametrize("emb", [{}, {"emb_init_std": 1.0}])
def test_parameters_are_the_references_own(emb):
    """Same names, same shapes, the same draws from the seed: the
    benchmark's ``init_gap`` limit is 0. With ``emb_init_std`` (the
    benchmark's configuration gives 1.0) the embedding's rows, and
    nothing else, are drawn at that deviation on both sides."""
    ours = leaves(init_params(5, config(**emb)))
    theirs = reference.init_params(5, reference.sizes(dict(SPEC, **emb)))
    assert sorted(ours) == sorted(theirs)
    for name in ours:
        np.testing.assert_array_equal(np.asarray(ours[name]),
                                      np.asarray(theirs[name]), name)
    plain = leaves(init_params(5, config()))
    for name in ours:
        scale = 50.0 if emb and name == "embed" else 1.0
        np.testing.assert_allclose(np.asarray(ours[name]),
                                   np.asarray(plain[name]) * scale,
                                   rtol=1e-6, err_msg=name)


def test_unit_embeddings_against_the_plain_reference():
    """The benchmark's initialisation (``emb_init_std`` 1.0: the
    residual stream is fifty times the other matrices' draws): loss and
    every gradient leaf against the reference, as below."""
    cfg, spec = config(emb_init_std=1.0), dict(SPEC, emb_init_std=1.0)
    tokens, segments, positions = sequence(7)
    sizes = reference.sizes(spec)
    want, want_grads = jax.value_and_grad(
        lambda p: reference.forward_sums(p, tokens, segments, positions, 1.0,
                                         sizes, lambda x: x)[0])(
        reference.init_params(7, sizes))
    loss, _, grads = program_side(7, cfg)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    for name, got in grads.items():
        scale = float(jnp.abs(want_grads[name]).max())
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want_grads[name]),
            rtol=1e-3, atol=2e-5 * scale, err_msg=name)


def test_parameter_count_of_the_benchmarks_configuration():
    """``benchmarks/configs/keye-vl2-30b-a3b-ep16.json``: 314,396,160
    parameters at the published widths, by part, from shapes alone."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "keye-vl2-30b-a3b-ep16.json")) as fh:
        spec = json.load(fh)
    cfg = config(dtype="bfloat16", spec=spec)
    by_layer, by_part = {}, {}
    for path, shape, _ in keye_vl2.param_shapes(cfg):
        by_layer[path[0]] = by_layer.get(path[0], 0) + int(np.prod(shape))
        if path[0] == "layer_0":
            part = path[1] if len(path) > 2 else "norms"
            by_part[part] = by_part.get(part, 0) + int(np.prod(shape))
    assert by_layer == {
        "embed": 38_895_616, "lm_head": 38_895_616, "final_norm": 2_048,
        **{f"layer_{i}": 59_150_720 for i in range(4)}}
    # q, k, v, o and QK-norm's two weights of 128; the indexer's
    # queries, key, head weights and layer norm; router and 8 experts.
    assert by_part == {"attn": 18_874_368 + 256, "indexer": 2_261_120,
                       "moe": 262_144 + 8 * 4_718_592, "norms": 4_096}
    assert sum(by_layer.values()) == 314_396_160
    assert cfg.expert_layers == (0, 1, 2, 3) and cfg.attention_window == 0
    assert cfg.held_experts == (0, 8) and cfg.held_vocab == (0, 18_992)
    # Every published width as published.
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim, cfg.indexer_num_heads, cfg.indexer_head_dim,
            cfg.sparse_topk, cfg.moe_intermediate_size, cfg.num_experts,
            cfg.num_experts_per_tok, cfg.rope_theta, cfg.mrope_section) == (
        2048, 32, 4, 128, 16, 64, 2048, 768, 128, 8, 10_000_000,
        (16, 24, 24))
    assert spec["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]


@pytest.mark.parametrize("seed", [7, 8])
def test_loss_and_gradients_against_the_plain_reference(seed):
    """Every gradient leaf within 1e-3 of the reference's (relative),
    plus 2e-5 of the leaf's largest element: both sides are float32, the
    program's products at the CPU's default precision and in another
    order, which is worth a few 1e-6; a query whose ninth-best key, or a
    token whose fifth-best expert, is within that of the one before it
    would flip, which none of these seeds has. The indexer's leaves get
    a gradient of exactly zero on both sides."""
    loss, (assigned, selected), grads = program_side(seed)
    want, n, want_grads = reference_side(seed)
    assert int(n) == S - len(LENGTHS)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    for name, got in grads.items():
        if name.split("/", 1)[-1] in INDEXER:
            assert not np.asarray(got).any(), name
            assert not np.asarray(want_grads[name]).any(), name
            continue
        scale = float(jnp.abs(want_grads[name]).max())
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want_grads[name]),
            rtol=1e-3, atol=2e-5 * scale, err_msg=name)
    # Top-4 of 16 for every token in each of the three layers, and in
    # each the selections' candidates, members and held tiles by the
    # lengths' own arithmetic.
    assert assigned.shape == (3, 16)
    assert (np.asarray(assigned).sum(1) == 4 * S).all()
    c = np.concatenate([np.arange(n) + 1 for n in LENGTHS])
    # One tile a layer: 64 positions are no whole number of the
    # kernels' 1,024-key blocks, so the packing block is the sequence.
    assert seq_layers.limbs_value(selected).tolist() == [
        [c.sum(), np.minimum(c, 8).sum(), 1]] * 3


@pytest.mark.parametrize("seed,lengths", [
    (8, LENGTHS), (9, [32, 16, 8, 4, 4]), (10, [4, 64, 12, 16])])
def test_the_references_gradient_by_documents_is_plain_autodiff(
        monkeypatch, seed, lengths):
    """What the benchmark runs (``sequence_gradient``: a document's
    queries in groups, each against the document's tokens up to its end,
    the forward pass's selections kept, the gradient chained out of
    ``jax.vjp`` of the pieces) against ``jax.value_and_grad`` of the
    whole packed sequence; documents that are halved twice, once and not
    at all, more than one block of queries a group, and lengths that
    are no whole blocks."""
    monkeypatch.setattr(reference, "GROUP_FLOOR", 8)
    monkeypatch.setattr(reference, "QUERY_BLOCK", 4)
    sizes = reference.sizes(SPEC)
    params, arrays = reference.init_params(seed, sizes), sequence(seed, lengths)
    assert len(reference.groups(arrays[1])) == {
        8: 6, 9: 8, 10: 9}[seed]
    (want, n), want_grads = jax.value_and_grad(
        lambda p: reference.forward_sums(p, *arrays, 1.0, sizes,
                                         lambda x: x), has_aux=True)(params)
    (got, count), grads = reference.sequence_gradient(sizes, lambda x: x)(
        params, *arrays, jnp.float32(1.0))
    assert int(count) == int(n) and sorted(grads) == sorted(want_grads)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for name, g in grads.items():
        scale = float(jnp.abs(want_grads[name]).max())
        np.testing.assert_allclose(np.asarray(g), np.asarray(want_grads[name]),
                                   rtol=1e-5, atol=1e-6 * scale, err_msg=name)
        if name.split("/", 1)[-1] in INDEXER:
            assert not np.asarray(g).any(), name


def test_a_documents_queries_are_asked_in_halves():
    """``groups``: (first token, tokens, the last of them that ask)."""
    segments = np.repeat([3, 4, 9], [4096, 1024, 24])
    assert reference.groups(segments) == [
        (0, 4096, 2048), (0, 2048, 1024), (0, 1024, 1024),
        (4096, 1024, 1024), (5120, 24, 24)]


def _selections(seed, lengths):
    """Of layer 0 on one sequence: the program's selection and the
    reference's, as masks ``[S, S]``."""
    cfg = config()
    params = init_params(seed, cfg)
    tokens, segments, positions = sequence(seed, lengths)
    local = tokens - cfg.held_vocab[0]
    x = params["embed"][local]
    p = params["layer_0"]
    a = seq_layers.rms_norm(x, p["in_norm"], cfg.norm_eps)
    packed, candidates, members = seq_layers.select_keys(
        *keye_vl2.indexer(p["indexer"], a, positions, cfg), segments,
        cfg.sparse_topk)
    ours = selected_attention.unpack_mask(
        packed, seq_layers.select_block(len(tokens)))
    sizes = reference.sizes(SPEC)
    q = reference.layer_leaves(reference.init_params(seed, sizes), 0)
    _, theirs = reference.attention(
        q, x, segments, jnp.broadcast_to(positions, (3, len(tokens))), sizes,
        lambda x: x)
    return (np.asarray(ours), np.asarray(theirs), segments,
            int(candidates.sum()),
            int(members.sum()))


@pytest.mark.parametrize("seed,lengths", [
    (0, LENGTHS), (1, [64]), (2, [1, 1, 2, 60]), (3, [16, 16, 32])])
def test_every_query_keeps_exactly_its_best_candidates(seed, lengths):
    """A query with ``c`` candidates (the tokens of its document up to
    itself) keeps ``min(c, 8)`` of them and nothing else, and they are
    the reference's own set (float32 scores on both sides)."""
    ours, theirs, segments, candidates, members = _selections(seed, lengths)
    allowed = candidates_of(segments)
    assert not (ours & ~allowed).any()
    c = allowed.sum(1)
    np.testing.assert_array_equal(ours.sum(1), np.minimum(c, 8))
    np.testing.assert_array_equal(ours, theirs)
    assert (candidates, members) == (c.sum(), np.minimum(c, 8).sum())


def test_ties_at_the_last_place_go_to_the_lower_position():
    """Equal scores at the boundary (zeros under the ReLU) are taken in
    position order, as ``jax.lax.top_k`` takes them; -0.0 ranks as 0.0;
    a row with fewer candidates than places keeps them all."""
    scores = jnp.asarray([[3., 0., -0., 0., 5., 0., 1., 0.],
                          [1., 1., 1., 1., 1., 1., 1., 1.],
                          [9., 8., 7., 6., 5., 4., 3., 2.],
                          [-1., -2., -3., -4., 0., 0., 0., 0.]])
    candidates = jnp.asarray([[True] * 8, [True] * 8,
                              [True, True, False, False, False, True, False,
                               False], [True] * 4 + [False] * 4])
    got = np.asarray(seq_layers.top_k_mask(scores, candidates, 4))
    want = np.zeros((4, 8), bool)
    want[0, [0, 1, 4, 6]] = True      # 5, 3, 1 and the first of the zeros
    want[1, :4] = True
    want[2, [0, 1, 5]] = True         # three candidates, four places
    want[3, :4] = True
    np.testing.assert_array_equal(got, want)
    _, chosen = jax.lax.top_k(jnp.where(candidates, jnp.where(
        scores == 0, 0.0, scores), -jnp.inf), 4)
    for row in (0, 1, 3):
        assert sorted(np.asarray(chosen[row])) == list(
            np.flatnonzero(want[row]))


def test_keeping_every_candidate_is_dense_attention():
    """With ``topk`` at least the longest document the selection drops
    nothing, and the layer's attention is ``seq_layers.dense_attention``
    on the same q, k, v."""
    tokens, segments, positions = sequence(4)
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((S, h, 16)), jnp.float32)
               for h in (4, 2, 2))
    qi, ki, w = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
                 for shape in ((S, 2, 8), (S, 8), (S, 2)))
    packed, _, members = seq_layers.select_keys(qi, ki, w, segments, 30)
    assert int(members.sum()) == candidates_of(segments).sum()
    np.testing.assert_allclose(
        np.asarray(seq_layers.selected_attention(q, k, v, packed)[0]),
        np.asarray(seq_layers.dense_attention(q, k, v, segments)),
        rtol=1e-6, atol=1e-6)


def _kernel_mask(kind, rng, s=512, block=128):
    """A selection ``[s, s]`` for the kernels' tests, 4 x 4 tiles.
    ``documents``: causal within documents of 256, 128, 64 and 64, 30%
    kept and the diagonal: tiles partly empty, rows of two to one held
    tiles. ``one_tile_a_row``: every query block's row holds one tile,
    the diagonal's in the first three and key block 1 in the last (a
    row's range of held tiles away from its own block)."""
    if kind == "documents":
        segments = np.repeat(np.arange(4), [256, 128, 64, 64])
        mask = candidates_of(segments) & (rng.random((s, s)) < 0.3)
        mask[np.arange(s), np.arange(s)] = True
        return mask
    mask = np.zeros((s, s), bool)
    for row, key in enumerate([0, 1, 2, 1]):
        rows, keys = slice(row * block, (row + 1) * block), slice(
            key * block, (key + 1) * block)
        mask[rows, keys] = rng.random((block, block)) < 0.3
        mask[rows, key * block + np.arange(block)[::-1]] = True
    return mask


@pytest.mark.parametrize("heads,kv_heads,step,kind", [
    (2, 2, None, "documents"), (4, 2, None, "documents"),
    (8, 2, None, "documents"), (8, 2, 2, "documents"),
    (4, 2, None, "one_tile_a_row"), (8, 2, None, "one_tile_a_row"),
], ids=["group_1", "group_2", "group_4", "group_4_two_a_step",
        "group_2_one_tile_a_row", "group_4_one_tile_a_row"])
def test_the_kernels_are_the_plain_form(monkeypatch, heads, kv_heads, step,
                                        kind):
    """``models/selected_attention.py``'s three kernels (interpreted
    here; the chip compiles them in ``test_chip_compile.py``) against
    the plain masked softmax, forward and every gradient, with 1, 2 and
    4 query heads on each key-value head, a grid step taking the whole
    group (or, where its blocks would not fit the kernels' memory, a
    part of it: ``step``), on a mask whose tiles are partly empty and on
    one whose every query block holds a single tile."""
    if step is not None:
        monkeypatch.setattr(selected_attention, "heads_per_step",
                            lambda group, *_: step)
    s, block = 512, 128
    rng = np.random.default_rng(0)
    q, k, v, weight = (jnp.asarray(rng.standard_normal((s, h, 32)),
                                   jnp.float32)
                       for h in (heads, kv_heads, kv_heads, heads))
    # Already scaled, as the kernels take q: scores of unit deviation.
    q = q / np.sqrt(32)
    mask = _kernel_mask(kind, rng)
    packed = selected_attention.pack_mask(jnp.asarray(mask), block)
    np.testing.assert_array_equal(
        np.asarray(selected_attention.unpack_mask(packed, block)), mask)
    held, ends = selected_attention.tile_tables(packed, block)
    want = mask.reshape(4, block, 4, block).any((1, 3))
    np.testing.assert_array_equal(np.asarray(held).reshape(4, 4), want)
    assert np.asarray(ends).tolist() == {
        "documents": [[0, 0, 2, 3], [0, 1, 2, 3], [0, 1, 2, 3],
                      [1, 1, 2, 3]],
        # Key block 3 holds nothing: its range is every query block,
        # and none of its tiles is computed.
        "one_tile_a_row": [[0, 1, 2, 1], [0, 1, 2, 1], [0, 1, 2, 0],
                           [0, 3, 2, 3]]}[kind]

    def ours(q, k, v):
        out = selected_attention.packed_attention(
            q.transpose(1, 0, 2), k.transpose(1, 0, 2), v.transpose(1, 0, 2),
            packed, block, True)
        return (out.transpose(1, 0, 2) * weight).sum()

    def plain(q, k, v):
        return (seq_layers.dense_attention(
            q, k, v, None, seen=jnp.asarray(mask)) * weight).sum()

    got = jax.value_and_grad(ours, argnums=(0, 1, 2))(q, k, v)
    want = jax.value_and_grad(plain, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-5)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-5)


def test_a_grid_step_takes_a_key_value_heads_group(monkeypatch):
    """The three kernels of one gradient (forward, dq, dk with dv) each
    walk ``kv_heads x n x n`` grid steps, not ``heads x n x n``: 2 x 4 x
    4 for 8 query heads on 2 key-value heads at 4 tiles a row, and the
    query-side blocks carry the group of 4 heads. At the chip's shape a
    step takes a whole group of 8 under the kernels' memory limit, and
    under a smaller one the largest divisor of the group that fits."""
    s, block, hd = 512, 128, 32
    q = jnp.zeros((8, s, hd), jnp.float32)
    k = v = jnp.zeros((2, s, hd), jnp.float32)
    packed = selected_attention.pack_mask(jnp.eye(s, dtype=bool), block)

    def loss(q, k, v):
        return selected_attention.packed_attention(q, k, v, packed, block,
                                                   True).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    calls = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                calls.append(eqn.params["grid_mapping"])
                continue
            for value in eqn.params.values():
                for sub in value if isinstance(value, tuple) else (value,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(jaxpr.jaxpr)
    assert [tuple(g.grid) for g in calls] == [(2, 4, 4), (2, 4, 4),
                                              (2, 4, 1, 4)]
    for g in calls:
        assert tuple(getattr(d, "block_size", d) for d in
                     g.block_mappings[0].block_shape) == (4, block, hd)
    assert selected_attention.grid_steps(8, 2, s, hd, jnp.float32,
                                         block) == 2 * 4 * 4
    assert selected_attention.heads_per_step(8, 1024, 128, 2) == 8
    monkeypatch.setattr(selected_attention, "_VMEM_LIMIT", 48 * 2**20)
    assert selected_attention.heads_per_step(8, 1024, 128, 2) == 4
    assert selected_attention.grid_steps(32, 4, 32768, 128, jnp.bfloat16,
                                         1024) == 8 * 32 * 32


def test_three_position_streams():
    """Frequency pair i of a head of 16 is turned by stream 0 for i < 2,
    stream 1 for 2 <= i < 5, stream 2 for 5 <= i < 8: three different
    streams against the reference's tables, three equal ones against the
    one-stream RoPE."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((S, 4, 16)), jnp.float32)
    streams = jnp.asarray(rng.integers(0, 5000, (3, S)), jnp.int32)
    inv_freq = seq_layers.rope_frequencies(1e7, 16)
    got = seq_layers.rope(x, streams, inv_freq, sections=(2, 3, 3))
    cos, sin = reference.rope_tables(1e7, 16, streams, (2, 3, 3))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(reference.rope(x, cos, sin)),
                               rtol=1e-5, atol=1e-5)
    # Pair 0 follows stream 0 and pair 7 stream 2, and nothing else.
    other = streams.at[1].add(7)
    moved = np.abs(np.asarray(
        seq_layers.rope(x, other, inv_freq, sections=(2, 3, 3)) - got)
        ).max((0, 1)) > 0
    assert moved.tolist() == ([False] * 2 + [True] * 3 + [False] * 3) * 2
    same = jnp.broadcast_to(streams[0], (3, S))
    np.testing.assert_array_equal(
        np.asarray(seq_layers.rope(x, same, inv_freq, sections=(2, 3, 3))),
        np.asarray(seq_layers.rope(x, streams[0], inv_freq)))
    with pytest.raises(ValueError, match="sections"):
        seq_layers.rope(x, streams, inv_freq, sections=(2, 3, 4))


def test_the_softmax_router_is_the_references():
    """Softmax over all 16, the 4 largest, their shares divided by
    their sum; the held experts' part against the reference's."""
    cfg = config()
    sizes = reference.sizes(SPEC)
    q = reference.layer_leaves(reference.init_params(3, sizes), 1)
    a = jnp.asarray(np.random.default_rng(0).standard_normal((S, 64)),
                    jnp.float32)
    chosen, weights = moe.route(a, q["moe/router"], jnp.zeros(16), top_k=4,
                                scoring="softmax")
    shares = jax.nn.softmax(jnp.matmul(a, q["moe/router"],
                                       precision="highest"), -1)
    _, want = jax.lax.top_k(shares, 4)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(want))
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(weights),
        np.asarray(jnp.take_along_axis(shares, want, -1)
                   / jnp.take_along_axis(shares, want, -1).sum(-1,
                                                               keepdims=True)),
        rtol=1e-6)
    moe_leaves = {k[len("moe/"):]: v for k, v in q.items()
                  if k.startswith("moe/")}
    got, assigned = keye_vl2.expert_ffn(
        {k: moe_leaves[k] for k in ("router", "w1", "w3", "w2")}, a,
        jnp.zeros(16), cfg)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(reference.routed_experts(
            lambda x, w: jnp.matmul(x, w, precision="highest"), moe_leaves,
            a, sizes)), rtol=2e-4, atol=2e-6)
    assert int(assigned.sum()) == 4 * S
    with pytest.raises(ValueError, match="scoring"):
        moe.route(a, q["moe/router"], jnp.zeros(16), top_k=4, scoring="tanh")


def test_the_shares_add_up_to_the_whole_layer(monkeypatch):
    """The four shares of 4 experts each against the uncut reference's
    whole ``MoE`` (no shared expert: nothing is counted twice), taken in
    parts of 16 tokens as the chip takes parts of 8,192."""
    monkeypatch.setattr(keye_vl2, "MOE_TOKENS", 16)
    cfg = config()
    sizes = dict(reference.sizes(SPEC), held=(0, 16))
    q = reference.layer_leaves(reference.init_params(3, dict(sizes)), 2)
    whole = {k[len("moe/"):]: v for k, v in q.items() if k.startswith("moe/")}
    a = jnp.asarray(np.random.default_rng(0).standard_normal((S, 64)),
                    jnp.float32)
    want = reference.routed_experts(
        lambda x, w: jnp.matmul(x, w, precision="highest"), whole, a, sizes)
    total = 0.0
    for first in range(0, 16, 4):
        rows = slice(first, first + 4)
        share = dataclasses.replace(cfg, experts_held=(first, 4))
        out, assigned = keye_vl2.expert_ffn(
            dict(whole, **{k: whole[k][rows] for k in ("w1", "w3", "w2")}),
            a, jnp.zeros(16), share)
        total = total + out
        assert int(assigned.sum()) == 4 * S
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=2e-4, atol=2e-6)


def test_tokens_that_are_no_whole_number_of_parts_are_refused(monkeypatch):
    """Past one part the expert layer takes whole parts only: it does
    not fall back to one layer over every token, whose worst-case row
    buffers the parts are there to avoid."""
    monkeypatch.setattr(keye_vl2, "MOE_TOKENS", S - 1)
    cfg = config()
    q = reference.layer_leaves(
        reference.init_params(3, reference.sizes(SPEC)), 2)
    m = {k[len("moe/"):]: v for k, v in q.items() if k.startswith("moe/")}
    with pytest.raises(ValueError, match="whole parts"):
        keye_vl2.expert_ffn(m, jnp.ones((S, 64)), jnp.zeros(16), cfg)


def _one_key_fewer(monkeypatch):
    return config(sa_config=dict(SPEC["sa_config"], topk=7))


def _no_relu(monkeypatch):
    monkeypatch.setattr(jax.nn, "relu", lambda x: x)
    return config()


def _no_head_weights(monkeypatch):
    real = keye_vl2.indexer

    def indexer(*args):
        q, k, w = real(*args)
        return q, k, jnp.ones_like(w)

    monkeypatch.setattr(keye_vl2, "indexer", indexer)
    return config()


def _no_qk_norm(monkeypatch):
    real = keye_vl2.rms_norm
    monkeypatch.setattr(
        keye_vl2, "rms_norm",
        lambda x, w, eps: x if w.shape == (16,) else real(x, w, eps))
    return config()


def _one_stream_for_all(monkeypatch):
    real = keye_vl2.rope
    monkeypatch.setattr(
        keye_vl2, "rope", lambda x, positions, inv_freq, sections=None: real(
            x, positions if sections is None else positions[0] * 2, inv_freq))
    return config()


@pytest.mark.parametrize("fault", [
    _one_key_fewer, _no_relu, _no_head_weights, _no_qk_norm,
    _one_stream_for_all,
], ids=["one_key_fewer", "no_relu", "no_head_weights", "no_qk_norm",
        "positions_doubled"])
def test_a_planted_fault_fails_the_comparison(fault, monkeypatch):
    """Each part of the mathematics left out or moved by one is far
    outside what the comparison above allows (1e-3): the sound program
    reads under 1e-4 here, every fault over 1e-2."""
    want = reference_side(7)
    assert worst_gap(program_side(7), want) < 1e-4
    faulty = worst_gap(program_side(7, fault(monkeypatch)), want)
    assert faulty > 1e-2, faulty


def test_nothing_crosses_a_document_boundary():
    """Other tokens in one document leave every other document's logits
    bit-equal: no candidate, no index score and no position reaches
    across."""
    cfg = config()
    params = init_params(3, cfg)
    tokens, segments, positions = sequence(lengths=[10, 40, 14])
    first = SPEC["deployment"]["vocab_rows_held"][0]

    def per_position(tok):
        x = seq_layers.embedding_rows(params["embed"], tok - first,
                                      jnp.float32)
        for i in cfg.kept_layers:
            x, _ = keye_vl2.block(params[f"layer_{i}"], x, jnp.zeros(16),
                                  segments, positions, cfg=cfg, layer=i)
        x = seq_layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x @ params["lm_head"].T

    changed = tokens.at[12].set(first + (tokens[12] - first + 7) % 96)
    before, after = per_position(tokens), per_position(changed)
    moved = np.flatnonzero(np.abs(np.asarray(before - after)).max(-1) > 0)
    assert moved.min() == 12 and moved.max() <= 49, moved


def test_bfloat16_compute_stays_near_float32():
    params = init_params(2, config())
    tokens, segments, positions = sequence(4)
    losses = [float(seq_layers.sequence_loss(
        params, jnp.zeros((3, 16)), tokens, segments, positions,
        cfg=config(dt), block=keye_vl2.block, saved=keye_vl2.SAVED)[0])
        for dt in ("float32", "bfloat16")]
    assert abs(losses[1] - losses[0]) < 5e-3 * abs(losses[0])


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("tie_word_embeddings", True),
    ("use_sliding_window", True), ("mlp_only_layers", [1]),
    ("decoder_sparse_step", 2), ("hidden_act", "gelu"),
    ("rope_scaling", {"mrope_section": [2, 3, 3], "rope_type": "yarn"}),
    ("sa_config", dict(SPEC["sa_config"], indexer_num_kv_heads=2)),
])
def test_a_config_the_family_does_not_have_is_refused(key, value):
    with pytest.raises(ValueError, match=key.split("_")[0]):
        config(**{key: value})


def test_a_head_the_sections_do_not_fill_is_refused():
    cfg = config(rope_scaling={"mrope_section": [2, 3, 4],
                               "rope_type": "default"})
    with pytest.raises(ValueError, match="mrope_section"):
        keye_vl2.param_shapes(cfg)
