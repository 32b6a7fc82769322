"""The ``lfm2_moe`` sequence-model family (``models/lfm2_moe.py``)
against the plain reference the benchmark keeps
(``benchmarks/references/lfm2_moe.py``: the published equations in
float32, importing nothing of the program), at a small size."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.compare import leaves
from benchmarks.references import lfm2_moe as reference
from dragonfly2_tpu.models import lfm2_moe, seq_layers
from dragonfly2_tpu.models.lfm2_moe import Lfm2MoeConfig

# The published pattern's start: two leading dense layers, a period of
# one attention layer and three convolution layers.
SPEC = {
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv"],
    "num_dense_layers": 2, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_experts_per_tok": 4,
    "num_attention_heads": 8, "num_key_value_heads": 2, "conv_L_cache": 3,
    "conv_bias": False, "norm_eps": 1e-5, "norm_topk_prob": True,
    "use_expert_bias": True, "routed_scaling_factor": 1,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "num_experts": 8, "vocab_size": 96,
    "published": {"num_experts": 32, "vocab_size": 768},
    "deployment": {"layers_kept": [0, 2, 3, 4, 5], "experts_held": [8, 8],
                   "vocab_rows_held": [96, 96]},
    "router_bias": {"beta": 0.05, "period": 8},
}
LENGTHS = [10, 30, 5, 19]
S = sum(LENGTHS)


def config(dtype="float32", **over):
    held = SPEC["deployment"]
    return Lfm2MoeConfig.from_published(
        dict(SPEC, **over), num_experts=SPEC["published"]["num_experts"],
        vocab_size=SPEC["published"]["vocab_size"],
        layers=tuple(held["layers_kept"]),
        experts_held=tuple(held["experts_held"]),
        vocab_held=tuple(held["vocab_rows_held"]), compute_dtype=dtype)


def sequence(seed=0, lengths=LENGTHS):
    rng = np.random.default_rng(seed)
    first, rows = SPEC["deployment"]["vocab_rows_held"]
    tokens = first + rng.integers(0, rows, sum(lengths))
    segments = np.repeat(np.arange(len(lengths)), lengths)
    positions = np.concatenate([np.arange(n) for n in lengths])
    return tuple(jnp.asarray(a, jnp.int32)
                 for a in (tokens, segments, positions))


def init_params(seed, cfg):
    return seq_layers.init_params(jax.random.key(seed),
                                  lfm2_moe.param_shapes(cfg))


def sequence_loss(params, bias, *sequence, cfg):
    return seq_layers.sequence_loss(params, bias, *sequence, cfg=cfg,
                                    block=lfm2_moe.block)


def bias_rows(cfg):
    return jnp.tile(jnp.asarray(reference.selection_bias(SPEC)),
                    (len(cfg.expert_layers), 1))


def test_parameters_are_the_references_own():
    """Same names, same shapes, the same draws from the seed: the
    benchmark's ``init_gap`` limit is 0."""
    cfg = config()
    ours = leaves(init_params(5, cfg))
    theirs = reference.init_params(5, reference.sizes(SPEC))
    assert sorted(ours) == sorted(theirs)
    for name in ours:
        np.testing.assert_array_equal(np.asarray(ours[name]),
                                      np.asarray(theirs[name]), name)


def test_parameter_count_of_the_benchmarks_configuration():
    """``benchmarks/configs/lfm2-24b-a2b-ep8.json``: 469,284,992
    parameters at the published widths, by part."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = json.load(open(os.path.join(
        root, "benchmarks", "configs", "lfm2-24b-a2b-ep8.json")))
    held = spec["deployment"]
    cfg = Lfm2MoeConfig.from_published(
        spec, num_experts=spec["published"]["num_experts"],
        vocab_size=spec["published"]["vocab_size"],
        layers=tuple(held["layers_kept"]),
        experts_held=tuple(held["experts_held"]),
        vocab_held=tuple(held["vocab_rows_held"]))
    by_layer = {}
    for path, shape, _ in lfm2_moe.param_shapes(cfg):
        by_layer[path[0]] = by_layer.get(path[0], 0) + int(np.prod(shape))
    assert by_layer == {
        "embed": 16_777_216, "final_norm": 2_048, "layer_0": 89_139_200,
        "layer_2": 86_118_528, "layer_3": 92_416_000, "layer_4": 92_416_000,
        "layer_5": 92_416_000}
    assert sum(by_layer.values()) == 469_284_992
    assert cfg.expert_layers == (2, 3, 4, 5) and cfg.head_dim == 64


@pytest.mark.parametrize("seed", [7, 8])
def test_loss_and_gradients_against_the_plain_reference(seed):
    cfg = config()
    params = init_params(seed, cfg)
    tokens, segments, positions = sequence(seed)
    sizes = reference.sizes(SPEC)

    def ours(p):
        return sequence_loss(
            p, bias_rows(cfg), tokens, segments, positions, cfg=cfg)

    def theirs(p):
        return reference.forward_sums(
            p, tokens, segments, positions,
            jnp.asarray(reference.selection_bias(SPEC)), 1.0, sizes,
            lambda x: x)

    (loss, counts), grads = jax.value_and_grad(ours, has_aux=True)(params)
    (want, n), want_grads = jax.value_and_grad(theirs, has_aux=True)(
        reference.init_params(seed, sizes))
    assert int(n) == int(seq_layers.target_positions(segments).sum()) == S - 4
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    for name, got in leaves(grads).items():
        scale = float(jnp.abs(want_grads[name]).max())
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want_grads[name]),
            rtol=1e-3, atol=2e-5 * scale, err_msg=name)
    # Top-4 of 32 for every token in each of the four expert layers.
    assert counts.shape == (4, 32) and (np.asarray(counts).sum(1) == 4 * S).all()


def test_the_batch_is_the_sum_of_its_sequences():
    cfg = config()
    params = init_params(1, cfg)
    rows = [sequence(seed, lengths) for seed, lengths in
            ((0, LENGTHS), (1, [64]), (2, [1, 1, 2, 60]))]
    batch = [jnp.stack(part) for part in zip(*rows)]
    loss, counts = seq_layers.batch_loss(
        params, bias_rows(cfg), *batch, cfg=cfg, block=lfm2_moe.block)
    each = [sequence_loss(params, bias_rows(cfg), *row, cfg=cfg)
            for row in rows]
    np.testing.assert_allclose(float(loss), sum(float(e[0]) for e in each),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(counts),
                                  sum(np.asarray(e[1]) for e in each))


def test_nothing_crosses_a_document_boundary():
    """Other tokens in one document leave every other document's loss
    terms bit-equal: no convolution tap, attention score or position
    reaches across."""
    cfg = config()
    params = init_params(3, cfg)
    tokens, segments, positions = sequence()
    first = SPEC["deployment"]["vocab_rows_held"][0]
    start, stop = LENGTHS[0], LENGTHS[0] + LENGTHS[1]
    changed = tokens.at[start:stop].set(
        first + (tokens[start:stop] - first + 7) % 96)

    def per_position(tok):
        """Each target position's loss term."""
        dt = jnp.float32
        x = seq_layers.embedding_rows(params["embed"], tok - first, dt)
        for i in cfg.kept_layers:
            routed = i in cfg.expert_layers
            bias = (bias_rows(cfg)[cfg.expert_layers.index(i)]
                    if routed else None)
            x, _ = lfm2_moe.block(params[f"layer_{i}"], x, bias, segments,
                                  positions, cfg=cfg, layer=i)
        x = seq_layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = x @ params["embed"].T
        nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, jnp.roll(tok - first, -1)[:, None], -1)[:, 0]
        return jnp.where(seq_layers.target_positions(segments), nll, 0.0)

    before, after = per_position(tokens), per_position(changed)
    other = np.ones(S, bool)
    other[start:stop] = False
    np.testing.assert_array_equal(np.asarray(before)[other],
                                  np.asarray(after)[other])
    assert (np.asarray(before)[start:stop - 1]
            != np.asarray(after)[start:stop - 1]).all()


def test_grouped_query_heads_against_repeated_key_value_heads():
    """Query heads 4j..4j+3 read key-value head j: the same as ordinary
    attention over key-value heads repeated four times."""
    rng = np.random.default_rng(0)
    heads, kv_heads, hd = 8, 2, 16
    q = jnp.asarray(rng.standard_normal((S, heads, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((S, kv_heads, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((S, kv_heads, hd)), jnp.float32)
    _, segments, _ = sequence()
    got = seq_layers.dense_attention(q, k, v, segments)
    k_all, v_all = (jnp.repeat(a, heads // kv_heads, axis=1) for a in (k, v))
    at = jnp.arange(S)
    seen = (at[:, None] >= at[None, :]) & (
        segments[:, None] == segments[None, :])
    scores = jnp.einsum("shd,thd->hst", q, k_all)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    want = jnp.einsum("hst,thd->shd", probs, v_all)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("window,groups", [(None, 2), (100, 2), (100, 3)],
                         ids=["causal", "window", "window_groups_of_3"])
def test_the_attention_kernel_is_the_plain_attention(window, groups,
                                                     monkeypatch):
    """The TPU kernel's path (here in interpret mode) against the plain
    form, with documents and grouped heads, values and gradients;
    with a window (the kernel's local mask, 128-wide tiles so that some
    lie wholly before it and are skipped) the documents are both
    shorter and longer than it."""
    rng = np.random.default_rng(1)
    s, kv_heads, hd = 256, 2, 64
    heads = groups * kv_heads
    q, k, v = (jnp.asarray(rng.standard_normal((s, h, hd)) * 0.3, jnp.float32)
               for h in (heads, kv_heads, kv_heads))
    segments = jnp.asarray(np.repeat([0, 1, 2], [100, 28, 128]), jnp.int32)

    def total(fn, q, k, v):
        return (fn(q, k, v, segments, window) ** 2).sum()

    def kernel(q, k, v, seg, window):
        return seq_layers.kernel_attention(q, k, v, seg, window,
                                           interpret=True)

    monkeypatch.setattr(seq_layers, "WINDOW_BLOCK", 128)
    np.testing.assert_allclose(
        np.asarray(kernel(q, k, v, segments, window)),
        np.asarray(seq_layers.dense_attention(q, k, v, segments, window)),
        rtol=2e-3, atol=2e-3)
    got = jax.grad(lambda *a: total(kernel, *a), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: total(seq_layers.dense_attention, *a),
                    argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-2, atol=2e-3)


# Six documents in 1,024 tokens: at 128-wide tiles they reach 17 of the
# 36 tiles on or under the diagonal.
DOCUMENTS = [100, 28, 300, 40, 200, 356]


def _library_tables(mask, block, backward):
    """``block_mask`` and ``data_next`` as the kernel's own mask
    processing makes them for a static mask, the backward's unshrunk as
    the fused kernel takes it."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as masks,
        splash_attention_mask_info as mask_info,
    )

    if isinstance(mask, np.ndarray):
        mask = masks.NumpyMask(mask)
    heads = masks.MultiHeadMask([mask] * 2)
    info, _ = (mask_info.process_mask_dkv(heads, (block, block),
                                          shrink_grid=False)
               if backward else
               mask_info.process_mask(heads, (block, block),
                                      shrink_grid=not isinstance(
                                          mask, masks.NumpyMask)))
    return np.asarray(info.block_mask), np.asarray(info.data_next)


@pytest.mark.parametrize("backward", [False, True], ids=["forward", "dkv"])
@pytest.mark.parametrize("block", [128, 256, 512])
def test_one_documents_tile_tables_are_the_librarys(block, backward):
    """A row that is one document: every causal tile kept, and the
    computed ``data_next`` is ``process_mask``'s (forward) and
    ``process_mask_dkv``'s (backward) for the static causal mask,
    element for element."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as masks,
    )

    s = 1024
    keep, *following = seq_layers.document_tile_tables(
        jnp.full(s, 7, jnp.int32), block)
    block_mask, data_next = _library_tables(
        masks.CausalMask((s, s)), block, backward)
    np.testing.assert_array_equal(
        np.where(np.asarray(keep), block_mask, 0), block_mask)
    np.testing.assert_array_equal(
        np.asarray(following[backward])[None], data_next)


@pytest.mark.parametrize("backward", [False, True], ids=["forward", "dkv"])
@pytest.mark.parametrize("block", [128, 256])
def test_several_documents_tile_tables_are_the_librarys(block, backward):
    """With documents the convention still holds: ``data_next`` equals
    what the library computes for a static mask whose tiles are the
    kept ones."""
    segments = np.repeat(np.arange(len(DOCUMENTS)), DOCUMENTS)
    keep, *following = seq_layers.document_tile_tables(
        jnp.asarray(segments, jnp.int32), block)
    keep = np.asarray(keep)
    assert keep.sum() == {128: 17, 256: 7}[block]
    block_mask, data_next = _library_tables(
        np.kron(keep, np.ones((block, block), bool)), block, backward)
    np.testing.assert_array_equal(block_mask[0] > 0, keep)
    np.testing.assert_array_equal(
        np.asarray(following[backward])[None], data_next)


def _tiles_with_a_pair(segments, block):
    """Brute force: some pair of the two blocks shares an id and is
    causal."""
    at = np.arange(len(segments))
    seen = (at[:, None] >= at[None, :]) & (
        segments[:, None] == segments[None, :])
    n = len(segments) // block
    return seen.reshape(n, block, n, block).any((1, 3))


@pytest.mark.parametrize("array", [np.asarray, jnp.asarray],
                         ids=["numpy", "jax"])
@pytest.mark.parametrize("ids", ["sorted", "shuffled"])
@pytest.mark.parametrize("block", [16, 64])
def test_kept_tiles_against_every_pair(block, ids, array):
    """``document_tiles`` against the brute force: equal for a packer's
    non-decreasing ids, a superset (never a tile with a pair dropped)
    for ids out of order and repeated; the same rule on ``numpy`` and
    ``jax`` arrays, and over a batch of rows."""
    rng = np.random.default_rng(block)
    rows = []
    for _ in range(6):
        lengths = rng.integers(1, 120, 40)
        row = np.repeat(np.arange(40), lengths)[:512].astype(np.int32)
        if ids == "shuffled":
            # Ids permuted (out of order) and folded (repeated: two
            # documents apart share an id).
            row = rng.permutation(40)[row] % 11
        rows.append(row)
    rows = np.stack(rows)
    keep = seq_layers.document_tiles(array(rows), block)
    assert isinstance(keep, np.ndarray) == (array is np.asarray)
    keep = np.asarray(keep)
    assert keep.shape == (6, 512 // block, 512 // block)
    for row, kept in zip(rows, keep):
        pairs = _tiles_with_a_pair(row, block)
        if ids == "sorted":
            np.testing.assert_array_equal(kept, pairs)
        else:
            assert (kept | ~pairs).all()
            assert not np.triu(kept, 1).any()
        np.testing.assert_array_equal(
            kept, np.asarray(seq_layers.document_tiles(array(row), block)))


@pytest.mark.parametrize("ids", ["in_order", "out_of_order"])
def test_the_attention_kernel_skips_tiles_no_document_reaches(ids,
                                                              monkeypatch):
    """The kernel's path with computed tile tables (interpret mode,
    128-wide tiles, six documents: 17 of 36 tiles kept) against the
    plain form, values and gradients, and the same row with its ids
    permuted out of order (the rule keeps more tiles, never fewer than
    hold a pair)."""
    rng = np.random.default_rng(3)
    s, kv_heads, hd, groups = sum(DOCUMENTS), 2, 64, 2
    q, k, v = (jnp.asarray(rng.standard_normal((s, h, hd)) * 0.3, jnp.float32)
               for h in (groups * kv_heads, kv_heads, kv_heads))
    names = (np.arange(len(DOCUMENTS)) if ids == "in_order"
             else np.array([4, 0, 5, 2, 1, 3]))
    segments = np.repeat(names, DOCUMENTS).astype(np.int32)
    monkeypatch.setattr(seq_layers, "ATTENTION_BLOCK", 128)
    kept = int(seq_layers.document_tiles(segments, 128).sum())
    assert kept == 17 if ids == "in_order" else 17 < kept < 36
    segments = jnp.asarray(segments)

    def kernel(q, k, v, seg):
        return seq_layers.kernel_attention(q, k, v, seg, interpret=True)

    def total(fn, q, k, v):
        return (fn(q, k, v, segments) ** 2).sum()

    np.testing.assert_allclose(
        np.asarray(jax.jit(kernel)(q, k, v, segments)),
        np.asarray(seq_layers.dense_attention(q, k, v, segments)),
        rtol=2e-3, atol=2e-3)
    got = jax.jit(jax.grad(lambda *a: total(kernel, *a),
                           argnums=(0, 1, 2)))(q, k, v)
    want = jax.grad(lambda *a: total(seq_layers.dense_attention, *a),
                    argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-2, atol=2e-3)


def test_a_window_is_the_causal_mask_cut_at_its_length():
    """``t - s < window`` and the token itself counts: a window as long
    as the sequence changes nothing, a window of 1 returns each token's
    own value, and a key ``window`` back is not seen."""
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.standard_normal((S, h, 16)), jnp.float32)
               for h in (4, 2, 2))
    _, segments, _ = sequence()
    whole = seq_layers.dense_attention(q, k, v, segments)
    np.testing.assert_array_equal(
        np.asarray(seq_layers.dense_attention(q, k, v, segments, S)),
        np.asarray(whole))
    own = seq_layers.dense_attention(q, k, v, segments, 1)
    np.testing.assert_allclose(np.asarray(own),
                               np.asarray(jnp.repeat(v, 2, axis=1)),
                               rtol=1e-6)
    # Position 25 lies in the second document (10 .. 39): with a window
    # of 8 it sees keys 18 .. 25, so key 17 moves nothing and 18 does.
    for key, moves in ((17, False), (18, True)):
        other = v.at[key].add(1.0)
        out = seq_layers.dense_attention(q, k, other, segments, 8)
        base = seq_layers.dense_attention(q, k, v, segments, 8)
        assert bool(jnp.any(out[25] != base[25])) is moves


def test_embedding_gradient_is_the_scatter_adds():
    table = jnp.asarray(np.random.default_rng(0).standard_normal((12, 8)),
                        jnp.float32)
    ids = jnp.asarray([3, 3, 0, 11, 3, 7], jnp.int32)
    probe = jnp.asarray(np.random.default_rng(1).standard_normal((6, 8)),
                        jnp.float32)
    got = jax.grad(lambda t: (seq_layers.embedding_rows(
        t, ids, jnp.float32) * probe).sum())(table)
    want = jax.grad(lambda t: (t[ids] * probe).sum())(table)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_bfloat16_compute_stays_near_float32():
    """The configuration's precision: bfloat16 products, float32
    parameters, norms, router and loss."""
    params = init_params(2, config())
    tokens, segments, positions = sequence(4)
    losses = [float(sequence_loss(
        params, bias_rows(config(dt)), tokens, segments, positions,
        cfg=config(dt))[0]) for dt in ("float32", "bfloat16")]
    assert abs(losses[1] - losses[0]) < 5e-3 * abs(losses[0])


def test_config_refuses_what_the_family_does_not_have():
    with pytest.raises(ValueError, match="conv_bias"):
        config(conv_bias=True)
    with pytest.raises(ValueError, match="layer type"):
        lfm2_moe.param_shapes(config(
            layer_types=["conv", "conv", "sliding", "conv", "conv", "conv"]))


def head_case(weighting, seed=3):
    """A head, a state ``[S, 64]``, targets and weights over ``sequence(1)``'s
    documents, in float32: ``weighting`` ``"mask"`` (the counted positions,
    as the non-looped families weight them) or ``"mixed"`` (a fraction on
    some counted positions, 0 on the rest of them and on the uncounted:
    a looped family's exit distribution, and zeros)."""
    rng = np.random.default_rng(seed)
    head = jnp.asarray(rng.standard_normal((96, 64)) * 0.1, jnp.float32)
    x = jnp.asarray(rng.standard_normal((S, 64)), jnp.float32)
    targets = jnp.asarray(rng.integers(0, 96, S), jnp.int32)
    counted = np.asarray(seq_layers.target_positions(sequence(1)[1]))
    weights = (counted.astype(np.float32) if weighting == "mask" else
               np.where(counted & (rng.random(S) > 0.3), rng.random(S), 0))
    return head, x, targets, jnp.asarray(weights, jnp.float32)


def plain_head(head, x, targets, weights):
    """``Σ weights·nll`` written plainly, for autodiff: the whole call's
    logits at once, the backward pass making them again."""
    logits = jnp.matmul(x, head.astype(x.dtype).T,
                        preferred_element_type=jnp.float32)
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, targets[:, None], -1)[:, 0]
    return (weights * nll).sum()


@pytest.mark.parametrize("blocks", [1, 4])
@pytest.mark.parametrize("weighting", ["mask", "mixed"])
def test_the_heads_gradient_is_plain_autodiffs(monkeypatch, blocks,
                                               weighting):
    """The head forms its gradients in its forward pass: its value and
    the gradients of its input, its rows and its weights are plain
    autodiff's of the same sum, in one block and in several (float32:
    the same products in another order, within 1e-6 of the loss and
    1e-6 of a gradient's largest element); a position of weight 0 gets
    a gradient of exactly 0."""
    head, x, targets, weights = head_case(weighting)
    if blocks > 1:
        monkeypatch.setattr(seq_layers, "HEAD_BLOCK", S // blocks)
    args = (head, x, targets, weights)
    loss, grads = jax.value_and_grad(seq_layers.head_loss,
                                     argnums=(0, 1, 3))(*args)
    want, want_grads = jax.value_and_grad(plain_head, argnums=(0, 1, 3))(
        *args)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)
    for got, expected, name in zip(grads, want_grads,
                                   ("rows", "input", "weights")):
        scale = float(jnp.abs(expected).max())
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                                   rtol=1e-5, atol=1e-6 * scale,
                                   err_msg=name)
    left_out = np.asarray(weights) == 0
    assert left_out.any() and not left_out.all()
    assert (np.asarray(grads[1])[left_out] == 0).all()
    # Undifferentiated, the same sum.
    np.testing.assert_allclose(float(seq_layers.head_loss(*args)),
                               float(want), rtol=1e-6)


@pytest.mark.parametrize("blocks", [2, 4])
def test_the_head_in_position_blocks_is_the_head(monkeypatch, blocks):
    """A call longer than ``HEAD_BLOCK`` takes its logits and loss a
    block of positions at a time (the third family's 32k sequences, a
    looped family's exits): the loss and every gradient are the whole
    head's, and the blocks are a loop, differentiated or not."""
    head, x, targets, weights = head_case("mixed")

    def loss(head, x, weights):
        return seq_layers.head_loss(head, x, targets, weights)

    whole = jax.value_and_grad(loss, argnums=(0, 1, 2))(head, x, weights)
    monkeypatch.setattr(seq_layers, "HEAD_BLOCK", S // blocks)
    parts = jax.value_and_grad(loss, argnums=(0, 1, 2))(head, x, weights)
    assert "scan" in str(jax.make_jaxpr(loss)(head, x, weights))
    assert "scan" in str(jax.make_jaxpr(jax.grad(loss))(head, x, weights))
    np.testing.assert_allclose(float(parts[0]), float(whole[0]), rtol=1e-6)
    for got, want in zip(parts[1], whole[1]):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


def test_a_length_that_is_no_whole_number_of_head_blocks_is_refused(
        monkeypatch):
    """Past one block the head takes whole blocks only: it does not fall
    back to the whole sequence's logits, which is what the blocks are
    there to avoid. One block or less is taken as it is."""
    head, x = jnp.ones((96, 64)), jnp.ones((S, 64))
    targets, weights = jnp.zeros(S, jnp.int32), jnp.ones(S)
    monkeypatch.setattr(seq_layers, "HEAD_BLOCK", S - 1)
    with pytest.raises(ValueError, match="whole blocks"):
        seq_layers.head_loss(head, x, targets, weights)
    with pytest.raises(ValueError, match="whole blocks"):
        jax.grad(seq_layers.head_loss)(head, x, targets, weights)
    monkeypatch.setattr(seq_layers, "HEAD_BLOCK", S + 1)
    assert seq_layers.head_blocks(S) == 1
    assert "scan" not in str(jax.make_jaxpr(jax.grad(
        lambda x: seq_layers.head_loss(head, x, targets, weights)))(x))


def test_parameters_are_drawn_set_to_one_or_set_to_zero():
    """``init_params`` draws ``normal``, sets ``ones`` and ``zeros`` (a
    layer norm's bias, the third family's)."""
    params = seq_layers.init_params(jax.random.key(0), [
        (("a", "w"), (4, 3), "normal"), (("a", "scale"), (3,), "ones"),
        (("a", "bias"), (3,), "zeros")])
    assert np.asarray(params["a"]["bias"]).tolist() == [0.0] * 3
    assert np.asarray(params["a"]["scale"]).tolist() == [1.0] * 3
    assert np.asarray(params["a"]["w"]).std() > 0


@pytest.mark.parametrize("cell", [
    "lfm2-24b-a2b-ep8.train", "laguna-xs2-ep32.train",
    "keye-vl2-30b-a3b-ep16.train"])
def test_the_heads_per_position_terms_sum_to_its_sum(cell):
    """The head's per-position terms (the gradient of its weights: each
    position's cross-entropy, which a looped family's exit distribution
    weighs) summed over the counted positions are the sum the three
    other families' steps take, with the counted positions as the
    weights, bit for bit, eagerly and compiled, on a row of each cell's
    own traffic at its rehearsal sizes; and a position's term is the
    head's sum with a weight of 1 there alone."""
    import importlib
    from types import SimpleNamespace

    from benchmarks import run

    _, _, _, spec = run.load_cell(cell, rehearse=True)
    row = {k: jnp.asarray(v[0]) for k, v in importlib.import_module(
        f"benchmarks.runners.{spec['kind']}").traffic(spec, 3).items()}
    first, rows = spec["deployment"]["vocab_rows_held"]
    d = spec["hidden_size"]
    cfg = SimpleNamespace(norm_eps=spec.get("rms_norm_eps",
                                            spec.get("norm_eps")))
    rng = np.random.default_rng(5)
    head = jnp.asarray(rng.normal(0, 0.02, (rows, d)), jnp.float32)
    norm = jnp.asarray(1 + 0.1 * rng.standard_normal(d), jnp.float32)
    x = seq_layers.rms_norm(jnp.asarray(
        rng.standard_normal((row["tokens"].shape[0], d)), jnp.bfloat16),
        norm, cfg.norm_eps)
    targets = jnp.roll(row["tokens"] - first, -1)
    counted = seq_layers.target_positions(row["segments"])
    mask = counted.astype(jnp.float32)

    def summed(x):
        return seq_layers.head_loss(head, x, targets, mask)

    def terms(x):
        return jax.grad(lambda w: seq_layers.head_loss(head, x, targets, w))(
            mask)

    each = terms(x)
    assert each.shape == targets.shape
    assert float((mask * each).sum()) == float(summed(x))
    assert float(jax.jit(lambda x: (mask * terms(x)).sum())(x)) == float(
        jax.jit(summed)(x))
    for at in (0, int(np.flatnonzero(~np.asarray(counted))[0])):
        one = jnp.zeros_like(mask).at[at].set(1)
        assert float(seq_layers.head_loss(head, x, targets, one)) == float(
            each[at])
