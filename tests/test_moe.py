"""The expert layer (``parallel/moe.py``): top-k routing over all
experts, computed for the experts held here, against a dense form that
applies every expert to every token and masks by the selection."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dragonfly2_tpu.parallel.moe import (
    ROW_TILE,
    expert_layer,
    group_tiles,
    route,
)

T, D, F, E, K = 48, 16, 24, 16, 4
# The second expert cell's shape class (``laguna-xs2-ep32``): top-8 of
# 256 fine-grained experts, 8 held, one 8k sequence, so that a held
# expert's group is around one ``ROW_TILE`` of rows (65,536 assignments,
# 2,048 of them held on average, 256 an expert) and the ordinary row
# buffer is 4,096 rows. The widths stay small: the CPU computes them.
MANY = {"tokens": 8192, "experts": 256, "top_k": 8, "held": (16, 8)}


def make(seed=0, experts=E, tokens=T):
    rng = np.random.default_rng(seed)
    return {
        "x": jnp.asarray(rng.standard_normal((tokens, D)), jnp.float32),
        "router": jnp.asarray(rng.standard_normal((D, experts)) * 0.5,
                              jnp.float32),
        "bias": jnp.asarray(rng.standard_normal(experts) * 0.1, jnp.float32),
        "w1": jnp.asarray(rng.standard_normal((experts, D, F)) * 0.2,
                          jnp.float32),
        "w3": jnp.asarray(rng.standard_normal((experts, D, F)) * 0.2,
                          jnp.float32),
        "w2": jnp.asarray(rng.standard_normal((experts, F, D)) * 0.2,
                          jnp.float32),
    }


def dense_layer(p, bias=None, top_k=K, held=None):
    """Every expert (or those of ``held = (first, count)`` alone) on
    every token, masked by the selection: the whole layer's result (the
    held experts' part of it), and the per-expert assignment counts."""
    bias = p["bias"] if bias is None else bias
    scores = jax.nn.sigmoid(jnp.matmul(p["x"], p["router"],
                                       precision="highest"))
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    weights = jnp.take_along_axis(scores, chosen, -1)
    weights = weights / (weights.sum(-1, keepdims=True) + 1e-6)
    out = 0.0
    first, count = held or (0, p["router"].shape[1])
    for e in range(first, first + count):
        w_e = jnp.where(chosen == e, weights, 0.0).sum(-1)
        hidden = jax.nn.silu(p["x"] @ p["w1"][e]) * (p["x"] @ p["w3"][e])
        out = out + w_e[:, None] * (hidden @ p["w2"][e])
    counts = np.bincount(np.asarray(chosen).ravel(),
                         minlength=p["router"].shape[1])
    return out, counts


def share(p, first, count, bias=None, top_k=K):
    rows = slice(first, first + count)
    return expert_layer(
        p["x"], p["router"], p["bias"] if bias is None else bias,
        p["w1"][rows], p["w3"][rows], p["w2"][rows], (first, count),
        top_k=top_k)


@pytest.mark.parametrize("count", [2, 4, 16])
def test_the_shares_add_up_to_the_whole_layer(count):
    """What each device of the group computes for the experts it holds
    sums to the uncut layer; selection and weights are over all experts
    on every device."""
    p = make()
    whole, counts = dense_layer(p)
    total = 0.0
    for first in range(0, E, count):
        out, assigned = jax.jit(
            lambda p, first=first: share(p, first, count))(p)
        total = total + out
        np.testing.assert_array_equal(np.asarray(assigned), counts)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=2e-5, atol=2e-6)
    assert counts.sum() == T * K


def test_nothing_is_dropped_when_every_token_goes_to_held_experts():
    """The worst case: a bias sends all T·k assignments to the experts
    held here, and the result is still the whole layer's."""
    p = make(1)
    bias = jnp.where(jnp.arange(E) < K, 100.0, 0.0)
    whole, counts = dense_layer(p, bias)
    out, assigned = share(p, 0, K, bias)
    assert int(assigned[:K].sum()) == T * K and counts[:K].sum() == T * K
    np.testing.assert_allclose(np.asarray(out), np.asarray(whole),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("second", [-10.0, 0.0])
def test_the_short_and_the_long_row_buffer_agree(second):
    """Two experts of 16 are held: their assignments end within twice
    their expected number (48 rows of 192) in an ordinary step, and the
    row buffers are that short. Every token on expert 0 and none on
    expert 1 is exactly 48; any token on expert 1 besides is more, and
    the step takes the worst-case buffers. Either way the result is the
    dense form's."""
    p = make(8)
    bias = jnp.zeros(E).at[0].set(10.0).at[1].set(second)
    whole, counts = dense_layer(p, bias)
    assert counts[0] == T
    assert (counts[1] == 0) if second else (counts[1] > 0)
    held = (jnp.arange(E) < 2)[:, None, None]
    want, _ = dense_layer(dict(p, w2=jnp.where(held, p["w2"], 0.0)), bias)
    out, assigned = jax.jit(lambda p: share(p, 0, 2, bias))(p)
    np.testing.assert_array_equal(np.asarray(assigned), counts)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-6)
    grads = jax.grad(lambda x: (share(dict(p, x=x), 0, 2, bias)[0] ** 2
                                ).sum())(p["x"])
    want_grads = jax.grad(lambda x: (dense_layer(dict(
        p, x=x, w2=jnp.where(held, p["w2"], 0.0)), bias)[0] ** 2).sum())(
        p["x"])
    np.testing.assert_allclose(np.asarray(grads), np.asarray(want_grads),
                               rtol=2e-4, atol=2e-6)


def many_experts(crowd: float):
    """The inputs of the ``MANY`` shape class: held expert 19 gets a
    bias that empties it, and the others of the 8 held ``crowd`` (0: an
    ordinary step's load, the 4,096-row buffer; 100: every token on all
    seven, 57,344 rows, the worst-case buffer of 65,536; -100: every
    token on the first of them alone, some 10,000 rows, the 16,384-row
    buffer between the two)."""
    p = make(11, MANY["experts"], MANY["tokens"])
    first, count = MANY["held"]
    at = jnp.arange(MANY["experts"])
    if crowd < 0:
        return p, jnp.zeros(MANY["experts"]).at[first].set(100.0).at[19].set(
            -100.0)
    bias = jnp.where((at >= first) & (at < first + count), crowd, 0.0)
    return p, bias.at[19].set(-100.0)


@pytest.mark.parametrize("crowd,rows", [
    (0.0, "usual"), (-100.0, "between"), (100.0, "every")])
def test_many_small_experts_one_of_them_empty(crowd, rows):
    """Top-8 of 256 with 8 held, groups around one row tile, one held
    expert with no row at all, under each length of row buffer: the
    result is the dense form's part of the held experts."""
    p, bias = many_experts(crowd)
    first, count = MANY["held"]
    want, counts = dense_layer(p, bias, MANY["top_k"], MANY["held"])
    out, assigned = jax.jit(lambda p: share(
        p, first, count, bias, MANY["top_k"]))(p)
    np.testing.assert_array_equal(np.asarray(assigned), counts)
    held = counts[first:first + count]
    every = MANY["tokens"] * MANY["top_k"]
    usual = 2 * every * count // MANY["experts"]
    assert counts.sum() == every and held[19 - first] == 0
    if rows == "usual":
        # Groups of about one row tile each, within the ordinary buffer.
        assert held.sum() <= usual == 4096
        assert 0.5 * ROW_TILE < np.delete(held, 19 - first).mean() < 1.5 * ROW_TILE
    elif rows == "between":
        assert usual < held.sum() <= 4 * usual and held[0] == MANY["tokens"]
    else:
        assert held.sum() == 7 * MANY["tokens"] > 4 * usual
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("m,k,n,tiles", [
    # lfm2-24b-a2b-ep8: 2048 x 1536 experts; the backward's transposes.
    (4096, 2048, 1536, (256, 1024, 512)), (4096, 1536, 2048, (256, 512, 1024)),
    # laguna-xs2-ep32: 2048 x 512 experts, 4,096- and 65,536-row buffers.
    (4096, 2048, 512, (256, 1024, 512)), (65536, 512, 2048, (256, 512, 1024)),
    # keye-vl2-30b-a3b-ep16: 2048 x 768 experts, which neither 1,024 nor
    # 512 divides, in 8,192- and 65,536-row buffers.
    (8192, 2048, 768, (256, 1024, 256)), (65536, 768, 2048, (256, 256, 1024)),
    # A width that no tile divides: no kernel (the plain grouped product).
    (4096, 2048, 24, (256, 1024, None)),
])
def test_group_tiles(m, k, n, tiles):
    assert group_tiles(m, k, n) == tiles


def test_no_token_for_the_held_experts_gives_zero():
    p = make(2)
    bias = jnp.where(jnp.arange(E) < K, -100.0, 0.0)
    out, assigned = share(p, 0, K, bias)
    assert int(assigned[:K].sum()) == 0
    np.testing.assert_array_equal(np.asarray(out), 0.0)
    grads = jax.grad(lambda x: share(dict(p, x=x), 0, K, bias)[0].sum())(
        p["x"])
    assert np.isfinite(np.asarray(grads)).all()


def test_the_selection_bias_selects_and_does_not_weigh():
    """The bias changes who is selected; a selected expert's weight is
    its score without the bias, normalised over the selected."""
    p = make(3)
    scores = jax.nn.sigmoid(jnp.matmul(p["x"], p["router"],
                                       precision="highest"))
    plain, w_plain = route(p["x"], p["router"], jnp.zeros(E), top_k=K)
    # A constant bias moves no selection and no weight.
    same, w_same = route(p["x"], p["router"], jnp.full(E, 0.7), top_k=K)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(same))
    np.testing.assert_array_equal(np.asarray(w_plain), np.asarray(w_same))
    # A large bias on expert 5 selects it for every token, at the weight
    # its own score gives it.
    biased, w_biased = route(p["x"], p["router"],
                             jnp.zeros(E).at[5].set(10.0), top_k=K)
    assert (np.asarray(biased) == 5).any(-1).all()
    assert not (np.asarray(plain) == 5).any(-1).all()
    picked = jnp.take_along_axis(scores, biased, -1)
    np.testing.assert_allclose(
        np.asarray(w_biased),
        np.asarray(picked / (picked.sum(-1, keepdims=True) + 1e-6)),
        rtol=1e-6)


@pytest.mark.parametrize("first,count,crowd", [
    (4, 8, None), (6, 2, None), (16, 8, 0.0), (16, 8, -100.0),
    (16, 8, 100.0)],
    ids=["8_of_16", "2_of_16", "8_of_256_usual", "8_of_256_between",
         "8_of_256_every"])
@pytest.mark.parametrize("name", ["x", "router", "w1", "w3", "w2"])
def test_gradients_against_the_dense_form(name, first, count, crowd):
    """The gather-only backward (custom VJPs of the two row moves)
    against autodiff of the dense form, for a share of the experts: 8
    of 16 (one length of row buffer), 2 of 16 (the short buffer of an
    ordinary step, under ``lax.cond``), and the ``MANY`` shape class
    (top-8, 8 of 256, one held expert empty) under each of its three
    buffers."""
    if crowd is None:
        p, bias, top_k = make(4), None, K
    else:
        (p, bias), top_k = many_experts(crowd), MANY["top_k"]
    probe = jnp.asarray(np.random.default_rng(9).standard_normal(
        p["x"].shape), jnp.float32)

    def ours(value):
        q = dict(p, **{name: value})
        return (share(q, first, count, bias, top_k)[0] * probe).sum()

    def dense(value):
        # The held experts' part of the dense form.
        q = dict(p, **{name: value})
        return (dense_layer(q, bias, top_k, (first, count))[0] * probe).sum()

    got, want = jax.grad(ours)(p[name]), jax.grad(dense)(p[name])
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-6 * max(scale, 1.0))


@pytest.mark.parametrize("top_k", [1, 2])
def test_other_top_k(top_k):
    p = make(5)
    whole, counts = dense_layer(p, top_k=top_k)
    out, assigned = share(p, 0, E, top_k=top_k)
    np.testing.assert_array_equal(np.asarray(assigned), counts)
    np.testing.assert_allclose(np.asarray(out), np.asarray(whole),
                               rtol=2e-5, atol=2e-6)


def test_products_run_in_the_tokens_dtype():
    p = make(6)
    low = dict(p, x=p["x"].astype(jnp.bfloat16))
    out, _ = share(low, 0, E)
    whole, _ = dense_layer(p)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(whole),
                               rtol=0.1, atol=0.05)


def test_held_must_match_the_stacked_weights():
    p = make(7)
    with pytest.raises(ValueError, match="held"):
        expert_layer(p["x"], p["router"], p["bias"], p["w1"][:4],
                     p["w3"][:4], p["w2"][:4], (0, 8), top_k=K)
    with pytest.raises(ValueError, match="held"):
        expert_layer(p["x"], p["router"], p["bias"], p["w1"][:4],
                     p["w3"][:4], p["w2"][:4], (14, 4), top_k=K)


def softmax_layer(p, top_k=K, held=None):
    """:func:`dense_layer` under the softmax router: shares over all
    experts, the ``top_k`` largest, divided by their sum; no bias."""
    shares = jax.nn.softmax(jnp.matmul(p["x"], p["router"],
                                       precision="highest"), -1)
    _, chosen = jax.lax.top_k(shares, top_k)
    weights = jnp.take_along_axis(shares, chosen, -1)
    weights = weights / weights.sum(-1, keepdims=True)
    out = 0.0
    first, count = held or (0, p["router"].shape[1])
    for e in range(first, first + count):
        w_e = jnp.where(chosen == e, weights, 0.0).sum(-1)
        hidden = jax.nn.silu(p["x"] @ p["w1"][e]) * (p["x"] @ p["w3"][e])
        out = out + w_e[:, None] * (hidden @ p["w2"][e])
    return out, np.bincount(np.asarray(chosen).ravel(),
                            minlength=p["router"].shape[1])


def softmax_share(p, first, count):
    rows = slice(first, first + count)
    return expert_layer(
        p["x"], p["router"], jnp.zeros(p["router"].shape[1]), p["w1"][rows],
        p["w3"][rows], p["w2"][rows], (first, count), top_k=K,
        scoring="softmax")


@pytest.mark.parametrize("count", [4, 16])
def test_the_shares_add_up_under_the_softmax_router(count):
    """The third family's router (``scoring="softmax"``): the shares sum
    to the uncut layer as under the sigmoid one, and the weights of a
    token's selected experts sum to 1 exactly (no guard term)."""
    p = make(8)
    whole, counts = softmax_layer(p)
    total = 0.0
    for first in range(0, E, count):
        out, assigned = jax.jit(softmax_share, static_argnums=(1, 2))(
            p, first, count)
        np.testing.assert_array_equal(np.asarray(assigned), counts)
        total = total + out
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=2e-5, atol=2e-6)
    _, weights = route(p["x"], p["router"], jnp.zeros(E), top_k=K,
                       scoring="softmax")
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0, rtol=1e-6)


@pytest.mark.parametrize("name", ["x", "router", "w1", "w3", "w2"])
def test_gradients_under_the_softmax_router(name):
    p = make(9)
    probe = jnp.asarray(np.random.default_rng(9).standard_normal(
        p["x"].shape), jnp.float32)

    def ours(value):
        return (softmax_share(dict(p, **{name: value}), 4, 8)[0] * probe).sum()

    def dense(value):
        return (softmax_layer(dict(p, **{name: value}), held=(4, 8))[0]
                * probe).sum()

    got, want = jax.grad(ours)(p[name]), jax.grad(dense)(p[name])
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-6 * max(scale, 1.0))


def test_the_sigmoid_router_is_the_default_and_unchanged():
    """``scoring`` left at its default is the two older families'
    router to the bit: sigmoid scores, the guard term in the sum."""
    p = make(3)
    scores = jax.nn.sigmoid(jnp.matmul(p["x"], p["router"],
                                       precision="highest"))
    _, chosen = jax.lax.top_k(scores + p["bias"], K)
    picked = jnp.take_along_axis(scores, chosen, -1)
    got_chosen, got = route(p["x"], p["router"], p["bias"], top_k=K)
    named_chosen, named = route(p["x"], p["router"], p["bias"], top_k=K,
                                scoring="sigmoid")
    np.testing.assert_array_equal(np.asarray(got_chosen), np.asarray(chosen))
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(picked / (picked.sum(-1, keepdims=True) + 1e-6)))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(named))
    np.testing.assert_array_equal(np.asarray(got_chosen),
                                  np.asarray(named_chosen))
    with pytest.raises(ValueError, match="scoring"):
        route(p["x"], p["router"], p["bias"], top_k=K, scoring="relu")
