"""The ``laguna`` sequence-model family (``models/laguna.py``) against
the plain reference the benchmark keeps
(``benchmarks/references/laguna.py``: the published equations in
float32, importing nothing of the program), at a small size: hidden 64,
2 key-value heads with groups of 2 (full layers) and 3 (sliding ones),
window 8 on 64-token sequences whose documents are both shorter and
longer than it, 16 experts top-4 with 4 held."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.compare import leaves
from benchmarks.references import laguna as reference
from dragonfly2_tpu.models import laguna, seq_layers
from dragonfly2_tpu.models.laguna import LagunaConfig

TYPES = ["full_attention", "sliding_attention", "sliding_attention",
         "sliding_attention", "full_attention", "sliding_attention"]
SPEC = {
    "model_type": "laguna", "layer_types": TYPES,
    "mlp_layer_types": ["dense"] + ["sparse"] * 5,
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4, 6],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 24, "num_experts_per_tok": 4,
    "rms_norm_eps": 1e-6, "sliding_window": 8, "gating": True,
    "attention_bias": False, "tie_word_embeddings": False,
    "moe_apply_router_weight_on_input": False,
    "moe_routed_scaling_factor": 2.5,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "num_experts": 4, "vocab_size": 96,
    "published": {"num_experts": 16, "vocab_size": 768},
    "deployment": {"layers_kept": [0, 1, 2, 3, 4], "experts_held": [4, 4],
                   "vocab_rows_held": [96, 96]},
}
# Documents shorter than the window of 8, and longer.
LENGTHS = [5, 30, 3, 19, 7]
S = sum(LENGTHS)


def config(dtype="float32", spec=SPEC, **over):
    held = spec["deployment"]
    return LagunaConfig.from_published(
        dict(spec, **over), num_experts=spec["published"]["num_experts"],
        vocab_size=spec["published"]["vocab_size"],
        layers=tuple(held["layers_kept"]),
        experts_held=tuple(held["experts_held"]),
        vocab_held=tuple(held["vocab_rows_held"]), compute_dtype=dtype)


def init_params(seed, cfg):
    return seq_layers.init_params(jax.random.key(seed),
                                  laguna.param_shapes(cfg))


def sequence(seed=0, lengths=LENGTHS):
    rng = np.random.default_rng(seed)
    first, rows = SPEC["deployment"]["vocab_rows_held"]
    tokens = first + rng.integers(0, rows, sum(lengths))
    segments = np.repeat(np.arange(len(lengths)), lengths)
    positions = np.concatenate([np.arange(n) for n in lengths])
    return tuple(jnp.asarray(a, jnp.int32)
                 for a in (tokens, segments, positions))


def program_side(seed, cfg=None):
    """Loss, assignment counts and gradient leaves of the program."""
    cfg = cfg or config()
    tokens, segments, positions = sequence(seed)

    def ours(p):
        return seq_layers.sequence_loss(
            p, jnp.zeros((len(cfg.expert_layers), cfg.num_experts)), tokens,
            segments, positions, cfg=cfg, block=laguna.block)

    (loss, counts), grads = jax.value_and_grad(ours, has_aux=True)(
        init_params(seed, cfg))
    return loss, counts, leaves(grads)


def reference_side(seed):
    """The same of the reference (target positions in place of the
    counts), on its own weights from the same seed."""
    tokens, segments, positions = sequence(seed)
    sizes = reference.sizes(SPEC)

    def theirs(p):
        return reference.forward_sums(p, tokens, segments, positions, 1.0,
                                      sizes, lambda x: x)

    (want, n), want_grads = jax.value_and_grad(theirs, has_aux=True)(
        reference.init_params(seed, sizes))
    return want, n, want_grads


def worst_gap(got, want) -> float:
    """Loss gap over the loss, or the worst leaf's largest gradient gap
    over that leaf's largest reference gradient, whichever is larger."""
    (loss, _, grads), (ref_loss, _, ref_grads) = got, want
    gaps = [abs(float(loss) - float(ref_loss)) / abs(float(ref_loss))]
    for name, g in grads.items():
        scale = float(jnp.abs(ref_grads[name]).max())
        gaps.append(float(jnp.abs(g - ref_grads[name]).max()) / scale)
    return max(gaps)


def test_parameters_are_the_references_own():
    """Same names, same shapes, the same draws from the seed: the
    benchmark's ``init_gap`` limit is 0."""
    ours = leaves(init_params(5, config()))
    theirs = reference.init_params(5, reference.sizes(SPEC))
    assert sorted(ours) == sorted(theirs)
    for name in ours:
        np.testing.assert_array_equal(np.asarray(ours[name]),
                                      np.asarray(theirs[name]), name)


def test_parameter_count_of_the_benchmarks_configuration():
    """``benchmarks/configs/laguna-xs2-ep32.json``: 464,541,696
    parameters at the published widths, by part, from shapes alone."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "laguna-xs2-ep32.json")) as fh:
        spec = json.load(fh)
    cfg = config(spec=spec)
    by_layer = {}
    for path, shape, _ in laguna.param_shapes(cfg):
        by_layer[path[0]] = by_layer.get(path[0], 0) + int(np.prod(shape))
    assert by_layer == {
        "embed": 25_690_112, "lm_head": 25_690_112, "final_norm": 2_048,
        "layer_0": 92_278_784, "layer_1": 83_365_888, "layer_2": 83_365_888,
        "layer_3": 83_365_888, "layer_4": 70_782_976}
    assert sum(by_layer.values()) == 464_541_696
    assert cfg.expert_layers == (1, 2, 3, 4) and cfg.attention_window == 512
    assert cfg.held_experts == (0, 8) and cfg.held_vocab == (0, 12_544)
    # Every published width as published.
    assert (cfg.hidden_size, cfg.head_dim, cfg.num_key_value_heads,
            cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.shared_expert_intermediate_size, cfg.num_experts,
            cfg.num_experts_per_tok) == (2048, 128, 8, 8192, 512, 512, 256, 8)
    assert [cfg.num_attention_heads_per_layer[i] for i in cfg.kept_layers
            ] == [48, 64, 64, 64, 48]


@pytest.mark.parametrize("seed", [7, 8])
def test_loss_and_gradients_against_the_plain_reference(seed):
    """Every gradient leaf within 1e-3 of the reference's (relative),
    plus 2e-5 of the leaf's largest element: both sides are float32, the
    program's products at the CPU's default precision and in another
    order (grouped heads, sorted rows), which is worth a few 1e-6; a
    token whose fifth-best expert is within that of the fourth would
    flip, which none of these seeds has."""
    loss, counts, grads = program_side(seed)
    want, n, want_grads = reference_side(seed)
    assert int(n) == S - len(LENGTHS)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    for name, got in grads.items():
        scale = float(jnp.abs(want_grads[name]).max())
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want_grads[name]),
            rtol=1e-3, atol=2e-5 * scale, err_msg=name)
    # Top-4 of 16 for every token in each of the four expert layers.
    assert counts.shape == (4, 16) and (np.asarray(counts).sum(1) == 4 * S
                                        ).all()


def test_the_references_layer_by_layer_gradient_is_plain_autodiff():
    """``readings`` differentiates layer by layer (one compiled program a
    kind of layer, so that the cell's own size fits the benchmark's
    host): the same sums, count and gradient leaves as
    ``jax.value_and_grad`` of the whole sequence."""
    want, n, want_grads = reference_side(8)
    sizes = reference.sizes(SPEC)
    (got, count), grads = reference.sequence_gradient(sizes, lambda x: x)(
        reference.init_params(8, sizes), *sequence(8), jnp.float32(1.0))
    assert int(count) == int(n) and sorted(grads) == sorted(want_grads)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for name, g in grads.items():
        scale = float(jnp.abs(want_grads[name]).max())
        np.testing.assert_allclose(np.asarray(g), np.asarray(want_grads[name]),
                                   rtol=1e-5, atol=1e-6 * scale, err_msg=name)


def _no_gate(monkeypatch):
    """``sigmoid`` of anything as wide as a layer's query heads (64 or
    96 lanes; the router's scores are 16 wide) is 1."""
    real = jax.nn.sigmoid
    monkeypatch.setattr(
        jax.nn, "sigmoid",
        lambda x: jnp.ones_like(x) if x.shape[-1] in (64, 96) else real(x))
    return config()


def _no_shared_expert(monkeypatch):
    real = laguna.gated_ffn
    monkeypatch.setattr(
        laguna, "gated_ffn",
        lambda p, a: real(p, a) * (p["w1"].shape[-1] != 24))
    return config()


@pytest.mark.parametrize("fault", [
    lambda m: config(sliding_window=9),             # window off by one
    _no_gate,
    _no_shared_expert,
    lambda m: config(moe_routed_scaling_factor=1.0),
], ids=["window_off_by_one", "no_gate", "no_shared_expert",
        "no_scaling_factor"])
def test_a_planted_fault_fails_the_comparison(fault, monkeypatch):
    """Each part of the mathematics left out or moved by one is far
    outside what the comparison above allows (1e-3): the sound program
    reads under 1e-4 here, every fault over 1e-2."""
    want = reference_side(7)
    assert worst_gap(program_side(7), want) < 1e-4
    faulty = worst_gap(program_side(7, fault(monkeypatch)), want)
    assert faulty > 1e-2, faulty


def test_the_shares_and_the_shared_expert_add_up_to_the_whole_layer():
    """The four shares of 4 experts each, the shared expert counted
    once, against the uncut reference's whole ``FF_l``."""
    cfg = config()
    sizes = dict(reference.sizes(SPEC), held=(0, 16))
    whole = reference.init_params(3, dict(sizes))
    layer = reference.common.layer
    a = jnp.asarray(np.random.default_rng(0).standard_normal((S, 64)),
                    jnp.float32)

    def mm(x, w):
        return jnp.matmul(x, w, precision="highest")

    shared = layer(whole, "layer_2/shared")
    moe = layer(whole, "layer_2/moe")
    want = reference.ffn(mm, a, shared["w1"], shared["w3"], shared["w2"]
                         ) + reference.routed_experts(mm, moe, a, sizes)
    total = 0.0
    for first in range(0, 16, 4):
        rows = slice(first, first + 4)
        share = dataclasses.replace(cfg, experts_held=(first, 4))
        p = {"shared": shared,
             "moe": dict(moe, **{k: moe[k][rows] for k in ("w1", "w3", "w2")})}
        out, assigned = laguna.feed_forward(
            p, a, jnp.zeros(16), share, 2)
        total = total + out
        assert int(assigned.sum()) == 4 * S
    once = total - 3 * seq_layers.gated_ffn(shared, a)
    np.testing.assert_allclose(np.asarray(once), np.asarray(want),
                               rtol=2e-4, atol=2e-6)


def test_nothing_crosses_a_document_boundary_or_the_window():
    """Other tokens in one document leave every other document's loss
    bit-equal, and in a model of sliding layers alone a token more than
    ``layers · (window - 1)`` positions back cannot reach the loss."""
    cfg = config(layer_types=["sliding_attention"] * 6,
                 num_attention_heads_per_layer=[6] * 6, sliding_window=4)
    cfg = dataclasses.replace(cfg, layers=(1, 2))
    params = init_params(3, cfg)
    tokens, segments, positions = sequence(lengths=[10, 40, 14])
    first = SPEC["deployment"]["vocab_rows_held"][0]

    def per_position(tok):
        x = seq_layers.embedding_rows(params["embed"], tok - first,
                                      jnp.float32)
        for i in cfg.kept_layers:
            x, _ = laguna.block(params[f"layer_{i}"], x, jnp.zeros(16),
                                segments, positions, cfg=cfg, layer=i)
        x = seq_layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x @ params["lm_head"].T

    # Position 12 is the third of the second document: two layers of
    # window 4 reach 6 positions on, to position 18.
    changed = tokens.at[12].set(first + (tokens[12] - first + 7) % 96)
    before, after = per_position(tokens), per_position(changed)
    moved = np.flatnonzero(np.abs(np.asarray(before - after)).max(-1) > 0)
    assert moved.min() == 12 and moved.max() == 18, moved


def test_yarn_frequencies_are_the_public_rule():
    """The published full-attention group over 64 rotated lanes:
    correction dimensions 5 and 16, so the 5 fastest frequencies stay,
    those from the 16th on are divided by 64, and the ramp between is
    linear; the sliding group is plain RoPE over all 128 lanes."""
    ropes = dict(config().rope)
    f = ropes["full_attention"].frequencies(128)
    plain = 500000.0 ** -(np.arange(32) / 32)
    np.testing.assert_allclose(f[:6], plain[:6], rtol=1e-6)
    np.testing.assert_allclose(f[16:], plain[16:] / 64, rtol=1e-6)
    ramp = (np.arange(6, 16) - 5) / 11
    np.testing.assert_allclose(
        f[6:16], plain[6:16] * (1 - ramp) + plain[6:16] / 64 * ramp,
        rtol=1e-6)
    assert ropes["full_attention"].scale == pytest.approx(
        0.1 * np.log(64) + 1)
    g = ropes["sliding_attention"].frequencies(128)
    np.testing.assert_allclose(g, 10000.0 ** -(np.arange(64) / 64), rtol=1e-6)
    assert ropes["sliding_attention"].scale is None


def test_bfloat16_compute_stays_near_float32():
    params = init_params(2, config())
    tokens, segments, positions = sequence(4)
    losses = [float(seq_layers.sequence_loss(
        params, jnp.zeros((4, 16)), tokens, segments, positions,
        cfg=config(dt), block=laguna.block)[0])
        for dt in ("float32", "bfloat16")]
    assert abs(losses[1] - losses[0]) < 5e-3 * abs(losses[0])


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("gating", False),
    ("tie_word_embeddings", True),
    ("moe_apply_router_weight_on_input", True)])
def test_config_refuses_what_the_family_does_not_have(key, value):
    with pytest.raises(ValueError, match=key):
        config(**{key: value})


def test_config_refuses_an_unknown_layer_or_rope_type():
    with pytest.raises(ValueError, match="layer type"):
        laguna.param_shapes(config(layer_types=["conv"] * 6))
    with pytest.raises(ValueError, match="rope_type"):
        laguna.Rope(rope_theta=1e4, rope_type="linear").frequencies(16)
