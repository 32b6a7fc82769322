"""The ``ouro`` sequence-model family (``models/ouro.py``, looped by
``seq_layers.sequence_loss``) against the plain reference the benchmark
keeps (``benchmarks/references/ouro.py``: the published equations in
float32, importing nothing of the program), at a small size: hidden 64,
4 heads of 16 on 4 key-value heads (multi-head, as published), 3 layers
run 4 times, 64-token sequences of five documents."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks.compare import leaves
from benchmarks.references import common
from benchmarks.references import ouro as reference
from dragonfly2_tpu.models import ouro, seq_layers
from dragonfly2_tpu.models.ouro import OuroConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPEC = {
    "model_type": "ouro", "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 6, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "vocab_size": 96,
    "rms_norm_eps": 1e-6, "rope_theta": 1_000_000, "total_ut_steps": 4,
    "early_exit_threshold": 1, "layer_types": ["full_attention"] * 6,
    "tie_word_embeddings": False, "attention_bias": False,
    "deployment": {"layers_kept": [0, 1, 2], "vocab_rows_held": [0, 96]},
}
LENGTHS = [5, 30, 3, 19, 7]
S = sum(LENGTHS)


def config(dtype="float32", spec=SPEC, **over):
    held = spec["deployment"]
    return OuroConfig.from_published(
        dict(spec, **over), layers=tuple(held["layers_kept"]),
        vocab_held=tuple(held["vocab_rows_held"]), compute_dtype=dtype)


def init_params(seed, cfg):
    return seq_layers.init_params(jax.random.key(seed),
                                  ouro.param_shapes(cfg))


def sequence(seed=0, lengths=LENGTHS):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, SPEC["vocab_size"], sum(lengths))
    segments = np.repeat(np.arange(len(lengths)), lengths)
    positions = np.concatenate([np.arange(n) for n in lengths])
    return tuple(jnp.asarray(a, jnp.int32)
                 for a in (tokens, segments, positions))


def program_loss(cfg, tokens, segments, positions, exit_gate=ouro.exit_gate):
    def ours(p):
        return seq_layers.sequence_loss(
            p, jnp.zeros((0, 0)), tokens, segments, positions, cfg=cfg,
            block=ouro.block, exit_gate=exit_gate)
    return ours


def program_side(seed, cfg=None):
    """Loss, what the loop counted and gradient leaves of the program."""
    cfg = cfg or config()
    (loss, counted), grads = jax.value_and_grad(
        program_loss(cfg, *sequence(seed)), has_aux=True)(
        init_params(seed, cfg))
    return loss, counted, leaves(grads)


@functools.lru_cache(maxsize=None)
def reference_side(seed):
    """The same of the reference (target positions in place of the
    counts), on its own weights from the same seed."""
    sizes = reference.sizes(SPEC)

    def theirs(p):
        return reference.forward_sums(p, *sequence(seed), 1.0, sizes,
                                      lambda x: x)

    with jax.default_matmul_precision("highest"):
        (want, n), want_grads = jax.value_and_grad(theirs, has_aux=True)(
            reference.init_params(seed, sizes))
    return want, n, want_grads


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
    """``benchmarks/configs/ouro-2.6b-pp12.json``: 406,884,353 parameters
    at the published widths and the whole vocabulary, by part, from
    shapes alone."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ouro-2.6b-pp12.json")) as fh:
        spec = json.load(fh)
    cfg = config(spec=spec, num_hidden_layers=48)
    by_part = {}
    for path, shape, _ in ouro.param_shapes(cfg):
        by_part[path[0]] = by_part.get(path[0], 0) + int(np.prod(shape))
    assert by_part == {
        "embed": 100_663_296, "lm_head": 100_663_296, "final_norm": 2_048,
        "exit_gate": 2_049, **{f"layer_{i}": 51_388_416 for i in range(4)}}
    assert sum(by_part.values()) == 406_884_353
    # Every published width as published, the whole vocabulary held.
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.held_vocab,
            cfg.total_ut_steps, cfg.rope_theta) == (
        2048, 5632, 16, 16, 128, (0, 49_152), 4, 1_000_000)
    assert cfg.kept_layers == (0, 1, 2, 3) and len(cfg.layer_types) == 48
    assert cfg.expert_layers == () and cfg.held_experts == (0, 0)


@pytest.mark.parametrize("seed", [7, 8])
def test_loss_and_gradients_against_the_plain_reference(seed):
    """The loss within 1e-5 (relative) and every gradient leaf within
    1e-4 of the reference's (relative) plus 1e-5 of the leaf's largest
    element: both sides are float32 with full-precision products on the
    CPU, in another order (the program scans its passes, grouping its
    heads, mixing its exits in logs of sigmoids where the reference takes
    the log of a product), which reads under 2e-6 of a leaf's largest
    element on these seeds; the whole loop runs 12 layer applications,
    so a part left out or applied once too often moves a leaf by far
    more (the fault test below)."""
    loss, (counts, mass), grads = program_side(seed)
    want, n, want_grads = reference_side(seed)
    assert int(n) == S - len(LENGTHS)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    assert sorted(grads) == sorted(want_grads)
    for name, got in grads.items():
        scale = float(jnp.abs(want_grads[name]).max())
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want_grads[name]),
            rtol=1e-4, atol=1e-5 * scale, err_msg=name)
    # No expert layer: an empty count; the exits' mass over the counted
    # positions, one position's p(t) summing to 1 (to the 2^-16 units
    # each position's share is rounded to, four times).
    assert counts.shape == (0, 0)
    mass = seq_layers.limbs_value(mass) / 2 ** seq_layers.EXIT_MASS_BITS
    assert mass.shape == (4,) and (mass > 0).all()
    assert abs(mass.sum() - int(n)) <= 4 * int(n) * 2.0 ** -17


def plain_looped_loss(cfg, tokens, segments, positions):
    """The looped objective as plain autodiff takes it: after each pass
    the final norm, the gate and that exit's per-position cross-entropy
    against the head, its logits made again in the backward pass, then
    the exits mixed per position, ``Σ_t p(t)·(CE_t + β log p(t))`` over
    the counted positions (the program's form before its head formed
    its gradient in its forward pass)."""
    local = tokens - cfg.held_vocab[0]
    counted = seq_layers.target_positions(segments)

    def loss(params):
        head, dt = params["lm_head"], jnp.dtype(cfg.compute_dtype)

        def one_pass(x, _):
            for i in cfg.kept_layers:
                x, _ = ouro.block(params[f"layer_{i}"], x, None, segments,
                                  positions, cfg=cfg, layer=i)
            h = seq_layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
            logits = jnp.matmul(h, head.astype(dt).T,
                                preferred_element_type=jnp.float32)
            nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
                logits, jnp.roll(local, -1)[:, None], -1)[:, 0]
            return h, (ouro.exit_gate(params["exit_gate"], h), nll)

        x = params["embed"][local].astype(dt)
        _, (z, nll) = jax.lax.scan(jax.checkpoint(one_pass), x,
                                   length=cfg.total_ut_steps)
        none = jnp.zeros_like(z[:1])
        log_p = (jnp.concatenate([none, jnp.cumsum(
            jax.nn.log_sigmoid(-z[:-1]), 0)]) + jnp.concatenate(
            [jax.nn.log_sigmoid(z[:-1]), none]))
        terms = (jnp.exp(log_p) * (nll + cfg.exit_entropy * log_p)).sum(0)
        return jnp.where(counted, terms, 0).sum()

    return loss


@pytest.mark.parametrize("seed", [7, 8])
def test_the_exits_in_one_head_call_are_the_plain_looped_loss(
        monkeypatch, seed):
    """One call of the head over every exit's positions, weighted by the
    exit distribution and forming its gradient in its forward pass,
    gives the loss and every gradient leaf of the plain looped loss
    above (float32 on the CPU, the same products in another order:
    within 1e-6 of the loss and 2e-6 of a leaf's largest element, where
    the seeds read up to 8.6e-7), in
    one block of positions and in two (an exit and a half each: a block
    need not hold whole exits)."""
    cfg = config()
    params = init_params(seed, cfg)
    tokens, segments, positions = sequence(seed)
    want, want_grads = jax.value_and_grad(plain_looped_loss(
        cfg, tokens, segments, positions))(params)
    for head_block in (seq_layers.HEAD_BLOCK, 2 * S):
        monkeypatch.setattr(seq_layers, "HEAD_BLOCK", head_block)
        (loss, _), grads = jax.value_and_grad(program_loss(
            cfg, tokens, segments, positions), has_aux=True)(params)
        np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)
        want_leaves = leaves(want_grads)
        for name, got in leaves(grads).items():
            scale = float(jnp.abs(want_leaves[name]).max())
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want_leaves[name]), rtol=1e-5,
                atol=2e-6 * scale, err_msg=f"{name} at {head_block}")


def test_the_references_piecewise_gradient_is_plain_autodiff():
    """``readings`` differentiates a piece at a time (a layer
    application, an exit, the mixture; one compiled program each, so
    that the cell's own size fits the benchmark's host): the same sums,
    count and gradient leaves as ``jax.value_and_grad`` of the whole
    sequence, within float32's reordering (1e-6 of a leaf's largest
    element)."""
    want, n, want_grads = reference_side(8)
    sizes = reference.sizes(SPEC)
    with jax.default_matmul_precision("highest"):
        (got, count), grads = reference.sequence_gradient(
            sizes, lambda x: x)(reference.init_params(8, sizes),
                                *sequence(8), jnp.float32(1.0))
    assert int(count) == int(n) and sorted(grads) == sorted(want_grads)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for name, g in grads.items():
        scale = float(jnp.abs(want_grads[name]).max())
        np.testing.assert_allclose(np.asarray(g), np.asarray(want_grads[name]),
                                   rtol=1e-5, atol=1e-6 * scale, err_msg=name)


def test_one_pass_is_one_cross_entropy():
    """With ``total_ut_steps`` 1 the exit distribution is 1 at the one
    exit whatever the gate says: the loss is the plain summed
    cross-entropy of one pass (the non-looped path's, with the same
    norm before the head), and the gate gets no gradient at all."""
    cfg = config(total_ut_steps=1)
    params = init_params(3, cfg)
    tokens, segments, positions = sequence(3)
    looped = program_loss(cfg, tokens, segments, positions)
    once = program_loss(cfg, tokens, segments, positions, exit_gate=None)
    (loss, _), grads = jax.value_and_grad(looped, has_aux=True)(params)
    (plain, counts), plain_grads = jax.value_and_grad(once, has_aux=True)(
        params)
    assert counts.shape == (0, 0)
    np.testing.assert_allclose(float(loss), float(plain), rtol=1e-6)
    assert float(jnp.abs(grads["exit_gate"]["w"]).max()) == 0.0
    assert float(grads["exit_gate"]["b"]) == 0.0
    # The same sums in another order (a scan of one pass, the head's
    # terms kept per position): within 1e-5 of a leaf's largest element.
    for name, g in leaves(plain_grads).items():
        scale = float(jnp.abs(g).max())
        np.testing.assert_allclose(np.asarray(leaves(grads)[name]),
                                   np.asarray(g), rtol=1e-5,
                                   atol=1e-5 * scale, err_msg=name)


def test_a_shared_leaf_gets_the_sum_of_its_applications_gradients():
    """The loop's gradient of a shared weight is what an unrolled model
    whose four passes each have a copy of the weights gives summed over
    the copies (the reference's equations, written with the copies)."""
    sizes = reference.sizes(SPEC)
    params = reference.init_params(4, sizes)
    tokens, segments, positions = sequence(4)
    layer_names = [k for k in params if k.startswith("layer_")]

    def unrolled(copies, rest):
        local = tokens - sizes["vocab"][0]
        x, zs, terms = rest["embed"][local], [], []
        for t in range(sizes["loops"]):
            mine = dict(rest, **copies[t])
            for i in sizes["kept"]:
                x = reference.block(reference.layer_leaves(mine, i), x,
                                    segments, positions, sizes, lambda a: a)
            x, z, term = reference.exit_of(
                rest["final_norm"], rest["exit_gate/w"], rest["exit_gate/b"],
                rest["lm_head"], x, local, segments, 1.0, sizes, lambda a: a)
            zs.append(z)
            terms.append(term)
        return reference.mixture(jnp.stack(zs), jnp.stack(terms), segments,
                                 sizes["beta"])[0]

    copies = [{k: params[k] for k in layer_names}] * sizes["loops"]
    rest = {k: v for k, v in params.items() if k not in layer_names}
    with jax.default_matmul_precision("highest"):
        by_copy = jax.grad(unrolled)(copies, rest)
        shared = jax.grad(lambda p: reference.forward_sums(
            p, tokens, segments, positions, 1.0, sizes, lambda a: a)[0])(
            params)
    program = leaves(jax.grad(lambda p: program_loss(
        config(), tokens, segments, positions)(p)[0])(init_params(4, config())))
    for name in layer_names:
        summed = sum(by_copy[t][name] for t in range(sizes["loops"]))
        scale = float(jnp.abs(summed).max())
        np.testing.assert_allclose(np.asarray(shared[name]),
                                   np.asarray(summed), rtol=1e-5,
                                   atol=1e-6 * scale, err_msg=name)
        np.testing.assert_allclose(np.asarray(program[name]),
                                   np.asarray(summed), rtol=1e-4,
                                   atol=1e-5 * scale, err_msg=name)
        # Each pass's copy has a gradient of its own: the four are
        # different contributions, not one counted four times.
        assert float(jnp.abs(by_copy[0][name] - by_copy[3][name]).max()) > (
            1e-3 * scale)


def test_the_exit_distribution_sums_to_one_and_the_gate_learns():
    """``p(t)`` over the exits sums to 1 at every position, from gates
    near 0, near 1 and between (the logs stay finite); the entropy bonus
    and the cross-entropies both reach the gate, whose gradient is not
    zero."""
    z = jnp.asarray(np.random.default_rng(0).normal(0, 4, (4, 50)),
                    jnp.float32).at[:, 0].set(-60.0).at[:, 1].set(60.0)
    nll = jnp.ones((4, 50), jnp.float32)
    counted = jnp.ones(50, bool)

    def mixture(z):
        # Every position counted: the weights are p itself.
        p, bonus = seq_layers.exit_mixture(z, counted, 0.1)
        return (p * nll).sum() + bonus, p

    total, p = mixture(z)
    np.testing.assert_allclose(np.asarray(p.sum(0)), 1.0, rtol=1e-6)
    assert np.isfinite(float(total)) and (np.asarray(p) >= 0).all()
    # Gate 1 at the first exit takes all of a position's mass there,
    # gate 0 everywhere leaves it all to the last.
    np.testing.assert_allclose(np.asarray(p[:, 1]), [1, 0, 0, 0], atol=1e-6)
    np.testing.assert_allclose(np.asarray(p[:, 0]), [0, 0, 0, 1], atol=1e-6)
    np.testing.assert_allclose(np.asarray(p), np.asarray(
        reference.exit_distribution(z)), rtol=1e-5, atol=1e-7)
    # The entropy bonus alone (every exit's loss the same) still moves
    # the gate.
    assert float(jnp.abs(jax.grad(lambda z: mixture(z)[0])(z)).max()) > 0
    _, _, grads = program_side(7)
    assert float(jnp.abs(grads["exit_gate/w"]).max()) > 1e-3
    assert float(jnp.abs(grads["exit_gate/b"])) > 1e-3


def test_one_step_of_adamw_is_the_references():
    """The trainer's own jitted step (``seq_trainer.build_train_step``:
    the mean over the batch's target positions, its gradient, AdamW) on
    two sequences against the reference's gradient of the same mean and
    ``references/common.py``'s AdamW, at a constant learning rate: every
    parameter within 1e-2 of the learning rate. Adam's first update
    moves an element by ``lr · g / (|g| + 1e-8)``, about lr whatever the
    gradient's size, so where a gradient element is as small as that
    ``1e-8`` a difference of 1e-10 between the two sides' (float32 in
    another order) moves the update by a few thousandths of lr; a wrong
    sign or a step left out moves it by lr."""
    from dragonfly2_tpu.parallel import data_parallel_mesh
    from dragonfly2_tpu.train import seq_trainer

    cfg, lr = config(), 1e-3
    rows = [sequence(seed) for seed in (11, 12)]
    tokens, segments, positions = (jnp.stack([r[k] for r in rows])
                                   for k in range(3))
    mesh = data_parallel_mesh(devices=jax.devices()[:1])
    state = seq_trainer.SeqTrainState.create(
        apply_fn=None, params=init_params(9, cfg),
        tx=optax.adamw(lr, weight_decay=0.1),
        router_bias=jnp.zeros((0, 0)),
        routing_counts=jnp.zeros((0, 0), jnp.uint32),
        exit_mass=jnp.zeros((4, 3), jnp.uint32))
    step = seq_trainer.build_train_step(cfg, mesh)
    state, loss = step(state, tokens, segments, jnp.arange(2, dtype=jnp.int32),
                       positions)

    sizes = reference.sizes(SPEC)
    params = reference.init_params(9, sizes)

    def mean(p):
        sums = [reference.forward_sums(p, *r, 1.0, sizes, lambda a: a)
                for r in rows]
        return sum(s for s, _ in sums) / sum(n for _, n in sums)

    with jax.default_matmul_precision("highest"):
        want, grads = jax.value_and_grad(mean)(params)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    after = common.AdamW(params, 0.1).update(params, grads, lr)
    ours = leaves(state.params)
    for name, value in after.items():
        np.testing.assert_allclose(np.asarray(ours[name]), np.asarray(value),
                                   rtol=0, atol=1e-2 * lr, err_msg=name)
    # Two sequences' exits counted once each, in 2^-16 units.
    mass = seq_layers.limbs_value(state.exit_mass) / 2 ** 16
    assert abs(mass.sum() - 2 * (S - len(LENGTHS))) < 1e-2


def _no_post_norms(monkeypatch):
    """The pre-norm form: no norm after either sub-layer."""
    def block(p, x, router_bias, segments, positions, *, cfg, layer):
        eps = cfg.norm_eps
        h = x + ouro.attention_operator(
            p["attn"], seq_layers.rms_norm(x, p["in_norm"], eps), segments,
            positions, cfg)
        out = seq_layers.gated_ffn(p["ff"],
                                   seq_layers.rms_norm(h, p["ff_norm"], eps))
        return h + out, jnp.zeros(0, jnp.int32)

    monkeypatch.setattr(ouro, "block", block)
    return config()


def _no_entropy_bonus(monkeypatch):
    real = seq_layers.exit_mixture
    monkeypatch.setattr(seq_layers, "exit_mixture",
                        lambda z, counted, _: real(z, counted, 0.0))
    return config()


@pytest.mark.parametrize("fault", [
    lambda m: config(total_ut_steps=5),
    lambda m: config(rope_theta=10_000),
    _no_post_norms,
    _no_entropy_bonus,
], ids=["one_pass_more", "another_rope_theta", "no_post_norms",
        "no_entropy_bonus"])
def test_a_planted_fault_fails_the_comparison(fault, monkeypatch):
    """Each part of the mathematics left out or moved is far outside
    what the comparison above allows (1e-4): the sound program reads
    under 1e-5 here, every fault over 1e-3."""
    want = reference_side(7)

    def worst(got):
        loss, _, grads = got
        gaps = [abs(float(loss) - float(want[0])) / abs(float(want[0]))]
        for name, g in grads.items():
            scale = float(jnp.abs(want[2][name]).max())
            gaps.append(float(jnp.abs(g - want[2][name]).max()) / scale)
        return max(gaps)

    assert worst(program_side(7)) < 1e-5
    assert worst(program_side(7, fault(monkeypatch))) > 1e-3


def test_nothing_crosses_a_document_boundary():
    """Every pass's attention, each exit and the mixture are per document
    and per position: the first document's share of the loss is the
    same alone and beside two different rests of the row."""
    cfg = config()
    params = init_params(2, cfg)
    tokens, segments, positions = sequence(2)
    other = tokens.at[LENGTHS[0]:].set((tokens[LENGTHS[0]:] + 7) % 96)
    first = LENGTHS[0]
    alone = program_loss(cfg, tokens[:first], segments[:first],
                         positions[:first])(params)[0]
    for row in (tokens, other):
        whole = program_loss(cfg, row, segments, positions)(params)[0]
        rest = program_loss(cfg, row[first:], segments[first:],
                            positions[first:])(params)[0]
        np.testing.assert_allclose(float(whole - rest), float(alone),
                                   rtol=2e-4)


def test_bfloat16_compute_stays_near_float32():
    loss32, _, _ = program_side(7, config("float32"))
    loss16, _, _ = program_side(7, config("bfloat16"))
    assert abs(float(loss16) - float(loss32)) / float(loss32) < 2e-2


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("tie_word_embeddings", True),
    ("use_sliding_window", True), ("hidden_act", "gelu"),
    ("rope_scaling", {"rope_type": "yarn"}),
    ("layer_types", ["sliding_attention"] * 6)])
def test_a_config_the_family_does_not_have_is_refused(key, value):
    with pytest.raises(ValueError, match=key):
        config(**{key: value})


def test_the_benchmarks_file_trains_through_the_trainers_entry():
    """``df2-trainer --train-seq benchmarks/configs/ouro-2.6b-pp12.json``
    reads the file as it is: ``model_type`` ``ouro``, the four layers it
    states, the whole vocabulary, 4k rows."""
    from dragonfly2_tpu.train.seq_trainer import config_from_dict

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ouro-2.6b-pp12.json")) as fh:
        config = config_from_dict(json.load(fh))
    assert isinstance(config.model, OuroConfig)
    assert config.model.kept_layers == (0, 1, 2, 3)
    assert config.model.held_vocab == (0, 49_152)
    assert config.model.exit_entropy == 0.1
    assert config.seq_len == 4096
