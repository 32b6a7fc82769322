"""``train_seq`` (``train/seq_trainer.py``) and the trainer service's
sequence-model job, at a tiny size: the packer, the loop through
``StepBudget``, data parallelism against one device, the ``tokens``
segments through ``TrainerStorage`` and ``Training.train``."""

import time

import jax
import numpy as np
import pytest

from dragonfly2_tpu.models.keye_vl2 import KeyeVL2Config
from dragonfly2_tpu.models.laguna import LagunaConfig, Rope
from dragonfly2_tpu.models.lfm2_moe import Lfm2MoeConfig
from dragonfly2_tpu.models import seq_layers
from dragonfly2_tpu.models.ouro import OuroConfig
from dragonfly2_tpu.parallel import data_parallel_mesh
from dragonfly2_tpu.train import step_budget
from dragonfly2_tpu.train.seq_trainer import (
    SeqCorpus,
    SeqTrainConfig,
    config_from_dict,
    family_of,
    fused_head_blocks,
    pack_documents,
    train_seq,
)

MODEL = Lfm2MoeConfig(
    layer_types=("conv", "full_attention", "conv"), num_dense_layers=1,
    hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
    num_experts=16, num_experts_per_tok=4, num_attention_heads=4,
    num_key_value_heads=2, vocab_size=64, experts_held=(4, 4),
    vocab_held=(0, 64))
# The second family the job trains: a full layer with a dense FFN, two
# sliding layers (window 8, groups of 3) with a shared expert and 4 of
# 16 routed ones held.
LAGUNA = LagunaConfig(
    layer_types=("full_attention", "sliding_attention", "sliding_attention"),
    mlp_layer_types=("dense", "sparse", "sparse"),
    num_attention_heads_per_layer=(4, 6, 6), hidden_size=32,
    intermediate_size=48, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, num_experts=16,
    num_experts_per_tok=4, num_key_value_heads=2, head_dim=8, vocab_size=64,
    sliding_window=8, moe_routed_scaling_factor=2.5,
    rope=(("full_attention", Rope(
        rope_theta=500000, rope_type="yarn", factor=64,
        original_max_position_embeddings=4096, beta_fast=64,
        partial_rotary_factor=0.5, attention_factor=1.4158883)),
        ("sliding_attention", Rope(rope_theta=10000))),
    experts_held=(4, 4), vocab_held=(0, 64))
# The third: two layers whose attention runs over the 6 best keys of an
# indexer's ranking, 4 of 16 experts held under a softmax router.
KEYE = KeyeVL2Config(
    hidden_size=32, moe_intermediate_size=16, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    num_experts=16, num_experts_per_tok=4, vocab_size=64,
    rope_theta=10000000, mrope_section=(1, 1, 2), indexer_num_heads=2,
    indexer_head_dim=8, sparse_topk=6, experts_held=(4, 4),
    vocab_held=(0, 64))
# The fourth: two dense layers run three times with an exit after each
# pass, no expert layer.
OURO = OuroConfig(
    hidden_size=32, intermediate_size=48, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=4, head_dim=8, vocab_size=64,
    rope_theta=1_000_000, total_ut_steps=3)
FAMILIES = pytest.mark.parametrize(
    "model,window", [(MODEL, 0), (LAGUNA, 8), (KEYE, 0)],
    ids=["lfm2_moe", "laguna", "KeyeVL2"])
SEQ = 32


def documents(seed=0, n=40):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 64, rng.integers(3, 50)).astype(np.uint16)
            for _ in range(n)]


def one_device():
    return data_parallel_mesh(devices=jax.devices()[:1])


def test_packer_cuts_rows_and_restarts_positions():
    docs = [np.arange(5), np.arange(10, 14), np.arange(20, 31)]
    corpus = pack_documents(docs, 8)
    assert corpus.tokens.shape == (2, 8)         # 20 tokens: 4 left out
    np.testing.assert_array_equal(
        corpus.tokens, [[0, 1, 2, 3, 4, 10, 11, 12],
                        [13, 20, 21, 22, 23, 24, 25, 26]])
    np.testing.assert_array_equal(
        corpus.segments, [[0, 0, 0, 0, 0, 1, 1, 1], [1, 2, 2, 2, 2, 2, 2, 2]])
    # The document cut at the row's end starts anew in the next row.
    np.testing.assert_array_equal(
        corpus.positions, [[0, 1, 2, 3, 4, 0, 1, 2], [0, 0, 1, 2, 3, 4, 5, 6]])
    with pytest.raises(ValueError, match="fill no sequence"):
        pack_documents(docs, 64)


def test_packer_splits_a_stream_at_the_end_id():
    stream = np.array([5, 6, 9, 7, 9, 9, 8, 1, 2, 3], np.uint16)
    corpus = pack_documents([stream], 10, end_id=9)
    np.testing.assert_array_equal(corpus.segments[0],
                                  [0, 0, 0, 1, 1, 2, 3, 3, 3, 3])
    np.testing.assert_array_equal(corpus.positions[0],
                                  [0, 1, 2, 0, 1, 0, 0, 1, 2, 3])


@pytest.fixture(scope="module", params=[(MODEL, 0), (LAGUNA, 8), (KEYE, 0)],
                ids=["lfm2_moe", "laguna", "KeyeVL2"])
def family_run(request):
    """One ``train_seq`` call of a family: its model, the window of its
    sliding layers, the corpus, the result, the ``training`` block before
    and after, and the call's wall seconds."""
    model, window = request.param
    corpus = pack_documents(documents(), SEQ)
    before = step_budget.TRAINING.snapshot()
    start = time.perf_counter()
    result = train_seq(corpus, SeqTrainConfig(
        model=model, batch_size=4, epochs=3, learning_rate=3e-3, seed=3,
        router_bias=tuple(np.linspace(-0.05, 0.05, 16))), one_device())
    wall = time.perf_counter() - start
    return (model, window, corpus, result, before,
            step_budget.TRAINING.snapshot(), wall)


def test_train_seq_reaches_finish_without_a_steady_compile(family_run):
    """Every family through the one loop: the same counters, the same
    one step program, and the loop's last-value entries for the window
    of the sliding layers and for the keys a learned selection keeps (0
    for a family without one)."""
    model, window, corpus, result, before, after, _ = family_run
    assert after["seq_attn_window"] == window
    assert after["seq_sparse_topk"] == getattr(model, "sparse_topk", 0)
    # A cut with a full-attention layer: every row is one tile at this
    # length, and a row's own tile is always reached.
    rows = corpus.tokens.shape[0] if "full_attention" in model.layer_types \
        else 0
    assert after["seq_attn_tiles"] == after["seq_attn_tiles_kept"] == rows
    steps = 3 * (corpus.tokens.shape[0] // 4)
    assert result.steps == steps and len(result.history) == 3
    assert result.history[-1] < result.history[0]
    assert after["steady_compiles"] == before["steady_compiles"]
    assert after["loop_compiles"] - before["loop_compiles"] == 1
    assert after["steps"] - before["steps"] == steps
    # A sample is a token position.
    assert (after["samples"] - before["samples"]
            == (steps - 1) * 4 * SEQ)
    # Top-4 of 16 for every token of every step, in both expert layers.
    counts = result.routing_counts
    assert counts.shape == (2, 16)
    assert (counts.sum(1) == steps * 4 * SEQ * 4).all()
    held = counts[:, 4:8]
    assert after["moe_steps"] - before["moe_steps"] == steps
    assert (after["moe_assignments_held"] - before["moe_assignments_held"]
            == held.sum())
    assert (after["moe_assignments_hottest"]
            - before["moe_assignments_hottest"] == held.max(1).sum())


def test_train_seq_set_up_phases_fit_in_the_call(family_run):
    """The three set-up phases (id checks and tiles; the state drawn and
    placed; the corpus placed) each read positive, and with the step's
    compile they are no more than the call took."""
    *_, before, after, wall = family_run
    spent = {key: after[key] - before[key] for key in (
        "setup_data_seconds", "setup_state_seconds",
        "setup_tables_seconds", "loop_compile_seconds")}
    assert all(v > 0 for v in spent.values()), spent
    assert sum(spent.values()) <= wall


@pytest.mark.parametrize("kept_layers,block,tiles", [
    ((0, 1, 2), 8, 10), ((0, 1, 2), 16, 3), ((0, 2), 8, 0),
    ((1,), 8, 10), ((0, 1, 2), 12, 0)],
    ids=["tile_8", "tile_16", "no_full_layer", "full_layer_alone",
         "rows_no_whole_tiles"])
def test_train_seq_counts_the_tiles_a_document_reaches(
        kept_layers, block, tiles, monkeypatch):
    """``seq_attn_tiles`` / ``seq_attn_tiles_kept``: the corpus's causal
    tiles at the kernel's tile and those a document reaches, by the rule
    the kernel's tables are built with; 0 and 0 for a cut that runs no
    full-attention layer."""
    import dataclasses

    from dragonfly2_tpu.models import seq_layers

    monkeypatch.setattr(seq_layers, "ATTENTION_BLOCK", block)
    corpus = pack_documents(documents(4), SEQ)
    rows = corpus.tokens.shape[0]
    train_seq(corpus, SeqTrainConfig(
        model=dataclasses.replace(MODEL, layers=kept_layers), batch_size=4,
        epochs=1), one_device())
    after = step_budget.TRAINING.snapshot()
    assert after["seq_attn_tiles"] == rows * tiles
    if not tiles:
        assert after["seq_attn_tiles_kept"] == 0
        return
    # A row's diagonal tiles are always reached; documents of 3-49
    # tokens leave some of the 8-wide tiles under it empty.
    kept = after["seq_attn_tiles_kept"]
    assert rows * SEQ // block <= kept <= rows * tiles
    assert kept == sum(
        int(seq_layers.document_tiles(row, block).sum())
        for row in corpus.segments)
    if block == 8:
        assert kept < rows * tiles


@FAMILIES
def test_data_parallel_is_one_device(model, window):
    """Two devices, each on half of a step's sequences, against one
    device on all of them: the same losses."""
    corpus = pack_documents(documents(1), SEQ)
    config = SeqTrainConfig(model=model, batch_size=4, epochs=1, seed=5)
    one = train_seq(corpus, config, one_device())
    two = train_seq(corpus, config,
                    data_parallel_mesh(devices=jax.devices()[:2]))
    np.testing.assert_allclose(two.history, one.history, rtol=2e-3)
    np.testing.assert_array_equal(two.routing_counts.sum(1),
                                  one.routing_counts.sum(1))
    with pytest.raises(ValueError, match="data-parallel"):
        train_seq(corpus, config,
                  data_parallel_mesh(devices=jax.devices()[:3]))


def test_token_ids_must_lie_in_the_rows_held():
    corpus = pack_documents([np.full(64, 70, np.uint16)], SEQ)
    with pytest.raises(ValueError, match="embedding rows held"):
        train_seq(corpus, SeqTrainConfig(model=MODEL), one_device())


def test_config_from_a_published_file():
    given = {
        "layer_types": ["conv", "conv", "full_attention", "conv"],
        "num_dense_layers": 2, "hidden_size": 32, "intermediate_size": 48,
        "moe_intermediate_size": 16, "num_experts": 16,
        "num_experts_per_tok": 4, "num_attention_heads": 4,
        "num_key_value_heads": 2, "vocab_size": 64, "conv_L_cache": 3,
        "norm_eps": 1e-5, "rope_parameters": {"rope_theta": 10000},
        "layers": [0, 2, 3], "experts_held": [8, 8], "seq_len": 32,
        "batch_size": 2, "document_end_id": 63}
    config = config_from_dict(given)
    assert config.model.kept_layers == (0, 2, 3)
    assert config.model.expert_layers == (2, 3)
    assert config.model.held_experts == (8, 8)
    assert (config.seq_len, config.batch_size, config.document_end_id) == (
        32, 2, 63)


def test_the_selections_counters_are_the_corpus_own_arithmetic():
    """``seq_sparse_candidates`` and ``seq_sparse_selected``: summed on
    the device over layers, sequences and steps in 16-bit limbs (a plain
    uint32 would wrap within a dozen steps of 32k sequences) and added
    once at the drain: for every query its document's tokens up to
    itself, and the 6 of them it keeps at the most."""
    corpus = pack_documents(documents(), SEQ)
    corpus = SeqCorpus(*(a[:8] for a in (
        corpus.tokens, corpus.segments, corpus.positions)))
    at = np.arange(SEQ)
    c = ((at[None, :, None] >= at[None, None, :])
         & (corpus.segments[:, :, None] == corpus.segments[:, None, :])
         ).sum(-1)
    before = step_budget.TRAINING.snapshot()
    result = train_seq(corpus, SeqTrainConfig(
        model=KEYE, batch_size=4, epochs=3, seed=1), one_device())
    after = step_budget.TRAINING.snapshot()
    assert result.steps == 6
    # Two layers, three epochs over all eight rows.
    assert (after["seq_sparse_candidates"] - before["seq_sparse_candidates"]
            == 2 * 3 * c.sum())
    assert (after["seq_sparse_selected"] - before["seq_sparse_selected"]
            == 2 * 3 * np.minimum(c, 6).sum())


def test_the_selections_tiles_and_the_kernels_grid(monkeypatch):
    """``seq_sparse_tiles_held``: the attention's ``[block, block]``
    tiles that hold a member, summed on the device over layers,
    sequences and steps from the kernels' tile table; with every
    candidate kept (``topk`` over the longest document) those are the
    causal tiles a document reaches. ``seq_sparse_grid_steps``: one
    kernel call's grid at the step's shapes, a step for each tile and
    key-value head (the group of 2 query heads in one step); 0 for a
    family without a selection."""
    import dataclasses

    from dragonfly2_tpu.models import seq_layers

    monkeypatch.setattr(seq_layers, "SELECT_BLOCK", 8)
    corpus = pack_documents(documents(), SEQ)
    corpus = SeqCorpus(*(a[:8] for a in (
        corpus.tokens, corpus.segments, corpus.positions)))
    before = step_budget.TRAINING.snapshot()
    train_seq(corpus, SeqTrainConfig(
        model=dataclasses.replace(KEYE, sparse_topk=SEQ), batch_size=4,
        epochs=1, seed=1), one_device())
    after = step_budget.TRAINING.snapshot()
    reached = sum(int(seq_layers.document_tiles(row, 8).sum())
                  for row in corpus.segments)
    # Two layers, one epoch over all eight rows.
    assert (after["seq_sparse_tiles_held"] - before["seq_sparse_tiles_held"]
            == 2 * reached)
    assert 8 * SEQ // 8 <= reached < 8 * 10
    assert after["seq_sparse_grid_steps"] == 2 * 4 * 4
    train_seq(corpus, SeqTrainConfig(model=MODEL, batch_size=4, epochs=1),
              one_device())
    assert step_budget.TRAINING.snapshot()["seq_sparse_grid_steps"] == 0


def test_limbs_carry_past_32_bits():
    from dragonfly2_tpu.models import seq_layers

    counts = np.array([2**31 - 1, 65_536, 0, 179_322_880], np.int32)
    limbs = seq_layers.count_limbs(jax.numpy.asarray(counts))
    total = limbs
    for _ in range(40):                 # 41 x 2^31: past uint32 and 2^36
        total = seq_layers.carry_limbs(total + limbs)
    assert (np.asarray(total) < 65_536).all()
    assert seq_layers.limbs_value(total).tolist() == [
        41 * int(v) for v in counts]


def test_config_from_a_published_file_names_its_family():
    """``model_type`` picks the family; the published laguna keys and
    what is held here come out of the one file."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "laguna-xs2-ep32.json")) as fh:
        given = json.load(fh)
    given = dict(given, num_experts=256, vocab_size=100352,
                 layers=[0, 1, 2, 3, 4], experts_held=[0, 8],
                 vocab_held=[0, 12544], batch_size=4, seq_len=8192)
    config = config_from_dict(given)
    assert isinstance(config.model, LagunaConfig)
    assert config.model.expert_layers == (1, 2, 3, 4)
    assert config.model.held_experts == (0, 8)
    assert config.model.attention_window == 512
    assert dict(config.model.rope)["full_attention"].factor == 64
    assert (config.seq_len, config.batch_size) == (8192, 4)
    with pytest.raises(ValueError, match="model_type"):
        config_from_dict(dict(given, model_type="mamba"))


def test_config_from_the_third_familys_published_file():
    """``df2-trainer --train-seq`` with a ``model_type`` ``KeyeVL2``
    file: the published keys (``sa_config``, ``rope_scaling``) and what
    is held here come out of the one file."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "keye-vl2-30b-a3b-ep16.json")) as fh:
        given = json.load(fh)
    given = dict(given, num_hidden_layers=48, num_experts=128,
                 vocab_size=151936, layers=[0, 1, 2, 3], experts_held=[0, 8],
                 vocab_held=[0, 18992], batch_size=2, seq_len=32768)
    config = config_from_dict(given)
    assert isinstance(config.model, KeyeVL2Config)
    assert config.model.expert_layers == (0, 1, 2, 3)
    assert config.model.held_experts == (0, 8)
    assert (config.model.sparse_topk, config.model.mrope_section) == (
        2048, (16, 24, 24))
    assert len(config.model.layer_types) == 48
    assert (config.seq_len, config.batch_size) == (32768, 2)


class _Registry:
    def __init__(self):
        self.created = []

    def create_model(self, **kwargs):
        import os

        kwargs["files"] = sorted(os.listdir(kwargs["artifact_dir"]))
        self.created.append(kwargs)


@FAMILIES
def test_token_segments_round_trip_and_a_seq_model_is_registered(
        tmp_path, model, window):
    from dragonfly2_tpu.train.checkpoint import load_model, seq_from_tree
    from dragonfly2_tpu.trainer import (
        TrainerStorage,
        Training,
        TrainingConfig,
    )
    from dragonfly2_tpu.trainer.storage import TOKENS_PREFIX

    storage = TrainerStorage(str(tmp_path / "data"))
    docs = documents(2, n=30)
    for doc in docs:
        # Little-endian uint16 ids, one document a segment, in two chunks.
        blob = doc.astype("<u2").tobytes()
        storage.append(TOKENS_PREFIX, "host-1", blob[:6], True)
        storage.append(TOKENS_PREFIX, "host-1", blob[6:], False)
    storage.close_host("host-1")
    back = storage.list_tokens("host-1")
    assert len(back) == len(docs)
    for got, want in zip(back, docs):
        np.testing.assert_array_equal(got, want)
    assert storage.has_closed_segments("host-1")

    registry = _Registry()
    saved = {}
    plain = registry.create_model

    def keep(**kwargs):
        saved["tree"], saved["meta"] = load_model(kwargs["artifact_dir"])
        plain(**kwargs)

    registry.create_model = keep
    training = Training(
        storage, registry,
        TrainingConfig(train_seq_model=True, seq=SeqTrainConfig(
            model=model, batch_size=2, seq_len=SEQ, epochs=1)),
        mesh=one_device())
    outcome = training.train("10.0.0.1", "sched-1", "host-1")
    assert outcome.errors == []
    assert outcome.seq_model_id and outcome.loss_history["seq"]
    (created,) = registry.created
    assert created["model_type"] == "seq"
    assert created["evaluation"]["n_samples"] % SEQ == 0
    params, counts = seq_from_tree(saved["tree"])
    assert saved["meta"].model_type == "seq"
    assert saved["meta"].config["model_type"] == model.model_type
    assert counts.shape == (2, 16) and "layer_1" in params
    # The trained segments are gone; nothing is left to train.
    assert storage.token_files("host-1") == []
    assert not storage.has_closed_segments("host-1")


def test_a_looped_family_counts_its_passes_and_exits_and_no_experts(
        monkeypatch):
    """``seq_loop_steps`` (3 here, on every step's span too, 0 for a
    family that runs its layers once) and ``seq_exit_mass_1`` ..: each
    exit's share of the counted positions, summed on the device over
    sequences and steps and added once at the drain, all of them
    together every counted position of every step. No expert layer: the
    routing counts are ``[0, 0]`` and no ``moe_*`` counter moves.
    ``seq_head_fused_blocks``: the head's blocks a sequence, over every
    exit's positions."""
    corpus = pack_documents(documents(), SEQ)
    corpus = SeqCorpus(*(a[:8] for a in (
        corpus.tokens, corpus.segments, corpus.positions)))
    counted = int(((corpus.segments[:, 1:] == corpus.segments[:, :-1])
                   ).sum())
    before = step_budget.TRAINING.snapshot()
    # Blocks of half a sequence's three exits: the head's one call takes
    # its 96 positions in two.
    monkeypatch.setattr(seq_layers, "HEAD_BLOCK", 3 * SEQ // 2)
    result = train_seq(corpus, SeqTrainConfig(
        model=OURO, batch_size=4, epochs=3, learning_rate=3e-3, seed=3),
        one_device())
    after = step_budget.TRAINING.snapshot()
    assert after["seq_loop_steps"] == 3
    assert after["seq_head_fused_blocks"] == 2
    assert result.history[-1] < result.history[0]
    assert result.routing_counts.shape == (0, 0)
    for key in ("moe_steps", "moe_assignments_held",
                "moe_assignments_hottest"):
        assert after[key] == before[key]
    assert after["loop_compiles"] - before["loop_compiles"] == 1
    mass = [after[f"seq_exit_mass_{t}"] - before[f"seq_exit_mass_{t}"]
            for t in (1, 2, 3)]
    assert all(m > 0 for m in mass)
    assert after["seq_exit_mass_4"] == before["seq_exit_mass_4"]
    # Three epochs over all eight rows; each position's p(t) rounded
    # to 2^-16 at each of three exits.
    assert abs(sum(mass) - 3 * counted) <= 3 * 3 * counted * 2.0 ** -17
    train_seq(corpus, SeqTrainConfig(model=MODEL, batch_size=4, epochs=1),
              one_device())
    assert step_budget.TRAINING.snapshot()["seq_loop_steps"] == 0
    assert step_budget.TRAINING.snapshot()["seq_head_fused_blocks"] == 1


def test_a_looped_family_on_two_devices_is_one_device():
    """Two devices, each on half of a step's sequences, against one
    device on all of them: the same losses, the same exits' mass."""
    corpus = pack_documents(documents(1), SEQ)
    config = SeqTrainConfig(model=OURO, batch_size=4, epochs=1, seed=5)
    before = step_budget.TRAINING.snapshot()["seq_exit_mass_1"]
    one = train_seq(corpus, config, one_device())
    middle = step_budget.TRAINING.snapshot()["seq_exit_mass_1"]
    two = train_seq(corpus, config,
                    data_parallel_mesh(devices=jax.devices()[:2]))
    after = step_budget.TRAINING.snapshot()["seq_exit_mass_1"]
    np.testing.assert_allclose(two.history, one.history, rtol=2e-3)
    np.testing.assert_allclose(after - middle, middle - before, rtol=2e-3)


def test_a_looped_family_is_registered_by_the_trainer_service(tmp_path):
    """The trainer service's sequence job with a ``model_type`` ``ouro``
    model: trained on the host's token segments and registered as a
    ``seq`` model whose tree holds the exit gate and an empty routing
    count."""
    from dragonfly2_tpu.train.checkpoint import load_model, seq_from_tree
    from dragonfly2_tpu.trainer import (
        TrainerStorage,
        Training,
        TrainingConfig,
    )
    from dragonfly2_tpu.trainer.storage import TOKENS_PREFIX

    storage = TrainerStorage(str(tmp_path / "data"))
    for doc in documents(2, n=30):
        storage.append(TOKENS_PREFIX, "host-1", doc.astype("<u2").tobytes(),
                       True)
    storage.close_host("host-1")
    registry = _Registry()
    saved = {}
    plain = registry.create_model

    def keep(**kwargs):
        saved["tree"], saved["meta"] = load_model(kwargs["artifact_dir"])
        plain(**kwargs)

    registry.create_model = keep
    outcome = Training(
        storage, registry,
        TrainingConfig(train_seq_model=True, seq=SeqTrainConfig(
            model=OURO, batch_size=2, seq_len=SEQ, epochs=1)),
        mesh=one_device()).train("10.0.0.1", "sched-1", "host-1")
    assert outcome.errors == [] and outcome.seq_model_id
    params, counts = seq_from_tree(saved["tree"])
    assert saved["meta"].config["model_type"] == "ouro"
    assert counts.shape == (0, 0)
    assert set(params["exit_gate"]) == {"w", "b"} and "layer_1" in params


def _head_products(jaxpr, rows, scopes=("df2.loss", "df2.seq.exit")):
    """The products of the loss head in a program (``jax.make_jaxpr``):
    ``dot_general`` equations under one of ``scopes`` with ``rows`` (the
    head's row count) among their operands' or result's dimensions,
    each counted as often as the ``scan`` loops around it run (a
    sequence, a block of positions); and how many of them lie inside a
    recomputed (``checkpoint``) region."""
    found = under_remat = 0

    def walk(jaxpr, path, times, remat):
        nonlocal found, under_remat
        for eqn in jaxpr.eqns:
            here = f"{path}/{eqn.source_info.name_stack}"
            if eqn.primitive.name == "dot_general" and any(
                    scope in here for scope in scopes) and any(
                    rows in v.aval.shape
                    for v in (*eqn.invars, *eqn.outvars)):
                found += times
                under_remat += times * remat
            inner = times * eqn.params.get("length", 1)
            for value in eqn.params.values():
                for sub in value if isinstance(value, (tuple, list)) else (
                        value,):
                    sub = getattr(sub, "jaxpr", sub)
                    if isinstance(sub, jax.extend.core.Jaxpr):
                        walk(sub, here, inner, remat or eqn.primitive.name
                             in ("checkpoint", "remat2"))

    walk(jaxpr.jaxpr, "", 1, False)
    return found, under_remat


@pytest.mark.parametrize("model,head_block,blocks", [
    (MODEL, SEQ, 1), (LAGUNA, SEQ, 1), (KEYE, SEQ // 4, 4),
    (OURO, 3 * SEQ // 2, 2)], ids=["lfm2_moe", "laguna", "KeyeVL2", "ouro"])
def test_the_head_forms_its_gradient_in_its_forward_pass(
        monkeypatch, model, head_block, blocks):
    """In the differentiated loss of a step's sequences (what the step's
    ``value_and_grad`` takes), the head's products are three a block of
    positions (logits, the input's gradient, the rows' gradient) and
    none of them lies in a recomputed region: no logits are made again
    in the backward pass. Blocks as the cells have them (1 in the two 8k
    cells, 4 of a 32k sequence, 2 of a looped family's exits), and
    ``fused_head_blocks``, which ``train_seq`` writes as
    ``seq_head_fused_blocks``, says so."""
    monkeypatch.setattr(seq_layers, "HEAD_BLOCK", head_block)
    family, batch = family_of(model), 4
    params = jax.eval_shape(lambda: seq_layers.init_params(
        jax.random.key(0), family.param_shapes(model)))
    bias = np.zeros((len(model.expert_layers), model.num_experts),
                    np.float32)

    def loss(p, *sequences):
        return seq_layers.batch_loss(
            p, bias, *sequences, cfg=model, block=family.block,
            saved=getattr(family, "SAVED", None),
            exit_gate=getattr(family, "exit_gate", None))[0]

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss))(
        params, *[jax.ShapeDtypeStruct((batch, SEQ), np.int32)] * 3)
    assert fused_head_blocks(model, SEQ) == blocks
    assert _head_products(jaxpr, model.held_vocab[1]) == (
        3 * blocks * batch, 0)
