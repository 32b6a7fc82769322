"""Training of sequence models (the families of ``FAMILIES``, chosen by
a config's ``model_type``) on packed token sequences.

The corpus is what a packer emits: ``[R, S]`` integer arrays of token
ids, document ids (``segments``) and positions within the document. It
is placed on the device once, as GraphSAGE's tables are, and a step is
given the ids of its sequences from the epoch's permutation: the host
sends ``batch_size`` integers a step. A sample is a token position, so
``samples_per_sec`` is tokens per second.

Sharding: parameters, optimizer state and the corpus replicate; the
step's sequence ids shard over ``data``, each device takes its own
sequences' part of the mean loss over all the step's target positions
and that part's gradient, and the parts are added over ``data``. Each
device holds the same experts
(``model.experts_held``); an ``expert`` axis with its exchange is not
here yet (``parallel/moe.py``).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax.training import train_state
from jax.sharding import PartitionSpec as P

from dragonfly2_tpu.models import keye_vl2, laguna, lfm2_moe, ouro, seq_layers
from dragonfly2_tpu.models.keye_vl2 import KeyeVL2Config
from dragonfly2_tpu.models.laguna import LagunaConfig
from dragonfly2_tpu.models.lfm2_moe import Lfm2MoeConfig
from dragonfly2_tpu.models.ouro import OuroConfig
from dragonfly2_tpu.parallel import MeshContext, data_parallel_mesh
from dragonfly2_tpu.train.step_budget import (
    TRAINING,
    StepBudget,
    setup_phase,
    step_loop,
)


# A family by the ``model_type`` of its published ``config.json``: the
# module (``param_shapes``, ``block``; ``exit_gate`` where it is looped)
# and its config.
FAMILIES = {"lfm2_moe": (lfm2_moe, Lfm2MoeConfig),
            "laguna": (laguna, LagunaConfig),
            "KeyeVL2": (keye_vl2, KeyeVL2Config),
            "ouro": (ouro, OuroConfig)}


def family_of(cfg):
    """The module of a model config's family."""
    return FAMILIES[cfg.model_type][0]


def fused_head_blocks(cfg, seq_len: int) -> int:
    """The blocks of positions in which the loss head forms its gradient
    in its forward pass, a sequence (``seq_layers.head_loss``): one call
    takes a sequence's positions, or a looped family's every exit's."""
    return seq_layers.head_blocks(
        seq_len * max(getattr(cfg, "total_ut_steps", 0), 1))


@dataclass(frozen=True)
class SeqCorpus:
    """Packed sequences, ``[R, S]`` each: token ids, a document id per
    position (equal ids within a row are one document) and the position
    within the document."""

    tokens: np.ndarray
    segments: np.ndarray
    positions: np.ndarray


def pack_documents(documents, seq_len: int,
                   end_id: int | None = None) -> SeqCorpus:
    """Documents (1-D id arrays) concatenated in order and cut into rows
    of ``seq_len`` with no padding; the tail that fills no row is left
    out. A document cut at a row's end becomes two: its positions
    restart in the next row. With ``end_id`` an array may hold several
    documents, each ended by that id (which stays its last token)."""
    if end_id is not None:
        documents = [part for d in documents for part in np.split(
            np.asarray(d), np.flatnonzero(np.asarray(d) == end_id) + 1)
            if len(part)]
    lengths = np.array([len(d) for d in documents], np.int64)
    rows = int(lengths.sum()) // seq_len
    if rows == 0:
        raise ValueError(f"{int(lengths.sum())} tokens fill no sequence of "
                         f"{seq_len}")
    kept = rows * seq_len
    tokens = np.concatenate(documents)[:kept].astype(np.int32)
    segments = np.repeat(np.arange(len(documents)), lengths)[:kept]
    starts = np.repeat(np.cumsum(lengths) - lengths, lengths)[:kept]
    positions = np.arange(kept) - starts
    tokens, segments, positions = (
        a.reshape(rows, seq_len) for a in (tokens, segments, positions))
    # The document a row begins in the middle of starts anew there.
    cut = segments == segments[:, :1]
    positions = np.where(cut, positions - positions[:, :1], positions)
    return SeqCorpus(tokens, segments.astype(np.int32),
                     positions.astype(np.int32))


@dataclass(frozen=True)
class SeqTrainConfig:
    """Recomputation is not an option: each block keeps its input alone
    for the backward pass and one sequence is in flight at a time
    (``seq_layers.batch_loss``), which is what lets a 0.47B-parameter
    model's state (16 bytes a parameter) and four 8k sequences, or a
    0.31B-parameter one's and two 32k sequences, share a 16 GB chip."""

    model: Lfm2MoeConfig | LagunaConfig | KeyeVL2Config | OuroConfig
    batch_size: int = 4              # sequences a step
    # For whoever packs the corpus (``trainer/training.py``): the rows'
    # length, and the id that ends a document inside a token segment
    # (None: a segment is one document).
    seq_len: int = 8192
    document_end_id: int | None = None
    learning_rate: float = 3e-4
    weight_decay: float = 0.1
    epochs: int = 1
    seed: int = 0
    # The selection bias of every expert layer, ``[num_experts]``; fixed
    # for the run (the published config gives no update rule). None: 0.
    router_bias: tuple | None = None
    max_seconds: float | None = None


def config_from_dict(given: dict) -> SeqTrainConfig:
    """From a published ``config.json``'s keys (``df2-trainer
    --train-seq FILE``; ``model_type`` names the family, ``lfm2_moe``
    where the file has none), beside which the file may state what is
    held here (``layers``, ``experts_held``, ``vocab_held``: pairs of
    first and count) and the job's own settings under this config's
    field names."""
    model_type = given.get("model_type", "lfm2_moe")
    if model_type not in FAMILIES:
        raise ValueError(f"model_type {model_type!r}: the sequence job "
                         f"trains {sorted(FAMILIES)}")
    _, config_class = FAMILIES[model_type]
    held = {k: tuple(given[k])
            for k in ("layers", "experts_held", "vocab_held") if k in given}
    job = {k: given[k] for k in (
        "batch_size", "seq_len", "document_end_id", "learning_rate",
        "weight_decay", "epochs", "seed") if k in given}
    if "router_bias" in given:
        job["router_bias"] = tuple(given["router_bias"])
    return SeqTrainConfig(
        model=config_class.from_published(given, **held), **job)


class SeqTrainState(train_state.TrainState):
    """Outside ``params``, because not trained: the selection bias and
    the assignments each expert got since the loop began (``[expert
    layers, num_experts]``; uint32, which holds 32,768 steps of the
    worst case, every assignment of a 32,768-token step on one expert).
    Where the family's attention runs over a selection of keys, also the
    selections' candidates and members and the attention tiles that
    hold a member since the loop began, summed over layers (``[3, 3]``:
    16-bit limbs, ``seq_layers.count_limbs``; a 32k-token sequence has
    5e8 causal pairs a layer); else None. Where the family is looped,
    the exit distribution's mass at each pass since the loop began
    (``[T, 3]`` limbs of ``2^-EXIT_MASS_BITS`` units); else None."""

    router_bias: jax.Array = None
    routing_counts: jax.Array = None
    sparse_counts: jax.Array = None
    exit_mass: jax.Array = None


@dataclass
class SeqTrainResult:
    params: dict                     # on the device still
    config: SeqTrainConfig
    loss: float                      # the last epoch's mean
    samples_per_sec: float           # token positions a second
    history: list = field(default_factory=list)
    steps: int = 0
    compile_seconds: float = 0.0
    # Assignments per expert over the run's steps, [expert layers, E].
    routing_counts: np.ndarray = None


def build_train_step(cfg, mesh: MeshContext):
    """The jitted step ``train_step(state, tokens, segments, seq_ids,
    positions) -> (state, loss)`` of ``cfg``'s family: the state donated,
    the corpus replicated, ``seq_ids`` (this step's rows of it) sharded
    over ``data``."""
    rep = mesh.replicated
    family = family_of(cfg)
    block, saved = family.block, getattr(family, "SAVED", None)
    exit_gate = getattr(family, "exit_gate", None)

    def loss_and_grads(params, router_bias, tokens, segments, positions,
                       seq_ids):
        """The mean loss over the step's target positions, its gradient
        and the assignment counts: each device's own sequences, added
        over ``data``."""
        tok, seg, pos = tokens[seq_ids], segments[seq_ids], positions[seq_ids]
        n = jnp.maximum(jax.lax.psum(
            seq_layers.target_positions(seg).sum(), "data"), 1)

        def mean(p):
            loss, counts = seq_layers.batch_loss(
                p, router_bias, tok, seg, pos, cfg=cfg, block=block,
                saved=saved, exit_gate=exit_gate)
            return loss / n, counts

        (loss, counts), grads = jax.value_and_grad(mean, has_aux=True)(params)
        return jax.lax.psum((loss, counts, grads), "data")

    # Per device from here down: the mesh's axes are explicit, and
    # nothing below the step's own sequence ids is sharded.
    loss_and_grads = jax.shard_map(
        loss_and_grads, mesh=mesh.mesh,
        in_specs=(P(), P(), P(), P(), P(), P("data")), out_specs=P(),
        check_vma=False)

    def train_step(state, tokens, segments, seq_ids, positions):
        # df2.* scopes: metadata by which ``df2-trace-tool train`` and
        # the benchmark's readers split a device trace; the model's and
        # the expert layer's blocks name themselves.
        with jax.named_scope("df2.model"):
            loss, counts, grads = loss_and_grads(
                state.params, state.router_bias, tokens, segments,
                positions, seq_ids)
        with jax.named_scope("df2.optimizer"):
            more = {}
            if state.sparse_counts is not None:
                # Beside the assignments, the selections' candidates and
                # members of each layer: summed over the layers here.
                counts, selected = counts
                more["sparse_counts"] = seq_layers.carry_limbs(
                    state.sparse_counts + selected.sum(0))
            if state.exit_mass is not None:
                # Beside them, the exit distribution's mass at each pass.
                counts, mass = counts
                more["exit_mass"] = seq_layers.carry_limbs(
                    state.exit_mass + mass)
            state = state.apply_gradients(
                grads=grads,
                routing_counts=state.routing_counts
                + counts.astype(jnp.uint32), **more)
        return state, loss

    return jax.jit(
        train_step,
        in_shardings=(None, rep, rep, mesh.batch_sharding, rep),
        donate_argnums=(0,))


def train_seq(
    corpus: SeqCorpus,
    config: SeqTrainConfig,
    mesh: MeshContext | None = None,
) -> SeqTrainResult:
    mesh = mesh or data_parallel_mesh()
    cfg = config.model
    if mesh.n_model > 1:
        raise ValueError("train_seq shards over data only")
    # Set-up in three phases (docs/OBSERVABILITY.md "Training loops").
    with setup_phase("data"):
        rows, seq_len = corpus.tokens.shape
        batch = min(config.batch_size, rows)
        if batch % mesh.n_data:
            raise ValueError(f"{batch} sequences a step over {mesh.n_data} "
                             "data-parallel devices")
        first, held = cfg.held_vocab
        if (corpus.tokens.min() < first
                or corpus.tokens.max() >= first + held):
            raise ValueError(f"token ids outside the embedding rows held "
                             f"here ({first} .. {first + held - 1})")
        # Last values set (docs/OBSERVABILITY.md): of the corpus's causal
        # tiles at the full-attention kernel's tile those that a document
        # reaches, which are the ones the kernel computes.
        tiles = tiles_kept = 0
        block = min(seq_layers.ATTENTION_BLOCK, seq_len)
        if seq_len % block == 0 and any(
                cfg.layer_types[i] == "full_attention"
                for i in cfg.kept_layers):
            keep = seq_layers.document_tiles(corpus.segments, block)
            tiles = rows * keep.shape[-1] * (keep.shape[-1] + 1) // 2
            tiles_kept = int(keep.sum())

    steps_per_epoch = max(rows // batch, 1)
    total_steps = max(config.epochs * steps_per_epoch, 2)
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, config.learning_rate, min(100, total_steps // 10 + 1),
        total_steps)
    n_moe = len(cfg.expert_layers)
    sparse_topk = getattr(cfg, "sparse_topk", 0)
    loop_steps = getattr(cfg, "total_ut_steps", 0)
    if loop_steps > len(TRAINING.POSITIONS):
        raise ValueError(f"{loop_steps} loop steps; the training block counts "
                         f"the mass of {len(TRAINING.POSITIONS)} exits")
    bias = np.zeros(cfg.num_experts, np.float32) if (
        config.router_bias is None or not cfg.use_expert_bias
    ) else np.asarray(config.router_bias, np.float32)
    with setup_phase("state") as placed:
        state = SeqTrainState.create(
            apply_fn=None,
            params=seq_layers.init_params(
                jax.random.key(config.seed),
                family_of(cfg).param_shapes(cfg)),
            tx=optax.adamw(schedule, weight_decay=config.weight_decay),
            router_bias=jnp.tile(bias, (n_moe, 1)),
            routing_counts=jnp.zeros((n_moe, cfg.num_experts), jnp.uint32),
            sparse_counts=(jnp.zeros((3, 3), jnp.uint32) if sparse_topk
                           else None),
            exit_mass=(jnp.zeros((loop_steps, 3), jnp.uint32) if loop_steps
                       else None))
        state = placed(mesh.put_replicated(state))
    rep = mesh.replicated
    with setup_phase("tables") as placed:
        tokens, segments, positions = placed(tuple(
            jax.device_put(a, rep)
            for a in (corpus.tokens, corpus.segments, corpus.positions)))

    train_step = build_train_step(cfg, mesh)
    # Last values set: which attention the loop's sliding layers ran, how
    # many keys a learned selection keeps, how many times a looped family
    # runs its layers and in how many blocks a sequence's loss head forms
    # its gradient in its forward pass (all four on every step's span
    # too), the tiles counted above, and the grid steps of one call of
    # the selection's attention kernels at the step's shapes.
    grid_steps, head_blocks = 0, fused_head_blocks(cfg, seq_len)
    if sparse_topk:
        from dragonfly2_tpu.models import selected_attention

        grid_steps = selected_attention.grid_steps(
            cfg.num_attention_heads, cfg.num_key_value_heads, seq_len,
            cfg.head_dim, cfg.compute_dtype, seq_layers.select_block(seq_len))
    TRAINING.set(seq_attn_window=cfg.attention_window,
                 seq_attn_tiles=tiles, seq_attn_tiles_kept=tiles_kept,
                 seq_sparse_topk=sparse_topk,
                 seq_sparse_grid_steps=grid_steps,
                 seq_loop_steps=loop_steps,
                 seq_head_fused_blocks=head_blocks)

    budget = StepBudget(config.max_seconds, step_samples=batch * seq_len)
    rng = np.random.default_rng((config.seed, 11))

    def epoch_steps(_):
        with jax.profiler.TraceAnnotation("df2.train.epoch_order"):
            order = rng.permutation(rows).astype(np.int32)
        for i in range(steps_per_epoch):
            ids = order[i * batch:(i + 1) * batch]
            yield lambda ids=ids: jax.device_put(ids, mesh.batch_sharding)

    def dispatch(seq_ids):
        nonlocal state
        state, loss = train_step(state, tokens, segments, seq_ids, positions)
        return loss

    history = step_loop(
        budget, config.epochs, epoch_steps, dispatch,
        step_samples=batch * seq_len, drain=lambda: state.params,
        serialize_launches=mesh.serialize_launches,
        step_facts={"seq_attn_window": cfg.attention_window,
                    "seq_sparse_topk": sparse_topk,
                    "seq_loop_steps": loop_steps,
                    "seq_head_fused_blocks": head_blocks})

    # One read of the routing counts, after the drain.
    routing = np.asarray(jax.device_get(state.routing_counts), np.int64)
    first_e, n_held = cfg.held_experts
    here = routing[:, first_e:first_e + n_held]
    if n_moe:
        TRAINING.add(moe_steps=budget.steps,
                     moe_assignments_held=int(here.sum()),
                     moe_assignments_hottest=int(here.max(1).sum()))
    if sparse_topk:
        candidates, members, held = seq_layers.limbs_value(
            jax.device_get(state.sparse_counts))
        TRAINING.add(seq_sparse_candidates=int(candidates),
                     seq_sparse_selected=int(members),
                     seq_sparse_tiles_held=int(held))
    if loop_steps:
        mass = seq_layers.limbs_value(jax.device_get(state.exit_mass))
        TRAINING.add(**{
            f"seq_exit_mass_{t + 1}":
                float(m) / 2 ** seq_layers.EXIT_MASS_BITS
            for t, m in enumerate(mass)})
    return SeqTrainResult(
        params=state.params,
        config=config,
        loss=history[-1] if history else float("nan"),
        samples_per_sec=budget.samples_per_sec(batch * seq_len),
        history=history,
        steps=budget.steps,
        compile_seconds=budget.compile_seconds,
        routing_counts=routing,
    )
