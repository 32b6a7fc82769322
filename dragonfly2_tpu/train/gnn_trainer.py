"""Data-parallel GraphSAGE training (BASELINE config #2).

Host pipeline (CSR fanout sampling) feeds static-shape index batches to one
jit-compiled step. TPU-first input-path design:
- the node-feature table is placed once, replicated, in HBM; batches ship
  int32 indices (+ per-edge RTT/mask floats) and the feature gather runs
  on device, fusing into the first layer — ~4× less H2D traffic than
  shipping gathered float features at F=9;
- worker threads sample and device-place up to ``prefetch_depth`` batches
  ahead (data/prefetch.py), so host sampling and transfer overlap the
  device step instead of serializing with it;
- batch arrays shard over ``data``, params/features replicate, state is
  donated; XLA inserts the gradient allreduce over ICI.

Eval accumulates the confusion matrix on device and reports
precision/recall/f1 — the registry schema for GNN models
(manager/rpcserver/manager_server_v2.go:840-844).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax.training import train_state

from dragonfly2_tpu.data.features import Graph
from dragonfly2_tpu.data.graph_sampler import CSRGraph, EdgeBatchSampler
from dragonfly2_tpu.data.prefetch import prefetch
from dragonfly2_tpu.train.fused_sampling import gather_nodes
from dragonfly2_tpu.train.step_budget import (
    StepBudget,
    epoch_mean,
    setup_phase,
)
from dragonfly2_tpu.models.graphsage import GraphSAGE, nodes_last
from dragonfly2_tpu.parallel import MeshContext, data_parallel_mesh


@dataclass(frozen=True)
class GNNTrainConfig:
    hidden: int = 128
    embed: int = 64
    fanouts: tuple = (10, 5)
    learning_rate: float = 5e-3
    weight_decay: float = 1e-4
    batch_size: int = 4096
    epochs: int = 5
    seed: int = 0
    eval_fraction: float = 0.1
    # 20 ms separates same-region paths (base ~10 ms and below) from
    # cross-region WAN (~60 ms) — "good parent path" ≈ same region or
    # closer. 5 ms (the probes' EWMA granularity class) gives a much
    # sparser positive class; both are operator-tunable.
    rtt_threshold_ns: int = 20_000_000
    # Wall-clock budget for the step loop (compile excluded); None = run
    # all epochs. The bench uses this so throughput comes from steps
    # actually completed instead of a fixed epoch count.
    max_seconds: Optional[float] = None
    # Incremental throughput publishing (bench watchdog honesty): called
    # every ~progress_every steps with (steps, samples_per_sec); the
    # compile callback fires once with measured compile seconds.
    progress_callback: Optional[Callable[[int, float], None]] = None
    compile_callback: Optional[Callable[[float], None]] = None
    # Wall-clock cap for the eval pass (None = run it all; 0 = skip eval
    # entirely, metrics report 0/nan). When exceeded, metrics come from
    # the chunks actually scored — still exact per-edge accounting over a
    # prefix of the (arbitrary-order) eval split.
    eval_max_seconds: Optional[float] = None
    # On-device fanout sampling (train/fused_sampling.py): the CSR tables
    # live in HBM and sampling fuses into the jitted step; the host ships
    # only [B] edge-id slices. ~2 orders of magnitude less host work and
    # H2D traffic than host-side sampling; False keeps the host path
    # (equivalence tests, and graphs too large for replicated HBM tables).
    device_sample: bool = True
    # >1 runs this many optimizer steps per dispatch under lax.scan
    # (device_sample only): one host→device round trip per K updates,
    # for when dispatch latency bounds throughput. Budget checks and
    # progress publishing then happen per dispatch.
    steps_per_call: int = 1
    prefetch_depth: int = 2
    prefetch_workers: int = 2


@dataclass
class GNNTrainResult:
    params: dict
    config: GNNTrainConfig
    node_features: np.ndarray
    # Registry metrics (gnn schema: precision/recall/f1).
    precision: float
    recall: float
    f1: float
    accuracy: float
    samples_per_sec: float  # steady-state (post-compile) throughput
    history: list = field(default_factory=list)
    steps: int = 0
    compile_seconds: float = 0.0

    @property
    def model(self) -> GraphSAGE:
        return GraphSAGE(hidden=self.config.hidden, embed=self.config.embed)


def edge_split(graph: Graph, eval_fraction: float, seed: int):
    """Split edges by (src, dst) PAIR, not edge id.

    Probe datasets contain repeated sightings of the same ordered pair;
    splitting by edge id would leave a same-pair train edge in the message
    graph for most eval edges — a near-direct probe of the answer sitting
    in the sampled neighborhood. Pair-level splitting keeps every sighting
    of an eval pair out of training entirely.
    """
    pair_key = graph.edge_src.astype(np.int64) * graph.n_nodes + graph.edge_dst
    uniq_pairs, pair_idx = np.unique(pair_key, return_inverse=True)
    order = np.random.default_rng((seed, 1)).permutation(len(uniq_pairs))
    n_eval_pairs = int(len(uniq_pairs) * eval_fraction)
    eval_pair_mask = np.zeros(len(uniq_pairs), bool)
    eval_pair_mask[order[:n_eval_pairs]] = True
    is_eval = eval_pair_mask[pair_idx]
    all_ids = np.arange(graph.n_edges)
    return all_ids[~is_eval], all_ids[is_eval]


def apply_indexed(model: GraphSAGE, params, node_features, center_idx,
                  nbr1_idx, nbr1_rtt, nbr1_mask, nbr2_idx, nbr2_rtt,
                  nbr2_mask, out_sharding=None):
    """Forward pass from an IndexEdgeBatch: on-device feature gather from
    the replicated node table, then the dense GraphSAGE graph.

    The host sampler's arrays are batch-major (``[B, 2, f1(, f2)]``); the
    model takes fan-outs leading and the batch trailing
    (models/graphsage.py), so this edge turns them over, the indices
    before their gathers: a transpose of a batch's index arrays, once.

    Under a mesh, gathering a replicated table with batch-sharded indices
    needs the output sharding stated explicitly (each device gathers its
    own index shard locally — no collective); single-device jit leaves
    ``out_sharding`` None.
    """
    def gather(idx):
        return gather_nodes(node_features, idx.T, out_sharding)

    return model.apply(
        params,
        gather(center_idx),
        gather(nbr1_idx), nbr1_rtt.T, nbr1_mask.T,
        gather(nbr2_idx), nbr2_rtt.T, nbr2_mask.T,
    )


def make_train_step(model: GraphSAGE, mesh: MeshContext):
    def train_step(state, node_features, center_idx, nbr1_idx, nbr1_rtt,
                   nbr1_mask, nbr2_idx, nbr2_rtt, nbr2_mask, labels):
        def loss_fn(params):
            logits = apply_indexed(
                model, params, node_features, center_idx,
                nbr1_idx, nbr1_rtt, nbr1_mask, nbr2_idx, nbr2_rtt, nbr2_mask,
                out_sharding=mesh.batch_sharding,
            )
            return optax.sigmoid_binary_cross_entropy(logits, labels).mean()

        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        return state.apply_gradients(grads=grads), loss

    b = mesh.batch_sharding
    return jax.jit(
        train_step,
        in_shardings=(None, mesh.replicated) + (b,) * 8,
        donate_argnums=(0,),
    )


def make_eval_step(model: GraphSAGE, mesh: MeshContext):
    def eval_step(params, node_features, center_idx, nbr1_idx, nbr1_rtt,
                  nbr1_mask, nbr2_idx, nbr2_rtt, nbr2_mask, labels, weights):
        logits = apply_indexed(
            model, params, node_features, center_idx,
            nbr1_idx, nbr1_rtt, nbr1_mask, nbr2_idx, nbr2_rtt, nbr2_mask,
            out_sharding=mesh.batch_sharding,
        )
        pred = (logits > 0).astype(jnp.float32)
        # weights zero out tail-padding rows so every eval edge counts
        # exactly once despite static batch shapes.
        tp = jnp.sum(weights * pred * labels)
        fp = jnp.sum(weights * pred * (1 - labels))
        fn = jnp.sum(weights * (1 - pred) * labels)
        tn = jnp.sum(weights * (1 - pred) * (1 - labels))
        return jnp.stack([tp, fp, fn, tn])

    b = mesh.batch_sharding
    return jax.jit(eval_step, in_shardings=(None, mesh.replicated) + (b,) * 9)


def train_gnn(
    graph: Graph,
    config: GNNTrainConfig = GNNTrainConfig(),
    mesh: MeshContext | None = None,
) -> GNNTrainResult:
    mesh = mesh or data_parallel_mesh()
    # Set-up in three phases (docs/OBSERVABILITY.md "Training loops").
    with setup_phase("data"):
        labels = graph.edge_labels(config.rtt_threshold_ns)
        train_ids, eval_ids = edge_split(graph, config.eval_fraction,
                                         config.seed)
        batch_size = (min(config.batch_size, len(train_ids))
                      // mesh.n_data) * mesh.n_data
        if batch_size == 0:
            raise ValueError(
                f"train split of {len(train_ids)} edges can't fill a "
                f"{mesh.n_data}-way batch"
            )

        # Message graph contains TRAIN edges only: an eval edge's probe
        # RTT is a deterministic function of its label, so letting eval
        # targets appear in sampled neighborhoods would leak the answer
        # and turn the registry f1 into a probe-lookup score instead of a
        # generalization measure.
        train_graph = Graph(
            node_ids=graph.node_ids,
            node_features=graph.node_features,
            edge_src=graph.edge_src[train_ids],
            edge_dst=graph.edge_dst[train_ids],
            edge_rtt_ns=graph.edge_rtt_ns[train_ids],
        )
        csr = CSRGraph.from_graph(train_graph)
        train_sampler = EdgeBatchSampler(
            csr, graph.edge_src[train_ids], graph.edge_dst[train_ids],
            labels[train_ids], config.fanouts,
        )
        eval_sampler = EdgeBatchSampler(
            csr, graph.edge_src[eval_ids], graph.edge_dst[eval_ids],
            labels[eval_ids], config.fanouts,
        )
        dummy = train_sampler.sample(np.zeros(2, np.int64),
                                     np.random.default_rng(0))

    model = GraphSAGE(hidden=config.hidden, embed=config.embed)
    with setup_phase("state") as placed:
        params = model.init(
            jax.random.key(config.seed),
            *nodes_last(*map(jnp.asarray, dummy.astuple()[:-1]))
        )
        steps_per_epoch = max(train_sampler.n_edges // batch_size, 1)
        total_steps = max(config.epochs * steps_per_epoch, 2)
        schedule = optax.warmup_cosine_decay_schedule(
            0.0, config.learning_rate, min(100, total_steps // 10 + 1),
            total_steps,
        )
        tx = optax.adamw(schedule, weight_decay=config.weight_decay)
        state = train_state.TrainState.create(
            apply_fn=model.apply, params=params, tx=tx)
        state = placed(mesh.put_replicated(state))

    with setup_phase("tables") as placed:
        # Host-sampling path only; the fused path keeps features inside
        # its replicated GraphTables instead (no second HBM copy).
        nf_dev = (None if config.device_sample
                  else placed(jax.device_put(csr.node_features,
                                             mesh.replicated)))
        if config.device_sample:
            from dragonfly2_tpu.train.fused_sampling import (
                make_fused_eval_step,
                make_fused_train_step,
                put_edge_tables,
                put_graph_tables,
            )

            graph_tables = placed(put_graph_tables(csr, mesh))
            # On every step's span, so that a trace of any window says
            # which sampler its steps ran (docs/OBSERVABILITY.md
            # "Training loops").
            step_facts = {"sampler_row_width": graph_tables.row_width}
            # The samplers already hold the sliced/cast split arrays —
            # reuse them instead of re-slicing ~2M-element fancy indexes.
            train_edges = placed(put_edge_tables(
                train_sampler.edge_src, train_sampler.edge_dst,
                train_sampler.labels, mesh))
            k = max(int(config.steps_per_call), 1)
            if k > 1:
                from dragonfly2_tpu.train.fused_sampling import (
                    make_fused_multi_step,
                )

                fused_step = make_fused_multi_step(model, mesh,
                                                   config.fanouts, k)
                ids_sharding = mesh.shard_spec(None, "data")
            else:
                fused_step = make_fused_train_step(model, mesh,
                                                   config.fanouts)
            base_key = placed(mesh.put_replicated(
                jax.random.key(config.seed + 1)))
            train_step = None
        else:
            train_step = make_train_step(model, mesh)
            step_facts = {}

    def place(batch) -> tuple:
        return tuple(mesh.put_batch(a) for a in batch.astuple())

    group = max(int(config.steps_per_call), 1) if config.device_sample else 1

    span = jax.profiler.TraceAnnotation

    def train_tasks():
        for epoch in range(config.epochs):
            # On the loop's thread, inside its df2.train.wait_input.
            with span("df2.train.epoch_order"):
                order = np.random.default_rng(
                    (config.seed, epoch)).permutation(train_sampler.n_edges)
            starts = range(0, train_sampler.n_edges - batch_size + 1,
                           batch_size)
            if group == 1:
                for step, start in enumerate(starts):
                    yield epoch, step, order[start:start + batch_size]
            else:
                # K-step groups for one scan dispatch; the within-epoch
                # remainder is dropped like remainder batches are.
                starts = list(starts)
                for gi in range(len(starts) // group):
                    chunk = starts[gi * group:(gi + 1) * group]
                    yield epoch, gi, np.stack(
                        [order[s:s + batch_size] for s in chunk])

    def build(task):
        # On a prefetch worker's thread; (epoch, step) joins the span to
        # the loop's df2.train.step.
        with span("df2.train.input", epoch=task[0], step=task[1]):
            return build_inputs(task)

    def build_inputs(task):
        # Per-task RNG: deterministic regardless of worker interleaving.
        epoch, step, ids = task
        if config.device_sample:
            # Device path ships only the id slice(s); sampling runs on chip.
            ids = ids.astype(np.int32)
            if group > 1:
                return epoch, jax.device_put(ids, ids_sharding)
            return epoch, mesh.put_batch(ids)
        rng = np.random.default_rng((config.seed, epoch, step, 3))
        return epoch, place(train_sampler.sample_indices(ids, rng))

    history: list = []
    epoch_losses: list = []
    current_epoch = 0
    budget = StepBudget(config.max_seconds,
                        on_compile=config.compile_callback,
                        on_progress=config.progress_callback,
                        step_samples=batch_size)
    # Multihost: device_put of a host array to a process-spanning
    # sharding runs a cross-process value-equality collective, so
    # PLACEMENT ORDER must be deterministic — concurrent prefetch
    # builds would pair different steps' batches across processes.
    # One worker still overlaps build with the running step.
    n_workers = (1 if len({d.process_index
                           for d in mesh.mesh.devices.flat}) > 1
                 else config.prefetch_workers)
    stream = prefetch(train_tasks(), build,
                      depth=config.prefetch_depth,
                      workers=n_workers)

    def end_epoch():
        # A host sync: it waits for every queued step.
        with span("df2.train.epoch_end"):
            history.append(epoch_mean(epoch_losses))

    # Host spans on the profiler's clock (free while no profiler runs);
    # docs/OBSERVABILITY.md "Training loops".
    step_num = 0
    while True:
        # Nothing to dispatch: the prefetch stream's next item, and what
        # the task generator does on this thread (an epoch's permutation).
        with span("df2.train.wait_input"):
            item = next(stream, None)
        if item is None:
            break
        epoch, arrays = item
        with jax.profiler.StepTraceAnnotation("df2.train.step",
                                              step_num=step_num,
                                              **step_facts):
            if epoch != current_epoch:
                if epoch_losses:
                    end_epoch()
                epoch_losses = []
                current_epoch = epoch
            with span("df2.train.dispatch"):
                if config.device_sample:
                    state, loss = fused_step(
                        state, graph_tables, train_edges, arrays, base_key)
                else:
                    state, loss = train_step(state, nf_dev, *arrays)
            if mesh.serialize_launches:
                jax.block_until_ready(loss)
            epoch_losses.append(jnp.mean(loss) if group > 1 else loss)
            with span("df2.train.tick"):
                stop = budget.tick(batch_size * group, loss)
        step_num += 1
        if stop:
            stream.close()
            break
    if epoch_losses:
        end_epoch()
    with span("df2.train.drain"):
        jax.block_until_ready(state.params)
    budget.finish()

    # Exact eval: fixed-size chunks with a zero-weighted padded tail, so
    # every eval edge counts exactly once under static batch shapes.
    from dragonfly2_tpu.train.metrics import metrics_from_confusion, padded_chunks

    cm = np.zeros(4)
    import time as _time

    eval_deadline = (
        _time.perf_counter() + config.eval_max_seconds
        if config.eval_max_seconds is not None else None)

    if config.eval_max_seconds == 0.0:
        # Explicit skip: not even one chunk (its compile alone can cost
        # more than a sweep iteration's whole budget); metrics come from
        # the shared zero-cm computation below.
        pass
    elif config.device_sample:
        eval_edges = put_edge_tables(
            eval_sampler.edge_src, eval_sampler.edge_dst,
            eval_sampler.labels, mesh)
        fused_eval = make_fused_eval_step(model, mesh, config.fanouts)
        for chunk_i, (ids, weights) in enumerate(padded_chunks(
                np.arange(eval_sampler.n_edges), batch_size)):
            chunk_key = mesh.put_replicated(
                jax.random.fold_in(base_key, chunk_i))
            cm += np.asarray(fused_eval(
                state.params, graph_tables, eval_edges,
                mesh.put_batch(ids.astype(np.int32)),
                mesh.put_batch(weights), chunk_key))
            if (eval_deadline is not None
                    and _time.perf_counter() >= eval_deadline):
                break
    else:
        eval_step = make_eval_step(model, mesh)

        def eval_build(task):
            ids, weights = task
            rng = np.random.default_rng(
                (config.seed, 2, ids[0] if len(ids) else 0))
            return place(eval_sampler.sample_indices(ids, rng)), weights

        eval_stream = prefetch(
            padded_chunks(np.arange(eval_sampler.n_edges), batch_size),
            eval_build, depth=config.prefetch_depth,
            workers=n_workers,
        )
        for arrays, weights in eval_stream:
            cm += np.asarray(
                eval_step(state.params, nf_dev, *arrays,
                          mesh.put_batch(weights))
            )
            if (eval_deadline is not None
                    and _time.perf_counter() >= eval_deadline):
                eval_stream.close()
                break
    metrics = metrics_from_confusion(cm)

    return GNNTrainResult(
        params=jax.device_get(state.params),
        config=config,
        node_features=csr.node_features,
        precision=metrics["precision"],
        recall=metrics["recall"],
        f1=metrics["f1"],
        accuracy=metrics["accuracy"],
        samples_per_sec=budget.samples_per_sec(batch_size * group),
        history=history,
        steps=budget.steps,
        compile_seconds=budget.compile_seconds,
    )
