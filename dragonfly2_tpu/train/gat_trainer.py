"""Full-graph GraphTransformer training (BASELINE config #3).

Sharding layout (the scaling-book recipe — annotate, let XLA insert
collectives):
- node features / neighbor lists / accumulator rows shard over ``data``
  (each device owns N/d query rows);
- params and optimizer state replicate (allreduce gradients over ICI);
- the per-step edge minibatch replicates (it indexes the full embedding
  table, whose row shards XLA all-gathers exactly once per step where the
  gather needs them).

Scale (round 4): the graph is held as padded neighbor lists, not dense
[N, N] bias/mask, and each row attends to its listed neighbours only
(`models/graph_transformer.py`) — full-topology graphs of 100k+ hosts
fit, where the dense layout capped out around a few thousand.

Train-graph/eval-edge leakage discipline matches gnn_trainer: the attention
structure is built from TRAIN edges only, so an eval edge's RTT (a
deterministic function of its label) never appears in the message
structure.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax.training import train_state

from dragonfly2_tpu.data.features import Graph
from dragonfly2_tpu.models.graph_transformer import (
    GraphTransformer,
    build_inverse_index,
    build_neighbor_lists,
    check_attention,
    pad_graph_sparse,
)
from dragonfly2_tpu.parallel import MeshContext, data_parallel_mesh
from dragonfly2_tpu.train.gnn_trainer import edge_split
from dragonfly2_tpu.train.metrics import metrics_from_confusion, padded_chunks
from dragonfly2_tpu.train.step_budget import TRAINING, setup_phase


@dataclass(frozen=True)
class GATTrainConfig:
    hidden: int = 128
    embed: int = 64
    layers: int = 2
    heads: int = 4
    learning_rate: float = 3e-3
    weight_decay: float = 1e-4
    edge_batch_size: int = 4096
    epochs: int = 5
    seed: int = 0
    eval_fraction: float = 0.1
    rtt_threshold_ns: int = 20_000_000
    # Ring mode's sub-block width: a visiting K/V block is scored
    # ``chunk`` columns at a time (peak activation memory is
    # O(rows · heads · chunk)); gather mode does not read it. And the
    # per-node neighbor cap (best-K by RTT bias; self always survives).
    chunk: int = 1024
    neighbor_cap: int = 128
    # "gather" (O(N·K) neighbor gather against full-width K/V, default)
    # | "ring" (K/V row-sharded, ppermuted around the mesh — no
    # full-width K/V at all). Anything else is a ValueError.
    attention: str = "gather"
    # >1 runs this many optimizer steps per dispatch under lax.scan —
    # the same dispatch amortization the GNN path uses
    # (gnn_trainer.steps_per_call): one host→device round trip per K
    # updates. The GAT step's edge minibatches are tiny next to the
    # resident graph tensors, so stacking K of them per call is nearly
    # free.
    steps_per_call: int = 1
    # Shared step-loop accounting (see GNNTrainConfig): wall cap for the
    # step loop plus incremental publishing hooks.
    max_seconds: float | None = None
    progress_callback: object = None
    compile_callback: object = None


@dataclass
class GATTrainResult:
    params: dict
    config: GATTrainConfig
    node_features: np.ndarray  # padded
    neighbors: np.ndarray      # [N, K] int32 (PAD_ID padded)
    neighbor_vals: np.ndarray  # [N, K] float32 RTT biases
    n_real_nodes: int
    precision: float
    recall: float
    f1: float
    accuracy: float
    samples_per_sec: float
    history: list = field(default_factory=list)
    steps: int = 0
    compile_seconds: float = 0.0

    @property
    def model(self) -> GraphTransformer:
        return GraphTransformer(
            hidden=self.config.hidden, embed=self.config.embed,
            layers=self.config.layers, heads=self.config.heads,
            chunk=self.config.chunk, attention=self.config.attention,
        )


def tp_state_shardings(tree, mesh: MeshContext):
    """Megatron placement for a TrainState-shaped pytree (params AND the
    optimizer moments, which mirror the param paths): within each
    attention block, q/k/v and MLP-up kernels shard column-wise over
    ``model`` (biases shard with their output features), the out and
    MLP-down kernels shard row-wise (their allreduce is inserted by
    ``TPDense``'s auto_axes region); everything else replicates.

    SURVEY §2.7's stretch row — layer WEIGHTS sharded over the mesh, not
    just activations; per-device parameter memory drops accordingly
    (see tests/test_gat_tp.py for the measured reduction).
    """
    import jax

    from jax.sharding import NamedSharding

    from jax.sharding import PartitionSpec as P

    col_kernel = NamedSharding(mesh.mesh, P(None, "model"))
    col_bias = NamedSharding(mesh.mesh, P("model"))
    row_kernel = NamedSharding(mesh.mesh, P("model", None))
    rep = mesh.replicated
    COLUMN, ROW = (0, 1, 2, 4), (3, 5)

    def rule(path, leaf):
        keys = [str(getattr(p, "key", getattr(p, "name", p))) for p in path]
        dense = [k for k in keys if k.startswith("Dense_")]
        if not any(k.startswith("blocks_") for k in keys) or not dense:
            return rep
        idx = int(dense[-1].split("_")[1])
        last = keys[-1]
        if idx in COLUMN:
            return col_kernel if last == "kernel" else col_bias
        if idx in ROW:
            return row_kernel if last == "kernel" else rep
        return rep

    return jax.tree_util.tree_map_with_path(rule, tree)


def train_gat(
    graph: Graph,
    config: GATTrainConfig = GATTrainConfig(),
    mesh: MeshContext | None = None,
) -> GATTrainResult:
    check_attention(config.attention)
    mesh = mesh or data_parallel_mesh()
    if mesh.n_model > 1:
        if config.attention == "ring":
            raise ValueError("ring attention shards rows only; use "
                             "attention='gather' with a model-parallel "
                             "mesh")
        if config.heads % mesh.n_model or (2 * config.hidden) % mesh.n_model:
            raise ValueError(
                f"heads ({config.heads}) and 2*hidden ({2 * config.hidden}) "
                f"must be divisible by the model axis ({mesh.n_model})")
    model = GraphTransformer(hidden=config.hidden, embed=config.embed,
                             layers=config.layers, heads=config.heads,
                             chunk=config.chunk, attention=config.attention)
    # Set-up in three phases (docs/OBSERVABILITY.md "Training loops").
    with setup_phase("data"):
        labels_all = graph.edge_labels(
            config.rtt_threshold_ns).astype(np.float32)
        # Pair-level split (shared with gnn_trainer): every sighting of an
        # eval (src, dst) pair stays out of training AND out of the bias.
        train_ids, eval_ids = edge_split(graph, config.eval_fraction,
                                         config.seed)

        # Attention structure from TRAIN edges only (leakage discipline).
        nbr, val = build_neighbor_lists(
            graph.n_nodes,
            graph.edge_src[train_ids], graph.edge_dst[train_ids],
            graph.edge_rtt_ns[train_ids],
            cap=config.neighbor_cap,
        )
        # Gather mode needs rows that shard evenly over the mesh. Ring
        # mode chunks PER-DEVICE rows, so once those exceed a chunk the
        # row count must be a multiple of n_data·chunk.
        if config.attention == "ring":
            per_device = -(-graph.n_nodes // mesh.n_data)
            multiple = (mesh.n_data * config.chunk
                        if per_device > config.chunk else mesh.n_data)
        else:
            multiple = mesh.n_data
        node_features, nbr, val, n_real = pad_graph_sparse(
            graph.node_features, nbr, val, multiple,
        )

        # Gather mode trains through the attention's own backward: with
        # the host-built transpose of the lists (who lists each host,
        # under which bias) dk and dv are summed host by host out of one
        # small table (models/graph_transformer.py: _attention_bwd);
        # autodiff's duplicate-index scatter-add serializes on a TPU. The
        # graph is fixed for the run, so the transpose is built and
        # placed once.
        inv = None
        if config.attention == "gather":
            inv = build_inverse_index(nbr, val, model.dtype)
            TRAINING.set(attn_inverse_slots=inv.rows.size,
                         attn_inverse_filled=int((inv.rows >= 0).sum()))

    # flax's lazy_init: the parameters are drawn as ``model.init`` draws
    # them, operation by operation (so bit-equal to it on any backend,
    # which one compiled init program is not on the v5e), and the
    # forward is traced over shapes, never run. Run op by op over a
    # 50,000-host fleet it holds 15.4 GB of a 16 GB chip (PERF.md, PR 25).
    def shape_of(a):
        return jax.ShapeDtypeStruct(
            a.shape, jax.dtypes.canonicalize_dtype(a.dtype))

    batch = min(config.edge_batch_size, len(train_ids))
    steps_per_epoch = max(len(train_ids) // batch, 1)
    with setup_phase("state") as placed:
        params = model.lazy_init(
            jax.random.key(config.seed),
            shape_of(node_features), shape_of(nbr), shape_of(val),
            jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int32),
        )
        total_steps = max(config.epochs * steps_per_epoch, 2)
        schedule = optax.warmup_cosine_decay_schedule(
            0.0, config.learning_rate, min(100, total_steps // 10 + 1),
            total_steps,
        )
        tx = optax.adamw(schedule, weight_decay=config.weight_decay)
        state = train_state.TrainState.create(
            apply_fn=model.apply, params=params, tx=tx)
        if mesh.n_model > 1:
            # Weights (and their Adam moments) shard over the model axis;
            # TPDense reads the placement off the values at trace time.
            state = jax.device_put(state, tp_state_shardings(state, mesh))
        else:
            state = mesh.put_replicated(state)
        placed(state)

    # Graph tensors: rows sharded over data; placed once, reused each step.
    row = mesh.shard_spec("data")
    with setup_phase("tables") as placed:
        g_feat, g_nbr, g_val, g_inv = placed(tuple(
            None if a is None else jax.device_put(a, row)
            for a in (node_features, nbr, val, inv)))
    rep = mesh.replicated

    # K optimizer steps per dispatch: a lax.scan over stacked [K, B]
    # edge minibatches with the graph tensors as loop invariants. k=1
    # degenerates to the plain single-step program (scan of length 1).
    k = max(min(int(config.steps_per_call), steps_per_epoch), 1)

    def train_step(state, feat, nbr_, val_, inv_, src_k, dst_k, y_k):
        def body(st, batch):
            src, dst, y = batch

            # df2.* scopes: metadata by which ``df2-trace-tool train``
            # splits a device trace (the attention gathers name
            # themselves, models/graph_transformer.py).
            def loss_fn(params):
                with jax.named_scope("df2.model"):
                    logits = st.apply_fn(params, feat, nbr_, val_, src, dst,
                                         inv=inv_)
                with jax.named_scope("df2.loss"):
                    return optax.sigmoid_binary_cross_entropy(
                        logits, y).mean()

            loss, grads = jax.value_and_grad(loss_fn)(st.params)
            with jax.named_scope("df2.optimizer"):
                return st.apply_gradients(grads=grads), loss

        return jax.lax.scan(body, state, (src_k, dst_k, y_k))

    train_step = jax.jit(
        train_step,
        in_shardings=(None, row, row, row, None if inv is None else row,
                      rep, rep, rep),
        donate_argnums=(0,),
    )

    def eval_step(params, feat, nbr_, val_, src, dst, y, w):
        logits = model.apply(params, feat, nbr_, val_, src, dst)
        pred = (logits > 0).astype(jnp.float32)
        tp = jnp.sum(w * pred * y)
        fp = jnp.sum(w * pred * (1 - y))
        fn = jnp.sum(w * (1 - pred) * y)
        tn = jnp.sum(w * (1 - pred) * (1 - y))
        return jnp.stack([tp, fp, fn, tn])

    eval_step = jax.jit(
        eval_step, in_shardings=(None, row, row, row, rep, rep, rep, rep))

    def rep_put(a):
        return jax.device_put(np.asarray(a), rep)

    from dragonfly2_tpu.train.step_budget import StepBudget, epoch_mean

    rng = np.random.default_rng((config.seed, 7))
    history = []
    budget = StepBudget(config.max_seconds,
                        on_compile=config.compile_callback,
                        on_progress=config.progress_callback,
                        step_samples=batch)
    span = jax.profiler.TraceAnnotation
    stop = False
    step_num = 0
    # Explicit-sharding mode: the in-model reshards (K/V + embedding
    # all-gathers, ring mode's shard_map) need the ambient mesh during trace.
    with jax.set_mesh(mesh.mesh):
        # Full-k groups plus one tail dispatch for the remainder — no
        # silently dropped steps when k ∤ steps_per_epoch (the tail is a
        # second, smaller scan program; compiled once).
        group_sizes = [k] * (steps_per_epoch // k)
        if steps_per_epoch % k:
            group_sizes.append(steps_per_epoch % k)
        seen_gk: set = set()
        for epoch in range(config.epochs):
            with span("df2.train.epoch_order"):
                order = rng.permutation(train_ids)
            losses = []  # per-STEP losses ([gk] arrays), k-invariant
            offset = 0
            for gk in group_sizes:
                ids = order[offset * batch:(offset + gk) * batch]
                if len(ids) < gk * batch:
                    break
                # Host spans on the profiler's clock (free while no
                # profiler runs); docs/OBSERVABILITY.md "Training loops".
                with jax.profiler.StepTraceAnnotation(
                        "df2.train.step", step_num=step_num):
                    with span("df2.train.input", epoch=epoch, step=offset):
                        ids_k = ids.reshape(gk, batch)
                        src_k = rep_put(graph.edge_src[ids_k].astype(np.int32))
                        dst_k = rep_put(graph.edge_dst[ids_k].astype(np.int32))
                        y_k = rep_put(labels_all[ids_k])
                    # The tail group (k ∤ steps_per_epoch) is a second
                    # scan program; its mid-run compile must be excluded
                    # from the throughput window like the first step's is.
                    new_prog = gk not in seen_gk
                    if new_prog:
                        seen_gk.add(gk)
                        budget.sync_point(state.params)
                    with span("df2.train.dispatch"):
                        state, loss_k = train_step(
                            state, g_feat, g_nbr, g_val, g_inv,
                            src_k, dst_k, y_k)
                    if mesh.serialize_launches:
                        jax.block_until_ready(loss_k)
                    losses.append(loss_k)
                    # A launch of its own and host work: not the tick's.
                    mean_loss = jnp.mean(loss_k)
                    with span("df2.train.tick"):
                        stop = budget.tick(gk * batch, mean_loss,
                                           new_program=new_prog)
                offset += gk
                step_num += 1
                if stop:
                    break
            if losses:
                # A host sync: it waits for every queued step.
                with span("df2.train.epoch_end"):
                    history.append(epoch_mean(losses))
            if stop:
                break
        with span("df2.train.drain"):
            jax.block_until_ready(state.params)
        budget.finish()

        # Exact eval in fixed-size chunks with a zero-weighted tail.
        cm = np.zeros(4)
        for ids, weights in padded_chunks(eval_ids, batch):
            cm += np.asarray(eval_step(
                state.params, g_feat, g_nbr, g_val,
                rep_put(graph.edge_src[ids].astype(np.int32)),
                rep_put(graph.edge_dst[ids].astype(np.int32)),
                rep_put(labels_all[ids]), rep_put(weights),
            ))
    metrics = metrics_from_confusion(cm)

    return GATTrainResult(
        params=jax.device_get(state.params),
        config=config,
        node_features=node_features,
        neighbors=nbr,
        neighbor_vals=val,
        n_real_nodes=n_real,
        precision=metrics["precision"],
        recall=metrics["recall"],
        f1=metrics["f1"],
        accuracy=metrics["accuracy"],
        samples_per_sec=budget.samples_per_sec(batch),
        history=history,
        steps=budget.steps,
        compile_seconds=budget.compile_seconds,
    )
