"""Training orchestration: dataset files → TPU models → manager registry.

Fills the reference stub trainer/training/training.go:60-98 for real. The
four commented steps the reference intended (load → preprocess → train →
upload to manager) become: CSV segments → arrow tables → feature arrays →
pjit training over the device mesh → orbax checkpoint → manager CreateModel.
GNN and MLP train concurrently (the reference used an errgroup; here the
device mesh is the serialized resource, so concurrency is across the
host-side pipelines and the two model jobs run back to back on device).
"""

from __future__ import annotations

import contextlib
import logging
import os
import shutil
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Optional, Protocol

from dragonfly2_tpu.data.features import graph_from_table, pair_examples_from_table
from dragonfly2_tpu.schema import Download, NetworkTopology
from dragonfly2_tpu.schema.io import records_to_table
from dragonfly2_tpu.train import (
    CostTrainConfig,
    GATTrainConfig,
    GNNTrainConfig,
    MLPTrainConfig,
    SeqTrainConfig,
    pack_documents,
    train_cost,
    train_gat,
    train_gnn,
    train_mlp,
    train_seq,
)
from dragonfly2_tpu.train.cost_trainer import (
    MIN_COST_EXAMPLES,
    cost_examples_from_corpus,
    cost_tree,
)
from dragonfly2_tpu.train.checkpoint import (
    ModelMetadata,
    gat_tree,
    gnn_tree,
    mlp_tree,
    save_model,
    seq_tree,
)
from dragonfly2_tpu.trainer.storage import TrainerStorage
from dragonfly2_tpu.utils.idgen import (
    cost_model_id_v1,
    gat_model_id_v1,
    gnn_model_id_v1,
    mlp_model_id_v1,
    seq_model_id_v1,
)

logger = logging.getLogger(__name__)

MODEL_TYPE_GNN = "gnn"
MODEL_TYPE_MLP = "mlp"
MODEL_TYPE_GAT = "gat"
MODEL_TYPE_COST = "cost"
MODEL_TYPE_SEQ = "seq"


class ModelRegistry(Protocol):
    """The manager-facing upload hook (manager CreateModel gRPC,
    manager/rpcserver/manager_server_v2.go:816-914)."""

    def create_model(
        self,
        model_id: str,
        model_type: str,
        host_id: str,
        ip: str,
        hostname: str,
        evaluation: dict,
        artifact_dir: str,
        scheduler_id: int = 0,
    ) -> None: ...


@dataclass
class TrainingConfig:
    gnn: GNNTrainConfig = field(default_factory=GNNTrainConfig)
    mlp: MLPTrainConfig = field(default_factory=MLPTrainConfig)
    # Config #3 (GraphTransformer) as an opt-in third job — the
    # reference trainer runs two (training.go trainGNN/trainMLP); the
    # scale-out model is this framework's extension, so it defaults off.
    gat: GATTrainConfig = field(default_factory=GATTrainConfig)
    train_gat_model: bool = False
    # A sequence model (``train/seq_trainer.py``) over the host's token
    # segments, opt-in as the GraphTransformer is; ``seq`` names the
    # model and has no default.
    seq: Optional[SeqTrainConfig] = None
    train_seq_model: bool = False
    # Learned piece-cost predictor over replay-plane decision corpora
    # (docs/REPLAY.md) — trained whenever replay segments arrive.
    cost: CostTrainConfig = field(default_factory=CostTrainConfig)
    # Minimum records before a model is trained at all (tiny datasets
    # produce garbage models that would evict good ones in the registry).
    min_gnn_records: int = 8
    min_mlp_records: int = 8
    min_gat_records: int = 8
    min_cost_records: int = MIN_COST_EXAMPLES
    # The one profile switch (``df2-trainer --profile-dir``): when set,
    # every model job runs under ``jax.profiler.trace`` writing an XPlane
    # dump here, with the loops' ``df2.train.*`` host spans and the step
    # programs' ``df2.*`` scopes in it; read it with
    # ``df2-trace-tool train`` (docs/OBSERVABILITY.md "Training loops").
    profile_dir: str = ""


@dataclass
class TrainOutcome:
    host_id: str
    gnn_model_id: Optional[str] = None
    mlp_model_id: Optional[str] = None
    gat_model_id: Optional[str] = None
    cost_model_id: Optional[str] = None
    seq_model_id: Optional[str] = None
    seq_evaluation: dict = field(default_factory=dict)
    gnn_evaluation: dict = field(default_factory=dict)
    mlp_evaluation: dict = field(default_factory=dict)
    gat_evaluation: dict = field(default_factory=dict)
    cost_evaluation: dict = field(default_factory=dict)
    # model type -> per-epoch mean training loss of this cycle's job.
    loss_history: dict = field(default_factory=dict)
    errors: list = field(default_factory=list)


class Training:
    def __init__(
        self,
        storage: TrainerStorage,
        registry: Optional[ModelRegistry] = None,
        config: Optional[TrainingConfig] = None,
        mesh=None,
        metrics=None,
    ) -> None:
        self.storage = storage
        self.registry = registry
        self.config = config or TrainingConfig()
        self.mesh = mesh
        self.metrics = metrics  # TrainerMetrics or None
        # One training job at a time: the device mesh is not re-entrant.
        self._train_lock = threading.Lock()

    @contextlib.contextmanager
    def _profiled(self, model: str):
        """One model job under the profile switch: an XPlane dump under
        ``<profile_dir>/<model>/``. JAX leaves operation metadata out of
        its persistent-cache key, so an executable cached by another
        build would bring that build's scope names (or none) into the
        dump; a profiled job therefore keys its programs with their
        metadata, and pays a compile the first time."""
        if not self.config.profile_dir:
            yield
            return
        import jax

        keyed = "jax_compilation_cache_include_metadata_in_key"
        before = getattr(jax.config, keyed)
        jax.config.update(keyed, True)
        try:
            with jax.profiler.trace(
                    os.path.join(self.config.profile_dir, model)):
                yield
        finally:
            jax.config.update(keyed, before)

    def _observe_job(self, model: str, seconds: float,
                     samples_per_sec: float) -> None:
        if self.metrics:
            self.metrics.training_duration.labels(model=model).observe(seconds)
            self.metrics.train_samples_per_sec.labels(model=model).set(
                samples_per_sec)

    def train(self, ip: str, hostname: str, host_id: str,
              scheduler_id: int = 0) -> TrainOutcome:
        """training.go:60-78 — run both model jobs, then delete exactly the
        dataset files that were trained from. A concurrent ingest stream's
        open segments are excluded from the snapshot, so mid-write files
        are never read or deleted; they feed the next round.

        ``scheduler_id`` keys the registry upload: the manager's
        single-active invariant is per (type, scheduler_id), so every
        cluster must upload under its own id or clusters evict each
        other's models (manager/models/model.go:44)."""
        outcome = TrainOutcome(host_id=host_id)
        with self._train_lock:
            (download_files, topology_files,
             replay_files) = self.storage.snapshot(host_id)
            token_files = (self.storage.token_files(host_id)
                           if self.config.train_seq_model else [])
            # Both graph jobs consume the identical topology snapshot:
            # parse the records and build the Graph ONCE per cycle.
            n_topology, graph = 0, None
            try:
                records = self.storage.list_network_topology(
                    host_id, topology_files)
                n_topology = len(records)
                thresholds = [self.config.min_gnn_records]
                if self.config.train_gat_model:
                    thresholds.append(self.config.min_gat_records)
                if n_topology >= min(thresholds):
                    graph = graph_from_table(
                        records_to_table(NetworkTopology, records))
            except Exception as exc:  # noqa: BLE001 — job isolation
                logger.exception("topology parse failed for %s", host_id)
                outcome.errors.append(f"topology: {exc}")
            try:
                self._train_gnn(ip, hostname, host_id, scheduler_id,
                                n_topology, graph, outcome)
            except Exception as exc:  # noqa: BLE001 — job isolation
                logger.exception("trainGNN failed for %s", host_id)
                outcome.errors.append(f"gnn: {exc}")
            try:
                self._train_mlp(ip, hostname, host_id, scheduler_id,
                                download_files, outcome)
            except Exception as exc:  # noqa: BLE001
                logger.exception("trainMLP failed for %s", host_id)
                outcome.errors.append(f"mlp: {exc}")
            if self.config.train_gat_model:
                try:
                    self._train_gat(ip, hostname, host_id, scheduler_id,
                                    n_topology, graph, outcome)
                except Exception as exc:  # noqa: BLE001
                    logger.exception("trainGAT failed for %s", host_id)
                    outcome.errors.append(f"gat: {exc}")
            try:
                self._train_cost(ip, hostname, host_id, scheduler_id,
                                 replay_files, outcome)
            except Exception as exc:  # noqa: BLE001
                logger.exception("trainCost failed for %s", host_id)
                outcome.errors.append(f"cost: {exc}")
            if self.config.train_seq_model:
                try:
                    self._train_seq(ip, hostname, host_id, scheduler_id,
                                    token_files, outcome)
                except Exception as exc:  # noqa: BLE001
                    logger.exception("trainSeq failed for %s", host_id)
                    outcome.errors.append(f"seq: {exc}")
            self.storage.discard_files(
                download_files + topology_files + replay_files + token_files)
        return outcome

    # -- jobs -----------------------------------------------------------------

    def _train_gnn(self, ip, hostname, host_id, scheduler_id,
                   n_records, graph, outcome: TrainOutcome) -> None:
        if n_records < self.config.min_gnn_records:
            logger.info(
                "skip GNN for %s: %d records < %d",
                host_id, n_records, self.config.min_gnn_records,
            )
            return
        if graph is None:
            # Enough records but the shared topology parse failed — the
            # 'topology:' entry in outcome.errors carries the cause.
            logger.info("skip GNN for %s: topology graph unavailable",
                        host_id)
            return
        job_start = time.monotonic()
        with self._profiled("gnn"):
            result = train_gnn(graph, self.config.gnn, self.mesh)
        self._observe_job("gnn", time.monotonic() - job_start,
                          result.samples_per_sec)
        evaluation = {
            "precision": result.precision,
            "recall": result.recall,
            "f1": result.f1,
            "n_samples": n_records,
        }
        model_id = gnn_model_id_v1(ip, hostname)
        self._register(
            model_id,
            MODEL_TYPE_GNN,
            host_id, ip, hostname, scheduler_id,
            evaluation,
            tree=gnn_tree(result.params, result.node_features),
            config={"hidden": result.config.hidden, "embed": result.config.embed,
                    "fanouts": list(result.config.fanouts)},
        )
        outcome.gnn_model_id = model_id
        outcome.loss_history["gnn"] = list(result.history)
        outcome.gnn_evaluation = evaluation

    def _train_gat(self, ip, hostname, host_id, scheduler_id,
                   n_records, graph, outcome: TrainOutcome) -> None:
        if n_records < self.config.min_gat_records:
            logger.info(
                "skip GAT for %s: %d records < %d",
                host_id, n_records, self.config.min_gat_records,
            )
            return
        if graph is None:
            logger.info("skip GAT for %s: topology graph unavailable",
                        host_id)
            return
        job_start = time.monotonic()
        with self._profiled("gat"):
            result = train_gat(graph, self.config.gat, self.mesh)
        self._observe_job("gat", time.monotonic() - job_start,
                          result.samples_per_sec)
        evaluation = {
            "precision": result.precision,
            "recall": result.recall,
            "f1": result.f1,
            "n_samples": n_records,
        }
        model_id = gat_model_id_v1(ip, hostname)
        self._register(
            model_id,
            MODEL_TYPE_GAT,
            host_id, ip, hostname, scheduler_id,
            evaluation,
            tree=gat_tree(result.params, result.node_features,
                          result.neighbors, result.neighbor_vals,
                          node_ids=graph.node_ids),
            config={"hidden": result.config.hidden,
                    "embed": result.config.embed,
                    "layers": result.config.layers,
                    "heads": result.config.heads,
                    # A record of how it was trained: serving scores
                    # every model in gather mode (same parameter tree).
                    "attention": result.config.attention},
        )
        outcome.gat_model_id = model_id
        outcome.loss_history["gat"] = list(result.history)
        outcome.gat_evaluation = evaluation

    def _train_mlp(self, ip, hostname, host_id, scheduler_id, files,
                   outcome: TrainOutcome) -> None:
        records = self.storage.list_download(host_id, files)
        if len(records) < self.config.min_mlp_records:
            logger.info(
                "skip MLP for %s: %d records < %d",
                host_id, len(records), self.config.min_mlp_records,
            )
            return
        X, y = pair_examples_from_table(records_to_table(Download, records))
        if len(X) < self.config.min_mlp_records:
            logger.info("skip MLP for %s: %d pair examples", host_id, len(X))
            return
        job_start = time.monotonic()
        with self._profiled("mlp"):
            result = train_mlp(X, y, self.config.mlp, self.mesh)
        self._observe_job("mlp", time.monotonic() - job_start,
                          result.samples_per_sec)
        evaluation = {"mse": result.mse, "mae": result.mae,
                      "n_samples": len(X)}
        model_id = mlp_model_id_v1(ip, hostname)
        self._register(
            model_id,
            MODEL_TYPE_MLP,
            host_id, ip, hostname, scheduler_id,
            evaluation,
            tree=mlp_tree(result.params, result.normalizer, result.target_norm),
            config={"hidden": list(result.config.hidden)},
        )
        outcome.mlp_model_id = model_id
        outcome.loss_history["mlp"] = list(result.history)
        outcome.mlp_evaluation = evaluation

    def _train_cost(self, ip, hostname, host_id, scheduler_id, files,
                    outcome: TrainOutcome) -> None:
        """Learned piece-cost job (docs/REPLAY.md): replay-plane
        decision events -> (features, realized cost) examples -> cost
        predictor, registered as type 'cost' (the manager's validation
        gate decides whether it ever serves)."""
        if not files:
            return
        records = self.storage.list_replay(host_id, files)
        X, y = cost_examples_from_corpus(records)
        if len(X) < self.config.min_cost_records:
            logger.info(
                "skip cost model for %s: %d examples < %d",
                host_id, len(X), self.config.min_cost_records,
            )
            return
        job_start = time.monotonic()
        with self._profiled("cost"):
            result = train_cost(X, y, self.config.cost, self.mesh)
        self._observe_job("cost", time.monotonic() - job_start,
                          result.samples_per_sec)
        evaluation = {"mse": result.mse, "mae": result.mae,
                      "n_samples": len(X)}
        model_id = cost_model_id_v1(ip, hostname)
        self._register(
            model_id,
            MODEL_TYPE_COST,
            host_id, ip, hostname, scheduler_id,
            evaluation,
            tree=cost_tree(result),
            config={"hidden": list(result.config.hidden)},
        )
        outcome.cost_model_id = model_id
        outcome.loss_history["cost"] = list(result.history)
        outcome.cost_evaluation = evaluation

    def _train_seq(self, ip, hostname, host_id, scheduler_id, files,
                   outcome: TrainOutcome) -> None:
        """Sequence-model job: token segments -> packed sequences ->
        ``train_seq``, registered as type 'seq'."""
        config = self.config.seq
        if config is None:
            raise ValueError("train_seq_model needs TrainingConfig.seq")
        documents = self.storage.list_tokens(host_id, files)
        n_tokens = sum(len(d) for d in documents)
        if n_tokens < config.seq_len:
            logger.info("skip seq model for %s: %d tokens < one sequence "
                        "of %d", host_id, n_tokens, config.seq_len)
            return
        corpus = pack_documents(documents, config.seq_len,
                                config.document_end_id)
        job_start = time.monotonic()
        with self._profiled("seq"):
            result = train_seq(corpus, config, self.mesh)
        self._observe_job("seq", time.monotonic() - job_start,
                          result.samples_per_sec)
        evaluation = {"loss": result.loss, "n_samples": int(corpus.tokens.size)}
        model_id = seq_model_id_v1(ip, hostname)
        model = config.model
        self._register(
            model_id,
            MODEL_TYPE_SEQ,
            host_id, ip, hostname, scheduler_id,
            evaluation,
            tree=seq_tree(result.params, result.routing_counts),
            config={"model_type": model.model_type,
                    "layer_types": list(model.layer_types),
                    "layers": list(model.kept_layers),
                    "hidden_size": model.hidden_size,
                    "num_experts": model.num_experts,
                    "experts_held": list(model.held_experts),
                    "vocab_held": list(model.held_vocab)},
        )
        outcome.seq_model_id = model_id
        outcome.loss_history["seq"] = list(result.history)
        outcome.seq_evaluation = evaluation

    def _register(self, model_id, model_type, host_id, ip, hostname,
                  scheduler_id, evaluation, tree, config) -> None:
        tmp = tempfile.mkdtemp(prefix=f"df2-model-{model_type}-")
        try:
            save_model(
                tmp,
                tree,
                ModelMetadata(
                    model_id=model_id,
                    model_type=model_type,
                    evaluation=evaluation,
                    config=config,
                ),
            )
            if self.registry is not None:
                self.registry.create_model(
                    model_id=model_id,
                    model_type=model_type,
                    host_id=host_id,
                    ip=ip,
                    hostname=hostname,
                    evaluation=evaluation,
                    artifact_dir=tmp,
                    scheduler_id=scheduler_id,
                )
            else:
                logger.info("no registry configured; model %s trained only", model_id)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
