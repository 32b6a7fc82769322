"""Training loops — the real implementation of the reference's trainer stub
(trainer/training/training.go:33-98: load → preprocess → train → upload).

Loops are pjit-compiled over a data-parallel mesh: batches shard over the
``data`` axis, parameters replicate, and XLA inserts the gradient allreduce
over ICI. The same code runs single-chip (mesh of 1) and on a v5e-8 slice.
"""

from dragonfly2_tpu.train.cost_trainer import (
    CostTrainConfig,
    CostTrainResult,
    train_cost,
)
from dragonfly2_tpu.train.gat_trainer import GATTrainConfig, GATTrainResult, train_gat
from dragonfly2_tpu.train.gnn_trainer import GNNTrainConfig, GNNTrainResult, train_gnn
from dragonfly2_tpu.train.mlp_trainer import MLPTrainConfig, MLPTrainResult, train_mlp
from dragonfly2_tpu.train.seq_trainer import (
    SeqCorpus,
    SeqTrainConfig,
    SeqTrainResult,
    pack_documents,
    train_seq,
)

__all__ = [
    "CostTrainConfig",
    "CostTrainResult",
    "GATTrainConfig",
    "GATTrainResult",
    "GNNTrainConfig",
    "GNNTrainResult",
    "MLPTrainConfig",
    "MLPTrainResult",
    "SeqCorpus",
    "SeqTrainConfig",
    "SeqTrainResult",
    "pack_documents",
    "train_cost",
    "train_gat",
    "train_gnn",
    "train_mlp",
    "train_seq",
]
