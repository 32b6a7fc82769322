"""Trainer service: dataset ingest + TPU training orchestration.

The reference trainer (trainer/) receives CSV datasets streamed from
schedulers and was meant to train GNN+MLP models — the training itself is a
TODO stub (trainer/training/training.go:82-98). Here the stub is real: the
ingest service persists per-scheduler-host datasets, then runs the JAX
GraphSAGE + MLP trainers over a device mesh and registers the resulting
models with the manager.
"""

from dragonfly2_tpu.trainer.storage import TrainerStorage
from dragonfly2_tpu.trainer.training import Training, TrainingConfig
from dragonfly2_tpu.trainer.service import (
    TRAINER_SPEC,
    TrainCostRequest,
    TrainerService,
    TrainGnnRequest,
    TrainMlpRequest,
    TrainRequest,
    TrainResponse,
    TrainSeqRequest,
)

__all__ = [
    "TrainerStorage",
    "Training",
    "TrainingConfig",
    "TrainerService",
    "TRAINER_SPEC",
    "TrainRequest",
    "TrainCostRequest",
    "TrainGnnRequest",
    "TrainMlpRequest",
    "TrainResponse",
    "TrainSeqRequest",
]
