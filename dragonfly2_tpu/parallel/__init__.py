"""Mesh/sharding helpers — the TPU-native communication backend.

Replaces the reference's intended NCCL path (its trainer stub was designed
for an external PyTorch/CUDA job; SURVEY.md §2.7): gradients are averaged by
XLA collectives over ICI/DCN, inserted automatically from sharding
annotations. No explicit allreduce calls anywhere in the framework — we
annotate, XLA lays out the collectives.
"""

from dragonfly2_tpu.parallel.mesh import MeshContext, data_parallel_mesh
from dragonfly2_tpu.parallel.moe import expert_layer
from dragonfly2_tpu.parallel.multihost import (
    MultihostMeshContext,
    agree,
    init_multihost,
    multihost_mesh,
    sync,
)

__all__ = ["MeshContext", "MultihostMeshContext", "agree",
           "data_parallel_mesh", "expert_layer", "init_multihost",
           "multihost_mesh", "sync"]
