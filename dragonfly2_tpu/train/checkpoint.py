"""Model checkpointing and export (orbax).

The reference has *no* training checkpoints (training was a stub; SURVEY.md
§5 checkpoint/resume). We add real ones: an orbax-saved pytree (params +
normalizer) plus a JSON metadata sidecar carrying the registry fields the
manager stores per model version (manager/models/model.go:19-46 — type,
evaluation metrics; idgen model IDs from pkg/idgen/model_id.go:32-38).
"""

from __future__ import annotations

import json
import os
from dataclasses import asdict, dataclass, field
from typing import Any

import numpy as np
import orbax.checkpoint as ocp

from dragonfly2_tpu.models.mlp import Normalizer

METADATA_FILE = "metadata.json"
TREE_DIR = "tree"


@dataclass
class ModelMetadata:
    """Registry-facing model description."""

    model_id: str
    model_type: str  # "mlp" | "gnn" (manager/models/model.go ModelType*)
    version: int = 1
    # mlp: {"mse": .., "mae": ..}; gnn: {"precision": .., "recall": .., "f1": ..}
    evaluation: dict = field(default_factory=dict)
    config: dict = field(default_factory=dict)
    feature_schema: list = field(default_factory=list)


def save_model(path: str, tree: Any, metadata: ModelMetadata) -> None:
    """Save ``tree`` (params/normalizer arrays) + metadata under ``path``."""
    path = os.path.abspath(path)
    os.makedirs(path, exist_ok=True)
    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(os.path.join(path, TREE_DIR), tree, force=True)
    with open(os.path.join(path, METADATA_FILE), "w") as f:
        json.dump(asdict(metadata), f, indent=2)


def load_model(path: str) -> tuple[Any, ModelMetadata]:
    path = os.path.abspath(path)
    with ocp.StandardCheckpointer() as ckptr:
        tree = ckptr.restore(os.path.join(path, TREE_DIR))
    with open(os.path.join(path, METADATA_FILE)) as f:
        metadata = ModelMetadata(**json.load(f))
    return tree, metadata


def gnn_tree(params: Any, node_features: np.ndarray) -> dict:
    """GNN checkpoint: params + the node-feature matrix snapshot the model
    was trained against (serving must featurize hosts identically)."""
    return {"params": params, "node_features": np.asarray(node_features)}


def gnn_from_tree(tree: dict) -> tuple[Any, np.ndarray]:
    return tree["params"], np.asarray(tree["node_features"])


def gat_tree(params: Any, node_features: np.ndarray,
             neighbors: np.ndarray, neighbor_vals: np.ndarray,
             node_ids=None) -> dict:
    """GraphTransformer checkpoint: params + the padded node features and
    neighbor lists (serving recomputes embeddings over the same padded
    attention structure the model trained on). ``node_ids`` — the REAL
    (pre-padding) rows' host IDs, row index = embedding index — ship as
    a newline-joined UTF-8 byte array (orbax/tensorstore has no string
    dtype), so serving can translate host IDs to table indexes."""
    tree = {"params": params,
            "node_features": np.asarray(node_features),
            "neighbors": np.asarray(neighbors),
            "neighbor_vals": np.asarray(neighbor_vals)}
    if node_ids is not None:
        blob = "\n".join(str(i) for i in node_ids).encode()
        tree["node_ids_utf8"] = np.frombuffer(blob, dtype=np.uint8).copy()
    return tree


def gat_from_tree(tree: dict) -> tuple:
    """→ (params, node_features, neighbors, neighbor_vals, node_ids) —
    ``node_ids`` is None for checkpoints written without them."""
    node_ids = None
    if "node_ids_utf8" in tree:
        blob = bytes(np.asarray(tree["node_ids_utf8"], dtype=np.uint8))
        node_ids = blob.decode().split("\n") if blob else []
    return (tree["params"], np.asarray(tree["node_features"]),
            np.asarray(tree["neighbors"]), np.asarray(tree["neighbor_vals"]),
            node_ids)


def seq_tree(params: Any, routing_counts: np.ndarray) -> dict:
    """Sequence-model checkpoint: params + the assignments each expert
    got over the run (``[expert layers, experts]``; what a later run
    would set a selection bias from). A model without an expert layer
    has no counts to keep (and the checkpointer writes no empty
    array)."""
    counts = np.asarray(routing_counts, np.int64)
    return {"params": params,
            **({"routing_counts": counts} if counts.size else {})}


def seq_from_tree(tree: dict) -> tuple[Any, np.ndarray]:
    return tree["params"], np.asarray(
        tree.get("routing_counts", np.zeros((0, 0), np.int64)))


def mlp_tree(params: Any, normalizer: Normalizer, target_norm: Normalizer) -> dict:
    return {
        "params": params,
        "norm_mean": np.asarray(normalizer.mean),
        "norm_std": np.asarray(normalizer.std),
        "target_mean": np.asarray(target_norm.mean),
        "target_std": np.asarray(target_norm.std),
    }


def mlp_from_tree(tree: dict) -> tuple[Any, Normalizer, Normalizer]:
    return (
        tree["params"],
        Normalizer(mean=np.asarray(tree["norm_mean"]), std=np.asarray(tree["norm_std"])),
        Normalizer(
            mean=np.asarray(tree["target_mean"]), std=np.asarray(tree["target_std"])
        ),
    )
