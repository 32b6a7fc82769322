"""Runner for the ``graph_transformer`` kind: the window drives
``train/gat_trainer.py``'s ``train_gat``, the function the trainer
service calls, once, on the graph made from the seed."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks import instrument
from benchmarks.traffic import probe_graph


def traffic(spec: dict, seed: int) -> dict:
    """The cell's inputs from the seed: the probe graph of the
    configuration's ``fleet`` group. Same seed, same arrays; every seed
    the same sizes (README.md, "A model kind")."""
    return probe_graph(spec["fleet"], seed)


def drive(spec: dict, arrays: dict, seed: int, plan, mesh, wrap_step) -> None:
    from dragonfly2_tpu.data.features import Graph
    from dragonfly2_tpu.models.graph_transformer import GraphTransformer
    from dragonfly2_tpu.train import gat_trainer, step_budget

    m, o = spec["model"], spec["optimizer"]
    ran = jnp.dtype(GraphTransformer.dtype).name
    if ran != m["compute_dtype"]:
        raise RuntimeError(f"the configuration states {m['compute_dtype']}; "
                           f"the program computes in {ran}")
    graph = Graph(
        node_ids=np.arange(len(arrays["node_features"])).astype(str),
        node_features=arrays["node_features"],
        edge_src=arrays["edge_src"], edge_dst=arrays["edge_dst"],
        edge_rtt_ns=arrays["edge_rtt_ns"])
    config = gat_trainer.GATTrainConfig(
        hidden=m["hidden"], embed=m["embed"], layers=m["layers"],
        heads=m["heads"], chunk=m["chunk"], neighbor_cap=m["neighbor_cap"],
        attention=m["attention"], learning_rate=o["learning_rate"],
        weight_decay=o["weight_decay"],
        rtt_threshold_ns=o["rtt_threshold_ns"],
        edge_batch_size=spec["batch"], steps_per_call=spec["steps_per_call"],
        epochs=spec["epochs"], seed=seed, max_seconds=plan.seconds,
        # train_gat has no eval_max_seconds; only an empty eval split
        # keeps its evaluation (and that program's compile) out of the run.
        eval_fraction=spec["eval_fraction"])
    with instrument.window_budget(plan, step_budget), \
            instrument.observed_jit(gat_trainer, "train_step", wrap_step):
        gat_trainer.train_gat(graph, config, mesh)

