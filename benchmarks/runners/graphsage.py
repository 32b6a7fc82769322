"""Runner for the ``graphsage`` kind: the window drives
``train/gnn_trainer.py``'s ``train_gnn``, the function the trainer
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
    from dragonfly2_tpu.models.graphsage import GraphSAGE
    from dragonfly2_tpu.train import fused_sampling, gnn_trainer

    m, o = spec["model"], spec["optimizer"]
    ran = jnp.dtype(GraphSAGE.dtype).name
    if ran != m["compute_dtype"]:
        raise RuntimeError(f"the configuration states {m['compute_dtype']}; "
                           f"the program computes in {ran}")
    if not m["device_sample"] or spec["steps_per_call"] != 1:
        raise ValueError("this runner observes the fused one-step program "
                         "only (device_sample, steps_per_call 1)")
    graph = Graph(
        node_ids=np.arange(len(arrays["node_features"])).astype(str),
        node_features=arrays["node_features"],
        edge_src=arrays["edge_src"], edge_dst=arrays["edge_dst"],
        edge_rtt_ns=arrays["edge_rtt_ns"])
    config = gnn_trainer.GNNTrainConfig(
        hidden=m["hidden"], embed=m["embed"], fanouts=tuple(m["fanouts"]),
        device_sample=True, learning_rate=o["learning_rate"],
        weight_decay=o["weight_decay"],
        rtt_threshold_ns=o["rtt_threshold_ns"],
        batch_size=spec["batch"], steps_per_call=1,
        epochs=spec["epochs"], seed=seed, max_seconds=plan.seconds,
        # No evaluation inside or after the window: an empty eval split,
        # and the eval pass (with its program's compile) skipped.
        eval_fraction=spec["eval_fraction"], eval_max_seconds=0.0)
    with instrument.window_budget(plan, gnn_trainer), \
            instrument.observed_jit(fused_sampling, "train_step", wrap_step):
        gnn_trainer.train_gnn(graph, config, mesh)

