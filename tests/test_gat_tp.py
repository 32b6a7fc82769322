"""Sharded-weight tensor parallelism for the GraphTransformer
(round-5 verdict item 8 / SURVEY §2.7 stretch row).

Ring mode sharded activations and K/V; these tests cover the missing
half — layer WEIGHTS sharded over a ``model`` mesh axis (Megatron
column/row split via ``TPDense``), verified against the replicated
model numerically and shown to reduce per-device parameter memory.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from dragonfly2_tpu.data import SyntheticCluster
from dragonfly2_tpu.models.graph_transformer import (
    GraphTransformer,
    build_neighbor_lists,
    pad_graph_sparse,
)
from dragonfly2_tpu.parallel import data_parallel_mesh
from dragonfly2_tpu.train.gat_trainer import (
    GATTrainConfig,
    tp_state_shardings,
    train_gat,
)


@pytest.fixture(scope="module")
def graph():
    return SyntheticCluster(n_hosts=48, seed=4).probe_graph(2500)


CFG = GATTrainConfig(hidden=32, embed=16, layers=2, heads=4, epochs=3,
                     edge_batch_size=512, eval_fraction=0.2)


@pytest.fixture(scope="module")
def dp_result(graph):
    """One data-parallel training shared by the comparison tests."""
    return train_gat(graph, CFG, data_parallel_mesh())


class TestTensorParallel:
    def test_tp_training_matches_data_parallel(self, graph, dp_result):
        """Same seed, same batches: a (4 data × 2 model) mesh must walk
        the same loss trajectory as pure data parallelism — weight
        sharding is a placement detail, invisible in the math."""
        tp = train_gat(graph, CFG, data_parallel_mesh(model_parallel=2))
        np.testing.assert_allclose(tp.history, dp_result.history,
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(tp.f1, dp_result.f1,
                                   rtol=5e-2, atol=5e-2)

    def test_tp_embeddings_match_and_param_memory_drops(self, graph,
                                                        dp_result):
        """TP-sharded weights produce the same embeddings, at roughly
        half the per-device parameter bytes for the sharded layers."""
        mesh_tp = data_parallel_mesh(model_parallel=2)
        result = dp_result
        nbr, val = build_neighbor_lists(
            graph.n_nodes, graph.edge_src, graph.edge_dst,
            graph.edge_rtt_ns)
        f, nb, vl, _ = pad_graph_sparse(graph.node_features, nbr, val, 8)
        model = result.model
        e_plain = np.asarray(model.apply(
            result.params, f, nb, vl,
            method=GraphTransformer.node_embeddings))

        # Jit, never eager: op-by-op collectives (the TP psum) abort
        # intermittently on XLA:CPU (conftest rendezvous note).
        @jax.jit
        def run(p, f_, nb_, vl_):
            return model.apply(p, f_, nb_, vl_,
                               method=GraphTransformer.node_embeddings)

        with jax.set_mesh(mesh_tp.mesh):
            row = mesh_tp.shard_spec("data")
            params_tp = jax.device_put(
                result.params, tp_state_shardings(result.params, mesh_tp))
            e_tp = np.asarray(run(
                params_tp, jax.device_put(f, row),
                jax.device_put(nb, row), jax.device_put(vl, row)))
        np.testing.assert_allclose(e_plain, e_tp, rtol=2e-2, atol=2e-2)

        per_device = sum(leaf.addressable_shards[0].data.nbytes
                         for leaf in jax.tree.leaves(params_tp))
        replicated = sum(np.asarray(leaf).nbytes
                         for leaf in jax.tree.leaves(result.params))
        # The six Dense layers per block dominate this model's params;
        # splitting them in half over `model` must show up.
        assert per_device < 0.75 * replicated, (per_device, replicated)

    def test_tp_shardings_place_kernels_as_megatron(self, graph,
                                                    dp_result):
        from jax.sharding import PartitionSpec as P

        mesh_tp = data_parallel_mesh(model_parallel=2)
        specs = tp_state_shardings(dp_result.params, mesh_tp)
        block = specs["params"]["blocks_0"]
        assert block["Dense_0"]["kernel"].spec == P(None, "model")  # q col
        assert block["Dense_0"]["bias"].spec == P("model")
        assert block["Dense_3"]["kernel"].spec == P("model", None)  # out row
        assert block["Dense_3"]["bias"].spec == P()
        assert block["Dense_4"]["kernel"].spec == P(None, "model")  # up col
        assert block["Dense_5"]["kernel"].spec == P("model", None)  # down row
        assert specs["params"]["input_proj"]["kernel"].spec == P()

    def test_tp_rejects_unsupported_configs(self, graph):
        mesh_tp = data_parallel_mesh(model_parallel=2)
        with pytest.raises(ValueError, match="ring"):
            train_gat(graph, GATTrainConfig(attention="ring"), mesh_tp)
        with pytest.raises(ValueError, match="divisible"):
            train_gat(graph, GATTrainConfig(heads=3, hidden=33), mesh_tp)
