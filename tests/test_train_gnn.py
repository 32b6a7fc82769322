"""GraphSAGE sampler + training tests (small scale; 1-core CPU host)."""

import numpy as np
import pytest

from dragonfly2_tpu.data import SyntheticCluster
from dragonfly2_tpu.data.graph_sampler import CSRGraph, EdgeBatchSampler
from dragonfly2_tpu.parallel import data_parallel_mesh
from dragonfly2_tpu.train import GNNTrainConfig, train_gnn


@pytest.fixture(scope="module")
def graph():
    return SyntheticCluster(n_hosts=100, seed=0).probe_graph(10000)


@pytest.fixture(scope="module")
def csr(graph):
    return CSRGraph.from_graph(graph)


class TestCSR:
    def test_structure(self, graph, csr):
        assert csr.n_nodes == graph.n_nodes
        assert csr.indptr[-1] == graph.n_edges
        # Every edge is represented exactly once.
        deg = np.diff(csr.indptr)
        np.testing.assert_array_equal(
            deg, np.bincount(graph.edge_src, minlength=graph.n_nodes)
        )

    def test_sample_neighbors_shapes_and_validity(self, csr):
        rng = np.random.default_rng(0)
        nodes = np.array([[0, 1], [2, 3]])
        nbr, rtt, mask = csr.sample_neighbors(nodes, 7, rng)
        assert nbr.shape == rtt.shape == mask.shape == (2, 2, 7)
        # Sampled neighbors of node v must be real out-neighbors of v.
        for i in (0, 1):
            for j in (0, 1):
                v = nodes[i, j]
                real = set(csr.indices[csr.indptr[v] : csr.indptr[v + 1]])
                for k in range(7):
                    if mask[i, j, k] > 0:
                        assert nbr[i, j, k] in real

    def test_zero_degree_padded(self, graph):
        # Nodes with no outgoing edges must pad cleanly — including the
        # highest-indexed node, whose CSR offset equals n_edges (the
        # out-of-bounds trap).
        g = graph
        last = g.n_nodes - 1
        keep = (g.edge_src != 0) & (g.edge_src != last)
        from dragonfly2_tpu.data.features import Graph

        g2 = Graph(g.node_ids, g.node_features, g.edge_src[keep],
                   g.edge_dst[keep], g.edge_rtt_ns[keep])
        csr2 = CSRGraph.from_graph(g2)
        for node in (0, last):
            nbr, rtt, mask = csr2.sample_neighbors(
                np.array([node]), 5, np.random.default_rng(0)
            )
            assert mask.sum() == 0 and nbr.sum() == 0 and rtt.sum() == 0

    def test_empty_graph_sampling(self):
        from dragonfly2_tpu.data.features import Graph

        g = Graph(np.array(["a", "b"]), np.zeros((2, 8), np.float32),
                  np.zeros(0, np.int32), np.zeros(0, np.int32),
                  np.zeros(0, np.int64))
        csr = CSRGraph.from_graph(g)
        nbr, rtt, mask = csr.sample_neighbors(
            np.array([0, 1]), 3, np.random.default_rng(0)
        )
        assert mask.sum() == 0 and nbr.shape == (2, 3)


class TestSampler:
    def test_static_shapes(self, graph, csr):
        labels = graph.edge_labels()
        s = EdgeBatchSampler(csr, graph.edge_src, graph.edge_dst, labels, (4, 3))
        batch = s.sample(np.arange(16), np.random.default_rng(0))
        F = graph.node_features.shape[1]
        assert batch.center_feat.shape == (16, 2, F)
        assert batch.nbr1_feat.shape == (16, 2, 4, F)
        assert batch.nbr2_feat.shape == (16, 2, 4, 3, F)
        assert batch.nbr2_mask.shape == (16, 2, 4, 3)
        assert batch.labels.shape == (16,)

    def test_epoch_batches_deterministic(self, graph, csr):
        labels = graph.edge_labels()
        s = EdgeBatchSampler(csr, graph.edge_src, graph.edge_dst, labels, (4, 3))
        a = [b.labels for b in s.epoch_batches(64, seed=1, epoch=0)]
        b = [b.labels for b in s.epoch_batches(64, seed=1, epoch=0)]
        c = [b.labels for b in s.epoch_batches(64, seed=1, epoch=1)]
        np.testing.assert_array_equal(np.concatenate(a), np.concatenate(b))
        assert not np.array_equal(np.concatenate(a), np.concatenate(c))

    def test_index_and_feature_modes_agree(self, graph, csr):
        """IndexEdgeBatch.to_features must reproduce the feature-mode
        arrays exactly — it's what proves the on-device gather computes
        the same batch the host gather did."""
        labels = graph.edge_labels()
        s = EdgeBatchSampler(csr, graph.edge_src, graph.edge_dst, labels, (4, 3))
        idx_batch = s.sample_indices(np.arange(32), np.random.default_rng(7))
        feat_batch = s.sample(np.arange(32), np.random.default_rng(7))
        from_idx = idx_batch.to_features(csr.node_features)
        for a, b in zip(from_idx.astuple(), feat_batch.astuple()):
            np.testing.assert_array_equal(a, b)


def _batch_major_logits(params, center, feat1, rtt1, mask1, feat2, rtt2,
                        mask2):
    """GraphSAGE as it was written until PR 34, plainly: batch-major
    tensors, ``[features | rtt]`` concatenated slot by slot and then a
    masked mean over ``axis=-2``."""
    import jax
    import jax.numpy as jnp

    def mean(x, mask):
        total = jnp.sum(x * mask[..., None], axis=-2)
        return total / jnp.maximum(jnp.sum(mask, axis=-1), 1.0)[..., None]

    def dense(x, layer):
        return jnp.matmul(x, layer["kernel"], precision="highest") + layer["bias"]

    p = params["params"]
    l1, l2 = p["SageLayer_0"]["Dense_0"], p["SageLayer_1"]["Dense_0"]
    x1 = jnp.concatenate([feat1, rtt1[..., None]], -1)     # [B, 2, f1, F+1]
    x2 = jnp.concatenate([feat2, rtt2[..., None]], -1)     # [B, 2, f1, f2, F+1]
    h1_nbr = jax.nn.relu(dense(
        jnp.concatenate([x1, mean(x2, mask2)], -1), l1))
    center0 = jnp.concatenate(
        [center, jnp.zeros(center.shape[:-1] + (1,))], -1)
    h1_center = jax.nn.relu(dense(
        jnp.concatenate([center0, mean(x1, mask1)], -1), l1))
    h2 = jax.nn.relu(dense(
        jnp.concatenate([h1_center, mean(h1_nbr, mask1)], -1), l2))
    a, b = h2[:, 0], h2[:, 1]
    pair = jnp.concatenate([a, b, a * b, jnp.abs(a - b)], -1)
    z = jax.nn.relu(dense(pair, p["Dense_0"]))
    return dense(z, p["Dense_1"])[:, 0]


class TestModelLayout:
    @pytest.mark.parametrize("fanouts", [(10, 5), (4, 3)],
                             ids=["cells_fanouts", "small_fanouts"])
    def test_fanout_leading_model_is_the_batch_major_formula(self, graph,
                                                             csr, fanouts):
        """The model takes fan-outs leading and the batch trailing and
        aggregates a hop before it concatenates the RTT column; in
        float32 its logits and parameter gradients are those of the old
        arithmetic (concatenate, then ``masked_mean`` over ``axis=-2`` of
        batch-major tensors) on a host-sampled batch with padded slots."""
        import jax
        import jax.numpy as jnp

        from dragonfly2_tpu.models.graphsage import GraphSAGE, nodes_last

        s = EdgeBatchSampler(csr, graph.edge_src, graph.edge_dst,
                             graph.edge_labels(), fanouts)
        batch = s.sample(np.arange(48), np.random.default_rng(4))
        args = [jnp.asarray(a) for a in batch.astuple()[:-1]]
        # Padded slots in both hops, as a zero-degree host leaves them.
        args[3] = args[3].at[:5, 0].set(0.0).at[7, 1, 1:].set(0.0)
        args[2] = args[2] * args[3]
        args[6] = (args[6] * args[3][..., None]).at[9, 0, 0, 1:].set(0.0)
        args[5] = args[5] * args[6]
        labels = jnp.asarray(batch.labels)
        model = GraphSAGE(hidden=16, embed=8, dtype=jnp.float32)
        params = model.init(jax.random.key(2), *nodes_last(*args))
        assert set(params["params"]) == {
            "SageLayer_0", "SageLayer_1", "Dense_0", "Dense_1"}
        assert params["params"]["SageLayer_0"]["Dense_0"]["kernel"].shape == (
            18, 16)

        def loss(logits):
            return jnp.mean(jnp.logaddexp(0.0, logits) - labels * logits)

        got, got_grad = jax.value_and_grad(
            lambda p: loss(model.apply(p, *nodes_last(*args))))(params)
        want, want_grad = jax.value_and_grad(
            lambda p: loss(_batch_major_logits(p, *args)))(params)
        np.testing.assert_allclose(
            np.asarray(model.apply(params, *nodes_last(*args))),
            np.asarray(_batch_major_logits(params, *args)),
            rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(got_grad), jax.tree.leaves(want_grad)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-6)


class TestPrefetch:
    def test_order_preserved_and_all_yielded(self):
        from dragonfly2_tpu.data.prefetch import prefetch

        out = list(prefetch(range(50), lambda i: i * i, depth=3, workers=4))
        assert out == [i * i for i in range(50)]

    def test_consumer_break_stops_cleanly(self):
        from dragonfly2_tpu.data.prefetch import prefetch

        seen = []
        stream = prefetch(range(1000), lambda i: seen.append(i) or i,
                          depth=2, workers=2)
        for v in stream:
            if v >= 5:
                stream.close()
                break
        # Bounded lookahead: at most depth+workers extra tasks started.
        assert len(seen) < 20

    def test_worker_exception_propagates(self):
        from dragonfly2_tpu.data.prefetch import prefetch

        def boom(i):
            if i == 3:
                raise RuntimeError("sampler died")
            return i

        with pytest.raises(RuntimeError, match="sampler died"):
            list(prefetch(range(10), boom, depth=2, workers=2))


class TestTrainGNN:
    def test_learns_topology(self, graph):
        res = train_gnn(
            graph,
            GNNTrainConfig(hidden=32, embed=16, batch_size=512, epochs=10,
                           learning_rate=1e-2),
            data_parallel_mesh(),
        )
        # The synthetic task is nearly separable; the GNN must crack it.
        assert res.f1 > 0.9
        assert res.precision > 0.85 and res.recall > 0.85
        assert res.history[-1] < 0.3
        assert res.samples_per_sec > 0

    def test_pair_level_split_no_leak(self, graph):
        from dragonfly2_tpu.train.gnn_trainer import edge_split as _edge_split

        train_ids, eval_ids = _edge_split(graph, 0.2, seed=0)
        assert len(train_ids) + len(eval_ids) == graph.n_edges
        train_pairs = set(zip(graph.edge_src[train_ids], graph.edge_dst[train_ids]))
        eval_pairs = set(zip(graph.edge_src[eval_ids], graph.edge_dst[eval_ids]))
        # No ordered (src, dst) pair may appear on both sides.
        assert not train_pairs & eval_pairs

    def test_gnn_checkpoint_roundtrip(self, graph, tmp_path):
        import jax.numpy as jnp

        from dragonfly2_tpu.data.graph_sampler import CSRGraph, EdgeBatchSampler
        from dragonfly2_tpu.models.graphsage import nodes_last
        from dragonfly2_tpu.train import checkpoint as ckpt

        res = train_gnn(
            graph,
            GNNTrainConfig(hidden=16, embed=8, batch_size=512, epochs=1),
            data_parallel_mesh(),
        )
        path = str(tmp_path / "gnn")
        ckpt.save_model(
            path,
            ckpt.gnn_tree(res.params, res.node_features),
            ckpt.ModelMetadata(model_id="g1", model_type="gnn",
                               evaluation={"f1": res.f1}),
        )
        tree, meta = ckpt.load_model(path)
        params, nf = ckpt.gnn_from_tree(tree)
        assert meta.model_type == "gnn"
        np.testing.assert_array_equal(nf, res.node_features)

        csr = CSRGraph.from_graph(graph)
        s = EdgeBatchSampler(csr, graph.edge_src, graph.edge_dst,
                             graph.edge_labels(), res.config.fanouts)
        batch = s.sample(np.arange(32), np.random.default_rng(0))
        args = nodes_last(*map(jnp.asarray, batch.astuple()[:-1]))
        a = res.model.apply(res.params, *args)
        b = res.model.apply(params, *args)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)

    def test_too_few_edges_raises(self):
        g = SyntheticCluster(n_hosts=10, seed=0).probe_graph(4)
        with pytest.raises(ValueError, match="can't fill"):
            train_gnn(g, GNNTrainConfig(batch_size=4096))

    def test_time_budget_stops_early(self, graph):
        """max_seconds caps the step loop but still returns a complete,
        evaluated result (the bench's un-killability contract)."""
        res = train_gnn(
            graph,
            GNNTrainConfig(hidden=16, embed=8, batch_size=256, epochs=50,
                           max_seconds=1.0),
            data_parallel_mesh(),
        )
        full_steps = 50 * (len(graph.edge_src) * 8 // 10 // 256)
        assert 1 <= res.steps < full_steps
        assert res.compile_seconds > 0
        assert res.samples_per_sec > 0
        assert 0.0 <= res.f1 <= 1.0
