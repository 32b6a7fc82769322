"""GraphTransformer (config #3) tests on the virtual 8-device mesh.

Verifies the block-sparse chunked-attention layout compiles and runs
sharded, the edge head learns on a separable synthetic topology,
padding/masking keep phantom nodes out of the math, and — the round-4
mandate — a 100k+-node full-topology graph trains without the O(N²)
dense bias/mask the old layout required.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from dragonfly2_tpu.data import SyntheticCluster
from dragonfly2_tpu.models.graph_transformer import (
    PAD_ID,
    GraphTransformer,
    build_neighbor_lists,
    pad_graph_sparse,
)
from dragonfly2_tpu.parallel import data_parallel_mesh
from dragonfly2_tpu.train.gat_trainer import GATTrainConfig, train_gat


@pytest.fixture(scope="module")
def trained():
    cluster = SyntheticCluster(n_hosts=48, seed=0)
    graph = cluster.probe_graph(4000)
    mesh = data_parallel_mesh()
    result = train_gat(
        graph,
        GATTrainConfig(hidden=32, embed=16, layers=2, heads=4, epochs=30,
                       edge_batch_size=512, learning_rate=1e-2,
                       eval_fraction=0.15),
        mesh,
    )
    return {"result": result, "graph": graph, "mesh": mesh}


class TestNeighborLists:
    def test_lists_and_bias(self):
        src = np.array([0, 1], dtype=np.int64)
        dst = np.array([1, 2], dtype=np.int64)
        rtt = np.array([1_000_000, 50_000_000], dtype=np.int64)  # 1ms, 50ms
        nbr, val = build_neighbor_lists(4, src, dst, rtt)

        def entries(row):
            return {int(c): float(v) for c, v in zip(nbr[row], val[row])
                    if c != PAD_ID}

        e0, e1, e3 = entries(0), entries(1), entries(3)
        assert 1 in e0 and 0 in e1          # symmetrized
        assert 2 not in e0                   # non-edge absent
        assert e3 == {3: 0.0}                # isolated node: self only
        assert e0[1] > e1[2]                 # faster edge → larger bias
        assert e0[0] == 0.0                  # self slot, max bias

    def test_dedup_best_rtt(self):
        """Repeated sightings of a pair (either direction) keep the BEST
        RTT — the scatter-add in the model relies on uniqueness."""
        src = np.array([0, 1, 0], dtype=np.int64)
        dst = np.array([1, 0, 1], dtype=np.int64)
        rtt = np.array([9_000_000, 2_000_000, 5_000_000], dtype=np.int64)
        nbr, val = build_neighbor_lists(2, src, dst, rtt)
        row0 = {int(c): float(v) for c, v in zip(nbr[0], val[0])
                if c != PAD_ID}
        assert list(nbr[0]).count(1) == 1    # deduped
        best = -np.log1p(2.0)
        np.testing.assert_allclose(row0[1], best, rtol=1e-6)

    def test_cap_keeps_best(self):
        """With a cap, the highest-bias (fastest) neighbors survive and
        self always survives."""
        n = 10
        src = np.zeros(9, dtype=np.int64)
        dst = np.arange(1, 10, dtype=np.int64)
        rtt = (np.arange(1, 10, dtype=np.int64)) * 1_000_000  # 1..9 ms
        nbr, val = build_neighbor_lists(n, src, dst, rtt, cap=4)
        row0 = {int(c) for c in nbr[0] if c != PAD_ID}
        assert row0 == {0, 1, 2, 3}          # self + 3 fastest
        assert nbr.shape[1] <= 4

    def test_pad_graph_sparse(self):
        feats = np.ones((10, 4), np.float32)
        nbr = np.zeros((10, 3), np.int32)
        val = np.zeros((10, 3), np.float32)
        f, nb, vl, n = pad_graph_sparse(feats, nbr, val, 8)
        assert f.shape == (16, 4) and nb.shape == (16, 3)
        assert n == 10
        assert nb[12, 0] == 12               # phantom self slot
        assert (nb[12, 1:] == PAD_ID).all()

    def test_divisor_block(self):
        from dragonfly2_tpu.models.graph_transformer import _divisor_block

        assert _divisor_block(104, 16) == 13   # the ADVICE r4 repro shape
        assert _divisor_block(1024, 256) == 256
        assert _divisor_block(7, 4) == 1       # prime: degenerate but legal
        assert _divisor_block(12, 100) == 12   # whole array in one block


class TestTraining:
    def test_runs_sharded_on_mesh(self, trained):
        mesh = trained["mesh"]
        assert mesh.n_data == jax.device_count()
        result = trained["result"]
        assert result.n_real_nodes == 48
        assert result.node_features.shape[0] % mesh.n_data == 0
        assert len(result.history) == 30
        assert result.samples_per_sec > 0

    def test_learns_separable_topology(self, trained):
        """Synthetic cluster RTTs are largely explained by idc/region
        affinity present in the node features + bias — the model must beat
        the trivial all-positive/all-negative baselines."""
        result = trained["result"]
        assert result.history[-1] < result.history[0]  # loss decreased
        assert result.accuracy > 0.6
        assert result.f1 > 0.3, (result.precision, result.recall)

    def test_padded_nodes_do_not_leak(self, trained):
        """Embeddings of real nodes must be invariant to padded phantom
        rows: recompute with extra padding and compare."""
        result = trained["result"]
        graph = trained["graph"]
        model = result.model
        nbr, val = build_neighbor_lists(
            graph.n_nodes, graph.edge_src, graph.edge_dst, graph.edge_rtt_ns)
        f1, n1, v1, _ = pad_graph_sparse(graph.node_features, nbr, val, 8)
        f2, n2, v2, _ = pad_graph_sparse(graph.node_features, nbr, val, 64)

        def embed(f, nb, vl):
            return model.apply(
                result.params, f, nb, vl,
                method=GraphTransformer.node_embeddings,
            )

        e1 = np.asarray(embed(f1, n1, v1))[: graph.n_nodes]
        e2 = np.asarray(embed(f2, n2, v2))[: graph.n_nodes]
        np.testing.assert_allclose(e1, e2, rtol=2e-2, atol=2e-2)

    def test_attention_impls_agree(self, trained):
        """The layout is a pure detail: gather-mode embeddings agree
        with ring mode's scan over key blocks (no mesh here, so the ring
        of one device), in many blocks (chunk=16) and in one."""
        result = trained["result"]
        graph = trained["graph"]
        nbr, val = build_neighbor_lists(
            graph.n_nodes, graph.edge_src, graph.edge_dst, graph.edge_rtt_ns)

        def embed(attention, chunk, rows=16):
            f, nb, vl, _ = pad_graph_sparse(graph.node_features, nbr, val,
                                            rows)
            model = GraphTransformer(
                hidden=result.config.hidden, embed=result.config.embed,
                layers=result.config.layers, heads=result.config.heads,
                chunk=chunk, attention=attention)
            return np.asarray(model.apply(
                result.params, f, nb, vl,
                method=GraphTransformer.node_embeddings))[:graph.n_nodes]

        # bf16 P·V accumulation order differs across implementations;
        # tolerance covers the reorder noise, not a semantic gap.
        gather = embed("gather", 4096)
        np.testing.assert_allclose(gather, embed("ring", 16),
                                   rtol=6e-2, atol=6e-2)
        np.testing.assert_allclose(gather, embed("ring", 4096),
                                   rtol=6e-2, atol=6e-2)
        # An odd count of key blocks: 112 rows at chunk=16 are 7.
        np.testing.assert_allclose(gather, embed("ring", 16, rows=112),
                                   rtol=6e-2, atol=6e-2)

    @pytest.mark.parametrize("chunk", [4, 16, 1024])
    @pytest.mark.parametrize("devices", [1, 2, 4, 8])
    def test_ring_matches_gather(self, trained, devices, chunk):
        """Ring mode (K/V row-sharded, ppermuted around the mesh) is the
        same math again, forward and through one training step's
        gradients: over 2, 4 and 8 devices, and on 1 with no mesh at all
        (the ring of one: the scan without collectives), in sub-blocks
        of 4 and 16 columns and with a chunk larger than a device's rows
        (one block a ring step). In float32, where the two differ by
        the order of their sums alone."""
        import jax.numpy as jnp
        import optax

        result = trained["result"]
        graph = trained["graph"]
        nbr, val = build_neighbor_lists(
            graph.n_nodes, graph.edge_src, graph.edge_dst, graph.edge_rtt_ns)
        # train_gat's padding rule for ring mode.
        per_device = -(-graph.n_nodes // devices)
        f, nb, vl, _ = pad_graph_sparse(
            graph.node_features, nbr, val,
            devices * chunk if per_device > chunk else devices)
        src = graph.edge_src[:256].astype(np.int32)
        dst = graph.edge_dst[:256].astype(np.int32)
        y = graph.edge_labels(result.config.rtt_threshold_ns)[:256].astype(
            np.float32)
        mesh = (data_parallel_mesh(devices=jax.devices()[:devices])
                if devices > 1 else None)

        def run(attention):
            model = GraphTransformer(
                hidden=result.config.hidden, embed=result.config.embed,
                layers=result.config.layers, heads=result.config.heads,
                chunk=chunk, attention=attention, dtype=jnp.float32)

            # Jit, never eager: op-by-op shard_map collectives abort
            # intermittently on XLA:CPU (conftest rendezvous note).
            @jax.jit
            def step(p, f_, nb_, vl_):
                def loss(p_):
                    logits = model.apply(p_, f_, nb_, vl_, src, dst)
                    return optax.sigmoid_binary_cross_entropy(
                        logits, y).mean()

                emb = model.apply(p, f_, nb_, vl_,
                                  method=GraphTransformer.node_embeddings)
                return emb, jax.grad(loss)(p)

            if mesh is None:
                emb, grads = step(result.params, f, nb, vl)
            else:
                row = mesh.shard_spec("data")
                with jax.set_mesh(mesh.mesh):
                    emb, grads = step(
                        jax.device_put(result.params, mesh.replicated),
                        jax.device_put(f, row), jax.device_put(nb, row),
                        jax.device_put(vl, row))
            return np.asarray(emb), np.concatenate([
                np.asarray(g, np.float32).ravel()
                for g in jax.tree.leaves(grads)])

        ring_emb, ring_grad = run("ring")
        emb, grad = run("gather")
        # float32 throughout, so only the order of the sums differs:
        # read 3e-7 (embeddings) and 1e-6 (gradients) of the largest.
        assert np.abs(ring_emb - emb).max() <= 1e-5 * np.abs(emb).max()
        assert np.abs(ring_grad - grad).max() <= 1e-5 * np.abs(grad).max()

    def test_ring_trains_end_to_end(self):
        cluster = SyntheticCluster(n_hosts=48, seed=1)
        graph = cluster.probe_graph(2500)
        result = train_gat(
            graph,
            GATTrainConfig(hidden=16, embed=8, layers=1, heads=2,
                           epochs=3, edge_batch_size=256,
                           eval_fraction=0.2, attention="ring", chunk=4),
            data_parallel_mesh(),
        )
        assert len(result.history) == 3
        assert np.isfinite(result.history[-1])
        assert result.history[-1] < result.history[0]

    @pytest.mark.parametrize("attention", ["gather", "ring"])
    def test_lazy_init_is_the_eager_init(self, attention):
        """``train_gat`` (and ``chip_smoke``) draw parameters with
        ``model.lazy_init`` over the graph's shapes: ``model.init``'s
        parameters bit for bit (the benchmark's reference draws its own
        eagerly and compares at a limit of 0), without a forward over
        the fleet. The shapes here are a fleet's; the eager call runs
        on 256 rows."""
        import jax.numpy as jnp

        rng = np.random.default_rng(0)
        n, fleet, cap = 256, 51_200, 64
        nbr, val = build_neighbor_lists(
            n, rng.integers(0, n, 2000), rng.integers(0, n, 2000),
            rng.integers(1_000_000, 90_000_000, 2000), cap=16)
        feats = rng.normal(size=(n, 8)).astype(np.float32)
        model = GraphTransformer(hidden=32, embed=16, heads=4, chunk=64,
                                 attention=attention)
        key, pair = jax.random.key(3), jnp.zeros(2, jnp.int32)

        eager = model.init(key, jnp.asarray(feats), jnp.asarray(nbr),
                           jnp.asarray(val), pair, pair)
        lazy = model.lazy_init(
            key, jax.ShapeDtypeStruct((fleet, 8), jnp.float32),
            jax.ShapeDtypeStruct((fleet, cap), jnp.int32),
            jax.ShapeDtypeStruct((fleet, cap), jnp.float32), pair, pair)
        assert jax.tree.structure(eager) == jax.tree.structure(lazy)
        for a, b in zip(jax.tree.leaves(eager), jax.tree.leaves(lazy)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_multi_step_scan_matches_single_step(self):
        """steps_per_call=K runs K optimizer steps per dispatch under
        lax.scan (the GNN path's amortization, ported per the round-5
        verdict); same seed and batch order, so the learning trajectory
        must match the single-step program to float-fusion noise."""
        cluster = SyntheticCluster(n_hosts=48, seed=3)
        graph = cluster.probe_graph(2500)

        def train(k):
            return train_gat(
                graph,
                GATTrainConfig(hidden=16, embed=8, layers=1, heads=2,
                               epochs=4, edge_batch_size=256,
                               eval_fraction=0.2, steps_per_call=k),
                data_parallel_mesh(),
            )

        one, four = train(1), train(4)
        # Full-k groups + tail dispatch cover the SAME steps in the same
        # order regardless of divisibility, so trajectories coincide.
        assert len(four.history) == len(one.history)
        np.testing.assert_allclose(four.history, one.history,
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(four.f1, one.f1, rtol=1e-3, atol=1e-3)

    def test_ring_small_graph_large_chunk(self):
        """ADVICE r4 (medium): ring mode where per-device rows fit one
        chunk but the PADDED global N exceeds it (104 rows, chunk=16 on
        8 devices) used to trip ``n % block == 0`` at model.init — init
        runs outside the mesh, so the ring falls back to the global
        chunked scan, and ring padding only aligns rows per-device. The
        fallback now shrinks its block to a divisor of N."""
        cluster = SyntheticCluster(n_hosts=100, seed=2)
        graph = cluster.probe_graph(1500)
        result = train_gat(
            graph,
            GATTrainConfig(hidden=16, embed=8, layers=1, heads=2,
                           epochs=2, edge_batch_size=256,
                           eval_fraction=0.2, attention="ring", chunk=16),
            data_parallel_mesh(),
        )
        assert np.isfinite(result.history[-1])

    def test_edge_scores_finite_and_discriminative(self, trained):
        result = trained["result"]
        graph = trained["graph"]
        labels = graph.edge_labels(result.config.rtt_threshold_ns)
        logits = np.asarray(result.model.apply(
            result.params, result.node_features, result.neighbors,
            result.neighbor_vals,
            graph.edge_src.astype(np.int32), graph.edge_dst.astype(np.int32),
        ))
        assert np.isfinite(logits).all()
        # good edges should score higher on average than bad ones
        assert logits[labels == 1].mean() > logits[labels == 0].mean()


class TestAttentionModes:
    """``attention`` has two values; anything else is refused where it
    enters, in the trainer before any work and in the model."""

    @pytest.mark.parametrize("mode", ["blocks", "flash", "", "Gather"])
    @pytest.mark.parametrize("entry", ["train_gat", "apply"])
    def test_unknown_mode_is_a_value_error(self, entry, mode):
        with pytest.raises(ValueError, match="'gather' or 'ring'"):
            if entry == "train_gat":
                # No graph: the refusal comes before anything reads one.
                train_gat(None, GATTrainConfig(attention=mode))
            else:
                nbr = np.zeros((8, 1), np.int32)
                nbr[:, 0] = np.arange(8)
                GraphTransformer(hidden=8, embed=4, layers=1, heads=2,
                                 attention=mode).init(
                    jax.random.key(0), np.zeros((8, 8), np.float32), nbr,
                    np.zeros((8, 1), np.float32),
                    np.zeros(2, np.int32), np.zeros(2, np.int32))


def _graph_100k(n_edges=400_000, cap=32):
    rng = np.random.default_rng(0)
    n_nodes, feat_dim = 100_000, 8
    src = rng.integers(0, n_nodes, n_edges)
    dst = rng.integers(0, n_nodes, n_edges)
    rtt = rng.integers(1_000_000, 50_000_000, n_edges)
    feats = rng.standard_normal((n_nodes, feat_dim)).astype(np.float32)
    nbr, val = build_neighbor_lists(n_nodes, src, dst, rtt, cap=cap)
    return n_nodes, feats, nbr, val, src, dst, rtt


class TestInverseIndex:
    """The attention's hand-written backward (build_inverse_index + the
    custom VJP over the whole attention): exactness of the host
    transpose and gradient parity with autodiff's scatter-add, on and
    off the mesh."""

    def _graph(self, n=220, e=2400, cap=12, seed=3):
        rng = np.random.default_rng(seed)
        src = rng.integers(0, n, e)
        dst = rng.integers(0, n, e)
        rtt = rng.integers(1_000_000, 90_000_000, e)
        nbr, val = build_neighbor_lists(n, src, dst, rtt, cap=cap)
        feats = rng.normal(size=(n, 10)).astype(np.float32)
        feats, nbr, val, _ = pad_graph_sparse(feats, nbr, val, 8)
        return feats, nbr, val, src, dst, rtt

    @staticmethod
    def _plain_transpose(nbr, val):
        """Who lists host j, and under which bias, by a loop."""
        listed = [[] for _ in range(nbr.shape[0])]
        for i, row in enumerate(nbr):
            for s, j in enumerate(row):
                if j != PAD_ID:
                    listed[j].append((i, val[i, s]))
        return listed

    @pytest.mark.parametrize("dtype,tile", [("bfloat16", 16),
                                            ("float32", 8)])
    def test_inverse_index_is_exact_transpose(self, dtype, tile):
        from dragonfly2_tpu.models.graph_transformer import (
            build_inverse_index,
        )

        _, nbr, val, _, _, _ = self._graph()
        inv = build_inverse_index(nbr, val, dtype)
        listed = self._plain_transpose(nbr, val)
        widest = max(map(len, listed))
        # The width is the widest in-degree, rounded up to the dtype's
        # sublane tile and no further.
        assert inv.rows.shape == inv.vals.shape
        assert inv.rows.shape[1] % tile == 0
        assert 0 <= inv.rows.shape[1] - widest < tile
        assert inv.rows.dtype == np.int32 and inv.vals.dtype == np.float32
        for j, pairs in enumerate(listed):
            d = len(pairs)
            assert inv.rows[j, :d].tolist() == [i for i, _ in pairs]
            assert inv.vals[j, :d].tolist() == [b for _, b in pairs]
            assert (inv.rows[j, d:] == -1).all()
            assert (inv.vals[j, d:] == 0).all()

    @staticmethod
    def _lists(shape):
        """Neighbor lists of one degree shape, through the product's own
        builder."""
        rng = np.random.default_rng(5)
        if shape == "regular":           # a ring lattice: every host 7
            n, cap = 120, 16
            src = np.repeat(np.arange(n), 3)
            dst = (src + np.tile([1, 2, 3], n)) % n
        elif shape == "skew":            # the benchmark's: 5-195 sent
            n, cap = 400, 64
            sent = 5 + (190 * rng.random(n) ** 4).astype(np.int64)
            assert sent.min() == 5 and sent.max() > 150
            src = np.repeat(np.arange(n), sent)
            dst = rng.integers(0, n, len(src))
        elif shape == "hub":             # every host lists host 0
            n, cap = 150, 16
            src = np.arange(1, n)
            dst = np.zeros(n - 1, np.int64)
        elif shape == "self_only":       # no probe seen yet
            n, cap = 40, 16
            src = dst = np.zeros(0, np.int64)
        rtt = rng.integers(1_000_000, 90_000_000, len(src))
        nbr, val = build_neighbor_lists(n, src, dst, rtt, cap=cap)
        return nbr, val, cap

    DEGREE_SHAPES = ["regular", "skew", "hub", "self_only"]

    @pytest.mark.parametrize("shape", DEGREE_SHAPES)
    def test_inverse_index_transposes_every_degree_shape(self, shape):
        from dragonfly2_tpu.models.graph_transformer import (
            build_inverse_index,
        )

        nbr, val, cap = self._lists(shape)
        n, k_width = nbr.shape
        filled = (nbr != PAD_ID).sum(axis=1)
        in_degree = np.bincount(nbr[nbr != PAD_ID], minlength=n)
        if shape == "regular":
            assert (filled == 7).all() and (in_degree == 7).all()
        elif shape == "skew":
            assert filled.min() < cap and (filled == cap).any()
            assert in_degree.max() > cap           # wider than the lists
        elif shape == "hub":
            assert filled[0] == cap and in_degree[0] == n
        else:
            assert k_width == 1 and (in_degree == 1).all()

        inv = build_inverse_index(nbr, val, "float32")
        assert inv.rows.shape == (n, -(-in_degree.max() // 8) * 8)
        # Row j holds exactly the hosts whose lists name j, each once,
        # with the bias of that listing.
        hosts, slots = np.nonzero(inv.rows >= 0)
        listing = inv.rows[hosts, slots]
        at = (nbr[listing] == hosts[:, None]).argmax(axis=1)
        assert (nbr[listing, at] == hosts).all()
        assert (val[listing, at] == inv.vals[hosts, slots]).all()
        assert len(np.unique(listing * n + hosts)) == len(hosts) == filled.sum()
        assert ((inv.rows >= 0).sum(axis=1) == in_degree).all()

    def _grads(self, use_inv, mesh=None):
        import jax.numpy as jnp
        import optax

        from dragonfly2_tpu.models.graph_transformer import (
            build_inverse_index,
        )

        feats, nbr, val, src, dst, rtt = self._graph()
        inv = build_inverse_index(nbr, val) if use_inv else None
        model = GraphTransformer(hidden=32, embed=16, layers=2, heads=4,
                                 attention="gather")
        params = model.init(
            jax.random.key(0), jnp.asarray(feats), jnp.asarray(nbr),
            jnp.asarray(val), jnp.zeros(4, jnp.int32),
            jnp.zeros(4, jnp.int32))
        bs = jnp.asarray(src[:256].astype(np.int32))
        bd = jnp.asarray(dst[:256].astype(np.int32))
        y = jnp.asarray((rtt[:256] > 2e7).astype(np.float32))

        def loss(p, feat_, nbr_, val_, inv_):
            logits = model.apply(p, feat_, nbr_, val_, bs, bd, inv=inv_)
            return optax.sigmoid_binary_cross_entropy(logits, y).mean()

        grad_fn = jax.jit(jax.value_and_grad(loss))
        if mesh is None:
            return grad_fn(params, jnp.asarray(feats), jnp.asarray(nbr),
                           jnp.asarray(val), jax.tree.map(jnp.asarray, inv))
        row = mesh.shard_spec("data")
        args = (jax.device_put(params, mesh.replicated),
                jax.device_put(feats, row), jax.device_put(nbr, row),
                jax.device_put(val, row),
                None if inv is None else jax.device_put(inv, row))
        with jax.set_mesh(mesh.mesh):
            return grad_fn(*args)

    def _assert_close(self, g0, g1):
        flat0 = jax.tree_util.tree_leaves(g0)
        flat1 = jax.tree_util.tree_leaves(g1)
        maxnorm = max(float(np.max(np.abs(a))) for a in flat0)
        maxdiff = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                      for a, b in zip(flat0, flat1))
        assert maxdiff <= 2e-2 * maxnorm + 1e-6, (maxdiff, maxnorm)

    def test_backward_matches_autodiff(self):
        l0, g0 = self._grads(use_inv=False)
        l1, g1 = self._grads(use_inv=True)
        assert abs(float(l0) - float(l1)) < 1e-5
        self._assert_close(g0, g1)

    def test_backward_matches_autodiff_on_mesh(self):
        mesh = data_parallel_mesh()
        l0, g0 = self._grads(use_inv=False, mesh=mesh)
        l1, g1 = self._grads(use_inv=True, mesh=mesh)
        assert abs(float(l0) - float(l1)) < 1e-5
        self._assert_close(g0, g1)


class TestLaneDenseGatherAttention:
    """``gather_graph_attention`` keeps q, the gathered [k|v] rows and
    their cotangents ``heads·head_dim`` lanes wide and never splits the
    head axis; a plain float32 per-head implementation, autodiff's
    scatter-add backward included, says what it has to compute."""

    N, HIDDEN, CAP = 120, 128, 12

    def _inputs(self, dtype):
        import jax.numpy as jnp

        rng = np.random.default_rng(11)
        src = rng.integers(0, self.N, 600)
        dst = rng.integers(0, self.N, 600)
        rtt = rng.integers(1_000_000, 90_000_000, 600)
        nbr, val = build_neighbor_lists(self.N, src, dst, rtt, cap=self.CAP)
        filled = (nbr != PAD_ID).sum(axis=1)
        assert nbr.shape[1] == self.CAP
        assert (filled == self.CAP).any() and (filled < self.CAP).any()
        q, k, v, w = (
            jnp.asarray(rng.normal(size=(self.N, self.HIDDEN)), dtype)
            for _ in range(4))
        return q, k, v, jnp.asarray(nbr), jnp.asarray(val), w

    @staticmethod
    def _inverse(nbr, val, dtype):
        from dragonfly2_tpu.models.graph_transformer import (
            build_inverse_index,
        )

        return jax.tree.map(jax.numpy.asarray, build_inverse_index(
            np.asarray(nbr), np.asarray(val), dtype))

    @staticmethod
    def _per_head(q, k, v, nbr, val, heads):
        import jax.numpy as jnp

        n, hidden = q.shape
        d = hidden // heads
        qh, kh, vh = (t.astype(jnp.float32).reshape(n, heads, d)
                      for t in (q, k, v))
        pad = nbr >= n
        idx = jnp.where(pad, 0, nbr)
        s = jnp.einsum("nhd,nkhd->nhk", qh, kh[idx],
                       precision="highest") / np.sqrt(d)
        s = jnp.where(pad[:, None, :], -1e9, s + val[:, None, :])
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("nhk,nkhd->nhd", p, vh[idx], precision="highest")
        return out.reshape(n, hidden)

    @staticmethod
    def _out_and_grads(attend, q, k, v, val, w):
        import jax.numpy as jnp

        def weighted(q_, k_, v_, val_):
            out = attend(q_, k_, v_, val_)
            return (out.astype(jnp.float32) * w.astype(jnp.float32)).sum()

        grads = jax.jit(jax.grad(weighted, argnums=(0, 1, 2, 3)))(
            q, k, v, val)
        return jax.jit(attend)(q, k, v, val), grads

    @staticmethod
    def _assert_close(got, want, tol):
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            assert np.abs(a - b).max() <= tol * np.abs(b).max() + 1e-6

    @pytest.mark.parametrize("dtype,tol", [("float32", 1e-5),
                                           ("bfloat16", 2e-2)])
    @pytest.mark.parametrize("heads,head_dim", [(4, 32), (2, 64), (1, 128)])
    def test_matches_per_head_float32(self, heads, head_dim, dtype, tol):
        from dragonfly2_tpu.models.graph_transformer import (
            gather_graph_attention,
        )

        assert heads * head_dim == self.HIDDEN
        q, k, v, nbr, val, w = self._inputs(dtype)
        inv = self._inverse(nbr, val, dtype)
        got = self._out_and_grads(
            lambda *a: gather_graph_attention(*a[:3], nbr, a[3], inv,
                                              heads=heads),
            q, k, v, val, w)
        want = self._out_and_grads(
            lambda *a: self._per_head(*a[:3], nbr, a[3], heads),
            q, k, v, val, w)
        assert got[0].shape == (self.N, self.HIDDEN)
        assert got[0].dtype == q.dtype
        self._assert_close(got, want, tol)

    @staticmethod
    def _masked_lists(n, k_width, seed):
        """``[n, k_width]`` lists with every mask the cells' traffic
        lacks: rows holding the self slot alone, rows cut by the cap
        (the width, or half the fleet where the width exceeds it: the
        columns past it are pads in every row) and rows with pad
        tails."""
        rng = np.random.default_rng(seed)
        cap = min(k_width, n // 2)
        lonely = n // 8                        # hosts no probe names
        pairs = np.arange(lonely, n // 4, 2)   # hosts with one neighbour
        rest = n // 4
        busy = np.arange(rest, rest + 4)       # hosts probing everyone
        src = np.concatenate([
            pairs, rng.integers(rest, n, 2 * n), np.repeat(busy, n - rest)])
        dst = np.concatenate([
            pairs + 1, rng.integers(rest, n, 2 * n),
            np.tile(np.arange(rest, n), len(busy))])
        rtt = rng.integers(1_000_000, 90_000_000, len(src))
        nbr, val = build_neighbor_lists(n, src, dst, rtt, cap=cap)
        extra = k_width - nbr.shape[1]
        nbr = np.pad(nbr, ((0, 0), (0, extra)), constant_values=PAD_ID)
        val = np.pad(val, ((0, 0), (0, extra)))
        filled = (nbr != PAD_ID).sum(axis=1)
        assert nbr.shape == (n, k_width)
        assert (filled == 1).any() and (filled == cap).any()
        assert ((filled > 1) & (filled < cap)).any()
        return nbr, val

    @staticmethod
    def _dense_masked_softmax(q, k, v, nbr, val, heads):
        """The same attention with nothing sparse about it: an ``[N, N]``
        bias and mask, every key column scored, float32 throughout."""
        import jax.numpy as jnp

        n, hidden = q.shape
        d = hidden // heads
        nbr = np.asarray(nbr)
        rows, slots = np.nonzero(nbr != PAD_ID)
        cols = nbr[rows, slots]
        mask = np.zeros((n, n), bool)
        mask[rows, cols] = True
        bias = jnp.zeros((n, n), jnp.float32).at[rows, cols].set(
            val.astype(jnp.float32)[rows, slots])
        qh, kh, vh = (t.astype(jnp.float32).reshape(n, heads, d)
                      for t in (q, k, v))
        s = jnp.einsum("ihd,jhd->hij", qh, kh,
                       precision="highest") / np.sqrt(d)
        s = jnp.where(mask[None], s + bias[None], -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("hij,jhd->ihd", p, vh, precision="highest")
        return out.reshape(n, hidden)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("what", ["output", "gradients"])
    @pytest.mark.parametrize("backward", ["autodiff", "inverse_index"])
    @pytest.mark.parametrize("heads", [1, 4])
    @pytest.mark.parametrize("n,k_width", [(96, 5), (128, 8), (200, 16),
                                           (64, 76)])
    def test_matches_dense_masked_softmax(self, n, k_width, heads, backward,
                                          what, dtype):
        """``gather_graph_attention`` against the dense reference, its
        output and its gradients with respect to q, k, v and the bias,
        by plain autodiff (``inv=None``) and by the attention's own
        backward over the inverse index. 76 is a list width that is no
        multiple of 16, as ``gat-fleet50k``'s widest in-degree is. In
        bfloat16 the inverse index sums a host's dk and dv in float32
        (read: 0.7% of the largest element at the most) where autodiff's
        scatter-add sums them in bfloat16 (dk 2.5% for the hosts 150
        lists name), hence its wider bound."""
        import jax.numpy as jnp

        from dragonfly2_tpu.models.graph_transformer import (
            gather_graph_attention,
        )

        hidden = self.HIDDEN
        nbr, val = self._masked_lists(n, k_width, seed=n + k_width)
        inv = (self._inverse(nbr, val, dtype)
               if backward == "inverse_index" else None)
        rng = np.random.default_rng(heads)
        q, k, v, w = (jnp.asarray(rng.normal(size=(n, hidden)), dtype)
                      for _ in range(4))
        nbr, val = jnp.asarray(nbr), jnp.asarray(val)
        got = self._out_and_grads(
            lambda *a: gather_graph_attention(*a[:3], nbr, a[3], inv,
                                              heads=heads),
            q, k, v, val, w)
        want = self._out_and_grads(
            lambda *a: self._dense_masked_softmax(*a[:3], nbr, a[3], heads),
            q, k, v, val, w)
        pick = 0 if what == "output" else 1
        tol = (1e-5 if dtype == "float32"
               else 2e-2 if backward == "inverse_index" else 4e-2)
        assert got[0].shape == (n, hidden) and got[0].dtype == q.dtype
        # A pad slot's bias moves nothing.
        assert not np.asarray(got[1][3])[np.asarray(nbr) == PAD_ID].any()
        self._assert_close(got[pick], want[pick], tol)

    @pytest.mark.parametrize("heads,head_dim", [(4, 32), (1, 128)])
    def test_bfloat16_error_no_worse_than_split_form(self, heads, head_dim):
        """What bfloat16 costs, as a root-mean-square distance from the
        float32 answer on the same (bfloat16) inputs: no more than in
        the per-head form this one replaced (bfloat16 einsums, the score
        rounded to bfloat16 once), in the output and in every gradient:
        the hand-written backward keeps the probabilities and the
        cotangent of the scores in float32 (autodiff rounded the
        probabilities' cotangent to bfloat16) and sums dk and dv over a
        host's listings in float32. A form that dropped a float32 sum
        would show in this where the 2e-2 of
        ``test_matches_per_head_float32`` lets it by."""
        import jax.numpy as jnp

        from dragonfly2_tpu.models.graph_transformer import (
            gather_graph_attention,
        )

        def split_form(q, k, v, val):
            n, hidden = q.shape
            qh, kh, vh = (t.reshape(n, heads, head_dim) for t in (q, k, v))
            pad = nbr >= n
            idx = jnp.where(pad, 0, nbr)
            s = jnp.einsum("nhd,nkhd->nhk", qh, kh[idx]).astype(
                jnp.float32) / np.sqrt(head_dim)
            s = jnp.where(pad[:, None, :], -1e9, s + val[:, None, :])
            p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
            return jnp.einsum("nhk,nkhd->nhd", p, vh[idx]).reshape(n, hidden)

        q, k, v, nbr, val, w = self._inputs("bfloat16")
        inv = self._inverse(nbr, val, "bfloat16")
        want = self._out_and_grads(
            lambda *a: self._per_head(*a[:3], nbr, a[3], heads),
            q, k, v, val, w)
        dense = self._out_and_grads(
            lambda *a: gather_graph_attention(*a[:3], nbr, a[3], inv,
                                              heads=heads),
            q, k, v, val, w)
        split = self._out_and_grads(split_form, q, k, v, val, w)

        def rms(got):
            return [float(np.sqrt(np.mean(
                (np.asarray(a, np.float32) - np.asarray(b, np.float32)) ** 2)))
                for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))]

        # Read here: out 0.98-0.99, dq 0.92-0.95, dk 0.62-0.67, dv
        # 0.58-0.59 and dval 0.87-0.93 of the split form's error (until
        # PR 30, by autodiff through the lane-dense forward and an
        # inverse-index gather of the cotangent: dq 1.04-1.10, dk 0.79-0.81,
        # dv 0.68-0.69, dval 1.13-1.17).
        room = {"out": 1.05, "dq": 1.05, "dk": 0.8, "dv": 0.8,
                "dval": 1.05}
        for name, new, old in zip(room, rms(dense), rms(split)):
            assert new <= room[name] * old, (name, new, old)

    @pytest.mark.parametrize("devices,model_parallel", [
        (1, 1), (2, 1), (4, 1), (8, 1), (2, 2), (8, 2)])
    def test_sharded_matches_unsharded(self, devices, model_parallel):
        """Rows over ``data`` on 1-8 devices; with ``model_parallel`` 2
        the lanes (so the heads) over ``model`` as well, as the
        tensor-parallel projections leave them: the same output and the
        same dq, dk, dv and dval as on one device, the backward's
        ``[q | dO | statistics]`` table gone full-width as k and v go."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from dragonfly2_tpu.models.graph_transformer import (
            gather_graph_attention,
        )

        q, k, v, nbr, val, w = self._inputs("bfloat16")
        inv = self._inverse(nbr, val, "bfloat16")

        def attend(nbr_, inv_):
            return lambda q_, k_, v_, val_: gather_graph_attention(
                q_, k_, v_, nbr_, val_, inv_, heads=4)

        want = self._out_and_grads(attend(nbr, inv), q, k, v, val, w)

        mesh = data_parallel_mesh(devices=jax.devices()[:devices],
                                  model_parallel=model_parallel)
        lanes = "model" if model_parallel > 1 else None

        def put(x, *spec):
            return jax.device_put(x, NamedSharding(mesh.mesh, P(*spec)))

        with jax.set_mesh(mesh.mesh):
            got = self._out_and_grads(
                attend(put(nbr, "data"), put(inv, "data")),
                put(q, "data", lanes), put(k, None, lanes),
                put(v, None, lanes), put(val, "data"), put(w, "data", lanes))
            assert jax.typeof(got[0]).sharding.spec == P("data", lanes)
        self._assert_close(got, want, 2e-2)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("heads", [1, 4])
    def test_forward_is_the_one_without_the_inverse_index(self, heads,
                                                          dtype):
        """The inverse index changes the backward alone: the output is
        ``inv=None``'s to the bit."""
        from dragonfly2_tpu.models.graph_transformer import (
            gather_graph_attention,
        )

        q, k, v, nbr, val, _ = self._inputs(dtype)
        inv = self._inverse(nbr, val, dtype)
        attend = jax.jit(gather_graph_attention, static_argnames="heads")
        with_inv = attend(q, k, v, nbr, val, inv, heads=heads)
        without = attend(q, k, v, nbr, val, None, heads=heads)
        assert with_inv.dtype == without.dtype == q.dtype
        assert (np.asarray(with_inv, np.float32)
                == np.asarray(without, np.float32)).all()

    @pytest.mark.parametrize("dtype,tol", [("float32", 1e-5),
                                           ("bfloat16", 2e-2)])
    @pytest.mark.parametrize("heads", [1, 4])
    @pytest.mark.parametrize("shape", TestInverseIndex.DEGREE_SHAPES)
    def test_backward_on_every_degree_shape(self, shape, heads, dtype, tol):
        """dq, dk, dv and dval by the attention's own backward against
        the dense reference's, where every host lists 7 and is listed by
        7, where in-degrees pass the cap, where 150 lists name one host
        (its dk within 0.7% in bfloat16: 150 addends, summed in
        float32) and where every host lists itself alone."""
        import jax.numpy as jnp

        from dragonfly2_tpu.models.graph_transformer import (
            gather_graph_attention,
        )

        nbr, val, _ = TestInverseIndex._lists(shape)
        n, hidden = nbr.shape[0], self.HIDDEN
        inv = self._inverse(nbr, val, dtype)
        rng = np.random.default_rng(heads)
        q, k, v, w = (jnp.asarray(rng.normal(size=(n, hidden)), dtype)
                      for _ in range(4))
        nbr, val = jnp.asarray(nbr), jnp.asarray(val)
        got = self._out_and_grads(
            lambda *a: gather_graph_attention(*a[:3], nbr, a[3], inv,
                                              heads=heads),
            q, k, v, val, w)
        want = self._out_and_grads(
            lambda *a: self._dense_masked_softmax(*a[:3], nbr, a[3], heads),
            q, k, v, val, w)
        self._assert_close(got, want, tol)
        if shape == "hub" and dtype == "bfloat16":
            hub_dk = np.asarray(got[1][1], np.float32)[0]
            ref_dk = np.asarray(want[1][1], np.float32)
            assert np.abs(hub_dk - ref_dk[0]).max() <= 7e-3 * np.abs(
                ref_dk).max()

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_padded_inverse_slots_add_exactly_nothing(self, dtype):
        """A pad slot of the inverse index is masked like a pad slot of
        a list: whatever row it fetches and whatever bias it carries, it
        adds exactly 0.0. Hosts whose inverse rows are blanked get dk =
        dv = 0.0, the others' sums do not move, and a wider index of
        nothing but more pads gives the same gradients."""
        from dragonfly2_tpu.models.graph_transformer import (
            InverseIndex,
            gather_graph_attention,
        )

        q, k, v, nbr, val, w = self._inputs(dtype)
        inv = self._inverse(nbr, val, dtype)

        def grads(inv_):
            return self._out_and_grads(
                lambda *a: gather_graph_attention(*a[:3], nbr, a[3], inv_,
                                                  heads=4),
                q, k, v, val, w)[1]

        whole = grads(inv)
        blank = np.arange(0, self.N, 3)
        rows = np.asarray(inv.rows).copy()
        vals = np.asarray(inv.vals).copy()
        rows[blank] = -1
        vals[blank] = 1e30                      # a pad's bias is not read
        cut = grads(InverseIndex(jax.numpy.asarray(rows),
                                 jax.numpy.asarray(vals)))
        kept = np.setdiff1d(np.arange(self.N), blank)
        for full, part in zip(whole[1:3], cut[1:3]):      # dk, dv
            full, part = (np.asarray(t, np.float32) for t in (full, part))
            assert (part[blank] == 0.0).all()
            assert (part[kept] == full[kept]).all()
        # dq and dval are the listing rows' own.
        for full, part in zip(whole[::3], cut[::3]):
            assert (np.asarray(full, np.float32)
                    == np.asarray(part, np.float32)).all()
        wider = InverseIndex(
            jax.numpy.pad(inv.rows, ((0, 0), (0, 16)), constant_values=-1),
            jax.numpy.pad(inv.vals, ((0, 0), (0, 16)), constant_values=7.0))
        self._assert_close(grads(wider), whole, 1e-6)


@pytest.mark.slow  # 16k-100k-node scale runs; minutes on a small box
class TestScale:
    def test_100k_node_train_step(self):
        """The round-4 scale mandate: a 100k-node full-topology graph —
        where the dense layout would need a 40 GB [N, N] score matrix —
        must complete a real jitted train step on the 8-device mesh.
        Peak activation memory is O(rows·K·hidden) per device."""
        import jax.numpy as jnp
        import optax

        mesh = data_parallel_mesh()
        rng = np.random.default_rng(0)
        n_nodes, n_edges, feat_dim = 100_000, 400_000, 8
        src = rng.integers(0, n_nodes, n_edges)
        dst = rng.integers(0, n_nodes, n_edges)
        rtt = rng.integers(1_000_000, 50_000_000, n_edges)
        feats = rng.standard_normal((n_nodes, feat_dim)).astype(np.float32)

        nbr, val = build_neighbor_lists(n_nodes, src, dst, rtt, cap=32)
        feats, nbr, val, _ = pad_graph_sparse(feats, nbr, val, mesh.n_data)
        assert nbr.shape[1] <= 32

        model = GraphTransformer(hidden=16, embed=8, layers=2, heads=2)
        row = mesh.shard_spec("data")
        rep = mesh.replicated
        # Init outside the mesh on a tiny same-width graph: flax init
        # executes eagerly, and eager collectives (the gather path's
        # all-gathers) are intermittently fatal on XLA:CPU's in-process
        # rendezvous; params depend on dims, not node count.
        t_feat, t_nbr, t_val, _ = pad_graph_sparse(
            feats[:1024], nbr[:1024], val[:1024], 8)
        params = model.init(
            jax.random.key(0), t_feat, t_nbr, t_val,
            jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int32))
        with jax.set_mesh(mesh.mesh):
            # Commit params replicated: the backward's kernel-grad dot
            # contracts over the data-sharded row axis, and explicit
            # mode resolves its psum only when the weights carry an
            # explicit (replicated) sharding.
            params = jax.device_put(params, rep)
            g_feat = jax.device_put(feats, row)
            g_nbr = jax.device_put(nbr, row)
            g_val = jax.device_put(val, row)
            e_src = jax.device_put(src[:1024].astype(np.int32), rep)
            e_dst = jax.device_put(dst[:1024].astype(np.int32), rep)
            y = jax.device_put(
                (rtt[:1024] < 20_000_000).astype(np.float32), rep)

            @jax.jit
            def step(params, feat, nbr_, val_, s, d, y):
                def loss_fn(p):
                    logits = model.apply(p, feat, nbr_, val_, s, d)
                    return optax.sigmoid_binary_cross_entropy(
                        logits, y).mean()
                loss, grads = jax.value_and_grad(loss_fn)(params)
                return loss, grads

            loss, grads = step(params, g_feat, g_nbr, g_val, e_src, e_dst, y)
            assert np.isfinite(float(loss))
            flat = jax.tree.leaves(grads)
            assert all(np.isfinite(np.asarray(g)).all() for g in flat)

    def test_ring_memory_below_gather_at_100k(self):
        """Round-5 verdict item 5: ring mode's POINT is memory scaling —
        measure it. The full train step (fwd+grad) for a 100k-node,
        3.2M-edge graph is lowered and compiled in both modes on the
        8-device mesh and the compiled executable's per-device temp
        memory compared: ring must come in materially below gather —
        both with gradients and on the forward (serving/embedding) path.

        Measured at this commit (XLA CPU, hidden=64, heads=4, cap=64,
        ring chunk=128): grad 628 MB vs 1105 MB; forward 103 MB vs
        442 MB. Execution at 100k is compile-checked only: ring scores
        all N key columns by design — O(N²) FLOPs that are MXU work on
        TPU but ~20 min on the CPU harness; executed ring training is
        covered at 16k nodes (test below) and in the multichip dryrun.
        """
        import jax.numpy as jnp
        import optax

        mesh = data_parallel_mesh()
        n_nodes, feats, nbr, val, src, dst, rtt = _graph_100k(
            n_edges=3_200_000, cap=64)
        row = mesh.shard_spec("data")
        rep = mesh.replicated

        def compiled_temp_mb(attention, chunk, grad):
            if attention == "ring":
                per_device = -(-n_nodes // mesh.n_data)
                multiple = (mesh.n_data * chunk
                            if per_device > chunk else mesh.n_data)
            else:
                multiple = mesh.n_data
            f, nb, vl, _ = pad_graph_sparse(feats, nbr, val, multiple)
            model = GraphTransformer(hidden=64, embed=16, layers=1,
                                     heads=4, chunk=chunk,
                                     attention=attention)
            # Init OUTSIDE the mesh scope on a tiny same-width graph —
            # params depend on feature/hidden dims, not node count, and
            # flax init runs EAGERLY: under an ambient mesh the ring
            # path would execute shard_map ppermutes op-by-op, which
            # XLA:CPU's in-process collectives abort intermittently
            # (the conftest-documented rendezvous fragility). Outside
            # the mesh, init takes the collective-free local fallback.
            tf, tn, tv, _ = pad_graph_sparse(
                feats[:1024], nbr[:1024], val[:1024], 8)
            params = model.init(
                jax.random.key(0), tf, tn, tv,
                jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int32))
            with jax.set_mesh(mesh.mesh):
                # Replicate-commit params: the backward's kernel-grad
                # dot contracts over the sharded row axis and needs
                # explicitly-replicated weights to place its psum.
                params = jax.device_put(params, rep)
                g = (jax.device_put(f, row), jax.device_put(nb, row),
                     jax.device_put(vl, row))
                es = jax.device_put(src[:1024].astype(np.int32), rep)
                ed = jax.device_put(dst[:1024].astype(np.int32), rep)
                y = jax.device_put(
                    (rtt[:1024] < 20_000_000).astype(np.float32), rep)

                if grad:
                    def step(params, feat, nbr_, val_, s, d, y):
                        def loss_fn(p):
                            logits = model.apply(p, feat, nbr_, val_, s, d)
                            return optax.sigmoid_binary_cross_entropy(
                                logits, y).mean()
                        return jax.value_and_grad(loss_fn)(params)

                    compiled = jax.jit(step).lower(
                        params, *g, es, ed, y).compile()
                else:
                    def fwd(params, feat, nbr_, val_):
                        return model.apply(
                            params, feat, nbr_, val_,
                            method=GraphTransformer.node_embeddings)

                    compiled = jax.jit(fwd).lower(params, *g).compile()
            return compiled.memory_analysis().temp_size_in_bytes / 1e6

        gather_grad = compiled_temp_mb("gather", 4096, grad=True)
        ring_grad = compiled_temp_mb("ring", 128, grad=True)
        gather_fwd = compiled_temp_mb("gather", 4096, grad=False)
        ring_fwd = compiled_temp_mb("ring", 128, grad=False)
        print(f"temp MB — grad: ring {ring_grad:.0f} vs gather "
              f"{gather_grad:.0f}; fwd: ring {ring_fwd:.0f} vs gather "
              f"{gather_fwd:.0f}")
        assert ring_grad < 0.75 * gather_grad, (ring_grad, gather_grad)
        assert ring_fwd < 0.5 * gather_fwd, (ring_fwd, gather_fwd)

    def test_16k_ring_training_executes(self):
        """Executed ring-mode training at a non-toy size: 16k nodes on
        the 8-device mesh, loss decreases. (100k ring execution is
        compile-checked above — O(N²) score FLOPs are prohibitive on
        the CPU harness, not on the MXU.)"""
        rng = np.random.default_rng(1)
        n_nodes, n_edges = 16_384, 60_000
        from dragonfly2_tpu.data.features import Graph

        src = rng.integers(0, n_nodes, n_edges)
        dst = rng.integers(0, n_nodes, n_edges)
        rtt = rng.integers(1_000_000, 50_000_000, n_edges)
        feats = rng.standard_normal((n_nodes, 8)).astype(np.float32)
        graph = Graph(
            node_ids=np.array([f"h{i}" for i in range(n_nodes)]),
            node_features=feats, edge_src=src.astype(np.int32),
            edge_dst=dst.astype(np.int32), edge_rtt_ns=rtt)
        result = train_gat(
            graph,
            GATTrainConfig(hidden=8, embed=8, layers=1, heads=2,
                           epochs=2, edge_batch_size=8192,
                           eval_fraction=0.1, attention="ring",
                           chunk=2048),
            data_parallel_mesh(),
        )
        assert np.isfinite(result.history[-1])
        assert result.history[-1] < result.history[0]
