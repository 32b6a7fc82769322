"""Chip smoke tier: one of everything that only real hardware can break.

Run: ``python -m pytest tests_tpu -m tpu -q`` (manually / with a timeout;
the default suite never touches the chip — tests/conftest.py pins the
virtual CPU mesh). Budget: <5 minutes with a warm compile cache.
"""

from __future__ import annotations

import numpy as np
import pytest


class TestChipBasics:
    def test_device_is_accelerator(self, tpu_device):
        assert tpu_device.platform != "cpu"

    def test_matmul_bf16_on_chip(self, tpu_device):
        import jax
        import jax.numpy as jnp

        a = jnp.ones((256, 256), jnp.bfloat16)
        out = jax.jit(lambda x: (x @ x).sum())(a)
        assert float(out) == pytest.approx(256.0 ** 3, rel=1e-2)


class TestTrainSmoke:
    def test_gnn_one_epoch_fused(self, tpu_device):
        from dragonfly2_tpu.data import SyntheticCluster
        from dragonfly2_tpu.parallel import data_parallel_mesh
        from dragonfly2_tpu.train import GNNTrainConfig, train_gnn

        graph = SyntheticCluster(n_hosts=100, seed=0).probe_graph(10000)
        res = train_gnn(
            graph,
            GNNTrainConfig(hidden=32, embed=16, batch_size=512, epochs=1,
                           eval_fraction=0.1),
            data_parallel_mesh(),
        )
        assert res.steps >= 1
        assert np.isfinite(res.history[-1])
        assert 0.0 <= res.f1 <= 1.0

    def test_gnn_multi_step_scan(self, tpu_device):
        """steps_per_call>1 on the real chip: the scan program compiles
        and the dispatch-amortized path learns."""
        from dragonfly2_tpu.data import SyntheticCluster
        from dragonfly2_tpu.parallel import data_parallel_mesh
        from dragonfly2_tpu.train import GNNTrainConfig, train_gnn

        graph = SyntheticCluster(n_hosts=100, seed=0).probe_graph(10000)
        res = train_gnn(
            graph,
            GNNTrainConfig(hidden=32, embed=16, batch_size=512, epochs=2,
                           steps_per_call=4, eval_max_seconds=0.0),
            data_parallel_mesh(),
        )
        assert res.steps >= 1
        assert np.isfinite(res.history[-1])
        assert res.samples_per_sec > 0

    def test_mlp_one_epoch(self, tpu_device):
        from dragonfly2_tpu.data import SyntheticCluster
        from dragonfly2_tpu.parallel import data_parallel_mesh
        from dragonfly2_tpu.train import MLPTrainConfig, train_mlp

        X, y = SyntheticCluster(n_hosts=50, seed=0).pair_example_columns(4096)
        res = train_mlp(
            X, y, MLPTrainConfig(hidden=(32,), epochs=1, batch_size=1024),
            data_parallel_mesh(),
        )
        assert res.history and np.isfinite(res.history[-1])
        assert res.samples_per_sec > 0


class TestScorerSmoke:
    def test_scorer_call_and_floor(self, tpu_device):
        """One scorer call end to end + the dispatch floor, so latency
        regressions on the chip path are visible outside bench."""
        import jax
        import jax.numpy as jnp

        from dragonfly2_tpu.inference import ParentScorer
        from dragonfly2_tpu.models.mlp import MLPBandwidthPredictor, Normalizer
        from dragonfly2_tpu.scheduler.evaluator.scoring import FEATURE_DIM

        model = MLPBandwidthPredictor(hidden=(32,))
        params = model.init(jax.random.key(0), jnp.zeros((1, FEATURE_DIM)))
        scorer = ParentScorer(model, params,
                              Normalizer.identity(FEATURE_DIM),
                              Normalizer.identity(1), max_batch=16)
        scores = scorer.score(
            np.random.default_rng(0).uniform(
                0, 1, (5, FEATURE_DIM)).astype(np.float32))
        assert scores.shape == (5,)
        assert np.all(np.isfinite(scores))
        lat = scorer.benchmark(batch=16, iters=20)
        assert lat["p50_ms"] > 0


class TestHBMSinkSmoke:
    def test_safetensors_pieces_to_device(self, tpu_device, tmp_path):
        """Config #5 path: unordered pieces → staging → device_put lands
        real arrays in device memory."""
        from dragonfly2_tpu.client.hbm_sink import HBMSink, write_safetensors

        rng = np.random.default_rng(1)
        tensors = {
            "w": rng.normal(size=(64, 32)).astype(np.float32),
            "b": rng.normal(size=(32,)).astype(np.float32),
        }
        path = str(tmp_path / "m.safetensors")
        write_safetensors(path, tensors)
        blob = open(path, "rb").read()
        sink = HBMSink(len(blob), device=tpu_device)
        piece = 4096
        offsets = list(range(0, len(blob), piece))
        rng.shuffle(offsets)
        for off in offsets:
            sink.write(off, blob[off:off + piece])
        arrays = sink.wait(timeout=60)
        for name, want in tensors.items():
            got = np.asarray(arrays[name])
            np.testing.assert_array_equal(got, want)
            assert arrays[name].devices() == {tpu_device}
        sink.close()

    def test_gat_gather_attention_on_chip(self, tpu_device):
        """Round-4 GAT path: neighbor-gather attention (O(N·K)) must
        train on the real chip — gathers/scatters are the layout-
        sensitive ops a CPU mesh can't vouch for."""
        from dragonfly2_tpu.data import SyntheticCluster
        from dragonfly2_tpu.parallel import data_parallel_mesh
        from dragonfly2_tpu.train import GATTrainConfig, train_gat

        graph = SyntheticCluster(n_hosts=64, seed=0).probe_graph(6000)
        res = train_gat(
            graph,
            GATTrainConfig(hidden=32, embed=16, layers=1, heads=4,
                           epochs=2, edge_batch_size=512,
                           eval_fraction=0.1),
            data_parallel_mesh(),
        )
        assert np.isfinite(res.history[-1])
        assert res.samples_per_sec > 0

    def test_ring_attention_on_chip(self, tpu_device):
        """shard_map + ppermute on the real backend (degenerate 1-chip
        ring): the collective path must compile and run on the chip."""
        import jax
        import numpy as np

        from dragonfly2_tpu.parallel import data_parallel_mesh, ring_attention

        mesh = data_parallel_mesh().mesh
        rng = np.random.default_rng(0)
        q, k, v = (rng.standard_normal((32, 2, 8)).astype(np.float32)
                   for _ in range(3))
        out = jax.jit(lambda *a: ring_attention(
            *a, mesh=mesh, causal=True))(q, k, v)
        assert np.isfinite(np.asarray(out)).all()

    def test_ulysses_attention_on_chip(self, tpu_device):
        """All-to-all sequence parallelism on the real backend
        (degenerate 1-chip exchange) — and on TPU the local attention
        IS the pallas flash kernel, so this exercises the production
        a2a + flash composition end to end."""
        import jax
        import numpy as np

        from dragonfly2_tpu.parallel import (
            data_parallel_mesh,
            ulysses_attention,
        )

        mesh = data_parallel_mesh().mesh
        rng = np.random.default_rng(0)
        q, k, v = (rng.standard_normal((256, 4, 128)).astype(np.float32)
                   for _ in range(3))
        out = jax.jit(lambda *a: ulysses_attention(
            *a, mesh=mesh, causal=True))(q, k, v)
        assert np.isfinite(np.asarray(out)).all()

    def test_pipeline_on_chip(self, tpu_device):
        """The pipeline layout on the real backend (a degenerate 1-stage
        mesh): the ppermute collective program must lower and run on the
        chip."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from dragonfly2_tpu.parallel import pipeline_apply, stack_stage_params

        n = jax.device_count()
        rng = np.random.default_rng(0)
        d = 8
        params = stack_stage_params([
            {"w": np.eye(d, dtype=np.float32)} for _ in range(n)])
        x = rng.standard_normal((4 * n, d)).astype(np.float32)

        # The MXU's default precision rounds f32 matmul operands to
        # bf16 (~3e-3 relative, seen on the v5e, PR 21). The stage's
        # matmul is the only arithmetic here, so it is pinned to full
        # f32 and the bounds stay as tight as the CPU tier's.
        def stage(p, t):
            return jnp.matmul(t, p["w"], precision="highest")

        mesh_s = jax.make_mesh((n,), ("stage",))
        out = pipeline_apply(stage, params, x, mesh=mesh_s)
        np.testing.assert_allclose(np.asarray(out), x, rtol=1e-5)

    def test_graph_flash_kernel_on_chip(self, tpu_device):
        """The graph-flash pallas kernel (blocks-mode inner loop on a
        single TPU device) must agree with gather-mode attention through
        the real Mosaic compiler — this is the production dispatch
        blocks_graph_attention takes on the bench/serving chip."""
        import numpy as np

        from dragonfly2_tpu.data import SyntheticCluster
        from dragonfly2_tpu.models.graph_transformer import (
            GraphTransformer,
            build_neighbor_lists,
            pad_graph_sparse,
        )

        graph = SyntheticCluster(n_hosts=64, seed=0).probe_graph(2000)
        nbr, val = build_neighbor_lists(
            graph.n_nodes, graph.edge_src, graph.edge_dst,
            graph.edge_rtt_ns)
        f, nb, vl, _ = pad_graph_sparse(graph.node_features, nbr, val, 8)

        def embed(attention):
            import jax

            model = GraphTransformer(hidden=32, embed=16, layers=1,
                                     heads=4, chunk=128,
                                     attention=attention)
            params = model.init(jax.random.key(0), f, nb, vl,
                                np.zeros(2, np.int32), np.zeros(2, np.int32))
            return np.asarray(model.apply(
                params, f, nb, vl,
                method=GraphTransformer.node_embeddings))

        # "blocks" on a single TPU device dispatches the pallas kernel.
        np.testing.assert_allclose(embed("gather"), embed("blocks"),
                                   rtol=6e-2, atol=6e-2)

    def test_online_softmax_backward_is_finite_in_bf16(self, tpu_device):
        """The four hand-written online-softmax scans take their scores
        from the dot in f32. With a bf16 dot converted up instead, the
        VJP of their row max was NaN in every element of dq and dk on the
        v5e (PR 21) while the forward stayed exact — so blocks mode, the
        graph-flash kernel's backward and the flash backward trained on
        NaN gradients. A CPU cannot show this."""
        import contextlib

        import jax
        import jax.numpy as jnp
        import numpy as np

        from dragonfly2_tpu.data import SyntheticCluster
        from dragonfly2_tpu.models.graph_transformer import (
            build_neighbor_lists,
            ring_graph_attention,
            sparse_graph_attention,
        )
        from dragonfly2_tpu.ops.flash_attention import chunked_attention
        from dragonfly2_tpu.parallel import data_parallel_mesh, ring_attention

        n = 96
        graph = SyntheticCluster(n_hosts=n, seed=0).probe_graph(2500)
        nbr, val = (jnp.asarray(a) for a in build_neighbor_lists(
            n, graph.edge_src, graph.edge_dst, graph.edge_rtt_ns, cap=8))
        rng = np.random.default_rng(0)
        q, k, v = (jnp.asarray(rng.standard_normal((n, 4, 32)),
                               jnp.bfloat16) for _ in range(3))
        mesh = data_parallel_mesh().mesh

        for name, fn, ambient in (
                ("sparse_graph_attention",
                 lambda *a: sparse_graph_attention(*a, nbr, val, n), False),
                ("ring_graph_attention",
                 lambda *a: ring_graph_attention(*a, nbr, val, n), True),
                ("chunked_attention",
                 lambda *a: chunked_attention(*a, causal=True, block=32),
                 False),
                ("ring_attention",
                 lambda *a: ring_attention(*a, mesh=mesh, causal=True),
                 False)):
            grad = jax.jit(jax.grad(
                lambda *a: (fn(*a).astype(jnp.float32) ** 2).sum(),
                argnums=(0, 1, 2)))
            # ring_graph_attention reads the ambient mesh at trace time.
            with jax.set_mesh(mesh) if ambient else contextlib.nullcontext():
                grads = grad(q, k, v)
            for g in grads:
                assert np.isfinite(np.asarray(g, np.float32)).all(), name

    def test_table_gather_kernels_on_chip(self, tpu_device):
        """The VMEM-resident gather/scatter-add kernels through the real
        Mosaic compiler: exact vs table[idx] and vs XLA's scatter-add
        (f32 accumulation both sides)."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from dragonfly2_tpu.ops.table_gather import (
            neighbor_gather_pallas, table_gather, table_scatter_add)

        rng = np.random.default_rng(2)
        n, d, m = 1024, 256, 4096
        t = jnp.asarray(rng.standard_normal((n, d)), jnp.bfloat16)
        idx = jnp.asarray(rng.integers(0, n, m), jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(table_gather(t, idx), np.float32),
            np.asarray(t, np.float32)[np.asarray(idx)])

        ct = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
        got = table_scatter_add(ct, idx, n)
        ref = jnp.zeros((n, d)).at[idx].add(ct)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

        ix2 = jnp.asarray(rng.integers(0, n, (64, 16)), jnp.int32)
        tf = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
        ga = jax.grad(lambda x: jnp.sum(
            jnp.sin(neighbor_gather_pallas(x, ix2))))(tf)
        gb = jax.grad(lambda x: jnp.sum(jnp.sin(x[ix2])))(tf)
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                                   rtol=1e-4, atol=1e-5)

    def test_flash_attention_kernel_on_chip(self, tpu_device):
        """The pallas kernel through the real Mosaic compiler. Tolerance
        covers MXU default-precision rounding vs the dense reference's
        different blocking (~4e-3 max observed)."""
        import numpy as np

        from dragonfly2_tpu.ops import flash_attention
        from dragonfly2_tpu.ops.flash_attention import _dense_reference

        rng = np.random.default_rng(0)
        t, h, d = 512, 4, 128
        q, k, v = (rng.standard_normal((t, h, d)).astype(np.float32)
                   for _ in range(3))
        for causal in (False, True):
            out = flash_attention(q, k, v, causal)
            ref = _dense_reference(q, k, v, causal, t)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=1e-2, atol=1e-2)
