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


class TestGraphAttentionSmoke:
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

    def test_online_softmax_backward_is_finite_in_bf16(self, tpu_device):
        """Ring mode's online-softmax scan takes its scores from the dot
        in f32. With a bf16 dot converted up instead, the VJP of the row
        max was NaN in every element of dq and dk on the v5e (PR 21)
        while the forward stayed exact. A CPU cannot show this. Under the
        one-chip mesh it is shard_map + ppermute on the real backend (a
        ring of one); with no mesh, the same scan without collectives."""
        import contextlib

        import jax
        import jax.numpy as jnp

        from dragonfly2_tpu.data import SyntheticCluster
        from dragonfly2_tpu.models.graph_transformer import (
            build_neighbor_lists,
            ring_graph_attention,
        )
        from dragonfly2_tpu.parallel import data_parallel_mesh

        n = 96
        graph = SyntheticCluster(n_hosts=n, seed=0).probe_graph(2500)
        nbr, val = (jnp.asarray(a) for a in build_neighbor_lists(
            n, graph.edge_src, graph.edge_dst, graph.edge_rtt_ns, cap=8))
        rng = np.random.default_rng(0)
        q, k, v = (jnp.asarray(rng.standard_normal((n, 4, 32)),
                               jnp.bfloat16) for _ in range(3))
        mesh = data_parallel_mesh().mesh

        for ambient in (False, True):
            grad = jax.jit(jax.grad(
                lambda *a: (ring_graph_attention(*a, nbr, val, 32).astype(
                    jnp.float32) ** 2).sum(), argnums=(0, 1, 2)))
            # ring_graph_attention reads the ambient mesh at trace time.
            with jax.set_mesh(mesh) if ambient else contextlib.nullcontext():
                grads = grad(q, k, v)
            for g in grads:
                assert np.isfinite(np.asarray(g, np.float32)).all(), ambient
