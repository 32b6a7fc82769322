"""Inference sidecar tests: serving surface, manager hot-reload, the
ml evaluator over gRPC, and the <1 ms p50 target end to end.

Closes the reference's designed-but-unimplemented loop:
trainer → manager CreateModel → sidecar (Triton stand-in) → scheduler
MLAlgorithm (evaluator.go:48 TODO).
"""

from __future__ import annotations

import json
import os
import time

import numpy as np
import pytest

from dragonfly2_tpu.inference.sidecar import (
    INFERENCE_SPEC,
    InferenceClient,
    InferenceService,
    ModelInferRequest,
    ModelReadyRequest,
)
from dragonfly2_tpu.manager import Database, FilesystemObjectStore, ManagerService
from dragonfly2_tpu.rpc import serve
from dragonfly2_tpu.scheduler.evaluator import new_evaluator
from dragonfly2_tpu.scheduler.evaluator.scoring import FEATURE_DIM


def train_tiny_mlp():
    from dragonfly2_tpu.data import SyntheticCluster
    from dragonfly2_tpu.train import MLPTrainConfig, train_mlp

    cluster = SyntheticCluster(n_hosts=16, seed=1)
    X, y = cluster.pair_example_columns(512)
    return train_mlp(
        X, y, MLPTrainConfig(hidden=(16,), epochs=1, batch_size=64,
                             eval_fraction=0.25), None,
    )


@pytest.fixture(scope="module")
def registered_model(tmp_path_factory):
    """Train once, register into a real manager, reuse across tests."""
    import tempfile

    from dragonfly2_tpu.train.checkpoint import ModelMetadata, mlp_tree, save_model

    base = tmp_path_factory.mktemp("sidecar")
    manager = ManagerService(
        Database(), FilesystemObjectStore(str(base / "objects")))
    result = train_tiny_mlp()
    artifact = tempfile.mkdtemp(dir=base)
    save_model(
        artifact, mlp_tree(result.params, result.normalizer, result.target_norm),
        ModelMetadata(model_id="df2-mlp-t", model_type="mlp",
                      evaluation={"mae": result.mae},
                      config={"hidden": [16]}),
    )
    manager.create_model("df2-mlp-t", "mlp", "h", "1.1.1.1", "hn",
                         {"mae": result.mae}, artifact)
    return {"manager": manager, "result": result}


class TestSidecar:
    def test_reload_and_infer_over_grpc(self, registered_model):
        service = InferenceService(manager=registered_model["manager"])
        assert service.reload_from_manager() is True
        assert service.reload_from_manager() is False  # same version: no-op
        server = serve([(INFERENCE_SPEC, service)])
        try:
            client = InferenceClient(server.target, timeout=5.0)
            assert client.server_live()
            assert client.model_ready("mlp")
            assert not client.model_ready("gnn")
            features = np.random.default_rng(0).normal(
                size=(8, FEATURE_DIM)).astype(np.float32)
            scores = client.model_infer("mlp", features)
            assert scores.shape == (8,)
            assert np.isfinite(scores).all()
            client.close()
        finally:
            server.stop()
            service.stop()

    def test_hot_reload_on_new_version(self, registered_model, tmp_path):
        """A new active version first loads in SHADOW (the incumbent
        keeps serving); the canary's clean batches promote it — the
        guarded-rollout default (docs/SERVING.md)."""
        import tempfile

        from dragonfly2_tpu.train.checkpoint import (
            ModelMetadata,
            mlp_tree,
            save_model,
        )

        manager = registered_model["manager"]
        service = InferenceService(manager=manager, canary_batches=2,
                                   canary_probe_grace_s=0.0)
        service.reload_from_manager()
        v1 = service._models["mlp"].version
        result = registered_model["result"]
        artifact = tempfile.mkdtemp(dir=tmp_path)
        save_model(
            artifact,
            mlp_tree(result.params, result.normalizer, result.target_norm),
            ModelMetadata(model_id="df2-mlp-t", model_type="mlp",
                          config={"hidden": [16]}),
        )
        manager.create_model("df2-mlp-t", "mlp", "h", "1.1.1.1", "hn", {},
                             artifact)
        assert service.reload_from_manager() is True
        # Shadow first: decisions still come from the incumbent.
        assert service._models["mlp"].version == v1
        assert service.shadow_stats()["mlp"]["version"] != v1
        # Canary probes (healthy model, zero grace) promote it.
        service.process_shadows()
        assert service._models["mlp"].version != v1
        assert service.shadow_stats() == {}
        service.stop()

    def test_unknown_model_aborts(self, registered_model):
        import grpc

        service = InferenceService(manager=registered_model["manager"])
        service.reload_from_manager()
        server = serve([(INFERENCE_SPEC, service)])
        try:
            client = InferenceClient(server.target, timeout=5.0)
            with pytest.raises(grpc.RpcError) as exc_info:
                client.model_infer("nope", np.zeros((1, FEATURE_DIM), np.float32))
            assert exc_info.value.code() == grpc.StatusCode.NOT_FOUND
            client.close()
        finally:
            server.stop()
            service.stop()


class TestRemoteMLEvaluator:
    def _peers(self):
        from tests.test_inference import FakeHost, FakePeer  # reuse fakes

        child = FakePeer("child", FakeHost(idc="a"))
        parents = [
            FakePeer(f"p{i}", FakeHost(idc="a" if i % 2 == 0 else "b",
                                       upload_count=10 * i),
                     _finished=i + 1)
            for i in range(6)
        ]
        return parents, child

    def test_ranking_via_sidecar_and_fallback(self, registered_model):
        service = InferenceService(manager=registered_model["manager"])
        service.reload_from_manager()
        server = serve([(INFERENCE_SPEC, service)])
        try:
            evaluator = new_evaluator(
                "ml", sidecar_target=server.target)
            parents, child = self._peers()
            ranked = evaluator.evaluate_parents(parents, child, 10)
            assert sorted(p.id for p in ranked) == sorted(p.id for p in parents)
            # kill the sidecar → graceful rule-based fallback
            server.stop()
            ranked2 = evaluator.evaluate_parents(parents, child, 10)
            assert sorted(p.id for p in ranked2) == sorted(p.id for p in parents)
        finally:
            service.stop()

    def test_resource_exhausted_becomes_shed_not_breaker(self):
        """A RESOURCE_EXHAUSTED reply (the sidecar's bounded-admission
        shed) must surface as BatcherSaturatedError — counted by
        MLEvaluator as a shed with rule fallback — and must NOT open the
        circuit breaker: the sidecar is alive, and the next decision may
        land on a lane with room."""
        import grpc

        from dragonfly2_tpu.inference.batcher import BatcherSaturatedError
        from dragonfly2_tpu.inference.scorer import MLEvaluator
        from dragonfly2_tpu.inference.sidecar import _RemoteScorer

        class FakeRpcError(Exception):
            def code(self):
                return grpc.StatusCode.RESOURCE_EXHAUSTED

        class FakeClient:
            def __init__(self):
                self.calls = 0
                self.fail_next = True

            def model_infer(self, name, inputs):
                self.calls += 1
                if self.fail_next:
                    self.fail_next = False
                    raise FakeRpcError()
                # Distinct finite scores: an all-constant batch would
                # (correctly) trip the runtime guard instead of counting
                # as a scored decision.
                return np.arange(len(inputs), dtype=np.float32)

        client = FakeClient()
        remote = _RemoteScorer(client, "mlp", cooldown=60.0)
        with pytest.raises(BatcherSaturatedError):
            remote.score(np.zeros((2, FEATURE_DIM), np.float32))
        # Breaker stayed closed: the next call reaches the sidecar
        # instead of failing instantly for the whole cooldown.
        assert remote.score(
            np.zeros((2, FEATURE_DIM), np.float32)).shape == (2,)
        assert client.calls == 2

        # Through the evaluator: the shed is a counted rule fallback.
        client2 = FakeClient()
        evaluator = MLEvaluator(_RemoteScorer(client2, "mlp",
                                              cooldown=60.0))
        parents, child = self._peers()
        ranked = evaluator.evaluate_parents(parents, child, 10)
        assert sorted(p.id for p in ranked) == sorted(p.id for p in parents)
        assert evaluator.shed_count == 1
        assert evaluator.fallback_count == 1
        evaluator.evaluate_parents(parents, child, 10)
        assert evaluator.scored_count == 1
        assert evaluator.shed_count == 1

    def test_other_rpc_errors_still_open_breaker(self):
        from dragonfly2_tpu.inference.sidecar import (
            CircuitOpenError,
            _RemoteScorer,
        )

        class DeadClient:
            def model_infer(self, name, inputs):
                raise ConnectionError("sidecar unreachable")

        remote = _RemoteScorer(DeadClient(), "mlp", cooldown=60.0)
        with pytest.raises(ConnectionError):
            remote.score(np.zeros((2, FEATURE_DIM), np.float32))
        with pytest.raises(CircuitOpenError):
            remote.score(np.zeros((2, FEATURE_DIM), np.float32))

    def test_parent_select_p50_under_1ms(self, registered_model):
        """BASELINE.md target: parent-selection p50 < 1 ms through the
        TPU-backed scorer (in-process scorer path, the deployment the
        scheduler uses when co-located)."""
        from dragonfly2_tpu.inference.scorer import ParentScorer

        result = registered_model["result"]
        scorer = ParentScorer(result.model, result.params, result.normalizer,
                              result.target_norm)
        latency = scorer.benchmark(batch=15, iters=100)
        assert latency["p50_ms"] < 1.0, latency


class TestGATServing:
    @pytest.fixture(scope="class")
    def gat_registered(self, tmp_path_factory):
        """Train config #3 tiny, register as type 'gat' beside an MLP."""
        import tempfile

        from dragonfly2_tpu.data import SyntheticCluster
        from dragonfly2_tpu.train import GATTrainConfig, train_gat
        from dragonfly2_tpu.train.checkpoint import (
            ModelMetadata,
            gat_tree,
            save_model,
        )

        base = tmp_path_factory.mktemp("sidecar-gat")
        manager = ManagerService(
            Database(), FilesystemObjectStore(str(base / "objects")))
        graph = SyntheticCluster(n_hosts=24, seed=2).probe_graph(1500)
        result = train_gat(
            graph,
            GATTrainConfig(hidden=16, embed=8, layers=1, heads=2,
                           epochs=2, edge_batch_size=128,
                           eval_fraction=0.25), None)
        artifact = tempfile.mkdtemp(dir=base)
        save_model(
            artifact,
            gat_tree(result.params, result.node_features,
                     result.neighbors, result.neighbor_vals,
                     node_ids=graph.node_ids),
            ModelMetadata(model_id="df2-gat-t", model_type="gat",
                          evaluation={"f1": result.f1},
                          config={"hidden": 16, "embed": 8, "layers": 1,
                                  "heads": 2, "attention": "gather"}),
        )
        manager.create_model("df2-gat-t", "gat", "h", "1.1.1.1", "hn",
                             {"f1": result.f1}, artifact)
        return {"manager": manager, "result": result, "graph": graph}

    def test_reload_and_pair_scoring(self, gat_registered):
        service = InferenceService(manager=gat_registered["manager"])
        assert service.reload_from_manager() is True
        server = serve([(INFERENCE_SPEC, service)])
        try:
            client = InferenceClient(server.target, timeout=10.0)
            assert client.model_ready("gat")
            pairs = np.array([[0, 1], [2, 3], [5, 4]], np.int32)
            scores = client.model_infer("gat", pairs)
            assert scores.shape == (3,)
            assert np.isfinite(scores).all()
            # Serving scores must match the model's training-path logits
            # for the same pairs (embedding table precompute is exact).
            result = gat_registered["result"]
            direct = np.asarray(result.model.apply(
                result.params, result.node_features, result.neighbors,
                result.neighbor_vals,
                pairs[:, 0].astype(np.int32), pairs[:, 1].astype(np.int32)))
            np.testing.assert_allclose(scores, direct, rtol=5e-2, atol=5e-2)
            client.close()
        finally:
            server.stop()
            service.stop()

    @pytest.mark.parametrize("registered_as",
                             ["blocks", "flash", "ring", None])
    def test_registry_mode_is_not_read(self, gat_registered, tmp_path,
                                       registered_as):
        """A model registered by an older trainer as ``blocks``,
        ``flash`` or ``ring`` (or with no mode at all) loads and scores a
        fixed announce exactly as the same parameters registered as
        ``gather`` do: the tree is the same in every mode, and the
        sidecar builds gather mode whatever the metadata says."""
        from dragonfly2_tpu.inference.sidecar import _gat_scorer_from_artifact
        from dragonfly2_tpu.manager.service import _tar_directory
        from dragonfly2_tpu.train.checkpoint import (
            ModelMetadata,
            gat_tree,
            save_model,
        )

        result = gat_registered["result"]
        config = {"hidden": 16, "embed": 8, "layers": 1, "heads": 2,
                  "chunk": 4}
        if registered_as is not None:
            config["attention"] = registered_as
        save_model(
            str(tmp_path),
            gat_tree(result.params, result.node_features, result.neighbors,
                     result.neighbor_vals,
                     node_ids=gat_registered["graph"].node_ids),
            ModelMetadata(model_id="df2-gat-old", model_type="gat",
                          evaluation={"f1": result.f1}, config=config))
        pairs = np.array([[0, 1], [2, 3], [5, 4], [7, 7], [23, 0]], np.int32)
        scores = _gat_scorer_from_artifact(
            _tar_directory(str(tmp_path))).score(pairs)
        active = gat_registered["manager"].get_active_model("gat", 0)
        as_gather = _gat_scorer_from_artifact(active.artifact).score(pairs)
        assert np.isfinite(scores).all()
        np.testing.assert_array_equal(scores, as_gather)

    def test_out_of_range_pair_rejected(self, gat_registered):
        from dragonfly2_tpu.inference.sidecar import _gat_scorer_from_artifact

        active = gat_registered["manager"].get_active_model("gat", 0)
        scorer = _gat_scorer_from_artifact(active.artifact)
        with pytest.raises(ValueError, match="host index"):
            scorer.score(np.array([[0, 10**6]], np.int32))
        with pytest.raises(ValueError, match="pairs"):
            scorer.score(np.zeros((4, 3), np.int32))

    def test_host_id_scoring(self, gat_registered):
        """Checkpoint node_ids make the scorer addressable by host ID —
        the form a scheduler actually holds."""
        from dragonfly2_tpu.inference.sidecar import _gat_scorer_from_artifact

        graph = gat_registered["graph"]
        active = gat_registered["manager"].get_active_model("gat", 0)
        scorer = _gat_scorer_from_artifact(active.artifact)
        ids = list(graph.node_ids[:4])
        by_id = scorer.score_host_pairs([(ids[0], ids[1]),
                                         (ids[2], ids[3])])
        by_index = scorer.score(np.array([[0, 1], [2, 3]], np.int32))
        np.testing.assert_allclose(by_id, by_index)
        assert scorer.index_of(ids[2]) == 2
        assert scorer.index_of("no-such-host") is None
