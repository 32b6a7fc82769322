"""Debug/profiling monitor (round-3 verdict item 8) — the pprof +
statsview role (reference cmd/dependency/dependency.go:95-130) and the
JAX profiler hook on trainers."""

from __future__ import annotations

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from dragonfly2_tpu.utils.debugmon import DebugMonitor, sample_profile


def get(url: str) -> tuple[int, bytes]:
    with urllib.request.urlopen(url, timeout=5) as resp:
        return resp.status, resp.read()


class TestDebugMonitor:
    def test_endpoints(self):
        mon = DebugMonitor(port=0)
        mon.start()
        base = f"http://{mon.address}"
        try:
            code, body = get(base + "/healthy")
            assert code == 200 and body == b"OK"

            # /debug/threads shows THIS test thread by name.
            marker = threading.current_thread().name
            code, body = get(base + "/debug/threads")
            assert code == 200
            assert marker.encode() in body
            assert b"test_debugmon.py" in body  # a real stack frame

            code, body = get(base + "/debug/vars")
            vars_ = json.loads(body)
            assert vars_["threads"] >= 2
            assert vars_["uptime_seconds"] >= 0

            # Unknown routes 404 with a hint.
            import urllib.error

            try:
                get(base + "/debug/nope")
                raise AssertionError("expected 404")
            except urllib.error.HTTPError as exc:
                assert exc.code == 404
        finally:
            mon.stop()

    def test_registered_vars_served_and_isolated(self):
        """Service-published vars (the sidecar registers batcher_stats
        here) appear on /debug/vars, and one failing var must not take
        down the page."""
        from dragonfly2_tpu.utils.debugmon import register_debug_var

        register_debug_var(
            "test_batcher_stats",
            lambda: {"mlp": {"sheds": 3, "per_lane": [{"lane": 0}]}})
        register_debug_var("test_broken_var", lambda: 1 / 0)
        mon = DebugMonitor(port=0)
        mon.start()
        try:
            code, body = get(f"http://{mon.address}/debug/vars")
            vars_ = json.loads(body)
            assert vars_["test_batcher_stats"]["mlp"]["sheds"] == 3
            assert "error" in vars_["test_broken_var"]
        finally:
            mon.stop()

    def test_sampling_profiler_catches_hot_thread(self):
        stop = threading.Event()

        def hot_loop():
            while not stop.is_set():
                sum(i * i for i in range(500))

        t = threading.Thread(target=hot_loop, name="hot-loop", daemon=True)
        t.start()
        try:
            report = sample_profile(0.4, hz=200)
        finally:
            stop.set()
            t.join(timeout=2)
        assert "hot_loop" in report
        assert "sampling rounds" in report

    def test_debug_profile_endpoint(self):
        mon = DebugMonitor(port=0)
        mon.start()
        try:
            code, body = get(
                f"http://{mon.address}/debug/profile?seconds=0.2")
            assert code == 200 and b"sampling rounds" in body
        finally:
            mon.stop()


@pytest.mark.slow  # real XLA profiler session writing xplane.pb (~20 s)
class TestTrainerProfileDir:
    def test_the_one_profile_switch_dumps_the_loops_spans(self, tmp_path):
        """``TrainingConfig.profile_dir`` (what ``df2-trainer
        --profile-dir`` sets) runs a model job under the JAX profiler:
        the dump lands under ``<dir>/<model>/`` and holds the loop's own
        ``df2.train.*`` spans, which ``df2-trace-tool train`` reads."""
        from dragonfly2_tpu import traintrace
        from dragonfly2_tpu.data import SyntheticCluster
        from dragonfly2_tpu.train import GNNTrainConfig
        from dragonfly2_tpu.trainer.training import (
            TrainOutcome,
            Training,
            TrainingConfig,
        )

        graph = SyntheticCluster(n_hosts=100, seed=0).probe_graph(4000)
        out = tmp_path / "xplane"
        training = Training(storage=None, config=TrainingConfig(
            gnn=GNNTrainConfig(hidden=32, embed=16, batch_size=512,
                               epochs=1),
            profile_dir=str(out)))
        outcome = TrainOutcome(host_id="h")
        training._train_gnn("127.0.0.1", "host", "h", 0, graph.n_edges,
                            graph, outcome)
        assert outcome.gnn_model_id
        dumped = list((out / "gnn").rglob("*.xplane.pb"))
        assert dumped, f"no xplane dump under {out}/gnn"
        report = traintrace.analyze(str(out / "gnn"))
        (loop,) = [t for t in report["threads"] if t["loop"]]
        assert loop["spans"]["df2.train.dispatch"]["count"] == (
            report["host_steps"]) > 0
