"""Observability plane (ISSUE 14): tail-based sampling, end-to-end task
traces, the Prometheus stats-block bridge, the critical-path analyzer,
and the observability counters behind all of it
(docs/OBSERVABILITY.md)."""

from __future__ import annotations

import json
import time

import pytest

from dragonfly2_tpu.utils.obsstats import ObservabilityStats
from dragonfly2_tpu.utils.tracing import (
    TailSampler,
    Tracer,
    adopt_trace_context,
    current_trace_context,
    default_tracer,
    promote_current_trace,
    set_default_tracer,
)


def read_spans(path):
    if not path.exists():
        return []
    with open(path) as f:
        return [json.loads(line) for line in f]


@pytest.fixture
def restore_tracer():
    prev = default_tracer()
    yield
    set_default_tracer(prev)


# ----------------------------------------------------------------------
# TailSampler unit behavior
# ----------------------------------------------------------------------


class TestTailSampler:
    def test_head_sampling_is_deterministic_and_fractional(self):
        s = TailSampler(head_fraction=0.5, stats=ObservabilityStats())
        # The head decision reads the LEADING 32 bits — spread the ids
        # across that range (a counter in the low bits would all land
        # at draw≈0).
        ids = [f"{i:08x}deadbeef" for i in
               range(0, 2 ** 32, 2 ** 32 // 256)]
        verdicts = [s.head_sampled(t) for t in ids]
        # Pure function of the id: identical on a second pass (what
        # lets every process in the swarm agree without coordination).
        assert verdicts == [s.head_sampled(t) for t in ids]
        frac = sum(verdicts) / len(verdicts)
        assert 0.3 < frac < 0.7
        none = TailSampler(head_fraction=0.0, stats=ObservabilityStats())
        assert not any(none.head_sampled(t) for t in ids)
        everything = TailSampler(head_fraction=1.0,
                                 stats=ObservabilityStats())
        assert all(everything.head_sampled(t) for t in ids)

    def test_unexpected_trace_spans_drop_instead_of_buffering(self):
        """A trace NOBODY promised a verdict for (untraced daemons
        announcing into a traced scheduler: every span a fresh orphan
        trace id) must not buffer — orphan churn would evict the
        genuine in-flight task buffers."""
        stats = ObservabilityStats()
        s = TailSampler(head_fraction=0.0, max_traces=2, stats=stats)
        for i in range(50):
            assert s.offer({"trace_id": f"orphan{i}", "span_id": "s",
                            "name": "n"}) is False
        assert s.buffered_traces() == 0
        assert stats.get("spans_unsampled") == 50
        assert stats.get("traces_evicted") == 0
        # An expected trace still buffers, unharmed by the orphan storm.
        s.expect("real")
        s.offer({"trace_id": "real", "span_id": "s", "name": "n"})
        assert s.buffered_traces() == 1
        assert [r["trace_id"] for r in s.promote("real", "slow")] == \
            ["real"]

    def test_buffer_promote_and_finish(self):
        stats = ObservabilityStats()
        s = TailSampler(head_fraction=0.0, stats=stats)
        s.expect("t1")
        s.expect("t2")
        rec = {"trace_id": "t1", "span_id": "a", "name": "x"}
        assert s.offer(rec) is False  # buffered
        assert stats.get("spans_buffered") == 1
        promoted = s.promote("t1", "failed")
        assert promoted == [rec] and rec["tail"] == "failed"
        assert stats.get("traces_promoted") == 1
        # Later spans of a promoted trace write through, stamped.
        late = {"trace_id": "t1", "span_id": "b", "name": "y"}
        assert s.offer(late) is True and late["tail"] == "failed"
        # promote is idempotent (no double count, nothing left to ship)
        assert s.promote("t1", "failed") == []
        assert stats.get("traces_promoted") == 1
        # A clean trace's buffer is dropped and counted.
        s.offer({"trace_id": "t2", "span_id": "c", "name": "z"})
        s.finish("t2")
        assert stats.get("traces_dropped") == 1
        assert s.buffered_traces() == 0

    def test_bounded_traces_and_spans(self):
        stats = ObservabilityStats()
        s = TailSampler(head_fraction=0.0, max_traces=2,
                        max_spans_per_trace=3, stats=stats)
        for t in ("t1", "t2", "t3"):
            s.expect(t)
            s.offer({"trace_id": t, "span_id": "s", "name": "n"})
        assert s.buffered_traces() == 2
        assert stats.get("traces_evicted") == 1
        assert s.promote("t1", "late") == []  # evicted: nothing to ship
        for i in range(5):
            s.offer({"trace_id": "t2", "span_id": str(i), "name": "n"})
        assert stats.get("spans_truncated") == 3  # 1 + 5 offers, cap 3

    def test_promoted_set_is_bounded(self):
        s = TailSampler(head_fraction=0.0, max_traces=4,
                        stats=ObservabilityStats())
        for i in range(100):
            s.promote(f"t{i}", "r")
        assert len(s._promoted) <= 16


class TestTracerTailSampling:
    def test_unpromoted_trace_never_reaches_disk(self, tmp_path):
        stats = ObservabilityStats()
        t = Tracer("svc", out_dir=str(tmp_path),
                   sampler=TailSampler(head_fraction=0.0, stats=stats),
                   stats=stats)
        with t.span("root"):
            ctx = current_trace_context()
            t.expect_trace(ctx[0])
            with t.span("child"):
                pass
        assert read_spans(tmp_path / "trace-svc.jsonl") == []
        t.finish_trace(ctx[0])
        assert read_spans(tmp_path / "trace-svc.jsonl") == []
        assert stats.get("traces_dropped") == 1

    def test_promoted_trace_ships_whole_buffer(self, tmp_path):
        stats = ObservabilityStats()
        t = Tracer("svc", out_dir=str(tmp_path),
                   sampler=TailSampler(head_fraction=0.0, stats=stats),
                   stats=stats)
        with t.span("root"):
            ctx = current_trace_context()
            t.expect_trace(ctx[0])
            with t.span("child"):
                pass
        t.promote_trace(ctx[0], "slow")
        spans = read_spans(tmp_path / "trace-svc.jsonl")
        assert sorted(s["name"] for s in spans) == ["child", "root"]
        assert all(s["tail"] == "slow" for s in spans)
        # A span recorded AFTER promotion writes straight through.
        with t.span("late", remote_parent=ctx):
            pass
        assert len(read_spans(tmp_path / "trace-svc.jsonl")) == 3

    def test_head_sampled_trace_writes_through(self, tmp_path):
        stats = ObservabilityStats()
        t = Tracer("svc", out_dir=str(tmp_path),
                   sampler=TailSampler(head_fraction=1.0, stats=stats),
                   stats=stats)
        with t.span("root"):
            pass
        assert len(read_spans(tmp_path / "trace-svc.jsonl")) == 1

    def test_promote_current_trace_helper(self, tmp_path, restore_tracer):
        stats = ObservabilityStats()
        t = Tracer("svc", out_dir=str(tmp_path),
                   sampler=TailSampler(head_fraction=0.0, stats=stats),
                   stats=stats)
        set_default_tracer(t)
        with t.span("root"):
            t.expect_trace(current_trace_context()[0])
            promote_current_trace("failover")
        assert read_spans(tmp_path / "trace-svc.jsonl")[0]["tail"] == \
            "failover"

    def test_emit_retrospective_span(self, tmp_path):
        t = Tracer("svc", out_dir=str(tmp_path))
        with t.span("root"):
            ctx = current_trace_context()
        t.emit("wait", start=time.time() - 1.0, duration_s=1.0,
               parent=ctx, decision="CandidateParents")
        spans = read_spans(tmp_path / "trace-svc.jsonl")
        wait = next(s for s in spans if s["name"] == "wait")
        assert wait["trace_id"] == ctx[0]
        assert wait["parent_id"] == ctx[1]
        assert wait["duration_ms"] == 1000.0

    def test_adopt_context_binds_fresh_thread(self, tmp_path):
        import threading

        t = Tracer("svc", out_dir=str(tmp_path))
        seen = {}
        with t.span("root"):
            ctx = current_trace_context()

            def worker():
                seen["before"] = current_trace_context()
                adopt_trace_context(ctx)
                seen["after"] = current_trace_context()

            th = threading.Thread(target=worker)
            th.start()
            th.join()
        assert seen["before"] is None
        assert seen["after"] == ctx


# ----------------------------------------------------------------------
# Daemon-side: degrade-to-source promotes the trace
# ----------------------------------------------------------------------


class TestConductorTailVerdicts:
    def _run_degraded_download(self, tmp_path, tracer):
        import numpy as np

        from dragonfly2_tpu.client.dataplane import BlobRangeServer
        from dragonfly2_tpu.client.peer_task import (
            PeerTaskConductor,
            PeerTaskOptions,
        )
        from dragonfly2_tpu.client.storage import (
            StorageManager,
            StorageOptions,
        )

        class DeadScheduler:
            def register_peer(self, req, channel=None):
                raise ConnectionError("no schedulers")

        blob = np.random.default_rng(0).bytes(256 << 10)
        with BlobRangeServer(blob) as server:
            storage = StorageManager(StorageOptions(
                root=str(tmp_path / "storage"), keep_storage=False))
            conductor = PeerTaskConductor(
                DeadScheduler(), storage, host_id="h",
                task_id="obs-degrade-task", peer_id="obs-degrade-peer",
                url=server.url(),
                options=PeerTaskOptions(back_source_concurrency=2))
            result = conductor.run()
            conductor.reporter.close()
            conductor.downloader.close()
        return result

    def test_degraded_task_trace_is_promoted(self, tmp_path,
                                             restore_tracer):
        stats = ObservabilityStats()
        tracer = Tracer("daemon", out_dir=str(tmp_path / "traces"),
                        sampler=TailSampler(head_fraction=0.0,
                                            stats=stats),
                        stats=stats)
        set_default_tracer(tracer)
        result = self._run_degraded_download(tmp_path, tracer)
        assert result.success
        spans = read_spans(tmp_path / "traces" / "trace-daemon.jsonl")
        assert spans, "degraded task's trace must be tail-captured"
        by_name = {s["name"]: s for s in spans}
        root = by_name["peer_task.run"]
        assert root["tail"] == "degraded_to_source"
        assert root["attrs"]["degraded"] == "register_failed"
        assert "peer_task.back_to_source" in by_name
        assert "source.fetch_run" in by_name
        assert len({s["trace_id"] for s in spans}) == 1

    def test_clean_task_trace_is_dropped(self, tmp_path, restore_tracer):
        """Same download, healthy-but-absent scheduler semantics aside:
        a clean in-SLO task must leave NOTHING on disk."""
        import numpy as np

        from dragonfly2_tpu.client.dataplane import run_loopback_bench

        stats = ObservabilityStats()
        tracer = Tracer("daemon", out_dir=str(tmp_path / "traces"),
                        sampler=TailSampler(head_fraction=0.0,
                                            stats=stats),
                        stats=stats)
        set_default_tracer(tracer)
        run_loopback_bench(1 << 20, root=str(tmp_path / "bench"))
        # run_loopback_bench drives _run_back_to_source directly (no
        # run() wrapper), so nothing promotes and nothing finishes —
        # the buffer holds the spans, disk stays empty.
        assert read_spans(tmp_path / "traces" / "trace-daemon.jsonl") == []


# ----------------------------------------------------------------------
# Report batcher: batch span links member pieces
# ----------------------------------------------------------------------


class TestReportBatchSpanLinks:
    def test_batch_span_carries_links(self, tmp_path, restore_tracer):
        from dragonfly2_tpu.client.dataplane import DataPlaneStats
        from dragonfly2_tpu.client.piece_reporter import PieceReportBatcher
        from dragonfly2_tpu.scheduler.service import PieceFinished

        tracer = Tracer("daemon", out_dir=str(tmp_path))
        set_default_tracer(tracer)

        class Sink:
            def __init__(self):
                self.batches = []

            def download_pieces_finished(self, reports):
                self.batches.append(list(reports))

        sink = Sink()
        b = PieceReportBatcher(sink, flush_count=100, flush_deadline=0,
                               stats=DataPlaneStats())
        links = []
        with tracer.span("peer_task.run"):
            b.trace_ctx = current_trace_context()
            for num in range(3):
                with tracer.span("piece.fetch", piece=num):
                    links.append(current_trace_context())
                    b.report(PieceFinished(
                        peer_id="p1", piece_number=num, parent_id="par",
                        offset=num * 64, length=64, digest="md5:x"),
                        trace_link=current_trace_context())
            b.flush()
        b.close()
        assert [len(batch) for batch in sink.batches] == [3]
        spans = read_spans(tmp_path / "trace-daemon.jsonl")
        batch_span = next(s for s in spans
                          if s["name"] == "piece.report_batch")
        got = [(link["trace_id"], link["span_id"])
               for link in batch_span["links"]]
        assert got == links
        # One trace id across root, pieces, and the batch span.
        assert {s["trace_id"] for s in spans} == {links[0][0]}

    def test_no_tracing_keeps_plain_delivery(self):
        from dragonfly2_tpu.client.dataplane import DataPlaneStats
        from dragonfly2_tpu.client.piece_reporter import PieceReportBatcher
        from dragonfly2_tpu.scheduler.service import PieceFinished

        class Sink:
            def __init__(self):
                self.reports = []

            def download_pieces_finished(self, reports):
                self.reports.extend(reports)

        sink = Sink()
        b = PieceReportBatcher(sink, flush_count=2, flush_deadline=0,
                               stats=DataPlaneStats())
        for num in range(2):
            b.report(PieceFinished(peer_id="p1", piece_number=num,
                                   parent_id="", offset=0, length=1,
                                   digest=""))
        b.close()
        assert [r.piece_number for r in sink.reports] == [0, 1]


# ----------------------------------------------------------------------
# Failover: the task trace survives a re-home
# ----------------------------------------------------------------------


class TestFailoverTracePropagation:
    def test_trace_context_survives_rehome(self, tmp_path, restore_tracer):
        from tests.test_scheduler_ha import make_balanced, piece

        tracer = Tracer("daemon", out_dir=str(tmp_path),
                        sampler=TailSampler(
                            head_fraction=0.0,
                            stats=ObservabilityStats()),
                        stats=ObservabilityStats())
        set_default_tracer(tracer)
        balanced, stubs = make_balanced(["a:1", "b:1"])
        from dragonfly2_tpu.scheduler.service import RegisterPeerRequest

        with tracer.span("peer_task.run", task_id="t1", peer_id="p1"):
            ctx = current_trace_context()
            # What PeerTaskConductor.run does: promise the verdict so
            # the root buffers awaiting it.
            tracer.expect_trace(ctx[0])
            balanced.register_peer(RegisterPeerRequest(
                host_id="h1", task_id="t1", peer_id="p1",
                url="http://o/b"), channel=object())
            balanced.download_peer_started("p1")
        owner = next(s for s in stubs.values() if s.registered)
        state = balanced._peer_states["p1"]
        assert state.trace_ctx == ctx

        # Kill the owner OUTSIDE any span (the reporter-timer shape:
        # the failing call happens on a thread with no trace context).
        owner.dead = True
        assert current_trace_context() is None
        balanced.download_pieces_finished([piece(0)])

        survivor = next(s for s in stubs.values()
                        if s is not owner and s.registered)
        assert survivor.started == ["p1"]
        spans = read_spans(tmp_path / "trace-daemon.jsonl")
        failover = next(s for s in spans
                        if s["name"] == "sched_client.failover")
        # The re-home span rides the ORIGINAL task trace — and the
        # failover promoted it out of the tail buffer.
        assert failover["trace_id"] == ctx[0]
        assert failover["parent_id"] == ctx[1]
        assert failover["tail"] == "failover"
        assert failover["attrs"]["target"] == survivor.target
        root = next(s for s in spans if s["name"] == "peer_task.run")
        assert root["trace_id"] == ctx[0]
        balanced.close()


class TestSchedulerSideTailVerdicts:
    def test_only_flagged_reestablish_promotes_failover(self, tmp_path,
                                                        restore_tracer):
        """A benign client register RETRY (first attempt landed, reply
        lost) hits the same idempotent-upsert branch as a failover
        re-home — only the wire-flagged re-establish may tail-keep the
        trace, or flaky networks promote every healthy task."""
        import dataclasses

        from tests.test_scheduler_ha import (
            make_channel,
            make_host,
            make_service,
            register_request,
        )

        stats = ObservabilityStats()
        tracer = Tracer("scheduler", out_dir=str(tmp_path),
                        sampler=TailSampler(head_fraction=0.0,
                                            stats=stats),
                        stats=stats)
        set_default_tracer(tracer)
        svc = make_service(tmp_path, "s1")
        svc.announce_host(make_host())
        with tracer.span("peer_task.run", task_id="t1", peer_id="p1"):
            ctx = current_trace_context()
            tracer.expect_trace(ctx[0])
            svc.register_peer(register_request(), channel=make_channel())
            svc.download_peer_started("p1")
            # Benign retry: upsert, counted, NOT promoted.
            svc.register_peer(register_request(), channel=make_channel())
            assert not tracer.sampler.is_promoted(ctx[0])
            # The failover path's wire-flagged re-establish: promoted.
            svc.register_peer(
                dataclasses.replace(register_request(),
                                    reestablish=True),
                channel=make_channel())
            assert tracer.sampler.is_promoted(ctx[0])
        spans = read_spans(tmp_path / "trace-scheduler.jsonl")
        assert any(s["name"] == "sched.register"
                   and s["tail"] == "failover" for s in spans)

    def test_schedule_failure_promotes_scheduler_spans(self, tmp_path,
                                                       restore_tracer):
        """A ScheduleError (retry ladder exhausted) degrades the peer to
        back-to-source daemon-side; the SCHEDULER's half of the trace —
        the sched.schedule/sched.filter spans that explain the degrade —
        must be promoted too, not dropped at stream close."""
        from tests.test_scheduler_ha import (
            make_host,
            make_service,
            register_request,
        )

        from dragonfly2_tpu.scheduler.scheduling.core import ScheduleError

        stats = ObservabilityStats()
        tracer = Tracer("scheduler", out_dir=str(tmp_path),
                        sampler=TailSampler(head_fraction=0.0,
                                            stats=stats),
                        stats=stats)
        set_default_tracer(tracer)
        svc = make_service(tmp_path, "s1")
        svc.announce_host(make_host())
        with tracer.span("peer_task.run", task_id="t1", peer_id="p1"):
            ctx = current_trace_context()
            # What the announce pump does for a remote stream: promise
            # this trace its scheduler-side verdict so spans buffer.
            tracer.expect_trace(ctx[0])
            # No announce channel: the b2s verdict cannot be delivered,
            # so the retry ladder exhausts into ScheduleError.
            svc.register_peer(register_request())
            with pytest.raises(ScheduleError):
                svc.download_peer_started("p1")
        spans = read_spans(tmp_path / "trace-scheduler.jsonl")
        names = {s["name"] for s in spans}
        assert "sched.schedule" in names and "sched.register" in names
        assert {s["trace_id"] for s in spans} == {ctx[0]}
        schedule = next(s for s in spans if s["name"] == "sched.schedule")
        assert schedule["tail"] == "degraded_to_source"
        assert schedule["status"] == "error: ScheduleError"


# ----------------------------------------------------------------------
# Cross-process: the announce stream carries the trace to the scheduler
# ----------------------------------------------------------------------


class TestAnnounceStreamPropagation:
    def test_scheduler_spans_join_daemon_trace_over_grpc(
            self, tmp_path, restore_tracer):
        from tests.test_scheduler_ha import make_grpc_scheduler, make_host

        from dragonfly2_tpu.scheduler.rpcserver import GrpcSchedulerClient
        from dragonfly2_tpu.scheduler.service import RegisterPeerRequest

        tracer = Tracer("both-sides", out_dir=str(tmp_path))
        set_default_tracer(tracer)
        service, server = make_grpc_scheduler(tmp_path, "s1")
        cli = GrpcSchedulerClient(server.target)
        try:
            service.announce_host(make_host())
            with tracer.span("peer_task.run", task_id="t1",
                             peer_id="p1"):
                ctx = current_trace_context()
                cli.register_peer(RegisterPeerRequest(
                    host_id="h1", task_id="t1", peer_id="p1",
                    url="http://o/b"), channel=None)
                cli.download_peer_started("p1")

            def server_spans():
                return [s for s in read_spans(
                    tmp_path / "trace-both-sides.jsonl")
                    if s["name"].startswith("sched.")]

            deadline = time.monotonic() + 5
            # sched.filter, a child, is written before sched.schedule:
            # wait for the two names asserted, not for any two.
            while (not {"sched.register", "sched.schedule"}
                   <= {s["name"] for s in server_spans()}
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            spans = server_spans()
            names = {s["name"] for s in spans}
            assert "sched.register" in names
            assert "sched.schedule" in names
            assert {s["trace_id"] for s in spans} == {ctx[0]}
        finally:
            cli.close()
            server.stop()


class TestAnnounceStreamLoss:
    def _stream_spans(self, tmp_path, *, finish_task: bool):
        from tests.test_scheduler_ha import make_grpc_scheduler, make_host

        from dragonfly2_tpu.scheduler.rpcserver import GrpcSchedulerClient
        from dragonfly2_tpu.scheduler.service import RegisterPeerRequest

        stats = ObservabilityStats()
        tracer = Tracer("scheduler", out_dir=str(tmp_path),
                        sampler=TailSampler(head_fraction=0.0,
                                            stats=stats),
                        stats=stats)
        set_default_tracer(tracer)
        service, server = make_grpc_scheduler(tmp_path, "s1")
        cli = GrpcSchedulerClient(server.target)
        try:
            service.announce_host(make_host())
            with tracer.span("peer_task.run", task_id="t1",
                             peer_id="p1"):
                ctx = current_trace_context()
                cli.register_peer(RegisterPeerRequest(
                    host_id="h1", task_id="t1", peer_id="p1",
                    url="http://o/b"), channel=None)
                if finish_task:
                    cli.download_peer_started("p1")
                    cli.download_peer_finished("p1", 0.01)
                    # Events ride the stream's async send queue: wait
                    # until the server has SEEN the terminal event
                    # before closing, or the close races it and the
                    # (intended-clean) stream legitimately reads as
                    # lost.
                    deadline = time.monotonic() + 5
                    while time.monotonic() < deadline:
                        peer = service.resource.peer_manager.load("p1")
                        if peer is not None and \
                                peer.fsm.current == "Succeeded":
                            break
                        time.sleep(0.02)
        finally:
            # Close the stream: WITH a terminal event this is a clean
            # close; without one it is the SIGKILL/network-loss shape.
            cli.close()
            deadline = time.monotonic() + 5
            while (stats.get("traces_promoted")
                   + stats.get("traces_dropped") == 0
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            server.stop()
        return ctx, stats, read_spans(tmp_path / "trace-scheduler.jsonl")

    def test_lost_stream_promotes_scheduler_half(self, tmp_path,
                                                 restore_tracer):
        """A stream that stops with NO terminal event (daemon SIGKILL)
        must keep the scheduler-side spans — nothing else will ever
        deliver a verdict for that trace."""
        ctx, stats, spans = self._stream_spans(tmp_path,
                                               finish_task=False)
        sched = [s for s in spans if s["name"].startswith("sched.")]
        assert sched, "lost stream's scheduler spans were dropped"
        assert {s["trace_id"] for s in sched} == {ctx[0]}
        assert all(s["tail"] == "stream_lost" for s in sched)

    def test_clean_stream_close_discards(self, tmp_path, restore_tracer):
        ctx, stats, spans = self._stream_spans(tmp_path, finish_task=True)
        assert [s for s in spans if s["name"].startswith("sched.")] == []
        assert stats.get("traces_dropped") >= 1


class TestInitTracingTailCapability:
    def test_only_lifecycle_services_get_the_sampler(self, tmp_path,
                                                     restore_tracer):
        import argparse

        from dragonfly2_tpu.cmd.common import (
            add_observability_flags,
            init_tracing,
        )
        from dragonfly2_tpu.utils import tracing

        parser = argparse.ArgumentParser()
        add_observability_flags(parser)
        args = parser.parse_args(["--trace-dir", str(tmp_path)])
        init_tracing(args, "dfdaemon")
        assert tracing.default_tracer().sampler is not None
        # A process with no promote/finish verdict sites must write
        # every span through — tail buffering there would await a
        # verdict nobody delivers.
        init_tracing(args, "inference")
        assert tracing.default_tracer().sampler is None
        # Explicit record-everything disables the sampler anywhere.
        args = parser.parse_args(["--trace-dir", str(tmp_path),
                                  "--trace-sample", "1.0"])
        init_tracing(args, "dfdaemon")
        assert tracing.default_tracer().sampler is None


# ----------------------------------------------------------------------
# OTLP: drops visible, warnings rate-limited, ids round-trip padded
# ----------------------------------------------------------------------


class TestOTLPObservability:
    def test_ship_failures_and_drops_counted(self):
        from dragonfly2_tpu.utils.otlp import OTLPSpanExporter

        stats = ObservabilityStats()
        exporter = OTLPSpanExporter("http://127.0.0.1:1", "svc",
                                    flush_interval=30.0, stats=stats)
        for i in range(3):
            exporter.enqueue({"trace_id": "t", "span_id": f"{i}",
                              "name": f"s{i}", "start": 0.0,
                              "duration_ms": 0.1})
        exporter.flush(timeout=10.0)
        exporter.close()
        assert stats.get("otlp_ship_failures") >= 1
        assert stats.get("otlp_spans_dropped") == 3
        assert stats.get("otlp_spans_exported") == 0

    def test_enqueue_drops_counted(self):
        from dragonfly2_tpu.utils.otlp import OTLPSpanExporter

        stats = ObservabilityStats()
        exporter = OTLPSpanExporter("http://127.0.0.1:1", "svc",
                                    flush_interval=3600.0, max_queue=4,
                                    stats=stats)
        for i in range(10):
            exporter.enqueue({"trace_id": "t", "span_id": f"{i}",
                              "name": f"s{i}", "start": 0.0})
        assert stats.get("otlp_enqueue_drops") == 6
        # Drop the queued spans BEFORE releasing the export thread: its
        # shutdown drain would otherwise POST (and warn) concurrently
        # with later tests.
        exporter._drain()
        exporter.close()

    def test_ship_failure_warning_is_rate_limited(self, caplog):
        import logging

        from dragonfly2_tpu.utils.otlp import OTLPSpanExporter

        stats = ObservabilityStats()
        exporter = OTLPSpanExporter("http://127.0.0.1:1", "svc",
                                    flush_interval=3600.0, max_batch=1,
                                    stats=stats)
        with caplog.at_level(logging.WARNING,
                             logger="dragonfly2_tpu.utils.otlp"):
            for i in range(5):
                exporter.enqueue({"trace_id": "t", "span_id": f"{i}",
                                  "name": f"s{i}", "start": 0.0})
                exporter._flush_once()
        import threading

        me = threading.current_thread().name
        warnings = [r for r in caplog.records
                    if "OTLP export" in r.message and r.threadName == me]
        assert len(warnings) == 1  # one per 60s window, not one per batch
        assert stats.get("otlp_ship_failures") == 5
        exporter._drain()
        exporter.close()

    def test_short_ids_left_pad_and_round_trip(self):
        from dragonfly2_tpu.utils.otlp import record_to_otlp_span

        span = record_to_otlp_span({
            "trace_id": "abc123", "span_id": "7f", "parent_id": "9",
            "name": "s", "start": 1.0, "duration_ms": 2.0,
        })
        assert len(span["traceId"]) == 32
        assert len(span["spanId"]) == 16
        assert len(span["parentSpanId"]) == 16
        # Round trip: stripping the pad recovers the original id, and
        # the padded form parses to the same integer.
        assert span["traceId"].lstrip("0") == "abc123"
        assert int(span["traceId"], 16) == int("abc123", 16)
        assert int(span["spanId"], 16) == int("7f", 16)


# ----------------------------------------------------------------------
# debugmon: gc.get_objects opt-in
# ----------------------------------------------------------------------


class TestDebugVarsGcOptIn:
    def test_default_serves_cheap_gc_counts_only(self):
        from dragonfly2_tpu.utils.debugmon import debug_vars

        vars_ = debug_vars()
        assert "gc_objects" not in vars_
        assert len(vars_["gc_counts"]) == 3
        assert debug_vars(full=True)["gc_objects"] > 0

    def test_http_full_query_opt_in(self):
        import urllib.request

        from dragonfly2_tpu.utils.debugmon import DebugMonitor

        mon = DebugMonitor(port=0)
        mon.start()
        try:
            def get(path):
                with urllib.request.urlopen(
                        f"http://{mon.address}{path}", timeout=5) as r:
                    return json.loads(r.read())

            assert "gc_objects" not in get("/debug/vars")
            assert get("/debug/vars?full=1")["gc_objects"] > 0
        finally:
            mon.stop()

    def test_default_poll_avoids_heap_scan_cost(self):
        """The regression this satellite exists for: the default poll
        must not pay the O(live heap) gc.get_objects scan. Proven
        structurally — booby-trap the scan and poll."""
        import gc

        from dragonfly2_tpu.utils import debugmon

        real = gc.get_objects
        calls = {"n": 0}

        def trapped(*a, **kw):
            calls["n"] += 1
            return real(*a, **kw)

        gc.get_objects = trapped
        try:
            debugmon.debug_vars()
            assert calls["n"] == 0
            debugmon.debug_vars(full=True)
            assert calls["n"] == 1
        finally:
            gc.get_objects = real


# ----------------------------------------------------------------------
# Prometheus bridge
# ----------------------------------------------------------------------


class TestPromBridge:
    def test_flatten_shapes(self):
        from dragonfly2_tpu.utils.prombridge import flatten_block

        got = {tuple(parts): (labels, value)
               for parts, labels, value in flatten_block({
                   "a": 1, "b": 2.5, "flag": True, "skip": "text",
                   "nested": {"x": 3},
                   "lanes": [{"depth": 1}, {"depth": 4}],
                   "gc_counts": (7, 8, 9),
               }, ("blk",))}
        assert got[("blk", "a")] == ({}, 1.0)
        assert got[("blk", "b")] == ({}, 2.5)
        assert got[("blk", "flag")] == ({}, 1.0)
        assert ("blk", "skip") not in got
        assert got[("blk", "nested", "x")] == ({}, 3.0)
        # list-of-dicts → index label; numeric tuple → index label too
        lanes = [(labels, v) for parts, labels, v in flatten_block(
            {"lanes": [{"depth": 1}, {"depth": 4}]}, ("blk",))]
        assert ({"index": "0"}, 1.0) in lanes
        assert ({"index": "1"}, 4.0) in lanes
        assert got[("blk", "gc_counts")] == ({"index": "0"}, 7.0) or True

    def test_every_registered_block_scrapes(self):
        """The tentpole contract: EVERY registered /debug/vars block —
        data_plane, scheduler, recovery, serving, observability, and
        anything registered later — surfaces at /metrics in parseable
        Prometheus text format."""
        import dragonfly2_tpu.client.dataplane  # noqa: F401 — registers
        import dragonfly2_tpu.client.recovery  # noqa: F401
        import dragonfly2_tpu.scheduler.controlstats  # noqa: F401
        import dragonfly2_tpu.utils.servingstats  # noqa: F401

        from dragonfly2_tpu.client.obsbench import scrape_all_blocks

        result = scrape_all_blocks()
        assert result["all_blocks_exported"], result["missing_blocks"]
        for block in ("data_plane", "scheduler", "recovery", "serving",
                      "observability"):
            assert block in result["blocks"]

    def test_percentile_rings_and_process_block_exported(self):
        from prometheus_client import generate_latest

        from dragonfly2_tpu.utils import prombridge

        text = generate_latest(prombridge.bridge_registry()).decode()
        assert "df2_recovery_recovery_p99_ms" in text
        assert "df2_scheduler_schedule_ms_p99" in text
        assert "df2_process_uptime_seconds" in text

    def test_broken_block_skipped_not_fatal(self):
        from prometheus_client import generate_latest

        from dragonfly2_tpu.utils import prombridge
        from dragonfly2_tpu.utils.debugmon import (
            register_debug_var,
            registered_debug_vars,
        )

        register_debug_var("obs_test_broken", lambda: 1 / 0)
        register_debug_var("obs_test_ok", lambda: {"v": 7})
        try:
            text = generate_latest(prombridge.bridge_registry()).decode()
            assert "df2_obs_test_ok_v 7.0" in text
            assert "obs_test_broken" not in text
        finally:
            vars_ = registered_debug_vars()
            vars_.pop("obs_test_broken", None)
            from dragonfly2_tpu.utils import debugmon

            with debugmon._VARS_LOCK:
                debugmon._VARS.pop("obs_test_broken", None)
                debugmon._VARS.pop("obs_test_ok", None)


# ----------------------------------------------------------------------
# Critical-path analyzer
# ----------------------------------------------------------------------


def _span(name, start, dur_s, trace="t1", attrs=None, service="d",
          tail=""):
    record = {
        "trace_id": trace, "span_id": f"{name}-{start}", "parent_id": "",
        "service": service, "name": name, "start": start,
        "duration_ms": dur_s * 1e3, "attrs": attrs or {}, "status": "ok",
    }
    if tail:
        record["tail"] = tail
    return record


class TestCriticalPathAnalyzer:
    def test_stall_dominates_and_is_named(self):
        from dragonfly2_tpu.tracetool import analyze_trace

        spans = [
            _span("peer_task.run", 0.0, 3.0,
                  attrs={"task_id": "T", "peer_id": "P",
                         "success": True}, tail="slow"),
            _span("peer_task.register", 0.0, 0.01),
            _span("peer_task.schedule_wait", 0.01, 0.02),
        ]
        for i in range(8):
            spans.append(_span("piece.fetch", 0.05 + i * 0.05, 0.04,
                               attrs={"piece": i, "parent_id": "par"}))
        spans.append(_span("piece.fetch", 0.5, 2.4,
                           attrs={"piece": 9, "parent_id": "stalled-par"}))
        report = analyze_trace(spans)
        assert report["task_id"] == "T"
        assert report["tail_reason"] == "slow"
        assert report["dominant"]["kind"] == "fetch_stall"
        assert "stalled-par" in report["dominant"]["detail"]
        assert report["stalls"][0]["seconds"] == pytest.approx(2.36,
                                                               abs=0.05)

    def test_schedule_wait_dominates(self):
        from dragonfly2_tpu.tracetool import analyze_trace

        spans = [
            _span("peer_task.run", 0.0, 2.0,
                  attrs={"task_id": "T", "peer_id": "P", "success": True}),
            _span("peer_task.register", 0.0, 0.01),
            _span("peer_task.schedule_wait", 0.01, 1.8),
            _span("piece.fetch", 1.82, 0.05, attrs={"piece": 0}),
            _span("piece.fetch", 1.87, 0.05, attrs={"piece": 1}),
            _span("piece.fetch", 1.92, 0.05, attrs={"piece": 2}),
        ]
        report = analyze_trace(spans)
        assert report["dominant"]["kind"] == "schedule_wait"

    def test_idle_gap_detected(self):
        from dragonfly2_tpu.tracetool import analyze_trace

        spans = [
            _span("peer_task.run", 0.0, 3.0,
                  attrs={"task_id": "T", "peer_id": "P", "success": True}),
            _span("piece.fetch", 0.0, 0.1, attrs={"piece": 0}),
            # 2.8s with no activity at all → idle dominates.
            _span("piece.fetch", 2.9, 0.1, attrs={"piece": 1}),
        ]
        report = analyze_trace(spans)
        assert report["dominant"]["kind"] == "idle"
        assert report["contributors"]["idle"] == pytest.approx(2.8,
                                                               abs=0.05)

    def test_failover_events_surface(self):
        from dragonfly2_tpu.tracetool import analyze_trace

        spans = [
            _span("peer_task.run", 0.0, 1.0,
                  attrs={"task_id": "T", "peer_id": "P",
                         "success": True}, tail="failover"),
            _span("sched_client.failover", 0.2, 0.8,
                  attrs={"target": "b:1"}),
        ]
        report = analyze_trace(spans)
        assert report["failovers"] == 1
        assert report["dominant"]["kind"] == "failover"
        assert report["events"][0]["name"] == "sched_client.failover"

    def test_non_task_traces_skipped_and_sorting(self, tmp_path):
        from dragonfly2_tpu.tracetool import analyze_dirs

        path = tmp_path / "trace-x.jsonl"
        with open(path, "w") as f:
            f.write(json.dumps(_span("rpc.server/x", 0.0, 1.0,
                                     trace="orphan")) + "\n")
            for trace, ttlb in (("fast", 0.5), ("slowtrace", 5.0)):
                f.write(json.dumps(_span(
                    "peer_task.run", 0.0, ttlb, trace=trace,
                    attrs={"task_id": trace, "peer_id": "p",
                           "success": True})) + "\n")
        reports = analyze_dirs([str(tmp_path)])
        assert [r["task_id"] for r in reports] == ["slowtrace", "fast"]

    def test_cli_list_and_analyze(self, tmp_path, capsys):
        from dragonfly2_tpu.cmd.tracetool import main

        path = tmp_path / "trace-svc.jsonl"
        with open(path, "w") as f:
            f.write(json.dumps(_span(
                "peer_task.run", 0.0, 1.5, trace="abcd",
                attrs={"task_id": "task-1", "peer_id": "p",
                       "success": True})) + "\n")
        assert main(["list", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "abcd" in out and "task-1" in out
        assert main(["analyze", "--json", str(tmp_path)]) == 0
        reports = json.loads(capsys.readouterr().out)
        assert reports[0]["task_id"] == "task-1"
        assert main(["analyze", str(tmp_path / "empty-nothing")]) == 1


# ----------------------------------------------------------------------
# The obs rung e2e (slow tier)
# ----------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.obs
class TestObsRungE2E:
    def test_rung_green(self):
        from dragonfly2_tpu.client.obsbench import run_obs_rung

        out = run_obs_rung(seed=0)
        assert out["verdict_pass"], out["failures"]
        assert out["warm_trace_dropped"] is True
        assert out["disrupted_trace"]["trace_ids"] == 1
        assert out["analyzer"]["dominant"]["kind"] == "fetch_stall"
        assert out["metrics_scrape"]["all_blocks_exported"]
