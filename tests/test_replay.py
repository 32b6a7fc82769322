"""Replay plane: recorder capture, deterministic replay, segment-rotation
roundtrip, learned cost model + evaluator seam, and the cost gate.

The expensive fixtures (one recorded in-process swarm corpus, one trained
cost model) are module-scoped and shared across the battery.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from dragonfly2_tpu.schema import (
    MAX_REPLAY_CANDIDATES,
    REPLAY_SCHEMA_VERSION,
    ReplayCandidate,
    ReplayDecision,
    ReplayFeatureRow,
)
from dragonfly2_tpu.schema.io import read_csv_records
from dragonfly2_tpu.scheduler import replay as rp
from dragonfly2_tpu.scheduler.controlstats import ControlPlaneStats
from dragonfly2_tpu.scheduler.evaluator import BaseEvaluator, new_evaluator
from dragonfly2_tpu.scheduler.evaluator import scoring
from dragonfly2_tpu.scheduler.evaluator.base import build_feature_matrix
from dragonfly2_tpu.scheduler.loadbench import run_swarm_bench
from dragonfly2_tpu.scheduler.replaylog import (
    ReplayRecorder,
    snapshot_mean,
    welford_snapshot,
)
from dragonfly2_tpu.scheduler.storage.storage import Storage, StorageConfig


# ---------------------------------------------------------------------------
# Shared corpus: one profiled swarm recorded through a rotating storage.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    base = tmp_path_factory.mktemp("replay-corpus")
    # Tiny max_size FORCES mid-recording rotation (the satellite case: a
    # decision recorded just before rotation must replay identically
    # from the rotated corpus).
    storage = Storage(str(base / "sched"),
                      StorageConfig(max_size=64 * 1024, buffer_size=10))
    stats = ControlPlaneStats()
    recorder = ReplayRecorder(storage, stats=stats)
    rung = run_swarm_bench(150, workers=4, recorder=recorder,
                           cost_profile="profiled", profile_seed=3)
    recorder.finalize_all()
    recorder.flush()
    ring_events = recorder.events()
    recorder.close()
    yield {"storage": storage, "stats": stats, "rung": rung,
           "ring": ring_events, "dir": str(base / "sched")}


@pytest.fixture(scope="module")
def cost_model(recorded):
    from dragonfly2_tpu.train.cost_trainer import (
        CostTrainConfig,
        cost_examples_from_corpus,
        train_cost,
    )

    corpus = rp.corpus_from_events(recorded["ring"])
    X, y = cost_examples_from_corpus(corpus)
    result = train_cost(
        X, y, CostTrainConfig(hidden=(16, 8), epochs=15, batch_size=256))
    return {"result": result, "X": X, "y": y, "corpus": corpus}


def _cost_scorer(result):
    from dragonfly2_tpu.inference.scorer import CostScorer, ParentScorer

    typical = float(np.expm1(float(result.target_norm.mean[0])))
    return CostScorer(
        ParentScorer(result.model, result.params, result.normalizer,
                     result.target_norm),
        version="test", typical_cost_s=typical)


# ---------------------------------------------------------------------------
# Schema + capture
# ---------------------------------------------------------------------------


class TestSchema:
    def test_feature_row_fields_match_canonical_layout(self):
        fields = tuple(f.name for f in dataclasses.fields(ReplayFeatureRow))
        assert fields == scoring.FEATURE_NAMES

    def test_csv_roundtrip(self, tmp_path):
        from dragonfly2_tpu.schema.io import CsvRecordWriter

        rec = ReplayDecision(
            seq=7, task_id="t", peer_id="p", total_piece_count=4,
            verdict="parents", chosen="c1", outcome="Succeeded",
            outcome_cost=0.5, decided_at=123, finalized_at=456,
            candidates=[ReplayCandidate(
                id="c1", rank=0,
                features=ReplayFeatureRow(parent_finished_pieces=4.0,
                                          total_pieces=4.0),
                cost_n=3, cost_last=0.02, cost_prior_mean=0.019,
                cost_prior_pstd=0.001, realized_n=5, realized_cost=0.021)],
        )
        path = tmp_path / "replay.csv"
        with CsvRecordWriter(ReplayDecision, str(path)) as w:
            w.write(rec)
        back = list(read_csv_records(ReplayDecision, str(path)))
        assert len(back) == 1
        assert back[0] == rec
        assert back[0].version == REPLAY_SCHEMA_VERSION


class TestRecorder:
    def test_capture_counters_and_outcomes(self, recorded):
        # Counters live in the rung's hermetic stats block (the bench
        # injects its own ControlPlaneStats into the recorder): every
        # delivered decision was captured and every capture was
        # finalized by its child's terminal report (the loadbench
        # drives all peers to a terminal state).
        rung = recorded["rung"]
        assert rung["replay_decisions"] == rung["decisions"] \
            + rung["back_to_source"]
        assert rung["replay_finalized"] == rung["replay_decisions"]
        assert rung["replay_evicted"] == 0

    def test_event_shape(self, recorded):
        events = [e for e in recorded["ring"] if e.verdict == "parents"]
        assert events, "no parent decisions recorded"
        for e in events[:20]:
            assert e.version == REPLAY_SCHEMA_VERSION
            assert e.candidates and len(e.candidates) <= MAX_REPLAY_CANDIDATES
            ranked = sorted((c for c in e.candidates if c.rank >= 0),
                            key=lambda c: c.rank)
            assert ranked, "no delivered ranking recorded"
            assert e.chosen == ranked[0].id
            assert e.outcome in ("Succeeded", "Failed", "Leave", "")
        # Realized costs flowed from the candidates' Welford stats.
        realized = [c.realized_cost for e in events for c in e.candidates
                    if c.realized_n > 0]
        assert realized and min(realized) > 0

    def test_feature_rows_bit_identical_to_staged_matrix(self, recorded):
        for e in recorded["ring"]:
            if not e.candidates:
                continue
            child, parents = rp.rebuild_decision(e)
            staged = build_feature_matrix(parents, child,
                                          e.total_piece_count)
            recorded_rows = np.stack(
                [rp._row_array(c) for c in e.candidates])
            assert np.array_equal(staged, recorded_rows)

    def test_eviction_bounds_pending(self):
        stats = ControlPlaneStats()
        rec = ReplayRecorder(max_pending=2, stats=stats)

        class _Task:
            id = "t"
            total_piece_count = 4

        class _Host:
            type = type("T", (), {"is_seed": False})()
            upload_count = 0
            upload_failed_count = 0
            concurrent_upload_limit = 10
            idc = ""
            location = ""

            def free_upload_count(self):
                return 10

        class _Peer:
            def __init__(self, pid):
                self.id = pid
                self.task = _Task()
                self.host = _Host()

            def state(self):
                return "Running"

            def finished_piece_count(self):
                return 1

            def piece_costs(self):
                return [0.01]

        cand = [_Peer("c1"), _Peer("c2")]
        for i in range(3):
            rec.record_decision(_Peer(f"p{i}"), cand, cand, 4)
        rec.drain()
        assert rec.pending_count() == 2
        snap = stats.snapshot()
        assert snap["replay_evicted"] == 1
        evicted = rec.events()
        assert len(evicted) == 1 and evicted[0].outcome == ""
        rec.close()

    def test_pending_order_compacts_on_healthy_outcomes(self, recorded):
        """On a healthy swarm (every decision gets an outcome, so the
        eviction path never runs) the eviction-order deque must not
        grow one stale tuple per decision forever — finalization
        triggers an amortized compaction."""
        class _Done:
            fsm = type("F", (), {"current": "Succeeded"})()
            cost = 0.1

            def __init__(self, pid):
                self.id = pid

        rec = ReplayRecorder()
        events = [e for e in recorded["ring"] if e.candidates][:10]
        pairs = [rp.rebuild_decision(e) for e in events]
        for round_ in range(60):
            for child, parents in pairs:
                rec.record_decision(child, parents, parents[:4], 4)
                rec.record_outcome(_Done(child.id))
        rec.drain()
        assert rec.pending_count() == 0
        assert len(rec._pending_order) <= 64, len(rec._pending_order)
        rec.close()

    def test_queue_overflow_sheds_before_extraction(self):
        rec = ReplayRecorder(queue_capacity=0)

        class _Boom:
            """A shed decision must never pay the extraction cost — the
            capacity check runs FIRST on the announce thread."""

            id = "p"
            task = type("T", (), {"id": "t", "total_piece_count": 4})()
            fsm = type("F", (), {"current": "Succeeded"})()
            cost = 0.0
            host = type("H", (), {"idc": "", "location": ""})()

            def finished_piece_count(self):
                raise AssertionError("extracted a shed decision")

        rec.record_decision(_Boom(), [], [], 4)
        assert rec.dropped == 1
        # Outcomes shed only past DOUBLE the decision capacity (bounded
        # with headroom; at capacity 0 that is immediately) — an
        # unbounded outcome queue would pin peer references without
        # limit on exactly the overloaded path shedding protects.
        rec.record_outcome(_Boom())
        assert rec.dropped == 2
        rec.close()
        # After close, record_* calls are counted no-ops, never queue
        # growth with no consumer.
        rec.record_outcome(_Boom())
        assert rec.dropped == 3


# ---------------------------------------------------------------------------
# Deterministic replay + rotation roundtrip
# ---------------------------------------------------------------------------


class TestReplayDeterminism:
    def test_same_corpus_same_seed_bit_identical(self, recorded):
        corpus = rp.corpus_from_events(recorded["ring"])
        a = rp.replay_decisions(corpus, BaseEvaluator(), seed=0)
        b = rp.replay_decisions(corpus, BaseEvaluator(), seed=0)
        assert a.digest == b.digest
        assert a.decisions == b.decisions

    def test_rotation_roundtrip(self, recorded):
        """The satellite case: the corpus was recorded through a
        rotating dataset (tiny max_size) — events that landed in rotated
        backups must replay identically to the in-memory ring."""
        storage = recorded["storage"]
        assert len(storage.replay.all_files()) > 1, \
            "rotation never happened; shrink max_size"
        disk = rp.corpus_from_storage(storage)
        ring = rp.corpus_from_events(recorded["ring"])
        assert len(disk) == len(ring)
        assert [e.seq for e in disk] == [e.seq for e in ring]
        d = rp.replay_decisions(disk, BaseEvaluator(), seed=0)
        r = rp.replay_decisions(ring, BaseEvaluator(), seed=0)
        assert d.digest == r.digest

    def test_reopened_storage_replays_identically(self, recorded):
        reopened = Storage(recorded["dir"])
        corpus = rp.corpus_from_storage(reopened)
        base = rp.replay_decisions(
            rp.corpus_from_events(recorded["ring"]), BaseEvaluator())
        fresh = rp.replay_decisions(corpus, BaseEvaluator())
        assert fresh.digest == base.digest

    def test_unknown_schema_version_refused(self, recorded):
        bad = ReplayDecision(version=REPLAY_SCHEMA_VERSION + 1, seq=0)
        with pytest.raises(ValueError, match="schema version"):
            rp.corpus_from_events([bad])

    def test_score_run_reports_regret_and_agreement(self, recorded):
        corpus = rp.corpus_from_events(recorded["ring"])
        evaluator = BaseEvaluator()
        run = rp.replay_decisions(corpus, evaluator, name="rule")
        scored = rp.score_run(corpus, run, evaluator=evaluator)
        assert scored["regret_scored"] > 0
        assert scored["regret_mean_s"] is not None \
            and scored["regret_mean_s"] >= 0
        assert scored["rank_agreement_scored"] > 0
        assert scored["decision_latency_p99_ms"] > 0


# ---------------------------------------------------------------------------
# Learned cost model + evaluator seam
# ---------------------------------------------------------------------------


class TestLearnedCost:
    def test_model_learns_the_profiled_cost_signal(self, cost_model):
        scorer = _cost_scorer(cost_model["result"])
        X, y = cost_model["X"], cost_model["y"]
        pred = np.concatenate([
            scorer.predict_cost_s(X[i:i + 64])
            for i in range(0, len(X), 64)])
        corr = float(np.corrcoef(pred, y)[0, 1])
        assert corr > 0.9, f"cost model failed to learn: corr={corr}"

    def test_evaluator_ranks_by_ascending_predicted_cost(self, cost_model):
        from dragonfly2_tpu.inference.scorer import LearnedCostEvaluator

        corpus = cost_model["corpus"]
        evaluator = LearnedCostEvaluator(_cost_scorer(cost_model["result"]))
        run = rp.replay_decisions(corpus, evaluator, name="cost")
        scored = rp.score_run(corpus, run)
        rule = rp.score_run(
            corpus, rp.replay_decisions(corpus, BaseEvaluator(),
                                        name="rule"))
        # On the profiled corpus the learned ranking must beat the
        # hand-tuned rule on realized regret.
        assert scored["regret_mean_s"] < rule["regret_mean_s"]
        assert evaluator.scored_count > 0
        assert evaluator.guard_trips == 0

    def test_learned_bad_node_catches_realized_outliers(self, cost_model):
        from dragonfly2_tpu.inference.scorer import LearnedCostEvaluator

        corpus = cost_model["corpus"]
        evaluator = LearnedCostEvaluator(_cost_scorer(cost_model["result"]))
        run = rp.replay_decisions(corpus, evaluator, name="cost")
        scored = rp.score_run(corpus, run, evaluator=evaluator)
        rule_scored = rp.score_run(
            corpus, rp.replay_decisions(corpus, BaseEvaluator()),
            evaluator=BaseEvaluator())
        # Recorded candidates all passed the live rule filter, so the
        # 3-sigma rule catches ~none of the realized outliers; the
        # learned absolute threshold must catch most with few false
        # alarms.
        assert scored["bad_node_recall"] is not None
        assert scored["bad_node_recall"] > 0.5
        if scored["bad_node_fp"]:
            assert scored["bad_node_precision"] > 0.5
        assert (rule_scored["bad_node_recall"] or 0.0) <= \
            scored["bad_node_recall"]

    def test_guard_trip_falls_back_to_inner(self, cost_model):
        from dragonfly2_tpu.inference.scorer import LearnedCostEvaluator

        class _NaNScorer:
            version = "poisoned"
            typical_cost_s = 0.05

            def score(self, features):
                return np.full(len(features), np.nan)

            def predict_cost_s(self, features):
                return np.full(len(features), np.nan)

        stats = ControlPlaneStats()
        evaluator = LearnedCostEvaluator(_NaNScorer(), stats=stats)
        corpus = [e for e in cost_model["corpus"] if e.candidates][:5]
        inner = BaseEvaluator()
        for event in corpus:
            child, parents = rp.rebuild_decision(event)
            ranked = evaluator.evaluate_parents(
                parents, child, event.total_piece_count)
            expect = inner.evaluate_parents(
                parents, child, event.total_piece_count)
            assert [p.id for p in ranked] == [p.id for p in expect]
            # Bad-node prediction also degrades to the inner rule.
            for p in parents[:2]:
                assert evaluator.is_bad_node(p) == inner.is_bad_node(p)
        snap = stats.snapshot()
        assert snap["cost_guard_trips"] > 0
        assert evaluator.scored_count == 0

    def test_bad_node_state_and_min_samples(self, cost_model):
        from dragonfly2_tpu.inference.scorer import LearnedCostEvaluator

        evaluator = LearnedCostEvaluator(_cost_scorer(cost_model["result"]))
        event = next(e for e in cost_model["corpus"] if e.candidates)
        _, parents = rp.rebuild_decision(event)
        bad_state = rp.ReplayPeer("x", parents[0].host, "Failed", 0.0,
                                  (5, 9.0, 0.02, 0.001))
        assert evaluator.is_bad_node(bad_state) is True
        fresh = rp.ReplayPeer("y", parents[0].host, "Running", 0.0,
                              (1, 0.02, 0.0, 0.0))
        assert evaluator.is_bad_node(fresh) is False


class TestCostGate:
    @pytest.fixture(scope="class")
    def artifact(self, cost_model, tmp_path_factory):
        from dragonfly2_tpu.train.checkpoint import ModelMetadata, save_model
        from dragonfly2_tpu.train.cost_trainer import cost_tree

        art_dir = tmp_path_factory.mktemp("cost-artifact")
        save_model(str(art_dir), cost_tree(cost_model["result"]),
                   ModelMetadata(model_id="m", model_type="cost",
                                 config={"hidden": [16, 8]}))
        return str(art_dir)

    def test_gate_promotes_good_cost_model(self, artifact, cost_model,
                                           tmp_path):
        from dragonfly2_tpu.manager import (
            Database,
            FilesystemObjectStore,
            ManagerService,
        )
        from dragonfly2_tpu.manager.validation import ValidationConfig

        manager = ManagerService(
            Database(str(tmp_path / "m.db")),
            FilesystemObjectStore(str(tmp_path / "obj")),
            validation=ValidationConfig())
        traces = [np.stack([rp._row_array(c) for c in e.candidates])
                  for e in cost_model["corpus"] if e.candidates]
        row = manager.create_model(
            model_id="cost-good", model_type="cost", host_id="h",
            ip="1.1.1.1", hostname="h", evaluation={},
            artifact_dir=artifact, traces=traces)
        assert row.state == "active"
        validation = row.evaluation["validation"]
        assert validation["passed"] is True
        # The rule-correlation is recorded as evidence, never enforced
        # for cost models (they rank by MEASURED costs).
        assert validation["checks"]["rank_correlation"] == "informational"
        # ...and the served artifact loads through the cost scorer.
        from dragonfly2_tpu.inference.sidecar import _cost_scorer_from_artifact

        active = manager.get_active_model("cost")
        scorer = _cost_scorer_from_artifact(active.artifact,
                                            version=active.version)
        assert scorer.version == active.version
        assert scorer.typical_cost_s > 0

    def test_gate_quarantines_poisoned_cost_model(self, cost_model,
                                                  tmp_path):
        from dragonfly2_tpu.inference.modelguard import poison_params
        from dragonfly2_tpu.manager import (
            Database,
            FilesystemObjectStore,
            ManagerService,
        )
        from dragonfly2_tpu.manager.validation import ValidationConfig
        from dragonfly2_tpu.train.checkpoint import ModelMetadata, save_model
        from dragonfly2_tpu.train.checkpoint import mlp_tree

        result = cost_model["result"]
        art_dir = tmp_path / "poisoned"
        save_model(str(art_dir),
                   mlp_tree(poison_params(result.params, "nan"),
                            result.normalizer, result.target_norm),
                   ModelMetadata(model_id="m", model_type="cost",
                                 config={"hidden": [16, 8]}))
        manager = ManagerService(
            Database(str(tmp_path / "m.db")),
            FilesystemObjectStore(str(tmp_path / "obj")),
            validation=ValidationConfig())
        row = manager.create_model(
            model_id="cost-bad", model_type="cost", host_id="h",
            ip="1.1.1.1", hostname="h", evaluation={},
            artifact_dir=str(art_dir))
        assert row.state == "quarantined"
        assert manager.get_active_model("cost") is None

    def test_factory_requires_gated_scorer(self):
        with pytest.raises(ValueError, match="gate-promoted"):
            new_evaluator("cost")

    def test_watcher_promotes_and_demotes(self, artifact, cost_model,
                                          tmp_path):
        """The df2-scheduler cost-registry watcher: a promotion swaps
        rule -> learned-cost; quarantining the only version (nothing
        restorable) demotes back to rules — the rollback contract's
        'none -> evaluators rule-fall-back'."""
        import time

        from dragonfly2_tpu.cmd.scheduler import _watch_cost_registry
        from dragonfly2_tpu.inference.scorer import LearnedCostEvaluator
        from dragonfly2_tpu.manager import (
            Database,
            FilesystemObjectStore,
            ManagerService,
        )
        from dragonfly2_tpu.manager.validation import ValidationConfig

        manager = ManagerService(
            Database(str(tmp_path / "m.db")),
            FilesystemObjectStore(str(tmp_path / "obj")),
            validation=ValidationConfig())
        traces = [np.stack([rp._row_array(c) for c in e.candidates])
                  for e in cost_model["corpus"] if e.candidates]

        class _Svc:
            scheduling = type("S", (), {})()

        svc = _Svc()
        svc.scheduling.evaluator = BaseEvaluator()
        _watch_cost_registry(svc, manager, interval_s=0.05)

        def wait_for(pred, what):
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if pred():
                    return
                time.sleep(0.02)
            raise AssertionError(what)

        row = manager.create_model(
            model_id="w", model_type="cost", host_id="h", ip="1.1.1.1",
            hostname="h", evaluation={}, artifact_dir=artifact,
            traces=traces)
        assert row.state == "active"
        wait_for(lambda: isinstance(svc.scheduling.evaluator,
                                    LearnedCostEvaluator),
                 "watcher never promoted")
        assert svc.scheduling.evaluator.serving_version == row.version
        # Quarantine the only-ever version: no restorable predecessor.
        manager.quarantine_version("cost", row.version, 0, reason="test")
        wait_for(lambda: isinstance(svc.scheduling.evaluator,
                                    BaseEvaluator),
                 "watcher never demoted to rules")


class TestTrainerCostJob:
    def test_trains_and_registers_from_replay_segments(self, recorded,
                                                       tmp_path):
        """The continuous-learning loop's new job type: replay segments
        streamed to the trainer → (features, realized cost) examples →
        cost model registered as type 'cost'."""
        from dragonfly2_tpu.train import (
            CostTrainConfig,
            GNNTrainConfig,
            MLPTrainConfig,
        )
        from dragonfly2_tpu.trainer import (
            TrainerStorage,
            Training,
            TrainingConfig,
        )

        ts = TrainerStorage(str(tmp_path / "trainer"))
        for path in recorded["storage"].open_replay():
            with open(path, "rb") as f:
                ts.append("replay", "sched-1", f.read(), new_file=True)
        ts.close_host("sched-1")

        registered = {}

        class Registry:
            def create_model(self, model_id, model_type, host_id, ip,
                             hostname, evaluation, artifact_dir,
                             scheduler_id=0):
                import os

                registered[model_type] = {
                    "evaluation": dict(evaluation),
                    "scheduler_id": scheduler_id,
                    "files": sorted(os.listdir(artifact_dir)),
                }

        config = TrainingConfig(
            gnn=GNNTrainConfig(epochs=1), mlp=MLPTrainConfig(epochs=1),
            cost=CostTrainConfig(hidden=(16, 8), epochs=5, batch_size=256))
        outcome = Training(ts, Registry(), config).train(
            "10.0.0.1", "sched1", "sched-1", scheduler_id=9)
        assert outcome.cost_model_id is not None, outcome.errors
        assert set(registered) == {"cost"}  # no download/topology data
        assert registered["cost"]["scheduler_id"] == 9
        assert set(outcome.cost_evaluation) == {"mse", "mae", "n_samples"}
        assert "metadata.json" in registered["cost"]["files"]
        # Trained segments were consumed.
        assert ts.replay_files("sched-1") == []


# ---------------------------------------------------------------------------
# Vectorized replay engine: bit-identity against the sequential harness
# ---------------------------------------------------------------------------


class TestVectorizedReplay:
    def test_recorded_corpus_bit_identical(self, recorded):
        """The ragged real-world case: a recorded swarm corpus replays
        bit-identically through sequential, whole-corpus vectorized and
        sharded fan-out paths — digest, decision sequence AND full
        tie-break order."""
        events = rp.corpus_from_events(recorded["ring"])
        cc = rp.as_columnar(events)
        seq = rp.replay_decisions(events, BaseEvaluator(), seed=0)
        vec = rp.replay_decisions_vectorized(cc, seed=0)
        sh = rp.replay_decisions_vectorized(cc, seed=0, shards=3)
        assert seq.digest == vec.digest == sh.digest
        assert seq.decisions == vec.decisions == sh.decisions
        assert seq.full_order == vec.full_order == sh.full_order
        assert sh.shards == 3 and len(sh.shard_stats) == 3
        assert sum(s["decisions"] for s in sh.shard_stats) == cc.n

    def test_bucket_parity_k1_and_kmax(self, recorded):
        """Padded-bucket edges: every decision truncated to ONE candidate
        (maximum padding) and every decision widened to
        MAX_REPLAY_CANDIDATES via feature-tied clones (zero padding) both
        stay bit-identical to the sequential replay."""
        from dragonfly2_tpu.scheduler.replaystore import bucket_candidates

        # Seq-ordered, as every replay takes its corpus: under load the
        # swarm's four workers finalize decisions out of seq order, the
        # columnar packing orders by seq and the sequential replay keeps
        # the order it is given, so the raw ring's digests differ.
        events = [e for e in rp.corpus_from_events(recorded["ring"])
                  if e.candidates]
        k1 = [dataclasses.replace(e, candidates=list(e.candidates[:1]))
              for e in events]
        kmax = []
        for e in events:
            clones = [dataclasses.replace(
                e.candidates[0], id=f"{e.candidates[0].id}~dup{j}", rank=-1)
                for j in range(MAX_REPLAY_CANDIDATES - len(e.candidates))]
            kmax.append(dataclasses.replace(
                e, candidates=list(e.candidates) + clones))
        for variant, want_k in ((k1, bucket_candidates(1)),
                                (kmax, bucket_candidates(
                                    MAX_REPLAY_CANDIDATES))):
            cc = rp.as_columnar(variant)
            assert cc.k == want_k
            seq = rp.replay_decisions(variant, BaseEvaluator())
            vec = rp.replay_decisions_vectorized(cc)
            assert seq.digest == vec.digest
            assert seq.full_order == vec.full_order
        # What a ring out of seq order is promised: the columnar packing
        # orders by seq, so the ring backwards replays to the digest of
        # the sequential replay of the seq-ordered corpus.
        backwards = rp.replay_decisions_vectorized(
            rp.as_columnar(events[::-1]))
        ordered = rp.replay_decisions(events, BaseEvaluator())
        assert backwards.digest == ordered.digest
        assert backwards.full_order == ordered.full_order

    def test_ties_resolved_in_candidate_order(self):
        """Score ties must break by original candidate position in BOTH
        engines (the sequential harness's stable argsort): tie every
        candidate's features within each decision and check the replayed
        order IS the slot order."""
        from dragonfly2_tpu.scheduler.replaybench import synth_replay_corpus
        from dragonfly2_tpu.scheduler.replaystore import ColumnarCorpus

        cc = synth_replay_corpus(300, seed=7)
        tied = np.ascontiguousarray(
            np.broadcast_to(cc.features[:, :1, :], cc.features.shape)
            * cc.valid[..., None], dtype=np.float32)
        cols = cc.columns()
        cols["features"] = tied
        cc2 = ColumnarCorpus(cols)
        seq = rp.replay_decisions(cc2.decisions(), BaseEvaluator())
        vec = rp.replay_decisions_vectorized(cc2)
        assert seq.digest == vec.digest
        assert seq.full_order == vec.full_order
        for i in range(cc2.n):
            nc = int(cc2.n_candidates[i])
            order = vec.full_order.get(int(cc2.seq[i]))
            if nc and order is not None:
                assert order == tuple(cc2.cand_id[i, :nc].tolist())

    def test_score_run_vectorized_matches_sequential(self, recorded):
        events = rp.corpus_from_events(recorded["ring"])
        cc = rp.as_columnar(events)
        evaluator = BaseEvaluator()
        run = rp.replay_decisions(events, evaluator, name="rule")
        seq_scored = rp.score_run(events, run, evaluator=evaluator)
        vec_scored = rp.score_run_vectorized(
            cc, run, bad_node_verdicts=rp.rule_bad_node_verdicts(cc))
        assert set(seq_scored) == set(vec_scored)
        for key, value in seq_scored.items():
            assert vec_scored[key] == value, key

    def test_bad_node_labels_batch_matches_per_event(self, recorded):
        events = rp.corpus_from_events(recorded["ring"])
        cc = rp.as_columnar(events)
        labels, has_label = rp.bad_node_labels_batch(cc)
        for i, event in enumerate(events):
            want = rp.bad_node_labels(event)
            by_id = {str(cc.cand_id[i, j]): (bool(labels[i, j]),
                                             bool(has_label[i, j]))
                     for j in range(int(cc.n_candidates[i]))}
            for cand_id, is_bad in want.items():
                assert by_id[cand_id] == (is_bad, True)
            assert sum(1 for lab, has in by_id.values() if has) == len(want)

    def test_ml_and_cost_evaluators_vectorized_parity(self, cost_model):
        from dragonfly2_tpu.inference.scorer import (
            LearnedCostEvaluator,
            MLEvaluator,
            ParentScorer,
        )

        result = cost_model["result"]
        scorer = ParentScorer(result.model, result.params,
                              result.normalizer, result.target_norm)
        corpus = cost_model["corpus"]
        cc = rp.as_columnar(corpus)
        for name, make in (
                ("ml", lambda: MLEvaluator(scorer)),
                ("cost", lambda: LearnedCostEvaluator(_cost_scorer(result)))):
            e_seq, e_vec = make(), make()
            seq = rp.replay_decisions(corpus, e_seq, name=name)
            vec = rp.replay_decisions_vectorized(cc, e_vec, name=name)
            assert seq.digest == vec.digest, name
            assert seq.full_order == vec.full_order, name
            assert e_vec.scored_count == e_seq.scored_count > 0, name

    def test_unsupported_evaluator_rejected(self, recorded):
        cc = rp.as_columnar(rp.corpus_from_events(recorded["ring"][:3]))

        class _Weird:
            def evaluate_parents(self, parents, child, total):
                return parents

        with pytest.raises(TypeError):
            rp.replay_decisions_vectorized(cc, _Weird())

    def test_trainers_consume_columnar_corpus_bit_equal(self, cost_model):
        from dragonfly2_tpu.train.cost_trainer import (
            cost_examples_from_corpus,
        )
        from dragonfly2_tpu.train.federated import (
            cluster_datasets_from_corpora,
        )
        from dragonfly2_tpu.train.mlp_trainer import (
            bandwidth_examples_from_corpus,
        )
        from dragonfly2_tpu.scheduler.replaystore import ColumnarCorpus

        corpus = cost_model["corpus"]
        cc = rp.as_columnar(corpus)
        X_seq, y_seq = cost_examples_from_corpus(corpus)
        X_col, y_col = cost_examples_from_corpus(cc)
        assert np.array_equal(X_seq, X_col)
        assert np.array_equal(y_seq, y_col)
        X_bw, y_bw = bandwidth_examples_from_corpus(cc)
        assert np.array_equal(X_bw, X_col)
        assert (y_bw > 0).all()
        datasets = cluster_datasets_from_corpora(
            {3: cc, 9: ColumnarCorpus.from_events([])})
        assert [d.scheduler_id for d in datasets] == [3]
        assert np.array_equal(datasets[0].X, X_bw)
        assert cluster_datasets_from_corpora({}) == []


class TestRecorderBatching:
    def test_commit_is_one_sink_call_per_drain(self):
        calls = []

        class _Sink:
            def create_replay_batch(self, records):
                calls.append(list(records))

        stats = ControlPlaneStats()
        rec = ReplayRecorder(_Sink(), stats=stats)
        staged = [("ready", ReplayDecision(seq=i, verdict="back_to_source"))
                  for i in range(12)]
        rec._commit(staged)
        assert len(calls) == 1 and len(calls[0]) == 12
        assert stats.snapshot()["replay_appends_batched"] == 1
        assert len(rec.events()) == 12
        rec._commit([])
        assert len(calls) == 1, "empty drains must not touch the sink"
        rec.close()

    def test_rung_reports_batched_appends(self, recorded):
        rung = recorded["rung"]
        assert 0 < rung["replay_appends_batched"] <= rung["replay_finalized"]
        assert "replay_appends_batched" in ControlPlaneStats().snapshot()


class TestThroughputLadder:
    def test_rung_report_keys_complete_from_birth(self):
        """Every consumer-read key must exist even on a rung that errors
        before measuring (the bench stage and the regression check index
        into these unconditionally)."""
        from dragonfly2_tpu.scheduler.replaybench import _ladder_rung_report

        report = _ladder_rung_report(10)
        assert {"decisions", "corpus_k", "seq_elapsed_s",
                "seq_decisions_per_s", "vec_elapsed_s",
                "vec_decisions_per_s", "sharded_elapsed_s",
                "sharded_decisions_per_s", "speedup", "sharded_speedup",
                "digests_equal", "digest", "error"} <= set(report)
        assert report["decisions"] == 10
        assert report["error"] is None and report["digests_equal"] is None

    def test_synth_corpus_is_structurally_valid(self, tmp_path):
        from dragonfly2_tpu.scheduler.replaybench import synth_replay_corpus
        from dragonfly2_tpu.scheduler.replaystore import (
            check_corpus,
            write_columns,
        )

        cc = synth_replay_corpus(500, seed=5)
        path = str(tmp_path / "synth.npc")
        write_columns(path, cc.columns())
        report = check_corpus(path)
        assert report["ok"], report["errors"]
        assert report["back_to_source"] > 0

    def test_small_ladder_smoke(self):
        """Tier-1 counters-only smoke: a tiny rung through the full
        ladder machinery — digests must match; the 20x bound is the slow
        battery's business."""
        from dragonfly2_tpu.scheduler.replaybench import (
            run_replay_throughput_ladder,
        )

        report = run_replay_throughput_ladder(rungs=(400,), bound=0.0)
        assert report["error"] is None
        assert report["verdict_pass"] is True, report
        rung = report["rungs"][0]
        assert rung["error"] is None
        assert rung["digests_equal"] is True
        assert rung["decisions"] == 400
        assert rung["vec_decisions_per_s"] > 0
        assert rung["sharded_decisions_per_s"] > 0


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------


class TestSnapshots:
    def test_snapshot_mean(self):
        assert snapshot_mean((0, 0.0, 0.0, 0.0)) == -1.0
        assert snapshot_mean((1, 2.0, 0.0, 0.0)) == 2.0
        assert snapshot_mean((3, 3.0, 1.5, 0.1)) == pytest.approx(2.0)

    def test_welford_snapshot_duck_typed(self):
        class _P:
            def piece_costs(self):
                return [1.0, 2.0, 3.0]

        n, last, mean, pstd = welford_snapshot(_P())
        assert (n, last) == (3, 3.0)
        assert mean == pytest.approx(1.5)


# ---------------------------------------------------------------------------
# Slow: the full bench stage + overhead guard
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.replay
class TestReplayStageE2E:
    def test_stage_green(self):
        from dragonfly2_tpu.scheduler.replaybench import run_replay_ab

        report = run_replay_ab(record_peers=300, overhead_guard=False)
        assert report.get("error") is None, report
        assert report["ab"]["deterministic"] is True
        assert all(g["state"] == "active"
                   for g in report["gate"].values()), report["gate"]
        assert report["regret_within_bound"] == {"ml": True, "cost": True}

    def test_recorder_overhead_guard(self):
        from dragonfly2_tpu.scheduler.loadbench import (
            run_recorder_overhead_guard,
        )

        guard = run_recorder_overhead_guard()
        assert guard["within_bound"], guard


@pytest.mark.slow
@pytest.mark.replay
class TestThroughputLadderE2E:
    def test_full_ladder_green(self):
        """The documented bound: vectorized >= 20x sequential on the
        100k rung, bit-identical digests on every rung."""
        from dragonfly2_tpu.scheduler.replaybench import (
            LADDER_RUNGS,
            VECTORIZED_SPEEDUP_BOUND,
            run_replay_throughput_ladder,
        )

        report = run_replay_throughput_ladder()
        assert report["verdict_pass"] is True, report
        assert [r["decisions"] for r in report["rungs"]] == list(LADDER_RUNGS)
        assert all(r["digests_equal"] for r in report["rungs"])
        top = report["rungs"][-1]
        assert top["speedup"] >= VECTORIZED_SPEEDUP_BOUND, top

    def test_check_regression_fails_on_synthetic_throughput_collapse(
            self, tmp_path):
        """Acceptance case: seed the state dir with a fabricated best
        ladder record claiming absurd throughput — the fresh re-measure
        cannot hold 0.33x of it, so the gate must go red."""
        import json as _json

        from dragonfly2_tpu.scheduler.replaybench import (
            check_replay_regression,
        )

        fake = {
            "rungs": [{"decisions": 10_000, "corpus_k": 16,
                       "vec_decisions_per_s": 1e12, "speedup": 1e9,
                       "digests_equal": True, "error": None}],
            "bound": 20.0, "bound_rung": 10_000, "shards": 2,
            "verdict_pass": True, "error": None,
        }
        with open(tmp_path / "replay_ladder_run_20990101_000000.json",
                  "w") as f:
            _json.dump(fake, f)
        result = check_replay_regression(str(tmp_path))
        assert result["ladder_throughput_ok"] is False
        assert result["passed"] is False
        assert result["best_recorded_ladder"]["rungs"] == fake["rungs"]
        # The fresh rung itself stayed healthy — only the relative
        # throughput floor failed.
        assert result["ladder_digests_ok"] is True
