"""Batched jit parent scorer — the <1 ms p50 scheduling-loop hot path.

Design for latency (SURVEY.md §7 hard parts):
- **No per-request compilation**: forwards are jit-compiled once per padded
  batch bucket (powers of two up to ``max_batch``) at construction; a
  request pads to the smallest bucket, so every call hits the compile
  cache.
- **Static shapes end-to-end**: the scheduler's candidate sets are already
  bounded (filterParentLimit=15 in the reference, constants.go:33-37), so
  buckets stay tiny; padding rows are zero and sliced off after.
- **One host→device→host round trip** per call: features are assembled
  host-side (numpy, <100 µs for 15 candidates), shipped once, scored in a
  single fused kernel (normalize → 4 matmuls → denorm), result copied back.

The scorer also powers :class:`MLEvaluator` — the ``ml`` algorithm of the
evaluator factory (reference left it falling through to rules,
evaluator.go:48-49) — with rule-based fallback when no model is loaded,
matching the reference's degradation path.
"""

from __future__ import annotations

import collections
import logging
import threading
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from dragonfly2_tpu.inference.batcher import BatcherSaturatedError
from dragonfly2_tpu.inference.modelguard import guard_reason
from dragonfly2_tpu.models.mlp import MLPBandwidthPredictor, Normalizer
from dragonfly2_tpu.scheduler.evaluator.base import (
    _BAD_STATES,
    MIN_AVAILABLE_COST_LEN,
    PEER_STATE_RECEIVED_NORMAL,
    PEER_STATE_RUNNING,
    BaseEvaluator,
    PeerLike,
    build_feature_matrix,
)
from dragonfly2_tpu.scheduler.evaluator.scoring import FEATURE_DIM, pack_features


def _buckets(max_batch: int) -> list[int]:
    out, b = [], 8
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return out


class ScoreHandle:
    """An in-flight dispatch: the un-materialized device result plus the
    valid row count. ``materialize`` blocks on the device and slices the
    padding off — callers that want stage/dispatch overlap (the
    double-buffered :class:`~dragonfly2_tpu.inference.batcher.MicroBatcher`)
    hold the handle while they assemble the next batch and only block
    when they actually need the numbers."""

    __slots__ = ("_out", "_n", "bucket")

    def __init__(self, out, n: int, bucket: int):
        self._out = out
        self._n = n
        self.bucket = bucket

    def materialize(self) -> np.ndarray:
        # np.asarray is the synchronization point: jax dispatch is async
        # on every backend, so this is where the host actually waits.
        return np.asarray(self._out)[: self._n]


class _StagingBuffers:
    """Preallocated zeroed host buffers per jit bucket, ``depth`` deep
    (default 2: double-buffered for one pipelined worker).

    Kills the per-call ``np.zeros`` + copy churn on the hot path: a
    request writes its rows into a preallocated buffer and only re-zeros
    the rows the previous occupant dirtied. Two buffers per bucket let
    the pipelined batcher (one dispatch in flight while the next is
    staged) never wait; a LANE-SHARDED batcher (N workers, each with its
    own in-flight slot) grows the pool to ``2 × lanes`` via
    ``ensure_depth`` so concurrent lanes keep the same no-wait property.

    Safety: jax's host→device transfer is ASYNC — the dispatch can
    return before the input buffer has been snapshotted (observed as
    torn batches under CPU contention), so a slot must not be refilled
    while the dispatch that used it may still read it. Each claim
    therefore blocks on the slot's previous dispatch (``commit`` records
    it); by the time that output is ready the input has long been
    consumed. With ``depth ≥ 2 × in-flight dispatchers`` this never
    actually blocks — slot K's previous dispatch was retired long ago;
    only an over-subscribed pool (more direct concurrent callers than
    depth in one bucket) serializes here.
    A PER-BUCKET lock covers claim+fill+dispatch+commit (so a stalled
    bucket never blocks scoring in the others); materialization happens
    outside it.
    """

    def __init__(self, buckets: Sequence[int], make, depth: int = 2):
        self._make = make
        self._locks = {b: threading.Lock() for b in buckets}
        self._bufs = {b: [make(b) for _ in range(depth)] for b in buckets}
        self._flip = {b: 0 for b in buckets}
        self._dirty = {b: [0] * depth for b in buckets}
        self._pending = {b: [None] * depth for b in buckets}

    @property
    def depth(self) -> int:
        return len(next(iter(self._bufs.values())))

    def ensure_depth(self, depth: int) -> None:
        """Grow every bucket's pool to at least ``depth`` slots. Growing
        only appends fresh zeroed buffers under the bucket lock — slots
        already committed to in-flight dispatches keep their guards, so
        this is safe while the scorer is serving."""
        for b, lock in self._locks.items():
            with lock:
                for _ in range(len(self._bufs[b]), depth):
                    self._bufs[b].append(self._make(b))
                    self._dirty[b].append(0)
                    self._pending[b].append(None)

    def lock_for(self, bucket: int) -> threading.Lock:
        return self._locks[bucket]

    def claim(self, bucket: int, n: int) -> tuple:
        """Under ``lock_for(bucket)``: (slot, buffer) for ``bucket`` with
        rows ``n:`` guaranteed zero and no dispatch still reading it."""
        i = self._flip[bucket]
        self._flip[bucket] = (i + 1) % len(self._bufs[bucket])
        pending = self._pending[bucket][i]
        if pending is not None:
            self._pending[bucket][i] = None
            try:
                pending.block_until_ready()
            except Exception:  # noqa: BLE001 — a failed dispatch can't
                pass           # be reading the buffer either
        buf = self._bufs[bucket][i]
        if self._dirty[bucket][i] > n:
            buf[n:self._dirty[bucket][i]] = 0
        self._dirty[bucket][i] = n
        return i, buf

    def commit(self, bucket: int, slot: int, out) -> None:
        """Under ``self.lock``: record the dispatch that now owns the
        slot's buffer contents."""
        self._pending[bucket][slot] = out


class ParentScorer:
    """Persistent compiled scorer over a trained bandwidth predictor."""

    def __init__(
        self,
        model: MLPBandwidthPredictor,
        params,
        normalizer: Normalizer,
        target_norm: Normalizer,
        max_batch: int = 64,
        device=None,
        staging_depth: int = 2,
    ):
        self._device = device or jax.devices()[0]
        self._params = jax.device_put(params, self._device)
        mean = jax.device_put(jnp.asarray(normalizer.mean), self._device)
        std = jax.device_put(jnp.asarray(normalizer.std), self._device)
        t_mean = float(target_norm.mean[0])
        t_std = float(target_norm.std[0])

        def forward(params, x):
            # Score = predicted log-bandwidth (monotone in MB/s — ranking
            # only needs the standardized output, but we denormalize so
            # scores are interpretable and comparable across model
            # versions).
            out = model.apply(params, (x - mean) / std)
            return out * t_std + t_mean

        self._forward = jax.jit(forward)
        self.buckets = _buckets(max_batch)
        self.max_batch = max_batch
        self._staging = _StagingBuffers(
            self.buckets, lambda b: np.zeros((b, FEATURE_DIM), np.float32),
            depth=max(staging_depth, 2))
        # Warm the compile cache for every bucket now — first-request
        # latency must not include XLA compilation.
        for b in self.buckets:
            self._forward(self._params, jnp.zeros((b, FEATURE_DIM))).block_until_ready()

    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"batch {n} exceeds max_batch {self.max_batch}")

    def ensure_staging_depth(self, depth: int) -> None:
        """Grow the per-bucket staging pool to at least ``depth`` slots —
        a lane-sharded batcher needs 2 buffers per concurrently
        pipelining lane so the completion guard never blocks."""
        self._staging.ensure_depth(max(depth, 2))

    def score_async(self, features: np.ndarray) -> ScoreHandle:
        """Stage ``[n, FEATURE_DIM]`` features into a preallocated bucket
        buffer and dispatch; returns without waiting for the device. The
        handle's ``materialize()`` blocks and yields the ``[n]`` scores."""
        n = len(features)
        if n == 0:
            # Same contract as score(): empty in, empty out, no device
            # dispatch for a batch with nothing in it.
            return ScoreHandle(np.zeros(0, np.float32), 0, self.buckets[0])
        b = self._bucket(n)
        with self._staging.lock_for(b):
            slot, buf = self._staging.claim(b, n)
            buf[:n] = features
            out = self._forward(self._params, buf)
            self._staging.commit(b, slot, out)
        return ScoreHandle(out, n, b)

    def score(self, features: np.ndarray) -> np.ndarray:
        """Scores for [n, FEATURE_DIM] features; higher is better."""
        if len(features) == 0:
            return np.zeros(0, np.float32)
        return self.score_async(features).materialize()

    def score_corpus(self, features: np.ndarray,
                     chunk: int = 4096) -> np.ndarray:
        """Corpus-scale scoring: [n, FEATURE_DIM] rows of ANY n, chunked
        through one fixed zero-padded jit shape (the same pow2-bucket
        zero-pad discipline as the staging pool, sized for offline
        batches instead of announce batches).

        Per-row outputs are BIT-IDENTICAL to :meth:`score` on any
        sub-batch containing the row — the jit forward is row-stable on
        this backend (row i never depends on batch shape or the zero
        rows padding it), which is what lets the vectorized replay
        engine keep the sequential harness's run digest. Owns its own
        buffer (no staging-pool interaction), so concurrent shard
        workers can call it freely.
        """
        feats = np.ascontiguousarray(features, dtype=np.float32)
        n = len(feats)
        if n == 0:
            return np.zeros(0, np.float32)
        b = 8
        while b < min(chunk, n):
            b *= 2
        buf = np.zeros((b, FEATURE_DIM), np.float32)
        out = np.empty(n, np.float32)
        dirty = 0
        for start in range(0, n, b):
            m = min(b, n - start)
            if dirty > m:
                buf[m:dirty] = 0
            buf[:m] = feats[start:start + m]
            dirty = m
            out[start:start + m] = np.asarray(
                self._forward(self._params, buf))[:m]
        return out

    def benchmark(self, batch: int = 16, iters: int = 200) -> dict:
        """Measure steady-state scoring latency; returns percentiles in ms."""
        rng = np.random.default_rng(0)
        feats = rng.uniform(0, 100, (batch, FEATURE_DIM)).astype(np.float32)
        self.score(feats)  # warm
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            self.score(feats)
            times.append((time.perf_counter() - t0) * 1e3)
        times.sort()
        return {
            "p50_ms": times[len(times) // 2],
            "p95_ms": times[int(len(times) * 0.95)],
            "p99_ms": times[int(len(times) * 0.99)],
        }


class MLEvaluator:
    """The ``ml`` evaluator algorithm (fills evaluator.go:48's TODO).

    Ranks parents by predicted bandwidth from the TPU scorer; keeps the
    rule-based evaluator for bad-node detection (a statistical property of
    observed piece costs, not a learned one) and as fallback when scoring
    fails.

    Every score batch passes the runtime guard before it ranks anything
    (:func:`~dragonfly2_tpu.inference.modelguard.guard_reason`): a
    NaN/Inf or collapsed-constant batch degrades THAT decision to rule
    scoring and ticks ``ml_guard_trips``; after ``guard_trip_limit``
    trips the evaluator escalates ONCE through ``on_quarantine`` — the
    hook owner quarantines the serving version back to the manager,
    whose rollback the sidecar watcher picks up fleet-wide on its next
    poll. ``reset_guard()`` re-arms the escalation latch after a model
    swap. A loadable-but-poisoned model is therefore a non-event: no
    poisoned batch ever orders parents, and the fleet converges back to
    the previous good version without an operator in the loop.
    """

    def __init__(self, scorer: ParentScorer | None, *,
                 stats=None, guard_trip_limit: int = 3,
                 on_quarantine=None, trace_log=None,
                 track_quality: bool = False):
        from dragonfly2_tpu.utils.servingstats import SERVING

        self._scorer = scorer
        self._fallback = BaseEvaluator()
        # Operators must be able to tell "model live" from "model silently
        # failing": count scores and fallbacks, log the first failure loudly.
        # Sheds (BatcherSaturatedError — the batcher's bounded-admission
        # fail-fast) are counted separately from failures: a saturated
        # serving plane degrading to rule scoring is expected overload
        # behavior, not a fault, so it is never exception-logged.
        self.scored_count = 0
        self.fallback_count = 0
        self.shed_count = 0
        self.guard_trips = 0
        self._logged_failure = False
        self._logged_guard = False
        self._stats = stats if stats is not None else SERVING
        self.guard_trip_limit = guard_trip_limit
        self._on_quarantine = on_quarantine
        self._quarantine_fired = False
        # Version the guard state belongs to: when a version-aware
        # scorer (the remote one stamps last_version from each reply)
        # starts serving a DIFFERENT version, trips and the escalation
        # latch auto-reset — a fresh version starts with a clean slate
        # and may escalate again. Versionless scorers rely on the owner
        # calling reset_guard() at swap time.
        self._guard_version: str | None = None
        # Guard bookkeeping is mutated from CONCURRENT announce threads
        # (gRPC pool): the trip counter's read-modify-write and the
        # escalate-once check-then-act need a lock or two threads at
        # limit-1 lose an increment / double-fire the quarantine RPC.
        # The hook itself runs OUTSIDE the lock (it's an RPC);
        # _quarantine_inflight keeps a second thread from duplicating it
        # meanwhile.
        self._guard_lock = threading.Lock()
        self._quarantine_inflight = False
        # Optional announce-trace recorder (validation.TraceLog): the
        # gate's replay corpus is captured here, on the live path.
        self._trace_log = trace_log
        # Optional decision-quality ring: per decision, the rule score
        # of the CHOSEN top parent normalized into [0, 1] against the
        # rule evaluator's own best/worst over the same candidates
        # (1.0 == the rule baseline's pick). The mlguard bench rung
        # bounds its minimum; off by default to keep the hot path lean.
        self.track_quality = track_quality
        self.quality_samples: collections.deque = collections.deque(
            maxlen=4096)

    @property
    def has_model(self) -> bool:
        return self._scorer is not None

    def reset_guard(self) -> None:
        """Re-arm the guard after a model swap: a fresh version starts
        with a clean trip count and may escalate again."""
        with self._guard_lock:
            self._reset_guard_locked()

    def _reset_guard_locked(self) -> None:
        self.guard_trips = 0
        self._quarantine_fired = False
        self._logged_guard = False

    def set_quarantine_hook(self, fn) -> None:
        """Late-bind the escalation hook (the scheduler CLI builds the
        evaluator before its manager client exists)."""
        self._on_quarantine = fn

    def set_trace_log(self, trace_log) -> None:
        """Late-bind the announce-trace recorder (validation.TraceLog)."""
        self._trace_log = trace_log

    def _record_quality(self, features: np.ndarray, chosen: int) -> None:
        if not self.track_quality:
            return
        from dragonfly2_tpu.scheduler.evaluator import scoring

        rule = np.asarray(scoring.rule_scores(features), dtype=np.float64)
        lo, hi = float(rule.min()), float(rule.max())
        q = 1.0 if hi - lo <= 1e-12 else (float(rule[chosen]) - lo) / (hi - lo)
        self.quality_samples.append(q)

    def _guard_trip(self, reason: str) -> None:
        with self._guard_lock:
            self.guard_trips += 1
            log_first = not self._logged_guard
            self._logged_guard = True
            escalate = (self.guard_trips >= self.guard_trip_limit
                        and not self._quarantine_fired
                        and not self._quarantine_inflight
                        and self._on_quarantine is not None)
            if escalate:
                self._quarantine_inflight = True
        self._stats.tick("ml_guard_trips")
        if log_first:
            logging.getLogger(__name__).error(
                "ML score batch rejected by runtime guard (%s); decision "
                "fell back to rule scoring (further trips counted, not "
                "logged)", reason)
        if not escalate:
            return
        # Latch only on a DELIVERED escalation: a transient manager
        # outage (or a hook returning False — "couldn't act yet", e.g.
        # no serving version known) leaves the latch unarmed so the
        # next trip retries instead of silently abandoning the
        # fleet-wide rollback. The hook runs outside the lock; the
        # inflight flag keeps concurrent trips from duplicating it.
        delivered = False
        try:
            delivered = self._on_quarantine(reason) is not False
        except Exception:  # noqa: BLE001 — escalation must never
            logging.getLogger(__name__).exception(
                "model quarantine escalation failed; will retry on "
                "the next guard trip")
        with self._guard_lock:
            self._quarantine_inflight = False
            if delivered:
                self._quarantine_fired = True
        if delivered:
            self._stats.tick("ml_quarantines_reported")

    def close(self) -> None:
        """Release the scorer if it owns resources (a micro-batcher's
        worker thread); scorers without a close are left alone. The
        evaluator owner calls this on teardown/model swap."""
        close = getattr(self._scorer, "close", None)
        if close is not None:
            close()

    def evaluate_parents(
        self, parents: Sequence[PeerLike], child: PeerLike, total_piece_count: int
    ) -> list[PeerLike]:
        if not parents:
            return []
        if self._scorer is None:
            return self._fallback.evaluate_parents(parents, child, total_piece_count)
        # One-pass fill into a fresh matrix (value-identical to stacking
        # pair_features rows). Fresh, not staged: the micro-batcher may
        # hold the rows across an async dispatch window.
        features = build_feature_matrix(parents, child, total_piece_count)
        if self._trace_log is not None:
            self._trace_log.record(features)
        try:
            scores = self._scorer.score(features)
        except BatcherSaturatedError:
            self.shed_count += 1
            self.fallback_count += 1
            self._stats.tick("ml_sheds")
            self._stats.tick("ml_fallbacks")
            ranked = self._fallback.evaluate_parents(
                parents, child, total_piece_count)
            if self.track_quality:
                self._record_quality(features, parents.index(ranked[0]))
            return ranked
        except Exception:
            self.fallback_count += 1
            self._stats.tick("ml_fallbacks")
            if not self._logged_failure:
                self._logged_failure = True
                logging.getLogger(__name__).exception(
                    "ML parent scoring failed; falling back to rule-based "
                    "evaluation (further failures counted, not logged)"
                )
            ranked = self._fallback.evaluate_parents(
                parents, child, total_piece_count)
            if self.track_quality:
                self._record_quality(features, parents.index(ranked[0]))
            return ranked
        version = getattr(self._scorer, "last_version", "")
        if version:
            with self._guard_lock:
                if version != self._guard_version:
                    if self._guard_version is not None:
                        self._reset_guard_locked()
                    self._guard_version = version
        reason = guard_reason(scores, features=features)
        if reason is not None:
            # The poisoned batch never orders anything: this decision is
            # the rule evaluator's, and the trip is counted/escalated.
            self.fallback_count += 1
            self._stats.tick("ml_fallbacks")
            self._guard_trip(reason)
            ranked = self._fallback.evaluate_parents(
                parents, child, total_piece_count)
            if self.track_quality:
                self._record_quality(features, parents.index(ranked[0]))
            return ranked
        self.scored_count += 1
        self._stats.tick("ml_scored")
        order = np.argsort(-scores, kind="stable")
        self._record_quality(features, int(order[0]))
        return [parents[i] for i in order]

    def is_bad_node(self, peer: PeerLike) -> bool:
        return self._fallback.is_bad_node(peer)


class CostScorer:
    """Ranking/threshold facade over a trained piece-cost predictor.

    Wraps the plain :class:`ParentScorer` jit machinery (whose raw
    output for a ``cost``-type checkpoint is the denormalized predicted
    ``log1p(cost_seconds)``) with the two views consumers need:
    ``score`` negates the prediction so HIGHER still means BETTER parent
    (the ``evaluate_parents`` contract every evaluator shares), and
    ``predict_cost_s`` maps back to seconds for the learned bad-node
    threshold. ``version`` carries the registry version the artifact was
    promoted under — the gate-provenance stamp the evaluator reports.
    ``typical_cost_s`` is the training corpus's typical piece cost
    (``expm1`` of the checkpoint's target-normalizer mean) — the
    calibrated absolute baseline the learned bad-node threshold uses for
    consistently-slow peers, whose own prediction is correctly high."""

    def __init__(self, scorer: ParentScorer, version: str = "",
                 typical_cost_s: float = 0.0):
        self._scorer = scorer
        self.version = version
        self.typical_cost_s = typical_cost_s
        self.max_batch = scorer.max_batch

    def predict_cost_s(self, features: np.ndarray) -> np.ndarray:
        # Clip before expm1: an out-of-distribution feature row must
        # produce a large-but-finite cost, not an overflow inf that
        # reads as a poisoned model. NaN passes through for the guard.
        return np.expm1(np.clip(self._scorer.score(features), -20.0, 20.0))

    def score(self, features: np.ndarray) -> np.ndarray:
        return -self._scorer.score(features)

    def score_corpus(self, features: np.ndarray,
                     chunk: int = 4096) -> np.ndarray:
        """Corpus-scale :meth:`score`: the same negation over the
        underlying scorer's row-stable chunked forward — bit-identical
        per row to ``score`` on any sub-batch."""
        return -self._scorer.score_corpus(features, chunk=chunk)

    def close(self) -> None:
        close = getattr(self._scorer, "close", None)
        if close is not None:
            close()


class LearnedCostEvaluator:
    """The ``cost`` evaluator algorithm — learned piece-cost ranking +
    a learned ``is_bad_node`` seam replacing the 3-sigma threshold
    (docs/REPLAY.md).

    Ranking: candidates order by ASCENDING predicted cost (the
    :class:`CostScorer` negation keeps the shared higher-is-better
    contract). Bad-node: a peer whose LATEST observed piece cost exceeds
    ``bad_cost_ratio`` x its feature-predicted cost is bad — an absolute
    threshold that catches a peer that has been consistently terrible
    from its first sample, which the relative 3-sigma rule structurally
    cannot (its own history IS the baseline).

    Guard discipline mirrors :class:`MLEvaluator`: every score batch and
    every bad-node prediction passes :func:`~dragonfly2_tpu.inference.
    modelguard.guard_reason` first; a tripped batch degrades THAT
    decision to the inner (rule) evaluator and ticks
    ``cost_guard_trips`` in the scheduler /debug/vars block — a
    poisoned cost model never orders parents and never condemns peers.

    The bad-node baseline is ``min(predicted cost for THIS peer's
    features, corpus-typical cost)``: the per-peer prediction catches a
    peer performing worse than its features explain (a sudden stall),
    while the calibrated typical cost catches a peer that has been
    consistently terrible from its first sample — which the relative
    3-sigma rule structurally cannot (its own history IS its baseline)
    and which a per-peer prediction alone also cannot (an accurate
    model predicts a slow host's slowness and would excuse it).
    """

    def __init__(self, cost_scorer: CostScorer, *, inner=None,
                 stats=None, bad_cost_ratio: float = 3.0,
                 min_predicted_cost_s: float = 1e-4,
                 bad_node_cache_size: int = 65536):
        from dragonfly2_tpu.scheduler import controlstats

        self._scorer = cost_scorer
        self._inner = inner if inner is not None else BaseEvaluator()
        self._stats = stats if stats is not None else controlstats.STATS
        self.bad_cost_ratio = bad_cost_ratio
        # Floor under the predicted cost so a near-zero prediction can't
        # turn every measured cost into a "bad" verdict.
        self.min_predicted_cost_s = min_predicted_cost_s
        self.scored_count = 0
        self.fallback_count = 0
        self.guard_trips = 0
        self._logged_failure = False
        # is_bad_node verdict cache keyed by (peer id, windowed sample
        # count, latest cost): the filter hot loop calls is_bad_node
        # once per CANDIDATE per announce, and each miss is a single-row
        # jit dispatch — without the cache a 15-candidate filter pays
        # ~15 sequential device round trips per announce. A peer's
        # verdict only changes when a new cost lands (the key changes),
        # so steady-state filters are dict hits. Bounded: cleared on
        # overflow (cheap; verdicts rebuild on demand).
        self._bad_node_cache: dict = {}
        self._bad_node_cache_size = bad_node_cache_size

    @property
    def serving_version(self) -> str:
        return getattr(self._scorer, "version", "")

    def close(self) -> None:
        close = getattr(self._scorer, "close", None)
        if close is not None:
            close()

    def _fallback_ranked(self, parents, child, total_piece_count):
        self.fallback_count += 1
        self._stats.observe_cost_fallback()
        return self._inner.evaluate_parents(parents, child,
                                            total_piece_count)

    def evaluate_parents(
        self, parents: Sequence[PeerLike], child: PeerLike, total_piece_count: int
    ) -> list[PeerLike]:
        if not parents:
            return []
        features = build_feature_matrix(parents, child, total_piece_count)
        try:
            scores = self._scorer.score(features)
        except Exception:
            if not self._logged_failure:
                self._logged_failure = True
                logging.getLogger(__name__).exception(
                    "learned-cost scoring failed; falling back to the "
                    "inner evaluator (further failures counted, not "
                    "logged)")
            return self._fallback_ranked(parents, child, total_piece_count)
        reason = guard_reason(scores, features=features)
        if reason is not None:
            self.guard_trips += 1
            self._stats.observe_cost_guard_trip()
            return self._fallback_ranked(parents, child, total_piece_count)
        self.scored_count += 1
        order = np.argsort(-scores, kind="stable")
        return [parents[i] for i in order]

    def is_bad_node(self, peer: PeerLike) -> bool:
        from dragonfly2_tpu.scheduler.replaylog import welford_snapshot

        state = peer.state()
        if state in _BAD_STATES:
            return True
        n, last, _, _ = welford_snapshot(peer)
        if n < MIN_AVAILABLE_COST_LEN:
            return False
        # The lifetime-append counter (when the stats carry one) marks
        # every new cost even when the window is full AND the new cost
        # equals the previous latest — (peer.id, n, last) alone would
        # pin a stale verdict on a constant-rate link forever.
        stats_of = getattr(peer, "piece_cost_stats", None)
        marker = (getattr(stats_of(), "appends", n)
                  if stats_of is not None else n)
        cache_key = (peer.id, marker, last)
        cached = self._bad_node_cache.get(cache_key)
        if cached is not None:
            self._stats.observe_bad_node_learned(bad=cached)
            return cached
        host = peer.host
        is_seed = bool(getattr(host.type, "is_seed", bool(host.type)))
        # The peer judged AS a parent against a fresh child of its own
        # task (the common announce-time pairing, so the row stays in
        # the training distribution): the prediction is "what should a
        # piece from this peer cost".
        total = getattr(getattr(peer, "task", None), "total_piece_count", 0)
        row = pack_features(
            parent_finished_pieces=peer.finished_piece_count(),
            child_finished_pieces=0,
            total_pieces=total,
            upload_count=host.upload_count,
            upload_failed_count=host.upload_failed_count,
            free_upload_count=host.free_upload_count(),
            concurrent_upload_limit=host.concurrent_upload_limit,
            is_seed=is_seed,
            seed_ready=is_seed and state in (PEER_STATE_RECEIVED_NORMAL,
                                             PEER_STATE_RUNNING),
        )[None, :]
        try:
            predicted = float(self._scorer.predict_cost_s(row)[0])
        except Exception:
            self._stats.observe_cost_fallback()
            return self._inner.is_bad_node(peer)
        if guard_reason(np.asarray([predicted])) is not None:
            self.guard_trips += 1
            self._stats.observe_cost_guard_trip()
            return self._inner.is_bad_node(peer)
        # Positive baselines only: a nonpositive prediction (an
        # out-of-distribution row pushed the regressor below zero after
        # expm1) carries no per-peer signal and must not collapse the
        # threshold to the floor — the calibrated typical cost stands
        # in alone.
        typical = getattr(self._scorer, "typical_cost_s", 0.0)
        positives = [v for v in (predicted, typical) if v > 0]
        baseline = min(positives) if positives else self.min_predicted_cost_s
        bad = last > self.bad_cost_ratio * max(baseline,
                                               self.min_predicted_cost_s)
        if len(self._bad_node_cache) >= self._bad_node_cache_size:
            self._bad_node_cache.clear()
        self._bad_node_cache[cache_key] = bad
        self._stats.observe_bad_node_learned(bad=bad)
        return bad


class GATParentScorer:
    """Pair scorer over a trained GraphTransformer (config #3).

    The expensive full-graph attention runs ONCE at construction —
    ``node_embeddings`` over the checkpointed padded features/neighbor
    lists — leaving an [N, E] table on device. Every request is then a
    [n, 2] host-index gather + the tiny edge head: the same
    bucketed-jit/zero-pad recipe as :class:`ParentScorer`, so serving
    latency is head-MLP-sized regardless of graph size.
    """

    def __init__(self, model, params, node_features, neighbors,
                 neighbor_vals, max_batch: int = 64, device=None,
                 node_ids=None, staging_depth: int = 2):
        self._device = device or jax.devices()[0]
        self._params = jax.device_put(params, self._device)
        self.n_nodes = int(np.asarray(node_features).shape[0])
        # Host-ID → embedding-row translation (checkpoint node_ids are
        # the REAL rows in training order). Index validation uses the
        # REAL count when ids ship — a padded phantom row would pass a
        # padded-count check and return a plausible-looking garbage
        # logit from an all-zero embedding.
        self.node_ids = list(node_ids) if node_ids is not None else None
        self.n_real = (len(self.node_ids) if self.node_ids is not None
                       else self.n_nodes)
        self._id_index = ({h: i for i, h in enumerate(self.node_ids)}
                          if self.node_ids is not None else None)
        # One full-graph pass, jitted like the trainer's eval pass: run
        # op by op, gather attention's [N, K, heads] float32
        # intermediates pad 4 lanes to 128 and a 50,000-host fleet does
        # not leave room for anything else on a 16 GB chip (PERF.md,
        # PR 25). Block until the table is resident.
        def embed(p, feats, nbr, val):
            return model.apply(p, feats, nbr, val,
                               method=type(model).node_embeddings)

        self._emb = jax.jit(embed)(*jax.device_put(
            (self._params, np.asarray(node_features), np.asarray(neighbors),
             np.asarray(neighbor_vals)), self._device))
        self._emb.block_until_ready()

        def forward(p, emb, src, dst):
            return model.apply(p, emb, src, dst,
                               method=type(model).score_pairs)

        self._forward = jax.jit(forward)
        self.buckets = _buckets(max_batch)
        self.max_batch = max_batch
        # Separate src/dst staging (the forward takes two flat [b] index
        # vectors; a [b, 2] buffer would force a strided copy per call).
        self._staging_src = _StagingBuffers(
            self.buckets, lambda b: np.zeros(b, np.int32),
            depth=max(staging_depth, 2))
        self._staging_dst = _StagingBuffers(
            self.buckets, lambda b: np.zeros(b, np.int32),
            depth=max(staging_depth, 2))
        for b in self.buckets:
            zero = jnp.zeros(b, jnp.int32)
            self._forward(self._params, self._emb, zero,
                          zero).block_until_ready()

    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"batch {n} exceeds max_batch {self.max_batch}")

    def ensure_staging_depth(self, depth: int) -> None:
        """Grow both (src, dst) staging pools for lane-sharded serving;
        see :meth:`ParentScorer.ensure_staging_depth`."""
        self._staging_src.ensure_depth(max(depth, 2))
        self._staging_dst.ensure_depth(max(depth, 2))

    def score_async(self, pairs: np.ndarray) -> ScoreHandle:
        """Stage validated [n, 2] (src, dst) host-index pairs and
        dispatch without waiting for the device."""
        pairs = np.asarray(pairs)
        n = len(pairs)
        if n == 0:
            return ScoreHandle(np.zeros(0, np.float32), 0, self.buckets[0])
        if pairs.ndim != 2 or pairs.shape[1] != 2:
            raise ValueError(f"expected [n, 2] host-index pairs, "
                             f"got {pairs.shape}")
        if (pairs < 0).any() or (pairs >= self.n_real).any():
            raise ValueError("host index out of range for the "
                             f"{self.n_real}-host embedding table")
        b = self._bucket(n)
        # src-then-dst lock order (always) for the claim+fill+dispatch
        # window so the two vectors stay paired under concurrent callers.
        with self._staging_src.lock_for(b), self._staging_dst.lock_for(b):
            si, src = self._staging_src.claim(b, n)
            di, dst = self._staging_dst.claim(b, n)
            src[:n] = pairs[:, 0]
            dst[:n] = pairs[:, 1]
            out = self._forward(self._params, self._emb, src, dst)
            self._staging_src.commit(b, si, out)
            self._staging_dst.commit(b, di, out)
        return ScoreHandle(out, n, b)

    def score(self, pairs: np.ndarray) -> np.ndarray:
        """Edge logits for [n, 2] (src, dst) host indices; higher is a
        better parent edge."""
        if len(pairs) == 0:
            return np.zeros(0, np.float32)
        return self.score_async(pairs).materialize()

    def index_of(self, host_id: str):
        """Embedding-row index for a host ID, or None when the host was
        not in the training graph (callers fall back to rules)."""
        if self._id_index is None:
            return None
        return self._id_index.get(host_id)

    def score_host_pairs(self, id_pairs) -> np.ndarray:
        """Edge logits for [(src_host_id, dst_host_id), ...]; raises
        KeyError on hosts outside the training graph."""
        if self._id_index is None:
            raise ValueError("checkpoint carries no node_ids")
        pairs = np.array([[self._id_index[a], self._id_index[b]]
                          for a, b in id_pairs], np.int32).reshape(-1, 2)
        return self.score(pairs)
