"""Concurrent-load latency measurement for the colocated scorer path.

Measures the number the <1 ms parent-select target is about: a
scheduler process colocated with its inference sidecar, with N scheduler
threads concurrently pushing parent-selection requests through the
:class:`MicroBatcher` (the serving path a real deployment uses —
reference integration point
scheduler/scheduling/evaluator/evaluator.go:48). Raw per-request
latencies are reported alongside a view with the caller-measured
dispatch floor (a blocking no-op device round trip) subtracted.

Since the batcher went pipelined (stage batch N+1 while N executes) and
then lane-sharded with bounded admission, the report also carries the
pipeline counters — in-flight depth, the stage/dispatch overlap ratio,
adaptive-window opens, per-bucket hit counts, and the per-lane
breakdown (dispatches, coalesce, sheds, lane p99) — so a load ladder
shows WHERE the coalescing ceiling sits and which lanes shed, not just
that throughput plateaued.

Shed semantics: a request rejected with
:class:`~dragonfly2_tpu.inference.batcher.BatcherSaturatedError` is
counted (never folded into the latency distribution — it was not
served) and the driving thread pays ``shed_fallback_s`` before its next
request, modeling the rule-based fallback scoring a real scheduler runs
for that decision instead.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List

import numpy as np

from dragonfly2_tpu.inference.batcher import BatcherSaturatedError, MicroBatcher
from dragonfly2_tpu.utils.percentile import percentile as _percentile


def measure_colocated(
    scorer,
    *,
    threads: int = 8,
    rows_per_request: int = 16,
    duration_s: float = 3.0,
    max_rows: int | None = None,
    dispatch_floor_ms: float = 0.0,
    max_wait_s: float = 0.0,
    adaptive_wait_s: float = 0.0,
    lanes: int = 1,
    queue_depth: int = 0,
    lane_grow_depth: int | None = None,
    shed_fallback_s: float = 0.0005,
) -> Dict[str, float]:
    """Drive ``threads`` concurrent request loops through a MicroBatcher
    wrapped around ``scorer`` for ``duration_s`` and return latency and
    throughput stats (milliseconds).

    ``dispatch_floor_ms`` — p50 of a blocking no-op device round trip,
    measured by the caller — yields the floor-corrected fields: each
    percentile minus that floor, clamped at zero.
    ``max_wait_s`` / ``adaptive_wait_s`` are the batcher's batch-window
    knobs, ``lanes`` / ``queue_depth`` its sharding and admission knobs,
    all passed through verbatim. ``shed_fallback_s`` is the simulated
    cost of the rule-based fallback a shed request degrades to.
    """
    from dragonfly2_tpu.scheduler.evaluator.scoring import FEATURE_DIM

    batcher = MicroBatcher(scorer, max_rows=max_rows,
                           max_wait_s=max_wait_s,
                           adaptive_wait_s=adaptive_wait_s,
                           lanes=lanes, queue_depth=queue_depth,
                           lane_grow_depth=lane_grow_depth)
    feature_dim = FEATURE_DIM
    rng = np.random.default_rng(0)
    features = rng.standard_normal(
        (threads, rows_per_request, feature_dim)).astype(np.float32)

    # Warm every thread once so per-bucket compiles don't pollute timing.
    batcher.score(features[0])

    latencies: List[List[float]] = [[] for _ in range(threads)]
    shed_counts = [0] * threads
    stop = threading.Event()
    start_barrier = threading.Barrier(threads + 1)

    def loop(tid: int) -> None:
        mine = features[tid]
        out = latencies[tid]
        start_barrier.wait()
        while not stop.is_set():
            t = time.perf_counter()
            try:
                batcher.score(mine)
            except BatcherSaturatedError:
                # Shed: this decision degrades to rule scoring — model
                # its cost, count it, and keep offering load. The shed
                # request is NOT a served latency sample.
                shed_counts[tid] += 1
                if shed_fallback_s > 0:
                    time.sleep(shed_fallback_s)
                continue
            out.append((time.perf_counter() - t) * 1e3)

    workers = [threading.Thread(target=loop, args=(i,), daemon=True)
               for i in range(threads)]
    for w in workers:
        w.start()
    start_barrier.wait()
    t_start = time.perf_counter()
    time.sleep(duration_s)
    stop.set()
    for w in workers:
        w.join(timeout=10)
    wall = time.perf_counter() - t_start
    batcher.close()

    merged = sorted(x for sub in latencies for x in sub)
    n = len(merged)
    sheds = sum(shed_counts)
    offered = n + sheds
    pipeline = batcher.stats()
    p50 = _percentile(merged, 0.50)
    p95 = _percentile(merged, 0.95)
    p99 = _percentile(merged, 0.99)
    return {
        "threads": threads,
        "requests": n,
        "requests_per_sec": round(n / wall, 1) if wall > 0 else 0.0,
        "p50_ms": round(p50, 4),
        "p95_ms": round(p95, 4),
        "p99_ms": round(p99, 4),
        "p50_floor_corrected_ms": round(max(p50 - dispatch_floor_ms, 0.0), 4),
        "p99_floor_corrected_ms": round(max(p99 - dispatch_floor_ms, 0.0), 4),
        "dispatch_floor_ms": round(dispatch_floor_ms, 4),
        "coalesce_factor": pipeline["coalesce_factor"],
        "dispatches": pipeline["dispatches"],
        "inflight_depth_avg": pipeline["inflight_depth_avg"],
        "overlap_ratio": pipeline["overlap_ratio"],
        "adaptive_opens": pipeline["adaptive_opens"],
        "max_queue_depth": pipeline["max_queue_depth"],
        "lanes": pipeline["lanes"],
        "active_lanes": pipeline["active_lanes"],
        "lane_activations": pipeline["lane_activations"],
        "queue_depth_cap": pipeline["queue_depth_cap"],
        "sheds": sheds,
        "shed_rate": round(sheds / offered, 4) if offered else 0.0,
        "per_lane": pipeline["per_lane"],
        "bucket_hits": {str(k): v
                        for k, v in pipeline["bucket_hits"].items()},
    }
