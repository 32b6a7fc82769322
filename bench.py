"""Benchmark entry point.

``python bench.py`` runs the device stages — ``init``, ``scorer``,
``gnn``, ``mlp`` — in THIS process, on the attached TPU, and prints one
JSON line. It starts no JAX child (a chip belongs to one process) and
has no CPU path: where ``jax.devices()[0].platform`` is not ``"tpu"``,
or a device stage raises, it exits non-zero and says why. Every result
names the platform, ``device_kind`` and device count it ran on.
``python bench.py gnn|mlp|scorer`` runs one device stage the same way.

Headline metric (BASELINE.json north star): GraphSAGE topology-model
training throughput in samples(edges)/sec/chip, steady-state (compile
excluded). Extras carry scheduler parent-selection latency through the
ML scorer (single-threaded and through the micro-batcher under 8/32/128
concurrent threads) plus MLP training stats.

``python bench.py <stage>`` for any other stage (``dataplane``,
``scheduler``, ``chaos``, ``fanout``, ``mlguard``, ``replay``, ``obs``,
``qos``, ``geo``, ``federated``) runs that HOST gate — loopback swarms
and control-plane ladders measured on the host's CPU and labelled
``"platform": "host"`` — and ``python bench.py <stage>
--check-regression`` compares a fresh run with the best persisted
record under artifacts/bench_state/.

``vs_baseline`` is measured/target against the self-established target
(the reference publishes no numbers and its training path is a stub; see
BASELINE.md): 100k samples/sec/chip for GraphSAGE training.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

TARGET_GNN_SAMPLES_PER_SEC_PER_CHIP = 100_000.0
TARGET_P50_MS = 1.0
# Round-5 latency budget (verdict item 6), extended at round 6 from 8 to
# 32 scheduler threads: colocated parent-selection p99 must stay under
# 2 ms on the CPU device at BOTH rungs — the lane-sharded micro-batcher
# owes a tail bound under real announce concurrency (the reference
# scheduler is per-stream concurrent, service_v2.go:88), not just at the
# 8-thread comfort point. The 128-thread rung is bounded by admission
# control: p99 within 2× the 32-thread row, shed rate reported.
COLOCATED_P99_TARGET_MS = 2.0
COLOCATED_P99_TARGET_THREADS = 32
# Lane-sharded serving config for the ladder: 2 independent pipelined
# lanes with a 32-deep admission cap each, load-aware activation
# (lane_grow_depth defaults to max_rows/16 = 32 requests — one full
# 512-row dispatch). Measured shape on the 2-core dev box: 8/32 threads
# stay on ONE active lane (full coalescing, zero sheds — identical to
# the pre-lane pipeline), 128 threads activate the second lane and the
# caps bound every lane's backlog to one large dispatch of waiting work,
# shedding the rest to the (counted) rule fallback — p99 within 2× the
# 32-thread row versus ~8× unbounded. 4 lanes measured worse here
# (fragmented coalescing + XLA CPU contention); raise on bigger hosts.
COLOCATED_LANES = 2
COLOCATED_LANE_DEPTH = 32

# Wall budget the stages divide among themselves.
BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", "200"))
STATE_DIR = os.environ.get(
    "BENCH_STATE_DIR",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "artifacts", "bench_state"))

_t0 = time.perf_counter()


def elapsed() -> float:
    return time.perf_counter() - _t0


class BenchState:
    """The result dict + thread-safe mutation + atomic disk persistence.

    Every mutation holds a reentrant lock; ``flush`` writes tmp+rename
    so a reader never sees a torn file.
    """

    def __init__(self, out_path: str | None = None):
        self.lock = threading.RLock()
        self.out_path = out_path
        self.result = {
            "metric": "graphsage_train_samples_per_sec_per_chip",
            "value": 0,
            "unit": "samples/sec/chip",
            "vs_baseline": 0.0,
            # Host gate stages never touch a device; the init stage
            # overwrites this with the TPU it found.
            "extras": {"stages_completed": [], "platform": "host"},
        }

    def record(self, **extras) -> None:
        with self.lock:
            self.result["extras"].update(extras)
        self.flush()

    def stamp(self, name: str) -> None:
        self.record(**{f"t_{name}": round(elapsed(), 1)})

    def stage_done(self, name: str) -> None:
        with self.lock:
            self.result["extras"]["stages_completed"].append(name)
        self.stamp(name)

    def set_headline(self, value: float) -> None:
        with self.lock:
            self.result["value"] = int(value)
            self.result["vs_baseline"] = round(
                value / TARGET_GNN_SAMPLES_PER_SEC_PER_CHIP, 3)
        self.flush()

    def flush(self) -> None:
        if not self.out_path:
            return
        with self.lock:
            blob = json.dumps(self.result)
        tmp = self.out_path + ".tmp"
        try:
            with open(tmp, "w") as f:
                f.write(blob)
            os.replace(tmp, self.out_path)
        except OSError:
            pass

    def emit(self) -> None:
        with self.lock:
            self.result["extras"]["wall_seconds"] = round(elapsed(), 1)
            line = json.dumps(self.result)
        self.flush()
        print(line, flush=True)


# --------------------------------------------------------------------------
# Stages live in ONE registry (STAGES, populated by @stage below), not a
# hand-maintained if/elif chain: `bench.py` runs the required (device)
# stages in declaration order, `bench.py <stage>` any single stage by
# name.
# --------------------------------------------------------------------------

STAGES: list = []


def _persist_json(dest: str, payload: dict) -> None:
    """Atomic best-effort stage-record write (tmp + rename) — the one
    copy of the idiom the green-run persists share."""
    tmp = dest + ".tmp"
    try:
        os.makedirs(os.path.dirname(dest), exist_ok=True)
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, dest)
    except OSError:
        pass


class _Stage:
    __slots__ = ("name", "min_left", "required", "needs_device", "fn")

    def __init__(self, name, min_left, required, needs_device, fn):
        self.name = name
        self.min_left = min_left
        self.required = required
        self.needs_device = needs_device
        self.fn = fn


def stage(name: str, *, min_left: float = 0.0, required: bool = False,
          needs_device: bool = False):
    """Register a bench stage. ``required`` marks the device stages:
    `bench.py` runs them, and their failures propagate (non-zero exit)
    instead of being recorded as <name>_error; ``min_left`` skips one,
    on the record, when `bench.py` reaches it with less wall budget
    left; ``needs_device`` makes a single-stage run execute the init
    stage first."""

    def deco(fn):
        STAGES.append(_Stage(name, min_left, required, needs_device, fn))
        return fn

    return deco


@stage("init", required=True)
def stage_init(state: BenchState, ctx: dict) -> None:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        # No CPU path: a host timing under a device metric's name is
        # worse than no number.
        raise SystemExit(
            "bench.py: the device stages need a TPU; JAX found "
            f"{sorted({d.platform for d in devices})}")

    from dragonfly2_tpu.parallel import data_parallel_mesh
    from dragonfly2_tpu.utils.compilecache import enable_compilation_cache

    mesh = data_parallel_mesh()
    ctx["mesh"] = mesh
    state.record(compile_cache_dir=enable_compilation_cache(),
                 platform="tpu", device_kind=devices[0].device_kind,
                 device_count=len(devices), n_devices=mesh.n_data)
    state.stage_done("init")


@stage("scorer", required=True, needs_device=True)
def stage_scorer(state: BenchState, ctx: dict) -> None:
    left = ctx["left"]
    # Parent-selection latency FIRST — it is weight-independent
    # (a synthetically initialized MLP exercises the same compiled
    # dispatch path a trained one would), so the <1 ms target gets
    # validated before the GNN stage can starve it. Two measurements:
    #   (a) single-threaded ParentScorer loop (the round-3 number), and
    #   (b) the COLOCATED number the target is actually about — 8
    #       scheduler threads through the MicroBatcher, end-to-end
    #       (round-3 verdict item 5).
    # Both are decomposed against the dispatch floor (a blocking no-op
    # jit round trip): raw and floor-corrected are published side by
    # side, clearly labeled.
    import jax
    import jax.numpy as jnp

    from dragonfly2_tpu.inference import ParentScorer
    from dragonfly2_tpu.inference.loadgen import measure_colocated
    from dragonfly2_tpu.models.mlp import MLPBandwidthPredictor, Normalizer
    from dragonfly2_tpu.scheduler.evaluator.scoring import FEATURE_DIM

    scorer_budget = max(min(left() * 0.2, 30.0), 4.0)
    scorer_t0 = time.perf_counter()

    mlp_model = MLPBandwidthPredictor()
    mlp_params = mlp_model.init(jax.random.key(0),
                                jnp.zeros((1, FEATURE_DIM)))
    # max_batch=512: the batcher drains up to the largest warm bucket,
    # so at 128 threads × 16 rows a dispatch can coalesce 32 requests —
    # the r05 ladder pinned at 8 because 128 rows was the ceiling. All
    # buckets compile here, before timing: the ladder must be cache hits
    # only.
    scorer = ParentScorer(mlp_model, mlp_params,
                          Normalizer.identity(FEATURE_DIM),
                          Normalizer.identity(1), max_batch=512)

    noop = jax.jit(lambda x: x + 1)
    x0 = jnp.zeros(8)
    noop(x0).block_until_ready()
    floor = []
    for _ in range(15):
        t = time.perf_counter()
        noop(x0).block_until_ready()
        floor.append((time.perf_counter() - t) * 1e3)
    floor_p50 = sorted(floor)[len(floor) // 2]
    state.record(dispatch_floor_p50_ms=round(floor_p50, 4))

    # (a) single-threaded loop, adaptive iteration count.
    probe = scorer.benchmark(batch=16, iters=10)
    solo_budget = (scorer_budget - (time.perf_counter() - scorer_t0)) * 0.4
    iters = int(max(20, min(300,
                            solo_budget * 1e3 / max(probe["p50_ms"], 1e-3))))
    latency = scorer.benchmark(batch=16, iters=iters)
    state.record(
        parent_select_p50_ms=round(latency["p50_ms"], 4),
        parent_select_p99_ms=round(latency["p99_ms"], 4),
        parent_select_iters=iters,
        parent_select_model_ms=round(
            max(latency["p50_ms"] - floor_p50, 0.0), 4),
        parent_select_vs_1ms_target=round(
            TARGET_P50_MS / max(latency["p50_ms"], 1e-9), 3),
    )

    # (b) colocated: concurrent scheduler threads → lane-sharded
    # MicroBatcher → one padded dispatch per lane in-flight window.
    # parent_select_colocated_* fields are the deliverable named by the
    # round-3 verdict; the 8/32/128-thread ladder is round 5's (verdict
    # item 6); round 6 shards the batcher into lanes with bounded
    # admission and moves the stated p99 < 2 ms target out to 32
    # threads, with the 128-thread rung bounded (p99 ≤ 2× the 32-thread
    # row) by shedding — the shed rate is reported, never dropped.
    colo_secs = max(min((scorer_budget
                         - (time.perf_counter() - scorer_t0)) / 3, 4.0), 1.0)
    load_ladder = {}
    for n_threads in (8, 32, 128):
        colo = measure_colocated(scorer, threads=n_threads,
                                 rows_per_request=16,
                                 duration_s=colo_secs,
                                 dispatch_floor_ms=floor_p50,
                                 adaptive_wait_s=0.0005,
                                 lanes=COLOCATED_LANES,
                                 queue_depth=COLOCATED_LANE_DEPTH)
        load_ladder[n_threads] = colo
        if n_threads == 8:
            state.record(
                parent_select_colocated_p50_ms=colo["p50_ms"],
                parent_select_colocated_p95_ms=colo["p95_ms"],
                parent_select_colocated_p99_ms=colo["p99_ms"],
                parent_select_colocated_p50_floor_corrected_ms=colo[
                    "p50_floor_corrected_ms"],
                parent_select_colocated_requests_per_sec=colo[
                    "requests_per_sec"],
                parent_select_colocated_coalesce_factor=colo[
                    "coalesce_factor"],
                parent_select_colocated_threads=colo["threads"],
                parent_select_colocated_sheds=colo["sheds"],
            )
        elif n_threads == COLOCATED_P99_TARGET_THREADS:
            state.record(
                parent_select_colocated32_p99_ms=colo["p99_ms"],
                parent_select_colocated32_shed_rate=colo["shed_rate"],
                parent_select_colocated_p99_target_ms=COLOCATED_P99_TARGET_MS,
                parent_select_colocated_p99_target_threads=(
                    COLOCATED_P99_TARGET_THREADS),
                parent_select_colocated_p99_vs_target=round(
                    COLOCATED_P99_TARGET_MS / max(colo["p99_ms"], 1e-9), 3),
            )
    p99_32 = load_ladder[32]["p99_ms"]
    state.record(
        parent_select_colocated_lanes=COLOCATED_LANES,
        parent_select_colocated_lane_depth=COLOCATED_LANE_DEPTH,
        parent_select_colocated128_p99_over_32=round(
            load_ladder[128]["p99_ms"] / max(p99_32, 1e-9), 3),
        parent_select_colocated128_shed_rate=load_ladder[128]["shed_rate"],
    )
    state.record(parent_select_colocated_load_ladder={
        str(k): {f: v[f] for f in ("p50_ms", "p95_ms", "p99_ms",
                                   "requests_per_sec", "coalesce_factor",
                                   "requests", "inflight_depth_avg",
                                   "overlap_ratio", "adaptive_opens",
                                   "max_queue_depth", "lanes",
                                   "active_lanes", "lane_activations",
                                   "queue_depth_cap", "sheds", "shed_rate",
                                   "per_lane", "bucket_hits")}
        for k, v in load_ladder.items()})
    state.stage_done("scorer")


@stage("gnn", required=True, needs_device=True)
def stage_gnn(state: BenchState, ctx: dict) -> None:
    """Headline: GraphSAGE on a probe graph. The step loop gets the
    remaining budget minus reserves for eval + emit, and publishes
    throughput incrementally."""
    left = ctx["left"]
    mesh = ctx["mesh"]

    from dragonfly2_tpu.data import SyntheticCluster
    from dragonfly2_tpu.train import GNNTrainConfig, train_gnn

    # (8192, 16) won the round-4 grid (artifacts/tune_gnn_r4.json: 351k
    # vs 275k at k=8 in matched windows); whether k > 1 still pays on an
    # attached chip is ROADMAP C6's to measure.
    n_edges, batch, steps_per_call = 2_000_000, 8192, 16
    cluster = ctx["cluster"] = SyntheticCluster(n_hosts=2000, seed=0)
    graph = cluster.probe_graph(n_edges)
    state.stamp("graph_built")

    def on_progress(steps: int, rate: float) -> None:
        state.set_headline(rate / mesh.n_data)
        state.record(gnn_steps=steps)

    def on_compile(seconds: float) -> None:
        state.record(gnn_compile_seconds=round(seconds, 1))
        state.stamp("gnn_compile_done")

    eval_reserve = max(min(left() * 0.2, 30.0), 5.0)
    emit_reserve = 10.0
    compile_reserve = 30.0  # uncached train-step compile; ~0 on cache hit
    gnn_budget = max(left() - eval_reserve - emit_reserve - compile_reserve,
                     5.0)
    state.record(gnn_step_seconds_budget=round(gnn_budget, 1))
    gnn = train_gnn(
        graph,
        GNNTrainConfig(batch_size=batch, epochs=1000, eval_fraction=0.02,
                       max_seconds=gnn_budget,
                       steps_per_call=steps_per_call,
                       progress_callback=on_progress,
                       compile_callback=on_compile,
                       eval_max_seconds=min(eval_reserve, 25.0)),
        mesh,
    )
    state.set_headline(gnn.samples_per_sec / mesh.n_data)
    state.record(
        gnn_f1=round(gnn.f1, 4),
        gnn_precision=round(gnn.precision, 4),
        gnn_recall=round(gnn.recall, 4),
        gnn_steps=gnn.steps,
        gnn_compile_seconds=round(gnn.compile_seconds, 1),
    )
    state.stage_done("gnn")


@stage("mlp", min_left=45.0, required=True, needs_device=True)
def stage_mlp(state: BenchState, ctx: dict) -> None:
    """MLP training throughput + honest registry mae from a
    really-trained model (budget-gated)."""
    left = ctx["left"]
    mesh = ctx["mesh"]

    from dragonfly2_tpu.train import MLPTrainConfig, train_mlp

    cluster = ctx.get("cluster")
    if cluster is None:
        from dragonfly2_tpu.data import SyntheticCluster

        cluster = ctx["cluster"] = SyntheticCluster(n_hosts=2000, seed=0)
    X, y = cluster.pair_example_columns(300_000)
    mlp = train_mlp(
        X, y,
        MLPTrainConfig(epochs=100, batch_size=16384,
                       max_seconds=max(min(left() - 25.0, 25.0), 2.0),
                       progress_callback=lambda s, r: state.record(
                           mlp_train_samples_per_sec_per_chip=int(
                               r / mesh.n_data)),
                       compile_callback=lambda c: state.record(
                           mlp_compile_seconds=round(c, 1))),
        mesh,
    )
    state.record(
        mlp_train_samples_per_sec_per_chip=int(
            mlp.samples_per_sec / mesh.n_data),
        mlp_eval_mae_mbps=round(mlp.mae, 3),
    )
    state.stage_done("mlp")


@stage("dataplane")
def stage_dataplane(state: BenchState, ctx: dict) -> None:
    """Data plane — three rungs:

    1. the PR-3 coalesce ladder (loopback back-to-source with the
       amortization counters; run=1 is the one-GET-per-piece baseline),
    2. the ISSUE-7 upload-loopback rung — the event-loop serving engine
       with the serve path pinned to pure-Python os.sendfile (native
       off), bound ≥ UPLOAD_SPEEDUP_BOUND× the persisted 134 MB/s
       thread-per-conn baseline,
    3. the concurrency-density rung — ≥256 concurrent keep-alive piece
       streams against one seed, every body md5-verified, server thread
       count bounded at a CONSTANT (the threaded engine held ~1 thread
       per connection),
    4. the ISSUE-15 DOWNLOAD density rung — 8/32/128 concurrent tasks
       against ONE real daemon on the async download engine, download
       threads bounded at dl_workers+2 at every rung (the threaded
       engine grew with task count) and the 128-task aggregate MB/s ≥
       a same-process thread-engine baseline,
    5. the ISSUE-16 DOWNLOAD SPLICE rung — PieceFetchOp bodies landing
       via the native socket→pwrite splice (zero-copy, no inline
       digest), every piece span md5-verified post-window, bound ≥
       SPLICE_BOUND_MB_S (1.5× the 536 MB/s native upload record),
    6. the ISSUE-16 TLS rungs — upload loopback and the ≥256-stream
       density rung repeated over nonblocking TLS (same serving engine,
       same constant thread census), with the handshake/fallback
       counters recorded; skipped explicitly when the openssl CLI
       can't mint certs.

    A green run (all verdicts) persists to
    artifacts/bench_state/dataplane_run_<tag>.json — the record
    `bench.py dataplane --check-regression` gates future PRs against."""
    left = ctx["left"]

    from dragonfly2_tpu.client.dataplane import run_loopback_bench
    from dragonfly2_tpu.client.uploadbench import (
        UPLOAD_SPEEDUP_BOUND,
        run_density_rung,
        run_upload_loopback_bench,
    )

    ladder = {}
    for run in (1, 8):
        ladder[run] = run_loopback_bench(
            64 << 20, coalesce_run=run, workers=4)
    best = ladder[8]
    state.record(
        dataplane_loopback_mb_per_s=best["mb_per_s"],
        dataplane_pieces=best["pieces"],
        dataplane_requests_saved=best["requests_saved"],
        dataplane_connections_opened=best["connections_opened"],
        dataplane_connections_reused=best["connections_reused"],
        dataplane_coalesce_run_p50=best["coalesce_run_p50"],
        dataplane_report_rpcs_saved=best["report_rpcs_saved"],
        dataplane_ladder={
            str(run): {k: v[k] for k in (
                "mb_per_s", "seconds", "source_requests",
                "source_pieces", "requests_saved",
                "connections_opened", "connections_reused",
                "server_connections", "server_requests",
                "coalesce_run_p50")}
            for run, v in ladder.items()},
    )
    if left() < 10.0:
        # Same contract as the budget-skipped kill rung: a skip must
        # never read as a verified pass.
        state.record(dataplane_upload_rungs_skipped=True)
        state.stage_done("dataplane")
        return
    upload = run_upload_loopback_bench(
        timeout_s=max(min(left() * 0.5, 45.0), 8.0))
    upload_pass = bool(
        upload["md5_ok"]
        and upload["speedup_vs_baseline"] >= UPLOAD_SPEEDUP_BOUND)
    state.record(
        dataplane_upload_mb_per_s=upload["mb_per_s"],
        dataplane_upload_attempts=upload["attempt_mb_per_s"],
        dataplane_upload_speedup=upload["speedup_vs_baseline"],
        dataplane_upload_speedup_bound=upload["speedup_bound"],
        dataplane_upload_serve_path=upload["serve_path"],
        dataplane_upload_server_threads=upload["server_threads"],
        dataplane_upload_verdict_pass=upload_pass,
    )
    if left() < 8.0:
        # The upload rung ate the remaining budget: a starved density
        # rung would go incomplete and record a False verdict that
        # reads as a perf regression. Record the skip explicitly; the
        # combined verdict below then covers the upload rung only, and
        # nothing persists as a full green.
        state.record(dataplane_density_skipped=True,
                     dataplane_verdict_pass=upload_pass)
        state.stage_done("dataplane")
        return
    density = run_density_rung(timeout_s=max(min(left() * 0.7, 60.0), 10.0))
    state.record(
        dataplane_density_streams=density["streams"],
        dataplane_density_mb_per_s=density["mb_per_s"],
        dataplane_density_p99_ms=density["time_to_piece_p99_ms"],
        dataplane_density_server_threads=density["server_threads"],
        dataplane_density_thread_bound=density["server_thread_bound"],
        dataplane_density_md5_ok=density["md5_ok"],
        dataplane_density_verdict_pass=density["verdict_pass"],
    )
    if left() < 12.0:
        # Budget-starved download rung: record the skip explicitly so
        # it never reads as a pass OR a regression, and persist nothing
        # (a record without the download rung would let the
        # check-regression gate grade against a partial green).
        state.record(dataplane_dl_density_skipped=True,
                     dataplane_verdict_pass=bool(
                         upload_pass and density["verdict_pass"]))
        state.stage_done("dataplane")
        return
    from dragonfly2_tpu.client.dataplane import run_download_density_rung

    dl_density = run_download_density_rung(
        timeout_s=max(min(left() * 0.8, 120.0), 12.0))
    state.record(
        dataplane_dl_density_top_mb_per_s=dl_density["top_rung_mb_per_s"],
        dataplane_dl_density_thread_bound=dl_density["thread_bound"],
        dataplane_dl_density_threads_bounded=dl_density["threads_bounded"],
        dataplane_dl_density_vs_thread_engine=dl_density.get(
            "vs_thread_engine"),
        dataplane_dl_density_rungs={
            n: {k: v for k, v in r.items() if k != "census_peak"}
            for n, r in dl_density["rungs"].items()},
        dataplane_dl_density_verdict_pass=dl_density["verdict_pass"],
    )
    base_pass = bool(upload_pass and density["verdict_pass"]
                     and dl_density["verdict_pass"])
    if left() < 8.0:
        # Budget-starved splice/TLS rungs: explicit skip, partial
        # verdict, nothing persists as a full green.
        state.record(dataplane_splice_skipped=True,
                     dataplane_tls_rungs_skipped=True,
                     dataplane_verdict_pass=base_pass)
        state.stage_done("dataplane")
        return
    from dragonfly2_tpu.client.dataplane import run_splice_loopback_bench

    splice = run_splice_loopback_bench(
        timeout_s=max(min(left() * 0.4, 45.0), 8.0))
    if splice.get("skipped"):
        state.record(dataplane_splice_skipped=True,
                     dataplane_splice_skip_reason=splice["reason"],
                     dataplane_verdict_pass=base_pass)
        state.stage_done("dataplane")
        return
    state.record(
        dataplane_splice_mb_per_s=splice["mb_per_s"],
        dataplane_splice_bound_mb_per_s=splice["bound_mb_per_s"],
        dataplane_splice_bytes=splice["splice_bytes"],
        dataplane_splice_zero_copy_fraction=splice.get(
            "zero_copy_fraction", 0.0),
        dataplane_splice_verified_pieces=splice.get("verified_pieces", 0),
        dataplane_splice_verdict_pass=splice["verdict_pass"],
    )
    if left() < 10.0:
        state.record(dataplane_tls_rungs_skipped=True,
                     dataplane_verdict_pass=bool(
                         base_pass and splice["verdict_pass"]))
        state.stage_done("dataplane")
        return
    tls_upload = run_upload_loopback_bench(
        size_bytes=128 << 20, attempts=2, tls=True,
        timeout_s=max(min(left() * 0.4, 40.0), 8.0))
    if tls_upload.get("skipped"):
        state.record(dataplane_tls_rungs_skipped=True,
                     dataplane_tls_skip_reason=tls_upload["reason"],
                     dataplane_verdict_pass=bool(
                         base_pass and splice["verdict_pass"]))
        state.stage_done("dataplane")
        return
    tls_density = run_density_rung(
        tls=True, timeout_s=max(min(left() * 0.7, 60.0), 10.0))
    tls_pass = bool(tls_upload["md5_ok"]
                    and tls_upload["tls_handshakes"] > 0
                    and tls_density.get("verdict_pass"))
    state.record(
        dataplane_tls_upload_mb_per_s=tls_upload["mb_per_s"],
        dataplane_tls_upload_md5_ok=tls_upload["md5_ok"],
        dataplane_tls_handshakes=tls_upload["tls_handshakes"],
        dataplane_tls_fallbacks=tls_upload["tls_fallbacks"],
        dataplane_tls_ktls_bytes=tls_upload["ktls_bytes"],
        dataplane_tls_density_streams=tls_density.get("streams"),
        dataplane_tls_density_mb_per_s=tls_density.get("mb_per_s"),
        dataplane_tls_density_server_threads=tls_density.get(
            "server_threads"),
        dataplane_tls_density_verdict_pass=tls_density.get(
            "verdict_pass"),
        dataplane_tls_verdict_pass=tls_pass,
    )
    verdict = bool(base_pass and splice["verdict_pass"] and tls_pass)
    state.record(dataplane_verdict_pass=verdict)
    state.stage_done("dataplane")
    if verdict:
        _persist_json(
            os.path.join(
                STATE_DIR,
                f"dataplane_run_{time.strftime('%Y%m%d_%H%M%S')}.json"),
            {"ladder": {str(k): v for k, v in ladder.items()},
             "upload_loopback": upload,
             "density": density,
             "download_density": dl_density,
             "download_splice": splice,
             "tls_upload": tls_upload,
             "tls_density": tls_density})


@stage("scheduler")
def stage_scheduler(state: BenchState, ctx: dict) -> None:
    """Scheduler control plane — two ladders:

    1. the in-process swarm ladder against one real SchedulerService
       (sharded managers + incremental GC + O(1) peer statistics), now
       extended to a 25k single-replica rung when budget allows, each
       rung reporting the peak-RSS + bytes/peer gauges next to the
       pre-slimming baseline;
    2. the ISSUE-11 CLUSTER ladder (scheduler/clusterbench.py): a
       4-replica subprocess cluster driven over real gRPC through the
       BalancedSchedulerClient, baseline rung + big rung with a
       mid-swarm replica SIGKILL, bounding announce p99 across the
       cluster by the same LADDER_P99_BOUND and the re-route p99 by
       the chaos-plane grace.

    Budget-starved rungs record explicit skips (never a silent pass);
    a green run persists to artifacts/bench_state/scheduler_run_*.json
    — the record `bench.py scheduler --check-regression` gates against.
    `--rungs` / `--cluster-peers` override the shapes from the CLI."""
    left = ctx["left"]

    from dragonfly2_tpu.scheduler.loadbench import run_swarm_ladder

    if ctx.get("rungs"):
        sizes = tuple(ctx["rungs"])
    elif left() > 240.0:
        sizes = (100, 1000, 5000, 25000)
    elif left() > 30.0:
        sizes = (100, 1000, 5000)
    else:
        sizes = (100, 500, 1500)
    sched = run_swarm_ladder(sizes, workers=8)
    ladder = sched["ladder"]
    largest = ladder[str(sizes[-1])]
    state.record(
        scheduler_swarm_sizes=list(sizes),
        scheduler_announce_p50_ms=largest["announce_p50_ms"],
        scheduler_announce_p99_ms=largest["announce_p99_ms"],
        scheduler_decisions_per_sec=largest["decisions_per_sec"],
        scheduler_piece_reports_per_sec=largest[
            "piece_reports_per_sec"],
        scheduler_gc_pause_p99_ms=largest["gc_pause_p99_ms"],
        scheduler_gc_budget_overruns=largest["gc_budget_overruns"],
        scheduler_bad_node_fast=largest["bad_node_fast"],
        scheduler_bad_node_slow=largest["bad_node_slow"],
        scheduler_peak_rss_mb=largest["peak_rss_mb"],
        scheduler_bytes_per_peer=largest["bytes_per_peer"],
        scheduler_bytes_per_peer_pre_slim=largest[
            "bytes_per_peer_pre_slim_baseline"],
        scheduler_decision_p99_ratio=sched["decision_p99_ratio"],
        scheduler_ladder_p99_bound=sched["ladder_p99_bound"],
        scheduler_p99_within_bound=sched["p99_within_bound"],
        scheduler_ladder={
            size: {k: v[k] for k in (
                "seconds", "announce_p50_ms", "announce_p99_ms",
                "decisions", "decisions_per_sec", "piece_reports",
                "piece_reports_per_sec", "back_to_source",
                "filter_ms_p99", "evaluate_ms_p99", "gc_ticks",
                "gc_pause_p50_ms", "gc_pause_p99_ms",
                "gc_budget_overruns", "gc_reclaimed", "peak_rss_mb",
                "peak_rss_scope", "rss_delta_mb", "bytes_per_peer",
                "bytes_per_peer_pre_slim_baseline", "tasks",
                "peers_per_task", "workers", "errors")}
            for size, v in ladder.items()},
    )

    # -- cluster ladder (multi-process, real gRPC) ----------------------
    # The full 100k rung is a ~10-minute drive on a small box; scale the
    # rung to the remaining budget and record the scale explicitly. The
    # persisted 100k green run comes from `BENCH_BUDGET_S=1800 bench.py
    # scheduler` (or --cluster-peers 100000).
    cluster = None
    # In a FULL bench run the chaos/fanout stages still need their
    # budget after this one — the cluster ladder may claim only a
    # share of what's left; a single-stage `bench.py scheduler` run
    # owns the whole budget.
    cluster_budget = (left() - 25.0 if ctx.get("single_stage")
                      else min(left() * 0.3, 240.0))
    if ctx.get("cluster_peers") is not None:
        cluster_peers = int(ctx["cluster_peers"])
    elif cluster_budget > 1000.0:
        cluster_peers = 100_000
    elif cluster_budget > 400.0:
        cluster_peers = 20_000
    elif cluster_budget > 150.0:
        cluster_peers = 4_000
    else:
        cluster_peers = 0
    if cluster_peers <= 0:
        state.record(scheduler_cluster_skipped=True)
    else:
        from dragonfly2_tpu.scheduler.clusterbench import run_cluster_ladder

        cluster = run_cluster_ladder(
            cluster_peers=cluster_peers, replicas=4,
            kill_replica=True,
            deadline_s=max(min(cluster_budget, left() - 25.0), 30.0))
        big = cluster.get("cluster")
        state.record(
            scheduler_cluster_peers=cluster_peers,
            scheduler_cluster_baseline_p99_ms=cluster["baseline"][
                "announce_p99_ms"],
            scheduler_cluster_baseline_samples=cluster["baseline"][
                "samples"],
        )
        if big is not None:
            state.record(
                scheduler_cluster_replicas=big["replicas"],
                scheduler_cluster_seconds=big["seconds"],
                scheduler_cluster_announce_p50_ms=big["announce_p50_ms"],
                scheduler_cluster_announce_p99_ms=big["announce_p99_ms"],
                scheduler_cluster_decisions_per_sec=big[
                    "decisions_per_sec"],
                scheduler_cluster_success_rate=big["success_rate"],
                scheduler_cluster_bytes_per_peer=big[
                    "bytes_per_peer_cluster"],
                scheduler_cluster_p99_ratio=cluster.get(
                    "cluster_p99_ratio"),
                scheduler_cluster_p99_bound=cluster["ladder_p99_bound"],
                scheduler_cluster_kill=big.get("killed"),
                scheduler_cluster_reroutes=big.get("reroutes"),
                scheduler_cluster_reroute_p99_ms=big.get("reroute_p99_ms"),
                scheduler_cluster_reroute_bound_s=big.get(
                    "reroute_bound_s"),
                scheduler_cluster_sessions_rehomed=big.get(
                    "sessions_rehomed"),
                scheduler_cluster_kill_verdict_pass=big.get(
                    "kill_verdict_pass"),
                scheduler_cluster_recovery=big["recovery_counters"],
                scheduler_cluster_failovers=big["recovery_counters"][
                    "scheduler_failovers"],
                scheduler_cluster_per_replica=big["per_replica"],
            )
        if cluster.get("verdict_skipped_budget"):
            state.record(scheduler_cluster_verdict_skipped=True)
        else:
            state.record(
                scheduler_cluster_p99_within_bound=cluster[
                    "p99_within_bound"],
                scheduler_cluster_verdict_pass=cluster["verdict_pass"])

    ladder_green = bool(sched["p99_within_bound"]
                        and not largest["errors"])
    # A budget-skipped cluster ladder is an EXPLICIT skip (recorded
    # above), not a failure: the overall verdict covers what ran — the
    # same contract as cluster_peers=0. Only an actually-failed cluster
    # verdict turns the run red.
    cluster_skipped = (cluster is not None
                      and bool(cluster.get("verdict_skipped_budget")))
    cluster_green = (cluster is not None
                     and cluster.get("verdict_pass") is True)
    green = bool(ladder_green
                 and (cluster is None or cluster_skipped or cluster_green))
    state.record(scheduler_verdict_pass=green)
    state.stage_done("scheduler")
    if green:
        _persist_json(
            os.path.join(
                STATE_DIR,
                f"scheduler_run_{time.strftime('%Y%m%d_%H%M%S')}.json"),
            {"ladder": sched,
             "cluster": (cluster if cluster is not None
                         and not cluster_skipped
                         else {"skipped": True})})


@stage("chaos")
def stage_chaos(state: BenchState, ctx: dict) -> None:
    """Chaos — deterministic fault-injection ladder over the loopback
    swarm (scheduler + two peers + origin, client/chaosbench.py), the
    same ladder repeated with every p2p leg over TLS plus mid-handshake
    resets in the mix (ISSUE 16), plus the ISSUE-6 scheduler-kill rung (three scheduler replica PROCESSES,
    one hard-killed mid-swarm by the seeded ``scheduler.process`` site)
    and the ISSUE-8 daemon-kill rung (a daemon process SIGKILLed at
    ~50% of a download, restarted on the same storage root).
    Ladder bound (docs/CHAOS.md): 100% task success at every rung and
    ≥70% goodput retention at the 5% rung. Scheduler-kill bound: 100%
    task success, p99 re-route ≤ scheduler_grace, 0 tasks degraded to
    back-to-source while ≥1 replica survives. Daemon-kill bound: 100%
    task success, md5-exact final bytes, re-downloaded bytes ≤ missing
    + one piece per worker, restarted seed re-announces and serves.
    The combined verdict lands in the bench JSON, and a passing run
    persists into artifacts/bench_state/ like the TPU runs do."""
    left = ctx["left"]

    from dragonfly2_tpu.client.chaosbench import (
        run_chaos_ladder,
        run_daemon_kill_rung,
        run_scheduler_kill_rung,
    )

    chaos = run_chaos_ladder(seed=0)
    top = chaos["ladder"][str(max(chaos["rates"]))]
    tls_chaos = None
    if left() <= 12.0:
        state.record(chaos_tls_ladder_skipped=True)
    else:
        # The same ladder with every p2p leg over TLS and mid-handshake
        # resets added to the fault mix (ISSUE 16) — skipped explicitly
        # when the openssl CLI can't mint a throwaway CA.
        tls_chaos = run_chaos_ladder(seed=0, tls=True)
        if tls_chaos.get("skipped"):
            state.record(chaos_tls_ladder_skipped=True,
                         chaos_tls_skip_reason=tls_chaos["reason"])
            tls_chaos = None
        else:
            tls_top = tls_chaos["ladder"][str(max(tls_chaos["rates"]))]
            state.record(
                chaos_tls_success_rate_at_max=tls_top["success_rate"],
                chaos_tls_goodput_retention_at_max=tls_chaos[
                    "goodput_retention_at_max"],
                chaos_tls_recovery_events=tls_top["recovery_events"],
                chaos_tls_handshake_faults=(tls_top.get("faults", {})
                                            .get("tls.handshake")),
                chaos_tls_all_rungs_full_success=tls_chaos[
                    "all_rungs_full_success"],
                chaos_tls_verdict_pass=tls_chaos["verdict_pass"],
            )
    state.record(
        chaos_rates=chaos["rates"],
        chaos_success_rate_at_max=top["success_rate"],
        chaos_goodput_retention_at_max=chaos[
            "goodput_retention_at_max"],
        chaos_goodput_retention_bound=chaos[
            "goodput_retention_bound"],
        chaos_recovery_p50_ms=top["recovery_p50_ms"],
        chaos_recovery_p99_ms=top["recovery_p99_ms"],
        chaos_recovery_events=top["recovery_events"],
        chaos_all_rungs_full_success=chaos[
            "all_rungs_full_success"],
        chaos_ladder={
            rate: {k: v[k] for k in (
                "success_rate", "downloads", "mb_per_s",
                "seconds", "recovery_events", "recovery_p50_ms",
                "recovery_p99_ms", "download_p99_s")}
            for rate, v in chaos["ladder"].items()},
    )
    kill = None
    if left() <= 8.0:
        # A skipped kill rung must never read as a verified pass: the
        # combined verdict below then covers the LADDER ONLY, and both
        # the bench JSON and the persisted artifact say so explicitly
        # (chaos_scheduler_kill_verdict_pass stays absent — a driver
        # gating on it sees a miss, not a green).
        state.record(chaos_scheduler_kill_skipped=True)
    else:
        kill = run_scheduler_kill_rung(seed=0)
        state.record(
            chaos_scheduler_kill_success_rate=kill["success_rate"],
            chaos_scheduler_kill_reroutes=kill["reroutes"],
            chaos_scheduler_kill_reroute_p50_ms=kill["reroute_p50_ms"],
            chaos_scheduler_kill_reroute_p99_ms=kill["reroute_p99_ms"],
            chaos_scheduler_kill_reroute_bound_s=kill["reroute_bound_s"],
            chaos_scheduler_kill_failovers=kill["failovers"],
            chaos_scheduler_kill_pieces_replayed=kill["pieces_replayed"],
            chaos_scheduler_kill_degraded=kill["degraded_to_source"],
            chaos_scheduler_kill_verdict_pass=kill["verdict_pass"],
        )
    daemon_kill = None
    if left() <= 8.0:
        # Same contract as a budget-skipped scheduler-kill rung: the
        # skip is recorded explicitly (never a silent pass) and the
        # persisted artifact says {"skipped": true}.
        state.record(chaos_daemon_kill_skipped=True)
    else:
        daemon_kill = run_daemon_kill_rung(seed=0)
        state.record(
            chaos_daemon_kill_success_rate=daemon_kill["success_rate"],
            chaos_daemon_kill_killed=daemon_kill["killed"],
            chaos_daemon_kill_resumed_pieces=daemon_kill.get(
                "resume", {}).get("resumed_pieces"),
            chaos_daemon_kill_bytes_fresh=daemon_kill.get(
                "resume", {}).get("bytes_fresh"),
            chaos_daemon_kill_refetch_bound=daemon_kill.get(
                "refetch_bound_bytes"),
            chaos_daemon_kill_reseed=daemon_kill.get("reseed"),
            chaos_daemon_kill_failures=daemon_kill["failures"][:5],
            chaos_daemon_kill_verdict_pass=daemon_kill["verdict_pass"],
        )
    verdict = bool(chaos["verdict_pass"]
                   and (tls_chaos is None or tls_chaos["verdict_pass"])
                   and (kill is None or kill["verdict_pass"])
                   and (daemon_kill is None
                        or daemon_kill["verdict_pass"]))
    state.record(chaos_verdict_pass=verdict)
    state.stage_done("chaos")
    if verdict:
        _persist_json(
            os.path.join(
                STATE_DIR,
                f"chaos_run_{time.strftime('%Y%m%d_%H%M%S')}.json"),
            {"ladder": chaos,
             "tls_ladder": (tls_chaos if tls_chaos is not None
                            else {"skipped": True}),
             "scheduler_kill": (kill if kill is not None
                                else {"skipped": True}),
             "daemon_kill": (daemon_kill if daemon_kill is not None
                             else {"skipped": True})})


@stage("mlguard")
def stage_mlguard(state: BenchState, ctx: dict) -> None:
    """Guarded model lifecycle — the ISSUE-12 poisoned-model rung
    (dragonfly2_tpu/inference/guardbench.py): a live loopback swarm
    scheduling through the ML serving stack (RemoteMLEvaluator → gRPC
    sidecar → manager registry, reload watcher running) while a
    NaN-poisoned model is published three ways: through the validation
    gate (must be quarantined OFFLINE, replaying announce traces
    recorded from this very swarm), force-published into SHADOW mode
    (canary must reject + quarantine it with the incumbent never
    leaving the decision path), and force-published LIVE with shadow
    off (the runtime guard must degrade every poisoned batch to rules,
    escalate to a manager quarantine, and the watcher must restore the
    previous version). Documented bounds (docs/CHAOS.md): 100 % task
    success, decision quality never below the rule baseline, rollback
    within 2 × reload_interval of exposure. A green run persists to
    artifacts/bench_state/mlguard_run_*.json; a budget-skipped rung
    records an explicit skip artifact — never a silent pass."""
    left = ctx["left"]

    from dragonfly2_tpu.inference.guardbench import run_mlguard_rung

    # The budget gate lives HERE (no registry min_left): a registry-level
    # skip would record nothing — this branch records the skip and
    # persists a {"skipped": true} artifact the record scan ignores.
    # An explicitly requested single stage always runs.
    if left() < 60.0 and not ctx.get("single_stage"):
        state.record(mlguard_skipped=True)
        _persist_json(
            os.path.join(
                STATE_DIR,
                f"mlguard_run_{time.strftime('%Y%m%d_%H%M%S')}.json"),
            {"skipped": True, "reason": "stage budget exhausted"})
        return
    rung = run_mlguard_rung(seed=0)
    state.record(
        mlguard_downloads=rung["downloads"],
        mlguard_success_rate=rung["success_rate"],
        mlguard_failures=rung["failures"][:5],
        mlguard_gate_rejected=rung["gate"]["rejected_offline"],
        mlguard_gate_trace_source=rung["gate"]["trace_source"],
        mlguard_shadow_rollback_s=rung["shadow_phase"]["rollback_s"],
        mlguard_shadow_incumbent_held=rung["shadow_phase"][
            "incumbent_held"],
        mlguard_guard_rollback_s=rung["guard_phase"]["rollback_s"],
        mlguard_rollback_bound_s=rung["rollback_bound_s"],
        mlguard_guard_trips=rung["counters"].get("ml_guard_trips"),
        mlguard_quality_mean=rung["quality_mean"],
        mlguard_quality_min=rung["quality_min"],
        mlguard_quarantines=rung["counters"].get("model_quarantines"),
        mlguard_rollbacks=rung["counters"].get("model_rollbacks"),
        mlguard_error=rung.get("error"),
        mlguard_verdict_pass=rung["verdict_pass"],
    )
    state.stage_done("mlguard")
    if rung["verdict_pass"]:
        _persist_json(
            os.path.join(
                STATE_DIR,
                f"mlguard_run_{time.strftime('%Y%m%d_%H%M%S')}.json"),
            rung)


@stage("replay")
def stage_replay(state: BenchState, ctx: dict) -> None:
    """Replay plane — the ISSUE-13 decision-quality A/B
    (dragonfly2_tpu/scheduler/replaybench.py): record a profiled-cost
    swarm's full announce decision stream (candidates + features +
    realized Welford costs + outcomes) into the rotating replay
    dataset, train a learned piece-cost model + a bandwidth MLP on the
    corpus, push both through the PR-12 validation gate, and replay
    the corpus head-to-head through rule vs ML vs learned-cost
    evaluators — reporting realized-cost regret, rank agreement,
    bad-node precision/recall and per-decision latency. Determinism is
    asserted (same corpus + seed ⇒ bit-identical decision sequence,
    each evaluator replayed twice), and the recorder overhead guard
    bounds announce p99 with the recorder ON within 5% of OFF
    (docs/REPLAY.md). A green run persists to
    artifacts/bench_state/replay_run_*.json — the record `bench.py
    replay --check-regression` reads; budget-starved runs record an
    explicit skip artifact, never a silent pass.

    The stage then climbs the vectorized replay throughput ladder
    (run_replay_throughput_ladder): synthetic columnar corpora at the
    10k/100k rungs, sequential vs whole-corpus vectorized vs sharded
    scoring — bit-identical digests required at every rung and the
    vectorized path ≥ 20× sequential on the 100k rung. A green ladder
    persists to replay_ladder_run_*.json (the throughput record
    --check-regression compares against); the same budget-skip
    artifact rule applies."""
    left = ctx["left"]

    from dragonfly2_tpu.scheduler.replaybench import (
        run_replay_ab, run_replay_throughput_ladder)

    # Budget gate inside the stage (the mlguard lesson): a registry
    # min_left skip would record nothing.
    if left() < 120.0 and not ctx.get("single_stage"):
        state.record(replay_skipped=True)
        _persist_json(
            os.path.join(
                STATE_DIR,
                f"replay_run_{time.strftime('%Y%m%d_%H%M%S')}.json"),
            {"skipped": True, "reason": "stage budget exhausted"})
        return
    report = run_replay_ab(seed=0)
    evaluators = (report.get("ab") or {}).get("evaluators") or {}
    state.record(
        replay_corpus_decisions=(report.get("record") or {}).get(
            "corpus_decisions"),
        replay_gate={name: g.get("state")
                     for name, g in (report.get("gate") or {}).items()},
        replay_deterministic=(report.get("ab") or {}).get("deterministic"),
        replay_regret_mean_s={name: s.get("regret_mean_s")
                              for name, s in evaluators.items()},
        replay_rank_agreement={name: s.get("rank_agreement_mean")
                               for name, s in evaluators.items()},
        replay_bad_node={name: {"precision": s.get("bad_node_precision"),
                                "recall": s.get("bad_node_recall")}
                         for name, s in evaluators.items()},
        replay_decision_latency_p99_ms={
            name: s.get("decision_latency_p99_ms")
            for name, s in evaluators.items()},
        replay_regret_within_bound=report.get("regret_within_bound"),
        replay_recorder_overhead_ratio=(report.get("recorder_overhead")
                                        or {}).get("p99_ratio"),
        replay_recorder_overhead_ok=(report.get("recorder_overhead")
                                     or {}).get("within_bound"),
        replay_error=report.get("error"),
        replay_verdict_pass=report.get("verdict_pass"),
    )
    if report.get("verdict_pass"):
        _persist_json(
            os.path.join(
                STATE_DIR,
                f"replay_run_{time.strftime('%Y%m%d_%H%M%S')}.json"),
            report)

    # Throughput ladder — same budget-skip discipline as the A/B: a
    # starved run leaves an explicit skip artifact, never nothing.
    if left() < 60.0 and not ctx.get("single_stage"):
        state.record(replay_ladder_skipped=True)
        _persist_json(
            os.path.join(
                STATE_DIR,
                f"replay_ladder_run_{time.strftime('%Y%m%d_%H%M%S')}.json"),
            {"skipped": True, "reason": "stage budget exhausted"})
        state.stage_done("replay")
        return
    ladder = run_replay_throughput_ladder()
    bound_rung = next(
        (r for r in ladder.get("rungs", ())
         if r.get("decisions") == ladder.get("bound_rung")), {})
    state.record(
        replay_ladder_rungs=[r.get("decisions")
                             for r in ladder.get("rungs", ())],
        replay_ladder_digests_equal=all(
            r.get("digests_equal") for r in ladder.get("rungs", ())),
        replay_ladder_seq_decisions_per_s=bound_rung.get(
            "seq_decisions_per_s"),
        replay_ladder_vec_decisions_per_s=bound_rung.get(
            "vec_decisions_per_s"),
        replay_ladder_speedup=bound_rung.get("speedup"),
        replay_ladder_sharded_speedup=bound_rung.get("sharded_speedup"),
        replay_ladder_bound=ladder.get("bound"),
        replay_ladder_error=ladder.get("error"),
        replay_ladder_verdict_pass=ladder.get("verdict_pass"),
    )
    state.stage_done("replay")
    if ladder.get("verdict_pass"):
        _persist_json(
            os.path.join(
                STATE_DIR,
                f"replay_ladder_run_{time.strftime('%Y%m%d_%H%M%S')}.json"),
            ladder)


@stage("obs")
def stage_obs(state: BenchState, ctx: dict) -> None:
    """Observability plane — the ISSUE-14 fleet-tracing stage
    (dragonfly2_tpu/client/obsbench.py): a live loopback swarm under a
    tail-sampling tracer with a ZERO head fraction. The clean warm-up
    task's trace must be dropped; a task disrupted by a seeded
    mid-download piece-body STALL must breach the SLO and be
    tail-captured END TO END (daemon + scheduler spans, one trace id),
    with the critical-path analyzer naming the injected stall as the
    dominant contributor; every registered /debug/vars stats block must
    scrape at /metrics in Prometheus text format; and the overhead
    guards must hold tracing-on within 1.05× of tracing-off on both
    the announce p99 and loopback MB/s (docs/OBSERVABILITY.md). A
    green run persists to artifacts/bench_state/obs_run_*.json; a
    budget-skipped stage records an explicit skip artifact, never a
    silent pass."""
    left = ctx["left"]

    from dragonfly2_tpu.client.obsbench import run_obs_stage

    # Budget gate inside the stage (the mlguard lesson): a registry
    # min_left skip would record nothing.
    if left() < 90.0 and not ctx.get("single_stage"):
        state.record(obs_skipped=True)
        _persist_json(
            os.path.join(
                STATE_DIR,
                f"obs_run_{time.strftime('%Y%m%d_%H%M%S')}.json"),
            {"skipped": True, "reason": "stage budget exhausted"})
        return
    report = run_obs_stage(seed=0)
    rung = report["rung"]
    state.record(
        obs_warm_trace_dropped=rung.get("warm_trace_dropped"),
        obs_disrupted_ttlb_s=rung.get("disrupted_ttlb_s"),
        obs_tail_reasons=rung.get("tail_reasons"),
        obs_dominant=(rung.get("analyzer") or {}).get("dominant"),
        obs_metrics_blocks=(rung.get("metrics_scrape") or {}).get(
            "blocks"),
        obs_metrics_all_exported=(rung.get("metrics_scrape") or {}).get(
            "all_blocks_exported"),
        obs_announce_p99_ratio=report["announce_guard"].get("p99_ratio"),
        obs_announce_within_bound=report["announce_guard"].get(
            "within_bound"),
        obs_loopback_ratio=report["loopback_guard"].get(
            "throughput_ratio"),
        obs_loopback_within_bound=report["loopback_guard"].get(
            "within_bound"),
        obs_failures=rung.get("failures", [])[:5],
        obs_verdict_pass=report["verdict_pass"],
    )
    state.stage_done("obs")
    if report["verdict_pass"]:
        _persist_json(
            os.path.join(
                STATE_DIR,
                f"obs_run_{time.strftime('%Y%m%d_%H%M%S')}.json"),
            report)


@stage("qos")
def stage_qos(state: BenchState, ctx: dict) -> None:
    """Multi-tenant QoS plane — the ISSUE-17 weighted-fair admission
    stage (dragonfly2_tpu/client/qosbench.py): a throttled seed serves
    interactive + bulk + background classed pulls CONCURRENTLY. The
    mixed rung gates interactive per-task p99 within its documented
    bound while bulk keeps ≥ 70% of its single-class saturation
    throughput; the flooding-tenant chaos rung gates that a background
    flood's 503 sheds land exclusively on the flooder and interactive
    still holds its (looser) bound (docs/QOS.md). A green run persists
    to artifacts/bench_state/qos_run_*.json; a budget-skipped stage
    records an explicit skip artifact, never a silent pass."""
    left = ctx["left"]

    from dragonfly2_tpu.client.qosbench import run_qos_stage

    # Budget gate inside the stage (the mlguard lesson): a registry
    # min_left skip would record nothing.
    if left() < 45.0 and not ctx.get("single_stage"):
        state.record(qos_skipped=True)
        _persist_json(
            os.path.join(
                STATE_DIR,
                f"qos_run_{time.strftime('%Y%m%d_%H%M%S')}.json"),
            {"skipped": True, "reason": "stage budget exhausted"})
        return
    report = run_qos_stage(seed=0)
    mixed, flood = report["mixed"], report["flood"]
    state.record(
        qos_interactive_p99_s=mixed.get("interactive_p99_s"),
        qos_interactive_p99_bound_s=mixed.get("interactive_p99_bound_s"),
        qos_bulk_alone_mb_per_s=mixed.get("bulk_alone_mb_per_s"),
        qos_bulk_mixed_mb_per_s=mixed.get("bulk_mixed_mb_per_s"),
        qos_bulk_fraction=mixed.get("bulk_fraction"),
        qos_upload_admitted_by_class=mixed.get(
            "upload_admitted_by_class"),
        qos_flood_interactive_p99_s=flood.get("interactive_p99_s"),
        qos_flood_shed_by_class=flood.get("upload_shed_by_class"),
        qos_flood_completed=flood.get("flood_completed"),
        qos_failures=(mixed.get("failures", [])
                      + flood.get("failures", []))[:5],
        qos_verdict_pass=report["verdict_pass"],
    )
    state.stage_done("qos")
    if report["verdict_pass"]:
        _persist_json(
            os.path.join(
                STATE_DIR,
                f"qos_run_{time.strftime('%Y%m%d_%H%M%S')}.json"),
            report)


@stage("fanout")
def stage_fanout(state: BenchState, ctx: dict) -> None:
    """Fleet-scale checkpoint fan-out — the ISSUE-9 dissemination
    ladder (client/fanoutbench.py): one throttled origin, a ≥256 MiB
    sharded checkpoint, cold fleet rungs of 4/16/32 in-process daemons
    plus a preheated variant at the largest rung. Reports
    time-to-last-byte per rung, origin-egress amplification, P2P share
    and per-daemon MB/s. Documented bounds (docs/FANOUT.md): cold
    amplification ≤ 2.0 at the 32-rung AND TTLB(32) ≤ 3× TTLB(4);
    preheated origin bytes ≈ 0. A green run persists to
    artifacts/bench_state/fanout_run_*.json — the record
    `bench.py fanout --check-regression` gates against. Budget-starved
    rungs record an explicit skip and withhold the verdict (never a
    silent pass)."""
    left = ctx["left"]

    from dragonfly2_tpu.client.fanoutbench import run_fanout_ladder

    ladder = run_fanout_ladder(seed=0, time_left=left)
    rungs = ladder["ladder"]
    largest = str(max(ladder["rungs"]))
    top = rungs.get(largest, {})
    state.record(
        fanout_rungs=ladder["rungs"],
        fanout_checkpoint_mb=ladder["checkpoint_bytes"] >> 20,
        fanout_origin_rate_mb_per_s=ladder["origin_rate_mb_per_s"],
        fanout_skipped_rungs=ladder["skipped_rungs"],
        fanout_ttlb_ratio=ladder.get("ttlb_ratio"),
        fanout_ttlb_ratio_bound=ladder["ttlb_ratio_bound"],
        fanout_cold_amplification=ladder.get("cold_amplification_at_max"),
        fanout_amplification_bound=ladder["amplification_bound"],
        fanout_cold_ttlb_s=top.get("ttlb_s"),
        fanout_cold_p2p_share=top.get("p2p_share"),
        fanout_per_daemon_mb_per_s_p50=top.get("per_daemon_mb_per_s_p50"),
        fanout_preheat_origin_fraction=ladder.get(
            "preheat_origin_fraction"),
        fanout_preheat_ttlb_s=(ladder.get("preheated") or {}).get(
            "ttlb_s"),
        fanout_ladder={
            n: {k: v.get(k) for k in (
                "ttlb_s", "origin_amplification", "p2p_share",
                "per_daemon_mb_per_s_p50", "per_daemon_mb_per_s_min",
                "success_rate", "origin_requests", "downloads",
                "failures")}
            for n, v in rungs.items()},
    )
    if "verdict_pass" in ladder:
        state.record(fanout_verdict_pass=ladder["verdict_pass"])
    state.stage_done("fanout")
    if ladder.get("verdict_pass"):
        _persist_json(
            os.path.join(
                STATE_DIR,
                f"fanout_run_{time.strftime('%Y%m%d_%H%M%S')}.json"),
            ladder)


@stage("geo")
def stage_geo(state: BenchState, ctx: dict) -> None:
    """Geo-hierarchical multi-site swarm — the ISSUE-18 WAN-aware
    routing ladder (client/geobench.py): three emulated sites of
    ``--cluster-id``-labeled daemon processes joined by seeded WAN
    link emulation (utils/geoplan.py), pulling a sharded checkpoint
    through scheduler-elected bridge peers. Gates (docs/GEO.md): cold
    WAN amplification ≤ 1 + #clusters at the largest rung with at
    least one bridge elected; cross-site preheat leaves the swarm
    phase WAN- and origin-quiet; the site-partition chaos rung's
    surviving sites finish 100% and the victim resumes crash-safe
    within the documented bound after heal. A green run persists to
    artifacts/bench_state/geo_run_*.json; a budget-skipped stage
    records an explicit skip artifact + ``geo_skipped``, never a
    silent pass."""
    left = ctx["left"]

    from dragonfly2_tpu.client.geobench import run_geo_ladder

    # Budget gate inside the stage (the mlguard lesson): a registry
    # min_left skip would record nothing.
    if left() < 120.0 and not ctx.get("single_stage"):
        state.record(geo_skipped=True)
        _persist_json(
            os.path.join(
                STATE_DIR,
                f"geo_run_{time.strftime('%Y%m%d_%H%M%S')}.json"),
            {"skipped": True, "reason": "stage budget exhausted"})
        return
    ladder = run_geo_ladder(seed=0, time_left=left)
    rungs = ladder["ladder"]
    largest = str(max(ladder["rungs"]))
    top = rungs.get(largest, {})
    partition = ladder.get("partition") or {}
    state.record(
        geo_sites=ladder["sites"],
        geo_rungs=ladder["rungs"],
        geo_checkpoint_mb=ladder["checkpoint_bytes"] >> 20,
        geo_skipped_rungs=ladder["skipped_rungs"],
        geo_wan_amplification=ladder.get("cold_wan_amplification_at_max"),
        geo_wan_amplification_bound=ladder["wan_amplification_bound"],
        geo_cold_ttlb_s=top.get("ttlb_s"),
        geo_site_ttlb_s=top.get("site_ttlb_s"),
        geo_bridge_grants=top.get("bridge_grants"),
        geo_bridge_denials=top.get("bridge_denials"),
        geo_origin_amplification=top.get("origin_amplification"),
        geo_preheat_wan_fraction=ladder.get("preheat_wan_fraction"),
        geo_preheat_origin_fraction=ladder.get(
            "preheat_origin_fraction"),
        geo_partition_survivor_success=partition.get(
            "survivor_success_rate"),
        geo_partition_resume_seconds=partition.get(
            "victim_resume_seconds"),
        geo_partition_resume_bound_s=partition.get("resume_bound_s"),
        geo_failures=(top.get("failures", [])
                      + partition.get("failures", []))[:5],
    )
    if "verdict_pass" in ladder:
        state.record(geo_verdict_pass=ladder["verdict_pass"])
    state.stage_done("geo")
    if ladder.get("verdict_pass"):
        _persist_json(
            os.path.join(
                STATE_DIR,
                f"geo_run_{time.strftime('%Y%m%d_%H%M%S')}.json"),
            ladder)


@stage("federated")
def stage_federated(state: BenchState, ctx: dict) -> None:
    """Byzantine-robust federated rounds — the ISSUE-20 stage
    (dragonfly2_tpu/train/fedbench.py): heterogeneous synthetic cluster
    corpora train a global bandwidth model through screened federated
    rounds (trainer/federation.py coordinator: norm/holdout/nonfinite
    admission screens, K-of-N quorum, durable round journal). Gates
    (docs/FEDERATED.md): the CLEAN rung's gate-promoted global must
    match-or-beat the best solo cluster model's replay-A/B regret on
    the mixed eval corpus, bit-deterministically; the POISONED rung's
    label-flipped/scaled cluster and NaN-params cluster must BOTH be
    screened every round, the persistent liar escalated to registry
    quarantine, and poisoned-fleet regret held within 1.2x clean; the
    COORDINATOR-KILL rung SIGKILLs a subprocess coordinator mid-round
    and must resume from the journal, committing the SAME round without
    retraining journaled clusters. A green run persists to
    artifacts/bench_state/federated_run_*.json; a budget-skipped stage
    records an explicit skip artifact + ``federated_skipped``, never a
    silent pass."""
    left = ctx["left"]

    from dragonfly2_tpu.train.fedbench import run_federated_bench

    # Budget gate inside the stage (the mlguard lesson): a registry
    # min_left skip would record nothing.
    if left() < 180.0 and not ctx.get("single_stage"):
        state.record(federated_skipped=True)
        _persist_json(
            os.path.join(
                STATE_DIR,
                f"federated_run_{time.strftime('%Y%m%d_%H%M%S')}.json"),
            {"skipped": True, "reason": "stage budget exhausted"})
        return
    # The kill rung costs two subprocess cold starts (~60s); drop it
    # when the budget is tight rather than losing the whole stage.
    report = run_federated_bench(seed=0,
                                 include_kill=bool(
                                     left() >= 300.0
                                     or ctx.get("single_stage")))
    clean, poisoned, kill = (report["clean"], report["poisoned"],
                             report["kill"])
    state.record(
        federated_rounds=len(clean.get("rounds", [])),
        federated_gate_state=clean.get("gate_state"),
        federated_regret_s=clean.get("federated_regret"),
        federated_best_solo_regret_s=clean.get("best_solo_regret"),
        federated_deterministic=clean.get("deterministic"),
        federated_clean_ok=clean.get("ok"),
        federated_screened_reasons=poisoned.get("screened_reasons"),
        federated_screens_ok=poisoned.get("screens_ok"),
        federated_escalated=poisoned.get("escalated"),
        federated_quarantined_version=poisoned.get("quarantined_version"),
        federated_poisoned_regret_s=poisoned.get("regret"),
        federated_within_poison_bound=poisoned.get("within_poison_bound"),
        federated_poisoned_ok=poisoned.get("ok"),
        federated_kill_ran=kill.get("ran"),
        federated_kill_resumed=kill.get("resumed"),
        federated_kill_no_retrain=kill.get("no_retrain"),
        federated_kill_ok=kill.get("ok"),
        federated_error=report.get("error"),
        federated_verdict_pass=report.get("verdict_pass"),
    )
    state.stage_done("federated")
    if report.get("verdict_pass"):
        _persist_json(
            os.path.join(
                STATE_DIR,
                f"federated_run_{time.strftime('%Y%m%d_%H%M%S')}.json"),
            report)


def run_stages(state: BenchState, budget: float,
               only: str | None = None,
               stage_opts: dict | None = None) -> None:
    """Drive the registry: the device stages (``only`` None — the
    `bench.py` path), or one named stage, preceded by the init stage
    when it needs the device (the `bench.py <stage>` path).
    ``stage_opts`` carries CLI per-stage options (e.g. the scheduler
    stage's ``rungs``/``cluster_peers``) into the stage ctx.

    A device stage that raises ends the run (non-zero exit, the cause
    on stderr). A host gate stage owes the driver its JSON line: its
    failure is recorded as <name>_error instead."""
    t_start = time.perf_counter()

    def left() -> float:
        return budget - (time.perf_counter() - t_start)

    ctx: dict = {"left": left, "single_stage": only is not None}
    ctx.update(stage_opts or {})
    by_name = {s.name: s for s in STAGES}
    if only is None:
        wanted = [s for s in STAGES if s.required]
    elif only not in by_name:
        raise SystemExit(
            f"unknown stage {only!r}; stages: {', '.join(by_name)}")
    else:
        wanted = [by_name[only]]
        if wanted[0].needs_device:
            wanted.insert(0, by_name["init"])
    for st in wanted:
        # An explicitly requested stage bypasses its budget gate — a
        # driver asking for `bench.py mlp` must get the stage (or its
        # error), never a skip that reads as pass.
        if only is None and st.min_left and left() < st.min_left:
            state.record(**{f"{st.name}_skipped": "budget"})
            continue
        if st.required:
            st.fn(state, ctx)
            continue
        try:
            st.fn(state, ctx)
        except Exception as exc:  # noqa: BLE001
            state.record(**{f"{st.name}_error":
                            f"{type(exc).__name__}: {exc}"})


def main(only: str | None = None, stage_opts: dict | None = None) -> None:
    """`bench.py`: the device stages; `bench.py <stage>`: ONE registry
    stage with the full budget — e.g. `bench.py gnn`, `bench.py chaos`
    or `bench.py scheduler --rungs 100,1000`. Prints the result as the
    JSON line; a single stage also persists it beside the gates'
    records."""
    out_path = None
    if only is not None:
        os.makedirs(STATE_DIR, exist_ok=True)
        out_path = os.path.join(STATE_DIR, f"stage_{only}.json")
    state = BenchState(out_path)
    run_stages(state, BUDGET_S, only=only, stage_opts=stage_opts)
    state.emit()


def parse_stage_opts(argv: list) -> dict:
    """Per-stage CLI options after the stage name. ``--rungs 100,1000``
    trims the scheduler's in-process ladder without editing source (the
    dev-box path); ``--cluster-peers N`` pins the cluster-rung swarm
    size (0 skips the cluster ladder)."""
    opts: dict = {}
    i = 0
    while i < len(argv):
        arg = argv[i]
        if arg == "--rungs" and i + 1 < len(argv):
            # Sorted + deduped: the ladder verdict compares LAST rung
            # against FIRST — a descending list would invert the ratio
            # and trivially green-light a contention regression.
            opts["rungs"] = sorted(
                {int(s) for s in argv[i + 1].split(",") if s})
            i += 2
        elif arg == "--cluster-peers" and i + 1 < len(argv):
            opts["cluster_peers"] = int(argv[i + 1])
            i += 2
        else:
            raise SystemExit(f"unknown stage option {arg!r} "
                             "(have: --rungs N,N,..., --cluster-peers N)")
    return opts


def check_regression_main(stage_name: str) -> None:
    """`bench.py <stage> --check-regression` — the one-command perf/
    robustness gates: a fresh run vs the best persisted
    artifacts/bench_state record, exiting non-zero on regression.

    - ``dataplane``: fresh upload-loopback rung vs the best recorded
      MB/s (docs/DATAPLANE.md fraction), PLUS a fresh download density
      rung + async-engine loopback + native splice rung — fails on a
      download thread-census breach at any rung, a density aggregate
      under 0.5× the best record, a single-task loopback under 0.7×
      the recorded MB/s, or a splice loopback under 0.5× the recorded
      splice MB/s.
    - ``chaos``: fresh fault ladder + daemon-kill rung vs the best
      recorded chaos run (docs/CHAOS.md) — any lost verdict or a
      goodput-retention collapse fails the gate.
    - ``fanout``: fresh dissemination ladder vs the best recorded
      fanout run (docs/FANOUT.md) — a lost verdict or a 2× TTLB /
      amplification collapse fails the gate.
    - ``scheduler``: fresh top-rung swarm run vs the best recorded
      scheduler run (docs/SCHEDULER.md) — under 0.5× the recorded
      decisions/sec or over 2× the recorded announce p99 fails.
    - ``mlguard``: a fresh poisoned-model rung must hold its absolute
      bounds (gate rejection, 100 % success, rollback ≤ 2 ×
      reload_interval, quality floor — docs/CHAOS.md); the best
      record rides along for trend reading.
    - ``replay``: a fresh record→gate→A/B pass must hold its absolute
      bounds (bit-identical determinism, both models gate-promoted,
      ML/learned-cost regret within the documented delta of the rule
      baseline, recorder overhead ≤ 5% — docs/REPLAY.md), PLUS a
      fresh vectorized throughput-ladder rung with bit-identical
      digests and vectorized decisions/sec ≥ 0.33× the best persisted
      replay_ladder_run record.
    - ``obs``: a fresh observability stage must hold its absolute
      bounds (disrupted task tail-captured end to end, analyzer blames
      the injected stall, every stats block scrapeable, tracing
      overhead ≤ 1.05× on announce p99 and loopback MB/s —
      docs/OBSERVABILITY.md).
    - ``qos``: a fresh mixed-workload + flooding-tenant stage must
      hold its absolute bounds (interactive p99 within bound in both
      rungs, bulk ≥ 70% of its alone throughput, sheds only on the
      flooding class — docs/QOS.md).
    - ``geo``: fresh multi-site ladder vs the best recorded geo run
      (docs/GEO.md) — a lost verdict (including the site-partition
      rung) or a 2× TTLB / WAN-amplification collapse fails the
      gate.
    - ``federated``: a fresh clean + poisoned federated pass (kill
      rung skipped — subprocess cold starts don't belong in a quick
      gate) must hold its absolute bounds (screens catch both the
      flipped/scaled and NaN clusters, gate-promoted global
      matches-or-beats the best solo regret, poisoned regret within
      1.2× clean — docs/FEDERATED.md); the best record rides along
      for trend reading."""
    if stage_name == "dataplane":
        from dragonfly2_tpu.client.dataplane import (
            check_download_regression,
        )
        from dragonfly2_tpu.client.uploadbench import check_regression

        upload = check_regression(STATE_DIR)
        download = check_download_regression(STATE_DIR)
        result = {"upload": upload, "download": download,
                  "passed": bool(upload["passed"] and download["passed"])}
    elif stage_name == "chaos":
        from dragonfly2_tpu.client.chaosbench import check_chaos_regression

        result = check_chaos_regression(STATE_DIR)
    elif stage_name == "fanout":
        from dragonfly2_tpu.client.fanoutbench import check_fanout_regression

        result = check_fanout_regression(STATE_DIR)
    elif stage_name == "scheduler":
        from dragonfly2_tpu.scheduler.loadbench import (
            check_scheduler_regression,
        )

        result = check_scheduler_regression(STATE_DIR)
    elif stage_name == "mlguard":
        from dragonfly2_tpu.inference.guardbench import (
            check_mlguard_regression,
        )

        result = check_mlguard_regression(STATE_DIR)
    elif stage_name == "replay":
        from dragonfly2_tpu.scheduler.replaybench import (
            check_replay_regression,
        )

        result = check_replay_regression(STATE_DIR)
    elif stage_name == "obs":
        from dragonfly2_tpu.client.obsbench import check_obs_regression

        result = check_obs_regression(STATE_DIR)
    elif stage_name == "qos":
        from dragonfly2_tpu.client.qosbench import check_qos_regression

        result = check_qos_regression(STATE_DIR)
    elif stage_name == "geo":
        from dragonfly2_tpu.client.geobench import check_geo_regression

        result = check_geo_regression(STATE_DIR)
    elif stage_name == "federated":
        from dragonfly2_tpu.train.fedbench import (
            check_federated_regression,
        )

        result = check_federated_regression(STATE_DIR)
    else:
        raise SystemExit(
            f"no regression gate for stage {stage_name!r} "
            "(have: dataplane, chaos, fanout, scheduler, mlguard, "
            "replay, obs, qos, geo, federated)")
    print(json.dumps(result), flush=True)
    sys.exit(0 if result["passed"] else 1)


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[2] == "--check-regression":
        check_regression_main(sys.argv[1])
    elif len(sys.argv) >= 2 and not sys.argv[1].startswith("-"):
        main(sys.argv[1], parse_stage_opts(sys.argv[2:]))
    else:
        main()
