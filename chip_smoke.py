"""Chip smoke: the three accelerator jobs of this system, once each, on
the attached TPU, at the full width of the models the repo ships.

    python chip_smoke.py             # one chip, phases 1-4
    python chip_smoke.py --chips 4   # four chips, the data-parallel phase only

One process, which is the one that holds the chip. ``df2-trainer`` and
``df2-inference`` are separate services in production, but a chip
belongs to one process, so the services stand up IN THIS PROCESS through
the functions the commands use (``rpc.serve`` of ``TrainerService`` /
``InferenceService``, a ``ManagerService`` + ``FilesystemObjectStore``
registry, an in-process ``Daemon``) and talk over loopback gRPC / HTTP.

Phases (each prints one JSON line as it finishes; any failure is fatal):

1. device  — platform must be ``tpu``; versions, compile cache, data plane
2. train   — synthetic scheduler datasets → announcer → gRPC ``Train``
             stream → ``Training.train`` (GraphSAGE, MLP, GraphTransformer
             at default widths) → registry
3. serve   — sidecar hot-loads the registered models; ``ModelInfer`` over
             gRPC through the micro-batcher; scores checked against the
             same params applied with numpy on the host
4. sink    — a safetensors file fetched by a ``Daemon`` from a loopback
             origin through ``download_to_hbm``; every byte compared

The repo holds no hand-written kernel: the GraphTransformer computes one
graph attention (gather mode, with a ring layout for sharded K/V) in
plain XLA, and the sequence model takes JAX's own splash-attention and
megablox kernels; the benchmark's cells time them.

There is no CPU path: without a TPU the script exits non-zero before any
phase. The last line of stdout is the one the driver reads:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Rehearsal (tests/test_chip_smoke.py) imports the phase functions and
calls them with ``Sizes.tiny()`` on CPU devices; that way in is not an
option of this script.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

SCHEDULER_ID = 1  # one scheduler cluster; both announcing hosts belong to it
# Device scores against the registered params applied in numpy with bf16
# rounding, relative to max(|score|, 1). Measured on the v5e (PR 21):
# MLP 0.0099, GraphTransformer head 0.0064 — a handful of bf16 roundings
# (2^-8 = 0.4 % each); a 2x margin, a wrong weight is far outside it.
SERVE_REL_ERR = 2e-2
# Per-epoch mean loss, 4-device mesh against 1-device mesh: same seed,
# batches and sampling hash; only reduction order differs.
DATA_PARALLEL_RTOL = 5e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Data and state sizes of one run. Widths are not here: every model
    runs at its config's defaults."""

    sage_hosts: int          # GraphSAGE dataset (bench.py's on-chip size)
    sage_records: int        # NetworkTopology records, 5 probes each
    sage_batch: int
    sage_steps_per_call: int
    mlp_downloads: int       # Download records (≤20 parents each)
    mlp_batch: int
    gat_hosts: int           # GraphTransformer dataset (BASELINE config #3)
    gat_records: int
    gat_cap: int
    gat_batch: int
    epochs: int
    max_seconds: float       # per-job cap on the step loop
    infer_requests: int
    infer_rows: int
    sink_tensors: int
    sink_tensor_elems: int   # bf16 elements per tensor

    @classmethod
    def full(cls) -> "Sizes":
        return cls(sage_hosts=2_000, sage_records=400_000, sage_batch=8192,
                   sage_steps_per_call=16, mlp_downloads=2_200,
                   mlp_batch=16_384, gat_hosts=20_000, gat_records=100_000,
                   gat_cap=64, gat_batch=8192, epochs=2, max_seconds=120.0,
                   infer_requests=48, infer_rows=16, sink_tensors=32,
                   sink_tensor_elems=1 << 24)  # 32 × 32 MiB = 1 GiB

    @classmethod
    def tiny(cls) -> "Sizes":
        return cls(sage_hosts=64, sage_records=400, sage_batch=128,
                   sage_steps_per_call=2, mlp_downloads=40, mlp_batch=64,
                   gat_hosts=96, gat_records=500, gat_cap=8, gat_batch=128,
                   epochs=2, max_seconds=60.0, infer_requests=8,
                   infer_rows=16, sink_tensors=4,
                   sink_tensor_elems=1 << 18)


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


def run_phase(name: str, fn, device) -> dict:
    """Run one phase, print its JSON line, return its details."""
    t0 = time.perf_counter()
    try:
        details = fn()
    except BaseException as exc:
        emit(phase=name, ok=False, error=f"{type(exc).__name__}: {exc}",
             seconds=round(time.perf_counter() - t0, 2))
        raise
    stats = device.memory_stats() or {}
    emit(phase=name, ok=True, seconds=round(time.perf_counter() - t0, 2),
         peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         bytes_in_use=stats.get("bytes_in_use"), **details)
    return details


def all_on(tree, device) -> bool:
    import jax

    return all(leaf.devices() == {device} for leaf in jax.tree.leaves(tree))


# ----------------------------------------------------------------------
# Phase 1: device
# ----------------------------------------------------------------------

def require_tpu(chips: int):
    """The devices, or exit: nothing below has a CPU meaning."""
    import jax

    devices = jax.devices()
    platforms = sorted({d.platform for d in devices})
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke.py: needs a TPU; JAX found {platforms}")
    if len(devices) != chips:
        raise SystemExit(f"chip_smoke.py: --chips {chips} but JAX found "
                         f"{len(devices)} device(s)")
    return devices


def phase_device(devices, cache_dir: str) -> dict:
    from importlib import metadata

    import jax
    import jaxlib

    from dragonfly2_tpu import native

    def version(dist: str) -> str:
        try:
            return metadata.version(dist)
        except metadata.PackageNotFoundError:
            return "not installed"

    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": version("libtpu"),
        "compile_cache_dir": cache_dir,
        "compile_cache_set_by_env": bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "data_plane": ("native C++ (pieceio.cpp)" if native.available()
                       else "pure-Python fallback"),
    }


# ----------------------------------------------------------------------
# Phase 2: train
# ----------------------------------------------------------------------

class _TrainerClient:
    """The scheduler's view of the trainer (cmd/scheduler.py)."""

    def __init__(self, target: str):
        from dragonfly2_tpu.rpc import ServiceClient
        from dragonfly2_tpu.trainer import TRAINER_SPEC

        self.cli = ServiceClient(target, TRAINER_SPEC)

    def train(self, requests):
        return self.cli.Train(requests, timeout=3600)


def phase_train(sizes: Sizes, seed: int, workdir: str, manager) -> dict:
    """Two scheduler hosts of one cluster announce their datasets to one
    ``df2-trainer --train-gat``: host A the GraphSAGE-sized probe graph
    plus download records, host B the config #3 topology. The trainer
    runs every job it has data for, as it does in production."""
    from dragonfly2_tpu import __version__
    from dragonfly2_tpu.data import SyntheticCluster
    from dragonfly2_tpu.rpc import serve
    from dragonfly2_tpu.scheduler.announcer import Announcer, AnnouncerConfig
    from dragonfly2_tpu.scheduler.storage import Storage
    from dragonfly2_tpu.schema import MAX_DEST_HOSTS, MAX_PARENTS
    from dragonfly2_tpu.train import (
        GATTrainConfig,
        GNNTrainConfig,
        MLPTrainConfig,
    )
    from dragonfly2_tpu.trainer import (
        TRAINER_SPEC,
        TrainerService,
        TrainerStorage,
        Training,
        TrainingConfig,
    )
    from dragonfly2_tpu.trainer.metrics import TrainerMetrics

    jobs: dict = {}      # (host_id, model) -> the job's own accounting
    compile_s: dict = {}  # model -> compile seconds of the running cycle

    def on_compile(model: str):
        # Cumulative per job: the first step plus any tail program.
        return lambda seconds: compile_s.__setitem__(model, round(seconds, 2))

    config = TrainingConfig(
        gnn=GNNTrainConfig(batch_size=sizes.sage_batch,
                           steps_per_call=sizes.sage_steps_per_call,
                           epochs=sizes.epochs, max_seconds=sizes.max_seconds,
                           seed=seed, compile_callback=on_compile("gnn")),
        mlp=MLPTrainConfig(batch_size=sizes.mlp_batch, epochs=5 * sizes.epochs,
                           max_seconds=sizes.max_seconds, seed=seed,
                           compile_callback=on_compile("mlp")),
        gat=GATTrainConfig(edge_batch_size=sizes.gat_batch,
                           neighbor_cap=sizes.gat_cap, epochs=sizes.epochs,
                           max_seconds=sizes.max_seconds, seed=seed,
                           compile_callback=on_compile("gat")),
        train_gat_model=True,
    )

    outcomes: dict = {}

    class RecordingTraining(Training):
        """``Training`` as the command builds it, keeping what a cycle
        returns and what each job reports to the trainer's metrics."""

        # Cycles queue on the trainer's lock, each in its own thread.
        cycle = threading.local()

        def train(self, ip, hostname, host_id, scheduler_id=0):
            self.cycle.host_id = host_id
            outcomes[host_id] = super().train(ip, hostname, host_id,
                                              scheduler_id)
            return outcomes[host_id]

        def _observe_job(self, model, seconds, samples_per_sec):
            jobs[(self.cycle.host_id, model)] = {
                "compile_s": compile_s.pop(model, None),
                "job_wall_s": round(seconds, 2),
                "steady_samples_per_sec": round(samples_per_sec)}
            super()._observe_job(model, seconds, samples_per_sec)

    storage = TrainerStorage(os.path.join(workdir, "trainer"))
    metrics = TrainerMetrics(version=__version__)
    service = TrainerService(
        storage, RecordingTraining(storage, manager, config=config,
                                   metrics=metrics), metrics=metrics)
    server = serve([(TRAINER_SPEC, service)])

    timings: dict = {}
    edges: dict = {}
    try:
        for host_id, n_hosts, n_records, n_downloads in (
                ("sched-a", sizes.sage_hosts, sizes.sage_records,
                 sizes.mlp_downloads),
                ("sched-b", sizes.gat_hosts, sizes.gat_records, 0)):
            t0 = time.perf_counter()
            cluster = SyntheticCluster(n_hosts=n_hosts, seed=seed)
            sched_storage = Storage(os.path.join(workdir, host_id))
            # Full records: the most probe edges per row the trainer's
            # host ingest has to parse.
            edges[host_id] = cluster.write_topology_csv(
                n_records, sched_storage.network_topology.active_path,
                n_dest=MAX_DEST_HOSTS)
            for record in cluster.downloads(n_downloads,
                                            max_parents=MAX_PARENTS):
                sched_storage.create_download(record)
            timings[f"{host_id}_generate_s"] = round(
                time.perf_counter() - t0, 2)
            t0 = time.perf_counter()
            response = Announcer(
                host_id=host_id, ip="127.0.0.1", hostname=host_id, port=0,
                storage=sched_storage,
                trainer_client=_TrainerClient(server.target),
                config=AnnouncerConfig(upload_chunk=8 << 20),
                scheduler_id=SCHEDULER_ID,
            ).train()
            check(response is not None and response.accepted_bytes > 0,
                  f"{host_id}: trainer accepted no bytes")
            timings[f"{host_id}_upload_s"] = round(
                time.perf_counter() - t0, 2)
            timings[f"{host_id}_upload_mb"] = round(
                response.accepted_bytes / 2**20, 1)
        t0 = time.perf_counter()
        service.wait_idle(timeout=3600)
        timings["train_wait_s"] = round(time.perf_counter() - t0, 2)
    finally:
        server.stop()

    report: dict = {"probe_edges": edges, **timings}
    expected = {"sched-a": ("gnn", "mlp", "gat"), "sched-b": ("gnn", "gat")}
    for host_id, models in expected.items():
        outcome = outcomes.get(host_id)
        check(outcome is not None, f"{host_id}: training never ran")
        check(not outcome.errors, f"{host_id}: {outcome.errors}")
        for model in models:
            check(getattr(outcome, f"{model}_model_id"),
                  f"{host_id}: {model} not trained")
            history = outcome.loss_history[model]
            check(len(history) >= 2 and np.isfinite(history).all(),
                  f"{host_id}: {model} loss not finite: {history}")
            check(history[-1] < history[0],
                  f"{host_id}: {model} loss did not decrease: {history}")
            evaluation = getattr(outcome, f"{model}_evaluation")
            check(all(np.isfinite(v) for v in evaluation.values()),
                  f"{host_id}: {model} evaluation not finite: {evaluation}")
            report[f"{host_id}_{model}"] = {
                "loss": [round(float(v), 4) for v in history],
                "evaluation": {k: round(float(v), 4)
                               for k, v in evaluation.items()},
                **jobs[(host_id, model)]}
    for model in ("gnn", "mlp", "gat"):
        check(manager.get_active_model(model, SCHEDULER_ID) is not None,
              f"no active {model} model in the registry")
    return report


# ----------------------------------------------------------------------
# Phase 3: serve
# ----------------------------------------------------------------------

def _bf16(a):
    import ml_dtypes

    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def _gelu(x):
    return 0.5 * x * (1.0 + np.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def mlp_reference(params, normalizer, target_norm, x) -> np.ndarray:
    """``ParentScorer.forward`` in numpy: the model computes in bf16
    (inputs, weights and every activation rounded), accumulates in f32."""
    layers = params["params"]
    h = _bf16((x - normalizer.mean) / normalizer.std)
    n_layers = len(layers)
    for i in range(n_layers):
        dense = layers[f"Dense_{i}"]
        h = _bf16(h @ _bf16(dense["kernel"]) + _bf16(dense["bias"]))
        if i < n_layers - 1:
            h = _bf16(_gelu(h))
    return (h[..., 0] * float(target_norm.std[0])
            + float(target_norm.mean[0]))


def gat_head_reference(params, emb, pairs) -> np.ndarray:
    """``GraphTransformer.score_pairs`` in numpy over the embedding table
    the sidecar computed at load."""
    p = params["params"]
    pair = np.concatenate([emb[pairs[:, 0]], emb[pairs[:, 1]]], axis=-1)
    h = _bf16(_bf16(pair) @ _bf16(p["head_hidden"]["kernel"])
              + _bf16(p["head_hidden"]["bias"]))
    h = np.maximum(h, 0.0)
    out = h @ np.asarray(p["head_out"]["kernel"], np.float32) + np.asarray(
        p["head_out"]["bias"], np.float32)
    return out[..., 0]


def _load_registered(manager, model_type: str, workdir: str):
    from dragonfly2_tpu.manager.service import untar_to_directory
    from dragonfly2_tpu.train.checkpoint import load_model

    active = manager.get_active_model(model_type, SCHEDULER_ID)
    check(active is not None, f"no active {model_type} model")
    tmp = tempfile.mkdtemp(prefix=f"registered-{model_type}-", dir=workdir)
    untar_to_directory(active.artifact, tmp)
    tree, _ = load_model(tmp)
    import jax

    return jax.tree.map(np.asarray, tree), active.version


def phase_serve(sizes: Sizes, seed: int, workdir: str, manager,
                device) -> dict:
    from concurrent.futures import ThreadPoolExecutor

    from dragonfly2_tpu.data import SyntheticCluster
    from dragonfly2_tpu.inference.sidecar import (
        INFERENCE_SPEC,
        InferenceClient,
        InferenceService,
    )
    from dragonfly2_tpu.rpc import serve
    from dragonfly2_tpu.train.checkpoint import gat_from_tree, mlp_from_tree

    t0 = time.perf_counter()
    service = InferenceService(manager=manager, scheduler_id=SCHEDULER_ID)
    check(service.reload_from_manager(), "sidecar loaded nothing")
    load_s = time.perf_counter() - t0
    server = serve([(INFERENCE_SPEC, service)])
    service.set_health(server.health)
    client = InferenceClient(server.target, timeout=60.0)
    report: dict = {"load_seconds": round(load_s, 2)}
    try:
        for name in ("mlp", "gat"):
            check(client.model_ready(name), f"{name} not ready")
            scorer = service._models[name].scorer
            resident = [scorer._params] + (
                [scorer._emb] if name == "gat" else [])
            check(all_on(resident, device),
                  f"{name} scorer arrays are not all on {device}")

        mlp_tree_, mlp_version = _load_registered(manager, "mlp", workdir)
        params, normalizer, target_norm = mlp_from_tree(mlp_tree_)
        features, _ = SyntheticCluster(
            n_hosts=sizes.sage_hosts, seed=seed + 1).pair_example_columns(
                sizes.infer_requests * sizes.infer_rows)
        mlp_batches = np.split(features, sizes.infer_requests)

        gat_tree_, gat_version = _load_registered(manager, "gat", workdir)
        gat_params = gat_from_tree(gat_tree_)[0]
        gat_scorer = service._models["gat"].scorer
        emb = np.asarray(gat_scorer._emb, np.float32)
        pairs = np.random.default_rng(seed).integers(
            0, gat_scorer.n_real,
            (sizes.infer_requests, sizes.infer_rows, 2))

        latencies: list = []

        def infer(name, inputs):
            t = time.perf_counter()
            scores, version = client.model_infer_full(name, inputs)
            latencies.append((time.perf_counter() - t) * 1e3)
            return scores, version

        # Four scheduler threads, so the micro-batcher has something to
        # coalesce.
        with ThreadPoolExecutor(4) as pool:
            mlp_out = list(pool.map(lambda x: infer("mlp", x), mlp_batches))
            gat_out = list(pool.map(lambda p: infer("gat", p), pairs))

        for (name, outs, inputs, reference, version) in (
                ("mlp", mlp_out, mlp_batches,
                 lambda x: mlp_reference(params, normalizer, target_norm, x),
                 mlp_version),
                ("gat", gat_out, pairs,
                 lambda p: gat_head_reference(gat_params, emb, p),
                 gat_version)):
            worst = 0.0
            for (scores, served), x in zip(outs, inputs):
                check(served == version,
                      f"{name}: served {served}, registry has {version}")
                check(scores.shape == (sizes.infer_rows,)
                      and np.isfinite(scores).all(),
                      f"{name}: bad scores {scores}")
                want = reference(x)
                worst = max(worst, float(np.max(
                    np.abs(scores - want) / np.maximum(np.abs(want), 1.0))))
            check(worst < SERVE_REL_ERR,
                  f"{name}: device scores differ from the host reference "
                  f"by {worst:.3g} (relative)")
            report[f"{name}_max_rel_err_vs_numpy"] = round(worst, 5)
        stats = service.batcher_stats()
        report["requests"] = len(latencies)
        report["request_ms_p50_host_clock"] = round(
            float(np.median(latencies)), 3)
        report["coalesce_factor"] = {
            k: v.get("coalesce_factor") for k, v in stats.items()}
    finally:
        client.close()
        service.stop()
        server.stop()
    return report


# ----------------------------------------------------------------------
# Phase 4: sink
# ----------------------------------------------------------------------

def phase_sink(sizes: Sizes, seed: int, workdir: str, device) -> dict:
    import ml_dtypes

    from dragonfly2_tpu.client.daemon import Daemon, DaemonConfig
    from dragonfly2_tpu.client.dataplane import BlobRangeServer
    from dragonfly2_tpu.client.hbm_sink import (
        download_to_hbm,
        write_safetensors,
    )
    from dragonfly2_tpu.scheduler.evaluator.base import BaseEvaluator
    from dragonfly2_tpu.scheduler.resource.resource import Resource
    from dragonfly2_tpu.scheduler.scheduling.core import (
        Scheduling,
        SchedulingConfig,
    )
    from dragonfly2_tpu.scheduler.service import SchedulerService
    from dragonfly2_tpu.scheduler.storage import Storage

    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    tensors = {
        f"layers.{i}.weight": rng.standard_normal(
            sizes.sink_tensor_elems, dtype=np.float32
        ).astype(ml_dtypes.bfloat16).reshape(-1, 1024)
        for i in range(sizes.sink_tensors)}
    path = os.path.join(workdir, "model.safetensors")
    write_safetensors(path, tensors)
    with open(path, "rb") as f:
        blob = f.read()
    os.remove(path)
    generate_s = time.perf_counter() - t0

    scheduler = SchedulerService(
        resource=Resource(),
        scheduling=Scheduling(BaseEvaluator(),
                              SchedulingConfig(retry_interval=0.01)),
        storage=Storage(os.path.join(workdir, "sink-datasets")))
    daemon = Daemon(scheduler, DaemonConfig(
        storage_root=os.path.join(workdir, "sink-daemon"),
        hostname="sink-peer"))
    daemon.start()
    try:
        with BlobRangeServer(blob) as origin:
            t0 = time.perf_counter()
            arrays = download_to_hbm(daemon, origin.url(), device=device,
                                     timeout=600.0)
            fetch_s = time.perf_counter() - t0
            origin_requests = origin.request_count
    finally:
        daemon.stop()
    check(set(arrays) == set(tensors), "sink returned other tensor names")
    t0 = time.perf_counter()
    for name, want in tensors.items():
        got = arrays[name]
        check(got.devices() == {device}, f"{name} landed on {got.devices()}")
        check(got.shape == want.shape and got.dtype == want.dtype,
              f"{name}: {got.dtype}{got.shape}")
        check(np.array_equal(np.asarray(got).view(np.uint16),
                             want.view(np.uint16)),
              f"{name}: bytes differ from the file's")
    return {"file_bytes": len(blob), "tensors": len(tensors),
            "generate_seconds": round(generate_s, 2),
            "first_piece_to_last_tensor_seconds": round(fetch_s, 2),
            "readback_compare_seconds": round(time.perf_counter() - t0, 2),
            "origin_requests": origin_requests}


# ----------------------------------------------------------------------
# --chips 4: data-parallel GraphSAGE, four chips against one
# ----------------------------------------------------------------------

class ObservedStep:
    """The trainer's own jitted step, compiled ahead of time on the
    arguments of its first call. Those arguments are what ``train_gnn``
    placed, and the compiled object is what it then ran (and a compiled
    object, unlike ``jit``, refuses an argument placed otherwise) — so
    placement and collectives are read off the training run itself, not
    off a second placement made here."""

    def __init__(self, jitted, mesh):
        self.jitted = jitted
        self.mesh = mesh
        self.device_ids = sorted(d.id for d in mesh.mesh.devices.flat)
        self.compiled = None
        self.kept = None        # (graph, edges, edge_ids): never donated
        self.ids_shard_shape = None

    def __call__(self, *args):
        if self.compiled is None:
            self._check_arguments(*args)
            self.kept = args[1:4]
            self.compiled = self.jitted.lower(*args).compile()
        return self.compiled(*args)

    def _check_arguments(self, state, graph, edges, edge_ids, key) -> None:
        """State, tables and key replicated on every device of the
        mesh; the edge-id batch split over all of them."""
        import jax

        n_dev = len(self.device_ids)
        for name, tree in (("train state", state), ("graph tables", graph),
                           ("edge tables", edges), ("sampling key", key)):
            for leaf in jax.tree.leaves(tree):
                on = sorted(d.id for d in leaf.sharding.device_set)
                check(on == self.device_ids
                      and leaf.sharding.is_fully_replicated,
                      f"{name}: the trainer put a {leaf.shape} leaf on {on}"
                      f" (replicated: {leaf.sharding.is_fully_replicated})")
        on = sorted(d.id for d in edge_ids.sharding.device_set)
        self.ids_shard_shape = edge_ids.sharding.shard_shape(edge_ids.shape)
        want = edge_ids.shape[:-1] + (edge_ids.shape[-1] // n_dev,)
        check(on == self.device_ids and self.ids_shard_shape == want,
              f"edge-id batch: shards of {self.ids_shard_shape} on {on}, "
              f"want {want} on {self.device_ids}")

    def report(self, f1: int, seed: int) -> dict:
        """The compiled step's own input shardings and its gradient
        all-reduce; then neighbours sampled from the trainer's tables
        for the trainer's first batch, split like the batch."""
        import jax
        import jax.numpy as jnp

        from dragonfly2_tpu.train.fused_sampling import sample_neighbors

        n_dev = len(self.device_ids)
        state_in, _, _, ids_in, _ = self.compiled.input_shardings[0]
        for sharding in jax.tree.leaves(state_in) + [ids_in]:
            check(len(sharding.device_set) == n_dev,
                  f"a compiled input spans {len(sharding.device_set)} "
                  f"device(s)")
        check(all(s.is_fully_replicated for s in jax.tree.leaves(state_in)),
              "the compiled step does not take its state replicated")
        check(n_dev == 1 or not ids_in.is_fully_replicated,
              "the compiled step takes its edge-id batch replicated")
        all_reduces = self.compiled.as_text().count("all-reduce")
        check(n_dev == 1 or all_reduces > 0,
              "no all-reduce in the compiled data-parallel step")

        b = self.mesh.batch_sharding

        def sample(graph, edges, ids):
            i = ids if ids.ndim == 1 else ids[0]
            centers = jnp.stack([edges.src.at[i].get(out_sharding=b),
                                 edges.dst.at[i].get(out_sharding=b)],
                                axis=0)
            return sample_neighbors(graph, centers, f1, jnp.uint32(seed),
                                    b)[0]

        shards = jax.jit(sample)(*self.kept).addressable_shards
        sampled_on = sorted(s.device.id for s in shards)
        rows = self.ids_shard_shape[-1]
        check(sampled_on == self.device_ids
              and all(s.data.shape[-1] == rows for s in shards),
              f"sampled neighbour index: shards of "
              f"{[s.data.shape for s in shards]} on {sampled_on}")
        return {"state_tables_key_replicated_on": self.device_ids,
                "edge_id_batch_shards": {
                    "devices": self.device_ids,
                    "shape": list(self.ids_shard_shape)},
                "sampled_index_shard_devices": sampled_on,
                "all_reduce_ops": all_reduces}


@contextlib.contextmanager
def observe_fused_step(fs, steps_per_call: int):
    """``train_gnn`` looks its step factory up in ``fused_sampling`` when
    it runs; inside this context the step that factory makes is an
    ``ObservedStep``. Yields the list of steps made."""
    name = ("make_fused_multi_step" if steps_per_call > 1
            else "make_fused_train_step")
    factory = getattr(fs, name)
    made: list = []

    def observing_factory(model, mesh, *args):
        made.append(ObservedStep(factory(model, mesh, *args), mesh))
        return made[-1]

    setattr(fs, name, observing_factory)
    try:
        yield made
    finally:
        setattr(fs, name, factory)


def phase_data_parallel(sizes: Sizes, seed: int, devices) -> dict:
    """The GraphSAGE job of phase 2 on a mesh over all ``devices`` and on
    a mesh over the first one: same seed, same global batch, same steps.
    Loss trajectories must agree, and what each run's trainer placed and
    compiled is checked on the run itself (``ObservedStep``)."""
    from dragonfly2_tpu.data import SyntheticCluster
    from dragonfly2_tpu.parallel import data_parallel_mesh
    from dragonfly2_tpu.train import GNNTrainConfig, train_gnn
    from dragonfly2_tpu.train import fused_sampling as fs

    n_dev = len(devices)
    graph = SyntheticCluster(n_hosts=sizes.sage_hosts, seed=seed).probe_graph(
        sizes.sage_records * 5)
    report: dict = {"devices": n_dev, "probe_edges": graph.n_edges}
    cfg = GNNTrainConfig(batch_size=sizes.sage_batch,
                         steps_per_call=sizes.sage_steps_per_call,
                         epochs=sizes.epochs + 1, seed=seed,
                         eval_max_seconds=0.0)
    histories = {}
    for label, devs in (("wide", devices), ("one", devices[:1])):
        compile_s: list = []
        run_cfg = dataclasses.replace(cfg,
                                      compile_callback=compile_s.append)
        mesh = data_parallel_mesh(devices=devs)
        t0 = time.perf_counter()
        with observe_fused_step(fs, cfg.steps_per_call) as steps:
            result = train_gnn(graph, run_cfg, mesh)
        wall_s = time.perf_counter() - t0
        check(len(steps) == 1 and steps[0].compiled is not None,
              f"{label}: train_gnn never ran the fused step")
        histories[label] = np.asarray(result.history, np.float64)
        report[label] = {
            "loss": [round(float(v), 5) for v in result.history],
            "steps": result.steps,
            "compile_s": round(compile_s[-1], 2) if compile_s else None,
            "wall_s": round(wall_s, 2),
            "samples_per_sec": round(result.samples_per_sec),
            **steps[0].report(cfg.fanouts[0], seed)}
    wide, one = histories["wide"], histories["one"]
    check(len(wide) == len(one) >= 2 and np.isfinite(wide).all()
          and np.isfinite(one).all(), f"bad histories {wide} {one}")
    check(wide[-1] < wide[0], f"loss did not decrease on the mesh: {wide}")
    report["max_rel_loss_diff"] = float(np.max(np.abs(wide - one) / one))
    check(np.allclose(wide, one, rtol=DATA_PARALLEL_RTOL),
          f"{n_dev}-device and 1-device loss trajectories differ: "
          f"{wide} vs {one}")
    return report


# ----------------------------------------------------------------------

def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 runs ONLY the data-parallel GraphSAGE "
                             "comparison (four chips against one)")
    args = parser.parse_args(argv)

    devices = require_tpu(args.chips)

    from dragonfly2_tpu.utils.compilecache import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    device = devices[0]
    sizes = Sizes.full()
    workdir = tempfile.mkdtemp(prefix="df2-chip-smoke-")
    t_start = time.perf_counter()
    try:
        run_phase("device", lambda: phase_device(devices, cache_dir), device)
        if args.chips == 4:
            run_phase("data_parallel",
                      lambda: phase_data_parallel(sizes, args.seed, devices),
                      device)
        else:
            from dragonfly2_tpu.manager import (
                Database,
                FilesystemObjectStore,
                ManagerService,
            )

            manager = ManagerService(
                Database(os.path.join(workdir, "manager.db")),
                FilesystemObjectStore(os.path.join(workdir, "objects")))
            run_phase("train", lambda: phase_train(
                sizes, args.seed, workdir, manager), device)
            run_phase("serve", lambda: phase_serve(
                sizes, args.seed, workdir, manager, device), device)
            run_phase("sink", lambda: phase_sink(
                sizes, args.seed, workdir, device), device)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    emit(total_seconds=round(time.perf_counter() - t_start, 1))
    emit(ok=True, device={"platform": device.platform,
                          "kind": device.device_kind,
                          "count": len(devices)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
