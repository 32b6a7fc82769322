#!/usr/bin/env python
"""10M-record scale proof (round-3 verdict item 3; SURVEY §7 hard part
"streaming ingestion at 10M records").

Measures, at SCALE_ROWS (default 10M) probe records:
  1. columnar generation + sharded-parquet write throughput,
  2. column-pruned ingestion throughput,
  3. deterministic-global-shuffle streaming throughput (+ a restart
     determinism check at scale),
  4. GraphSAGE training steady-state samples/sec on the 10M-edge graph,
  5. (budget permitting) MLP training at 10M pair examples streamed
     from the sharded files.

Writes artifacts/scale_proof_r4.json incrementally (atomic) so a kill
mid-run still leaves the completed stages on disk. Platform: probes the
TPU in a subprocess and falls back to CPU with the platform honestly
recorded.

Usage: python artifacts/scale_proof.py  [SCALE_ROWS=10000000]
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SCALE = int(os.environ.get("SCALE_ROWS", 10_000_000))
N_SHARDS = int(os.environ.get("SCALE_SHARDS", 16))
OUT = os.path.join(REPO, "artifacts", f"scale_proof_r4.json")
WORK = os.environ.get("SCALE_WORK_DIR",
                      os.path.join(REPO, "artifacts", "scale_work"))
GNN_SECONDS = float(os.environ.get("SCALE_GNN_SECONDS", 90))
MLP_SECONDS = float(os.environ.get("SCALE_MLP_SECONDS", 45))

result = {"scale_rows": SCALE, "n_shards": N_SHARDS,
          "stages_completed": [], "platform": "unknown"}


def flush(stage: str | None = None) -> None:
    if stage:
        result["stages_completed"].append(stage)
    tmp = OUT + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f, indent=1)
    os.replace(tmp, OUT)


def probe_tpu(timeout: float = 25.0) -> bool:
    code = "import jax; print(jax.devices()[0].platform)"
    try:
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=timeout)
    except subprocess.TimeoutExpired:
        return False
    out = proc.stdout.strip()
    return proc.returncode == 0 and out not in ("", "cpu")


def main() -> None:
    import numpy as np

    on_tpu = probe_tpu()
    if not on_tpu:
        import jax

        jax.config.update("jax_platforms", "cpu")

    from dragonfly2_tpu.data import SyntheticCluster, write_columns_sharded
    from dragonfly2_tpu.data.sharded import ShardedParquetDataset
    from dragonfly2_tpu.utils.compilecache import enable_compilation_cache

    enable_compilation_cache()

    # -- 1. generate + write ------------------------------------------------
    t0 = time.perf_counter()
    cluster = SyntheticCluster(n_hosts=10_000, seed=0)
    cols = cluster.probe_edge_columns(SCALE)
    gen_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    paths = write_columns_sharded(cols, WORK, n_shards=N_SHARDS)
    write_s = time.perf_counter() - t0
    total_bytes = sum(os.path.getsize(p) for p in paths)
    result.update(
        generate_rows_per_sec=int(SCALE / gen_s),
        write_rows_per_sec=int(SCALE / write_s),
        parquet_bytes=total_bytes,
        parquet_mb_per_sec=round(total_bytes / 1e6 / write_s, 1),
    )
    flush("write")

    # -- 2. column-pruned ingestion ----------------------------------------
    def extractor(table):
        return tuple(table.column(i).to_numpy()
                     for i in range(table.num_columns))

    ds = ShardedParquetDataset(paths, extractor)
    t0 = time.perf_counter()
    rows = ds.ingest_all(columns=["src", "rtt_ns"])
    ingest_s = time.perf_counter() - t0
    assert rows == SCALE
    result.update(ingest_rows_per_sec=int(SCALE / ingest_s),
                  ingest_seconds=round(ingest_s, 1),
                  n_tiles=ds.n_tiles)
    flush("ingest")

    # -- 3. shuffled streaming + restart determinism -----------------------
    batch = 65_536
    t0 = time.perf_counter()
    n_stream, first = 0, None
    for b in ds.batches(batch, seed=11, epoch=0):
        if first is None:
            first = b[2][:64].copy()
        n_stream += len(b[0])
    stream_s = time.perf_counter() - t0
    # A fresh reader (restart) must reproduce the identical global order.
    ds2 = ShardedParquetDataset(paths, extractor)
    first2 = next(iter(ds2.batches(batch, seed=11, epoch=0)))[2][:64]
    assert np.array_equal(first, first2), "shuffle not deterministic!"
    result.update(
        shuffle_stream_rows_per_sec=int(n_stream / stream_s),
        shuffle_stream_rows=n_stream,
        shuffle_deterministic_after_restart=True,
    )
    flush("shuffle_stream")

    # -- 4. GNN at 10M edges -----------------------------------------------
    import jax

    from dragonfly2_tpu.data.features import Graph
    from dragonfly2_tpu.parallel import data_parallel_mesh
    from dragonfly2_tpu.train import GNNTrainConfig, train_gnn

    result["platform"] = jax.devices()[0].platform
    mesh = data_parallel_mesh()
    graph = Graph(
        node_ids=np.array([f"host-{i}" for i in range(10_000)]),
        node_features=cluster.node_feature_matrix(),
        edge_src=cols["src"].astype(np.int32),
        edge_dst=cols["dst"].astype(np.int32),
        edge_rtt_ns=cols["rtt_ns"],
    )
    del cols, ds, ds2
    batch_size = 8192 if on_tpu else 2048

    def on_progress(steps: int, rate: float) -> None:
        result["gnn_samples_per_sec_per_chip"] = int(rate / mesh.n_data)
        result["gnn_steps"] = steps
        flush()

    gnn = train_gnn(graph, GNNTrainConfig(
        batch_size=batch_size, epochs=50,
        max_seconds=GNN_SECONDS,
        steps_per_call=16 if on_tpu else 1,  # tune_gnn_r4.json winner
        eval_fraction=0.005,
        eval_max_seconds=30.0,
        progress_callback=on_progress,
        compile_callback=lambda s: result.update(
            gnn_compile_seconds=round(s, 1))), mesh)
    result.update(
        gnn_samples_per_sec_per_chip=int(gnn.samples_per_sec / mesh.n_data),
        gnn_f1=round(gnn.f1, 4),
        gnn_edges=graph.n_edges,
    )
    flush("gnn_10m")

    # -- 5. MLP at 10M pair examples round-tripped through the sharded
    # files: write → deterministic shuffled stream → train. -----------------
    del graph
    X, y = cluster.pair_example_columns(SCALE)
    n_feats = X.shape[1]
    feat_cols = {f"f{i}": X[:, i] for i in range(n_feats)}
    feat_cols["y"] = y
    del X, y
    mlp_paths = write_columns_sharded(feat_cols, WORK, n_shards=N_SHARDS,
                                      basename="pairs")
    del feat_cols

    def pair_extractor(table):
        Xb = np.stack([table.column(f"f{i}").to_numpy()
                       for i in range(n_feats)], axis=1)
        return Xb, table.column("y").to_numpy()

    pds = ShardedParquetDataset(mlp_paths, pair_extractor)
    t0 = time.perf_counter()
    xs, ys = [], []
    for b in pds.batches(262_144, seed=1, epoch=0):
        xs.append(b[0])
        ys.append(b[1])
    X_stream = np.concatenate(xs)
    y_stream = np.concatenate(ys)
    del xs, ys
    result["mlp_stream_rows_per_sec"] = int(
        len(X_stream) / (time.perf_counter() - t0))
    flush()

    from dragonfly2_tpu.train import MLPTrainConfig, train_mlp

    mlp = train_mlp(X_stream, y_stream, MLPTrainConfig(
        epochs=50, batch_size=16384, max_seconds=MLP_SECONDS,
        progress_callback=lambda s, r: result.update(
            mlp_samples_per_sec_per_chip=int(r / mesh.n_data))), mesh)
    result.update(
        mlp_samples_per_sec_per_chip=int(mlp.samples_per_sec / mesh.n_data),
        mlp_eval_mae_mbps=round(mlp.mae, 3),
        mlp_rows=len(X_stream),
    )
    flush("mlp_10m")

    # Clean the multi-GB work dir; the JSON is the artifact.
    for p in os.listdir(WORK):
        os.remove(os.path.join(WORK, p))
    os.rmdir(WORK)
    result["wall_seconds_total"] = round(time.perf_counter() - T_START, 1)
    flush()
    print(json.dumps(result))


T_START = time.perf_counter()
if __name__ == "__main__":
    main()
