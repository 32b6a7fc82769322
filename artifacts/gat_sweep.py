"""Config #3 k-sweep: is the steps_per_call scan costing GAT throughput?

Round-5 on-chip data showed k=16 at 17.2k edge-samples/sec vs round 4's
20.9k at k=1 (same model/batch; GNN headline unchanged between rounds,
so the two set-ups are comparable). At ~0.5 s/step GAT was never
dispatch-bound, so the k-scan's win is nil and any scan/remat overhead
is pure loss. This sweep measures steady-state throughput per k on the
same process/graph to pick the right default for gat_bench.
"""
import json
import sys
import time

sys.path.insert(0, "/root/repo")
from dragonfly2_tpu.utils.compilecache import enable_compilation_cache

enable_compilation_cache()

import jax  # noqa: E402

from dragonfly2_tpu.data import SyntheticCluster  # noqa: E402
from dragonfly2_tpu.parallel import data_parallel_mesh  # noqa: E402
from dragonfly2_tpu.train import GATTrainConfig, train_gat  # noqa: E402

mesh = data_parallel_mesh()
out = {"platform": jax.devices()[0].platform, "devices": mesh.n_data,
       "sweep": []}
print(json.dumps({"platform": out["platform"]}), flush=True)

cluster = SyntheticCluster(n_hosts=20_000, seed=0)
graph = cluster.probe_graph(500_000)

for k in (1, 2, 4, 16):
    t0 = time.perf_counter()
    res = train_gat(
        graph,
        GATTrainConfig(hidden=128, embed=64, layers=2, heads=4,
                       edge_batch_size=8192, epochs=1000,
                       neighbor_cap=64, eval_fraction=0.02,
                       steps_per_call=k, max_seconds=25.0),
        mesh,
    )
    row = {"steps_per_call": k,
           "samples_per_sec_per_chip": int(res.samples_per_sec / mesh.n_data),
           "wall_s": round(time.perf_counter() - t0, 1)}
    out["sweep"].append(row)
    print(json.dumps(row), flush=True)

if len(sys.argv) > 1:
    with open(sys.argv[1], "w") as f:
        json.dump(out, f, indent=1)
