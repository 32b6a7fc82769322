"""dragonfly2_tpu — a TPU-native P2P distribution + ML-scheduling framework.

A from-scratch rebuild of the capabilities of Dragonfly2 (CNCF P2P file
distribution and container-image acceleration), designed TPU-first:

- The P2P control plane (scheduler with peer-DAG parent selection, dfdaemon
  peer engine, manager, seed peers) is rebuilt idiomatically in Python/gRPC
  with C++ for hot native paths.
- The ML scheduling loop the reference left as TODO stubs
  (reference: trainer/training/training.go:82-98,
  scheduler/scheduling/evaluator/evaluator.go:48) is implemented for real on
  TPU: network-topology probes and download records feed a columnar pipeline
  into JAX/XLA training of an MLP bandwidth predictor and a GraphSAGE
  topology model (pjit data parallelism with allreduce over ICI), and parent
  selection is served by a TPU-backed batched jit scorer at <1 ms p50.

Subpackage map (mirrors SURVEY.md §2 component inventory):

- ``utils``     — idgen, digest, host types, units (reference: pkg/)
- ``schema``    — dataset record schemas + CSV/parquet IO
                  (reference: scheduler/storage/types.go)
- ``data``      — feature extraction + input pipeline (host-side, static shapes)
- ``models``    — flax models: MLP, GraphSAGE, GAT (reference stubs filled)
- ``parallel``  — mesh/sharding helpers (ICI/DCN-aware)
- ``train``     — pjit training loops, orbax checkpointing, federated averaging
- ``inference`` — batched jit scorer + KServe-style sidecar
                  (reference: pkg/rpc/inference/client/client_v1.go)
- ``scheduler`` — resource model, scheduling core, evaluator, networktopology,
                  dataset storage (reference: scheduler/)
- ``daemon``    — peer engine, piece storage, upload server, source clients
                  (reference: client/daemon/)
- ``manager``   — model registry, cluster CRUD, searcher (reference: manager/)

Importing this package is intentionally lightweight: JAX is only imported by
the subpackages that need it (models/train/inference/parallel), so
control-plane services can run without pulling in an accelerator runtime.
"""

from dragonfly2_tpu.version import __version__

__all__ = ["__version__"]
