"""Device mesh construction and canonical shardings.

Axis convention (scaling-book style):
- ``data``  — batch sharding; gradient allreduce rides ICI within a slice
  and DCN across hosts (XLA picks the collective from the mesh topology).
- ``model`` — tensor sharding for the wider GNN configs (GraphTransformer);
  unused (size 1) for MLP/GraphSAGE-scale models.

Training code never names a collective: it jits with in_shardings built
here, and XLA inserts psum/all-gather where the annotations require them.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class MeshContext:
    """A mesh plus its canonical shardings."""

    mesh: Mesh

    @property
    def n_data(self) -> int:
        return self.mesh.shape["data"]

    @property
    def n_model(self) -> int:
        return self.mesh.shape.get("model", 1)

    @property
    def batch_sharding(self) -> NamedSharding:
        """Leading-axis sharding over the data axis."""
        return NamedSharding(self.mesh, P("data"))

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    @property
    def serialize_launches(self) -> bool:
        """True on a multi-device CPU mesh. XLA:CPU's in-process
        collectives deadlock when several launches of a multi-device
        program are in flight at once (the rendezvous starves the shared
        thread pool: "expected 8 threads ... only 7 arrived", then the
        process aborts) — a step loop with little host work per step
        stacks launches through async dispatch, so on this platform only
        it blocks on each step. Real TPU collectives pipeline fine."""
        return (self.mesh.devices.flat[0].platform == "cpu"
                and self.mesh.size > 1)

    def shard_spec(self, *axes: str | None) -> NamedSharding:
        return NamedSharding(self.mesh, P(*axes))

    def put_batch(self, batch):
        """Place host arrays with the batch sharding (leading axis split
        across data-parallel devices)."""
        return jax.tree.map(
            lambda a: jax.device_put(a, self.batch_sharding), batch
        )

    def put_replicated(self, tree):
        return jax.tree.map(lambda a: jax.device_put(a, self.replicated), tree)


def data_parallel_mesh(
    devices: Sequence[Any] | None = None, model_parallel: int = 1
) -> MeshContext:
    """Build a ``(data, model)`` mesh over the available devices.

    On a v5e-8 slice this is an 8-way (or 4×2 with model parallelism) mesh
    whose collectives ride ICI; under the test harness it spans the 8
    virtual CPU devices; on the single-chip bench it degenerates to 1×1
    (sharding annotations become no-ops — same code everywhere).
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    assert n % model_parallel == 0, (n, model_parallel)
    mesh = jax.make_mesh(
        (n // model_parallel, model_parallel), ("data", "model"), devices=devices
    )
    return MeshContext(mesh)
