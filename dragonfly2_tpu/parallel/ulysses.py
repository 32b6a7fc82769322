"""All-to-all (Ulysses-style) sequence parallelism — the second of the
two long-context layouts (SURVEY §2.7: "ring attention or all-to-all
sequence/context parallelism").

Where ring attention keeps K/V moving and the sequence axis sharded
throughout (d ppermute hops per layer, O(T/d) rows per device at all
times), the all-to-all layout re-partitions ONCE per attention call:
an ``all_to_all`` turns the sequence-sharded ``[T/d, H, D]`` into a
head-sharded ``[T, H/d, D]``, each device runs ordinary full-sequence
attention over its own head group, and the inverse ``all_to_all``
restores sequence sharding for the (sequence-local) MLP that follows.
Two collectives per call moving ``T·H·D/d`` elements each — cheaper
than the ring's d hops when heads are plentiful and ICI all-to-all
bandwidth is good (a TPU torus does this well); the trade is that the
head axis must divide the mesh (``H % d == 0``) and each device must
hold O(T · H/d) activations.

The local attention is the flash layout: on a real TPU device it IS the
pallas ``flash_attention`` kernel (``ops/flash_attention.py`` — its
[T, H/d, D] per-device shape is exactly the kernel's contract); off-TPU
a chunked online-softmax ``lax.scan`` with the same algebra. No
reference counterpart (the reference's data plane moves files, not
activations); the algorithm follows the published DeepSpeed-Ulysses
layout, implemented here on ``jax.lax.all_to_all`` over the mesh.

Differentiable end to end: ``all_to_all`` transposes to the inverse
exchange, so ``jax.grad`` works without a custom VJP.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
from jax.sharding import Mesh, PartitionSpec as P


def _local_attention(q, k, v, causal: bool, chunk: int, use_flash: bool):
    """Full-sequence attention on ONE device: [T, h, d] → [T, h, d] —
    the pallas kernel on TPU (backward recomputes through the chunked
    scan, so training-scale T stays in the flash memory class), the
    same chunked scan directly elsewhere."""
    from dragonfly2_tpu.ops.flash_attention import (
        chunked_attention,
        flash_attention,
    )

    if use_flash:
        return flash_attention(q, k, v, causal)
    return chunked_attention(q, k, v, causal, block=chunk)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "data",
    causal: bool = False,
    chunk: int = 1024,
    use_flash: Optional[bool] = None,
) -> jax.Array:
    """Softmax attention with the sequence axis sharded over ``axis``,
    computed by head-partitioning: all-to-all to ``[T, H/d, D]`` per
    device, local full attention, inverse all-to-all back.

    q/k/v: ``[T, H, D]`` with T sharded over the mesh axis; ``H`` must
    be divisible by the axis size. Returns attention output shaped and
    sharded like ``q``.
    """
    if q.ndim != 3:
        raise ValueError(f"expected [T, heads, head_dim], got {q.shape}")
    n_dev = mesh.shape[axis]
    heads = q.shape[1]
    if heads % n_dev:
        raise ValueError(
            f"heads ({heads}) must be divisible by the '{axis}' mesh "
            f"axis ({n_dev}) — that is the Ulysses layout's constraint; "
            "use ring_attention when heads are scarce")
    if use_flash is None:
        # Decide off the MESH's devices, not jax.devices(): a virtual
        # CPU mesh on a TPU-attached host must take the scan path.
        use_flash = mesh.devices.flat[0].platform == "tpu"
    seq_spec = P(axis, None, None)

    @partial(jax.shard_map, mesh=mesh, in_specs=(seq_spec,) * 3,
             out_specs=seq_spec)
    def run(ql, kl, vl):
        # [T/d, H, D] → [T, H/d, D]: sequence gathers, heads scatter.
        def seq_to_heads(x):
            return jax.lax.all_to_all(x, axis, split_axis=1,
                                      concat_axis=0, tiled=True)

        out = _local_attention(
            seq_to_heads(ql), seq_to_heads(kl), seq_to_heads(vl),
            causal, chunk, use_flash)
        # [T, H/d, D] → [T/d, H, D]: the inverse exchange.
        return jax.lax.all_to_all(out, axis, split_axis=0,
                                  concat_axis=1, tiled=True)

    return run(q, k, v)
