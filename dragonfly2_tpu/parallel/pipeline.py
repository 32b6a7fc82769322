"""Pipeline parallelism — GPipe-style microbatch schedule over a mesh
axis (completing the parallelism set next to data (mesh.py), tensor
(TPDense), and sequence (ring/ulysses) layouts; SURVEY §2.7).

Layout: the model is S stages; stage s's params live ONLY on mesh
position s of the ``stage`` axis (leaves carry a leading stage dim,
sharded over the axis — per-device parameter memory is 1/S of the
model). A batch is split into M microbatches that flow through the
ring: at schedule step t, device s runs ``stage_fn`` on microbatch
``t - s`` (when 0 ≤ t - s < M) and the activation hops to device s+1
via ``lax.ppermute`` — the classic (S + M − 1)-step GPipe fill/drain
diagram, bubble fraction (S−1)/(S+M−1), driven entirely by XLA
collectives on ICI.

Implementation notes (the TPU-native choices):
- the whole schedule is ONE ``lax.scan`` inside ``shard_map`` — no
  per-step dispatch, no data-dependent control flow; devices outside
  their active window compute on garbage and MASK the result (that is
  the bubble — compute is spent either way, branching would only break
  SPMD uniformity);
- microbatch injection/extraction use static-shape ``dynamic_slice``/
  masked scatter; the outputs are summed over the stage axis at the
  end (every device contributes zeros except the last stage), which
  doubles as the gather that makes the result replicated;
- ``jax.checkpoint`` on the per-step body keeps backward residents at
  one activation per schedule step.

No reference counterpart (the reference distributes files, not
activations). The schedule follows the published GPipe construction.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def check_stacked(params, n: int, axis: str, name: str, unit: str) -> None:
    """Every leaf's leading dim must equal the mesh axis size — with a
    mismatch, shard_map hands each device several slices and downstream
    code would silently use only the first (a finite, plausible, wrong
    answer). Shared by the pipeline and MoE layouts."""
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        if leaf.ndim == 0 or leaf.shape[0] != n:
            have = "a scalar" if leaf.ndim == 0 else str(leaf.shape[0])
            raise ValueError(
                f"{name} leaf {jax.tree_util.keystr(path)} has {have} "
                f"{unit} but the '{axis}' axis has {n} devices; stack "
                f"exactly one per device")


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "stage",
    microbatches: int | None = None,
) -> jax.Array:
    """Run ``x`` through S pipelined stages of ``stage_fn``.

    ``stage_fn(params_slice, x_mb) -> y_mb`` is one stage's compute;
    activations must keep a constant shape across stages (the pipeline
    contract). ``stage_params`` leaves are stacked ``[S, ...]`` and
    sharded over ``axis``; ``x`` is ``[B, ...]`` (replicated), split
    into ``microbatches`` equal slices (default: S — the minimum that
    keeps every stage busy at steady state). Returns ``[B, ...]``
    replicated.
    """
    n_stages = mesh.shape[axis]
    if microbatches is not None and microbatches < 1:
        raise ValueError(f"microbatches must be >= 1, got {microbatches}")
    m = microbatches if microbatches is not None else n_stages
    batch = x.shape[0]
    if batch % m:
        raise ValueError(f"batch ({batch}) must split into {m} equal "
                         "microbatches")
    # Stage count must MATCH the axis: with more stacked stages than
    # devices, shard_map would hand each device several and the
    # pipeline would silently run only the first of each — a finite,
    # plausible, wrong answer.
    check_stacked(stage_params, n_stages, axis, "stage_params", "stages")
    mb = batch // m
    x_mbs = x.reshape(m, mb, *x.shape[1:])
    n_steps = m + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(axis), P(None)), out_specs=P(None))
    def run(params_local, x_all):
        # params_local leaves: [1, ...] — this device's stage.
        params_s = jax.tree.map(lambda p: p[0], params_local)
        s_idx = jax.lax.axis_index(axis)
        # The carries differ per stage from step one, so their init
        # must already be marked varying over the axis or the scan
        # rejects the carry type.
        carry_act = jax.lax.pcast(jnp.zeros_like(x_all[0]), axis,
                                  to="varying")
        out_buf = jax.lax.pcast(jnp.zeros_like(x_all), axis, to="varying")

        def step(carry, t):
            act, out = carry
            # Stage 0 ingests microbatch t (a fresh one each step while
            # any remain); later stages consume the ppermuted inbound.
            feed = jax.lax.dynamic_index_in_dim(
                x_all, jnp.clip(t, 0, m - 1), 0, keepdims=False)
            x_in = jnp.where(s_idx == 0, feed, act)
            y = stage_fn(params_s, x_in)
            # Device s is working on microbatch t - s; outside [0, M)
            # it computed on garbage — mask it out of the output and
            # hand zeros around the bubble.
            mb_idx = t - s_idx
            active = (mb_idx >= 0) & (mb_idx < m)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # The LAST stage banks its finished microbatch; everyone
            # else contributes zeros at a clamped slot.
            is_last = s_idx == n_stages - 1
            slot = jnp.clip(mb_idx, 0, m - 1)
            bank = jnp.where(active & is_last, y, jnp.zeros_like(y))
            out = out.at[slot].add(bank)
            # Activation hops one stage forward around the ring.
            act = jax.lax.ppermute(y, axis, perm)
            return (act, out), None

        (_, out_buf), _ = jax.lax.scan(
            jax.checkpoint(step), (carry_act, out_buf),
            jnp.arange(n_steps))
        # Only the last stage holds real outputs; the psum doubles as
        # the broadcast that returns a replicated result.
        return jax.lax.psum(out_buf, axis)

    out = run(stage_params, x_mbs)
    return out.reshape(batch, *x.shape[1:])


def stack_stage_params(param_list):
    """[per-stage param trees] → stacked [S, ...] leaves (host-side
    convenience for building the sharded pipeline layout)."""
    import numpy as np

    return jax.tree.map(lambda *leaves: np.stack(leaves), *param_list)
