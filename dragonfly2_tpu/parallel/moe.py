"""Expert parallelism — Switch-style top-1 mixture-of-experts routing
over a mesh axis (the last letter of the dp/tp/sp/pp/ep set; SURVEY
§2.7's communication-backend mandate covers the all-to-all it rides).

Layout (the GShard/Switch construction, built on ``jax.lax.all_to_all``
like :mod:`.ulysses`): tokens are data-sharded over the ``expert``
axis; each device also OWNS one expert's parameters (leading stage dim
sharded over the axis — per-device expert memory is 1/E). A token's
top-1 gate picks its expert; each device packs its tokens into a
capacity-bounded dispatch buffer ``[E, C, d]``, one all-to-all routes
every buffer row to the device owning that expert, the expert runs its
FFN over everything it received, and the inverse all-to-all + combine
scatter returns outputs to their tokens, scaled by the gate
probability. Tokens past an expert's capacity are DROPPED (output 0
for the expert contribution) — the documented Switch trade; size
``capacity_factor`` to bound the drop rate.

All shapes static, both exchanges are single collectives on ICI, and
the whole thing is differentiable (gate probabilities get gradients
through the combine scale — the straight-through Switch estimator).
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def moe_apply(
    expert_fn: Callable,
    expert_params,
    x: jax.Array,
    gate_logits: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "expert",
    capacity_factor: float = 1.25,
) -> jax.Array:
    """Route ``x`` through per-device experts by top-1 gating.

    ``expert_fn(params_slice, tokens) -> tokens`` is one expert's
    compute (shape-preserving); ``expert_params`` leaves are stacked
    ``[E, ...]`` with E == the ``axis`` size, sharded over it.
    ``x``: ``[T, d]`` and ``gate_logits``: ``[T, E]``, both sharded
    over ``axis`` on dim 0 (tokens are data-parallel across expert
    devices). Returns ``[T, d]`` sharded like ``x``.
    """
    from dragonfly2_tpu.parallel.pipeline import check_stacked

    if x.ndim != 2 or gate_logits.ndim != 2:
        raise ValueError(
            f"expected x as [tokens, d] and gate_logits as "
            f"[tokens, experts], got {x.shape} / {gate_logits.shape}; "
            "flatten batch dims before routing")
    n_exp = mesh.shape[axis]
    if gate_logits.shape[-1] != n_exp:
        raise ValueError(
            f"gate_logits last dim ({gate_logits.shape[-1]}) must equal "
            f"the '{axis}' axis size ({n_exp}) — one expert per device")
    if gate_logits.shape[0] != x.shape[0]:
        raise ValueError(
            f"gate_logits covers {gate_logits.shape[0]} tokens but x "
            f"has {x.shape[0]}")
    check_stacked(expert_params, n_exp, axis, "expert_params", "experts")
    t_total = x.shape[0]
    if t_total % n_exp:
        raise ValueError(f"tokens ({t_total}) must shard evenly over "
                         f"the {n_exp}-device '{axis}' axis")
    t_loc = t_total // n_exp
    capacity = max(int(np.ceil(t_loc / n_exp * capacity_factor)), 1)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(axis), P(axis, None), P(axis, None)),
             out_specs=P(axis, None))
    def run(params_local, xl, gl):
        params_e = jax.tree.map(lambda p: p[0], params_local)
        # Top-1 gate (softmax prob of the winner scales the output and
        # carries the gradient back into the gate).
        probs = jax.nn.softmax(gl.astype(jnp.float32), axis=-1)
        expert_idx = jnp.argmax(gl, axis=-1)               # [T_loc]
        gate = jnp.take_along_axis(
            probs, expert_idx[:, None], axis=-1)[:, 0]     # [T_loc]

        # Position of each token within its expert's capacity window:
        # cumulative count of same-expert tokens before it.
        onehot = jax.nn.one_hot(expert_idx, n_exp, dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) - 1)[
            jnp.arange(xl.shape[0]), expert_idx]           # [T_loc]
        keep = pos < capacity
        slot = jnp.clip(pos, 0, capacity - 1)

        # Dispatch: [E, C, d] buffer, dropped tokens scatter nowhere.
        zeros = jnp.zeros((n_exp, capacity, xl.shape[-1]), xl.dtype)
        dispatch = zeros.at[expert_idx, slot].add(
            xl * keep[:, None].astype(xl.dtype))
        # Exchange: row e of every device's buffer lands on device e —
        # each device then holds [E_src=n_exp, C, d] for ITS expert.
        routed = jax.lax.all_to_all(dispatch, axis, split_axis=0,
                                    concat_axis=0, tiled=True)
        routed = routed.reshape(n_exp * capacity, xl.shape[-1])
        out = expert_fn(params_e, routed)
        out = out.reshape(n_exp, capacity, -1)
        # Inverse exchange: expert outputs return to the token owners.
        back = jax.lax.all_to_all(out, axis, split_axis=0,
                                  concat_axis=0, tiled=True)
        # Combine: gather each kept token's slot, scale by its gate.
        gathered = back[expert_idx, slot]                  # [T_loc, d]
        scale = (gate * keep.astype(jnp.float32)).astype(xl.dtype)
        return gathered * scale[:, None]

    return run(expert_params, x, gate_logits)
