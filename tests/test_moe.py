"""Expert-parallel (Switch top-1) routing on the 8-device mesh.

The routing must be a pure distribution detail when capacity is ample:
every token's output equals gate_prob * expert_fn(its expert, token),
computed against a direct dense reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dragonfly2_tpu.parallel.moe import moe_apply
from dragonfly2_tpu.parallel.pipeline import stack_stage_params


def expert_fn(params, x):
    return jnp.tanh(x @ params["w"]) + params["b"]


def dense_reference(params, x, gate_logits):
    probs = jax.nn.softmax(gate_logits.astype(np.float32), axis=-1)
    idx = np.argmax(gate_logits, axis=-1)
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        e = int(idx[t])
        p_e = {k: v[e] for k, v in params.items()}
        out[t] = np.asarray(
            expert_fn(p_e, x[t][None, :]))[0] * probs[t, e]
    return out


@pytest.fixture(scope="module")
def mesh():
    return jax.make_mesh((jax.device_count(),), ("expert",))


def make_experts(n, d, seed=0):
    rng = np.random.default_rng(seed)
    return stack_stage_params([
        {"w": (rng.standard_normal((d, d)) / np.sqrt(d)).astype(np.float32),
         "b": rng.standard_normal(d).astype(np.float32) * 0.1}
        for _ in range(n)
    ])


class TestMoE:
    def test_matches_dense_reference(self, mesh):
        d, t = 16, 64
        rng = np.random.default_rng(1)
        params = make_experts(8, d)
        x = rng.standard_normal((t, d)).astype(np.float32)
        gates = rng.standard_normal((t, 8)).astype(np.float32)
        # Ample capacity: nothing drops, so routed == dense.
        out = jax.jit(lambda p, x, g: moe_apply(
            expert_fn, p, x, g, mesh=mesh, capacity_factor=8.0))(
            params, x, gates)
        ref = dense_reference(params, x, gates)
        np.testing.assert_allclose(np.asarray(out), ref,
                                   rtol=1e-4, atol=1e-5)

    def test_capacity_drops_excess_tokens(self, mesh):
        """Every token gated to ONE expert with capacity 1 per device:
        exactly one token per device survives, the rest output zero —
        the documented Switch drop semantics, not silent corruption."""
        d, t = 8, 64
        params = make_experts(8, d)
        x = np.ones((t, d), np.float32)
        gates = np.full((t, 8), -10.0, np.float32)
        gates[:, 3] = 10.0                       # everyone wants expert 3
        out = np.asarray(jax.jit(lambda p, x, g: moe_apply(
            expert_fn, p, x, g, mesh=mesh, capacity_factor=1.0))(
            params, x, gates))
        t_loc = t // 8
        kept = 0
        for dev in range(8):
            rows = out[dev * t_loc:(dev + 1) * t_loc]
            nonzero = np.abs(rows).sum(axis=1) > 0
            # capacity = ceil(t_loc/8 * 1.0) = 1 survivor per device
            assert nonzero.sum() == 1, nonzero
            kept += int(nonzero.sum())
        assert kept == 8

    def test_grads_flow_to_experts_and_gates(self, mesh):
        d, t = 8, 32
        rng = np.random.default_rng(2)
        params = make_experts(8, d, seed=3)
        x = rng.standard_normal((t, d)).astype(np.float32)
        gates = rng.standard_normal((t, 8)).astype(np.float32)

        def loss(p, g):
            return (moe_apply(expert_fn, p, x, g, mesh=mesh,
                              capacity_factor=8.0) ** 2).sum()

        with jax.set_mesh(mesh):
            gp, gg = jax.jit(jax.grad(loss, argnums=(0, 1)))(params, gates)
        assert all(np.isfinite(np.asarray(l)).all()
                   for l in jax.tree.leaves(gp))
        # The straight-through combine gives the gate a real gradient.
        assert np.abs(np.asarray(gg)).sum() > 0

    def test_rejects_bad_shapes(self, mesh):
        params = make_experts(8, 8)
        with pytest.raises(ValueError, match="flatten batch"):
            moe_apply(expert_fn, params,
                      np.zeros((2, 16, 8), np.float32),
                      np.zeros((2, 8), np.float32), mesh=mesh)
        with pytest.raises(ValueError, match="gate_logits"):
            moe_apply(expert_fn, params, np.zeros((16, 8), np.float32),
                      np.zeros((16, 4), np.float32), mesh=mesh)
        with pytest.raises(ValueError, match="experts"):
            moe_apply(expert_fn, make_experts(4, 8),
                      np.zeros((16, 8), np.float32),
                      np.zeros((16, 8), np.float32), mesh=mesh)
