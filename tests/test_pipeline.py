"""GPipe-style pipeline parallelism on the 8-device mesh.

The pipeline must be a pure scheduling detail: outputs (and gradients)
equal running the stages sequentially on one device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dragonfly2_tpu.parallel.pipeline import (
    pipeline_apply,
    stack_stage_params,
)


def stage_fn(params, x):
    w, b = params["w"], params["b"]
    return jnp.tanh(x @ w + b)


def sequential(stacked, x):
    for s in range(stacked["w"].shape[0]):
        x = stage_fn(jax.tree.map(lambda p: p[s], stacked), x)
    return x


@pytest.fixture(scope="module")
def mesh():
    return jax.make_mesh((jax.device_count(),), ("stage",))


def make_params(n_stages, d, seed=0):
    rng = np.random.default_rng(seed)
    return stack_stage_params([
        {"w": (rng.standard_normal((d, d)) / np.sqrt(d)).astype(np.float32),
         "b": rng.standard_normal(d).astype(np.float32) * 0.1}
        for _ in range(n_stages)
    ])


class TestPipeline:
    def test_matches_sequential(self, mesh):
        d = 16
        params = make_params(8, d)
        x = np.random.default_rng(1).standard_normal((32, d)).astype(
            np.float32)
        out = jax.jit(lambda p, x: pipeline_apply(
            stage_fn, p, x, mesh=mesh))(params, x)
        ref = sequential(params, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

    def test_more_microbatches_than_stages(self, mesh):
        d = 8
        params = make_params(8, d)
        x = np.random.default_rng(2).standard_normal((48, d)).astype(
            np.float32)
        out = jax.jit(lambda p, x: pipeline_apply(
            stage_fn, p, x, mesh=mesh, microbatches=16))(params, x)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(sequential(params, x)),
                                   rtol=1e-5, atol=1e-6)

    def test_grads_match_sequential(self, mesh):
        """Backward through the schedule (scan + ppermute + masking)
        must produce the same parameter gradients as the sequential
        program — including for stage params living on other devices."""
        d = 8
        params = make_params(8, d, seed=3)
        x = np.random.default_rng(4).standard_normal((16, d)).astype(
            np.float32)
        y = np.random.default_rng(5).standard_normal((16, d)).astype(
            np.float32)

        def pipe_loss(p):
            out = pipeline_apply(stage_fn, p, x, mesh=mesh)
            return ((out - y) ** 2).mean()

        def seq_loss(p):
            return ((sequential(p, x) - y) ** 2).mean()

        with jax.set_mesh(mesh):
            g_pipe = jax.jit(jax.grad(pipe_loss))(params)
        g_seq = jax.grad(seq_loss)(params)
        for a, b in zip(jax.tree.leaves(g_pipe), jax.tree.leaves(g_seq)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_param_memory_is_sharded(self, mesh):
        """Stage params sharded over the axis: each device holds 1/S of
        the parameter bytes — the reason pipelines exist."""
        from jax.sharding import NamedSharding, PartitionSpec

        d = 32
        params = make_params(8, d)
        sharded = jax.device_put(
            params, NamedSharding(mesh, PartitionSpec("stage")))
        total = sum(np.asarray(l).nbytes for l in jax.tree.leaves(params))
        per_dev = sum(l.addressable_shards[0].data.nbytes
                      for l in jax.tree.leaves(sharded))
        assert per_dev * 8 == total
        # And the pipeline runs with the sharded placement.
        x = np.zeros((16, d), np.float32)
        out = jax.jit(lambda p, x: pipeline_apply(
            stage_fn, p, x, mesh=mesh))(sharded, x)
        assert np.isfinite(np.asarray(out)).all()

    def test_rejects_ragged_microbatches(self, mesh):
        params = make_params(8, 8)
        with pytest.raises(ValueError, match="microbatch"):
            pipeline_apply(stage_fn, params,
                           np.zeros((30, 8), np.float32), mesh=mesh)

    def test_rejects_stage_count_mismatch(self, mesh):
        """16 stacked stages on an 8-device axis would silently run
        only the first stage of each device's pair — must raise, not
        return a plausible wrong answer."""
        params = make_params(16, 8)
        with pytest.raises(ValueError, match="16 stages"):
            pipeline_apply(stage_fn, params,
                           np.zeros((16, 8), np.float32), mesh=mesh)

    def test_rejects_zero_microbatches(self, mesh):
        params = make_params(8, 8)
        with pytest.raises(ValueError, match=">= 1"):
            pipeline_apply(stage_fn, params,
                           np.zeros((16, 8), np.float32), mesh=mesh,
                           microbatches=0)
