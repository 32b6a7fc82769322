"""All-to-all (Ulysses) sequence parallelism on the 8-device mesh.

Same discipline as the ring tests: every property is checked against a
dense single-device reference — the head re-partition must be a pure
distribution detail, invisible in the math — plus cross-checks against
ring attention (the two long-context layouts must agree exactly).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dragonfly2_tpu.parallel import (
    data_parallel_mesh,
    ring_attention,
    ulysses_attention,
)
from tests.test_ring_attention import _qkv, dense_reference


@pytest.fixture(scope="module")
def mesh():
    return data_parallel_mesh().mesh


class TestUlyssesAttention:
    def test_full_matches_dense(self, mesh):
        q, k, v = _qkv((64, 8, 4), seed=0)
        out = jax.jit(lambda *a: ulysses_attention(*a, mesh=mesh))(q, k, v)
        ref = dense_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_causal_matches_dense(self, mesh):
        q, k, v = _qkv((64, 8, 4), seed=1)
        out = jax.jit(lambda *a: ulysses_attention(
            *a, mesh=mesh, causal=True))(q, k, v)
        ref = dense_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_agrees_with_ring(self, mesh):
        """The two sequence-parallel layouts are interchangeable: same
        inputs, same outputs, different collectives."""
        q, k, v = _qkv((128, 8, 8), seed=2)
        ring = jax.jit(lambda *a: ring_attention(
            *a, mesh=mesh, causal=True))(q, k, v)
        a2a = jax.jit(lambda *a: ulysses_attention(
            *a, mesh=mesh, causal=True))(q, k, v)
        np.testing.assert_allclose(np.asarray(a2a), np.asarray(ring),
                                   rtol=1e-4, atol=1e-5)

    def test_grad_matches_dense(self, mesh):
        q, k, v = _qkv((32, 8, 4), seed=3)
        with jax.set_mesh(mesh):
            grads = jax.jit(jax.grad(
                lambda q, k, v: (ulysses_attention(
                    q, k, v, mesh=mesh, causal=True) ** 2).sum(),
                argnums=(0, 1, 2)))(q, k, v)
        dense_grads = jax.grad(
            lambda q, k, v: (dense_reference(
                q, k, v, causal=True) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for g, d in zip(grads, dense_grads):
            np.testing.assert_allclose(np.asarray(g), np.asarray(d),
                                       rtol=1e-3, atol=1e-4)

    def test_chunked_local_attention(self, mesh):
        """chunk smaller than T exercises the online-softmax scan with
        a ragged tail block."""
        q, k, v = _qkv((88, 8, 4), seed=4)
        out = jax.jit(lambda *a: ulysses_attention(
            *a, mesh=mesh, causal=True, chunk=16))(q, k, v)
        ref = dense_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_output_keeps_row_sharding(self, mesh):
        from jax.sharding import NamedSharding, PartitionSpec as P

        q, k, v = _qkv((64, 8, 4), seed=5)
        spec = NamedSharding(mesh, P("data", None, None))
        args = [jax.device_put(a, spec) for a in (q, k, v)]
        out = jax.jit(lambda *a: ulysses_attention(*a, mesh=mesh))(*args)
        assert out.sharding.spec == P("data", None, None)

    def test_rejects_indivisible_heads(self, mesh):
        q, k, v = _qkv((64, 6, 4), seed=6)
        with pytest.raises(ValueError, match="divisible"):
            ulysses_attention(q, k, v, mesh=mesh)

    def test_bf16_path(self, mesh):
        q, k, v = _qkv((64, 8, 4), seed=7)
        qb, kb, vb = (jnp.asarray(a, jnp.bfloat16) for a in (q, k, v))
        out = jax.jit(lambda *a: ulysses_attention(*a, mesh=mesh))(
            qb, kb, vb)
        assert out.dtype == jnp.bfloat16
        ref = dense_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), rtol=5e-2, atol=5e-2)

    def test_32k_tokens_memory_bounded(self, mesh):
        """Long-context tier: T=32k causal compiles with per-device temp
        far below the 4.3 GB dense score matrix, runs, and spot-checks
        rows against direct per-row attention."""
        t, heads, hd = 32_768, 8, 8
        q, k, v = _qkv((t, heads, hd), seed=8)
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = NamedSharding(mesh, P("data", None, None))
        qs, ks, vs = (jax.device_put(a, spec) for a in (q, k, v))
        jitted = jax.jit(lambda *a: ulysses_attention(
            *a, mesh=mesh, causal=True, chunk=2048))
        compiled = jitted.lower(qs, ks, vs).compile()
        temp_mb = compiled.memory_analysis().temp_size_in_bytes / 1e6
        dense_mb = t * t * 4 / 1e6
        assert temp_mb < dense_mb / 4, (temp_mb, dense_mb)

        out = np.asarray(compiled(qs, ks, vs))
        assert np.isfinite(out).all()
        scale = 1.0 / np.sqrt(hd)
        for i in (0, 5000, t - 1):
            scores = (k[: i + 1, 3] @ q[i, 3]) * scale
            p = np.exp(scores - scores.max())
            p /= p.sum()
            np.testing.assert_allclose(out[i, 3], p @ v[: i + 1, 3],
                                       rtol=2e-3, atol=2e-3)
