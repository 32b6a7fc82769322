"""Ring attention (sequence/context parallelism) on the 8-device mesh.

Every property is checked against a dense single-device reference:
full, causal, padded, batched, and the gradient — the ring must be a
pure distribution detail, invisible in the math.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dragonfly2_tpu.parallel import data_parallel_mesh
from dragonfly2_tpu.parallel.ring_attention import ring_attention


def dense_reference(q, k, v, causal=False, kv_valid=None):
    scale = 1.0 / np.sqrt(q.shape[-1])
    batched = q.ndim == 4
    s = (jnp.einsum("bnhd,bmhd->bhnm" if batched else "nhd,mhd->hnm",
                    q, k) * scale).astype(jnp.float32)
    t = q.shape[-3]
    mask = jnp.ones((t, t), bool)
    if causal:
        mask = jnp.tril(mask)
    mask = mask[None, None] if batched else mask[None]
    if kv_valid is not None:
        key_mask = (kv_valid[:, None, None, :] if batched
                    else kv_valid[None, None, :])
        mask = mask & key_mask
    s = jnp.where(mask, s, -1e9)
    p = jax.nn.softmax(s, axis=-1) * mask
    return jnp.einsum("bhnm,bmhd->bnhd" if batched else "hnm,mhd->nhd",
                      p.astype(q.dtype), v)


@pytest.fixture(scope="module")
def mesh():
    return data_parallel_mesh().mesh


def _qkv(shape, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return tuple(rng.standard_normal(shape).astype(dtype)
                 for _ in range(3))


class TestRingAttention:
    def test_full_matches_dense(self, mesh):
        q, k, v = _qkv((64, 2, 8))
        out = jax.jit(lambda *a: ring_attention(*a, mesh=mesh))(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(dense_reference(q, k, v)),
            rtol=1e-5, atol=1e-5)

    def test_causal_matches_dense(self, mesh):
        q, k, v = _qkv((64, 2, 8), seed=1)
        out = jax.jit(lambda *a: ring_attention(
            *a, mesh=mesh, causal=True))(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(dense_reference(q, k, v, causal=True)),
            rtol=1e-5, atol=1e-5)

    def test_padding_mask(self, mesh):
        q, k, v = _qkv((64, 2, 8), seed=2)
        valid = np.arange(64) < 50
        out = jax.jit(lambda *a: ring_attention(
            *a, mesh=mesh, kv_valid=jnp.asarray(valid)))(q, k, v)
        ref = dense_reference(q, k, v, kv_valid=jnp.asarray(valid))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_batched(self, mesh):
        q, k, v = _qkv((3, 64, 2, 8), seed=3)
        out = jax.jit(lambda *a: ring_attention(
            *a, mesh=mesh, causal=True))(q, k, v)
        ref = dense_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_grad_matches_dense(self, mesh):
        q, k, v = _qkv((32, 2, 8), seed=4)

        with jax.set_mesh(mesh):
            ring_grads = jax.jit(jax.grad(
                lambda q, k, v: (ring_attention(
                    q, k, v, mesh=mesh, causal=True) ** 2).sum(),
                argnums=(0, 1, 2)))(q, k, v)
        dense_grads = jax.grad(
            lambda q, k, v: (dense_reference(
                q, k, v, causal=True) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for g1, g2 in zip(ring_grads, dense_grads):
            np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                       rtol=1e-4, atol=1e-4)

    def test_output_keeps_row_sharding(self, mesh):
        from jax.sharding import NamedSharding, PartitionSpec as P

        q, k, v = _qkv((64, 2, 8), seed=5)
        spec = NamedSharding(mesh, P("data", None, None))
        args = [jax.device_put(a, spec) for a in (q, k, v)]
        out = jax.jit(lambda *a: ring_attention(*a, mesh=mesh))(*args)
        assert out.sharding.spec == P("data", None, None)

    def test_bf16_path(self, mesh):
        q, k, v = _qkv((64, 2, 8), seed=6)
        qb, kb, vb = (jnp.asarray(a, jnp.bfloat16) for a in (q, k, v))
        out = jax.jit(lambda *a: ring_attention(*a, mesh=mesh))(qb, kb, vb)
        assert out.dtype == jnp.bfloat16
        ref = dense_reference(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref),
            rtol=5e-2, atol=5e-2)


class TestLongContext:
    """Round-5 verdict item 5: ring attention at T in the tens of
    thousands — the regime the primitive exists for. The dense [T, T]
    reference is unbuildable here (a 32k² f32 score matrix is 4.3 GB),
    which is exactly the point: correctness is spot-checked row-wise
    against direct per-row attention, and the compiled per-device
    memory is asserted far below the dense score matrix."""

    T = 32_768

    def test_32k_tokens_causal(self, mesh):
        from jax.sharding import NamedSharding, PartitionSpec as P

        t, heads, hd = self.T, 1, 8
        q, k, v = _qkv((t, heads, hd), seed=7)
        spec = NamedSharding(mesh, P("data", None, None))
        qs, ks, vs = (jax.device_put(a, spec) for a in (q, k, v))

        jitted = jax.jit(lambda *a: ring_attention(
            *a, mesh=mesh, causal=True))
        compiled = jitted.lower(qs, ks, vs).compile()
        temp_mb = compiled.memory_analysis().temp_size_in_bytes / 1e6
        # Dense causal scores alone would be t*t*4 bytes = 4295 MB.
        dense_mb = t * t * 4 / 1e6
        assert temp_mb < dense_mb / 4, (temp_mb, dense_mb)

        out = np.asarray(compiled(qs, ks, vs))
        assert out.shape == (t, heads, hd)
        assert np.isfinite(out).all()

        # Spot-check rows against direct causal attention over keys
        # [0, i] — O(rows · T · d), cheap where the full matrix is not.
        scale = 1.0 / np.sqrt(hd)
        for i in (0, 1, 4097, 17_000, t - 1):
            scores = (k[: i + 1, 0] @ q[i, 0]) * scale
            p = np.exp(scores - scores.max())
            p /= p.sum()
            ref_row = p @ v[: i + 1, 0]
            np.testing.assert_allclose(out[i, 0], ref_row,
                                       rtol=2e-3, atol=2e-3)
