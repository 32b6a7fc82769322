"""GraphSAGE topology model (BASELINE config #2 — the headline model).

Fills the reference's ``trainGNN`` stub (trainer/training/training.go:82-90)
with a real GraphSAGE trained on the probe graph the scheduler's
networktopology subsystem exports (scheduler/storage/types.go NetworkTopology
rows). Registry metrics: precision/recall/f1 — exactly the fields the
manager's CreateModel expects for GNNs (manager_server_v2.go:840-844).

**The one layout.** Every tensor of a sampled neighbourhood has its
fan-out axes leading and the batch trailing, the axes of the sampler's
logical ``[B, 2, f1, f2]`` tensor in reverse:

    center_feat [2, B, F]         (src, dst) features
    nbr1_feat   [f1, 2, B, F]     nbr1_rtt, nbr1_mask [f1, 2, B]
    nbr2_feat   [f2, f1, 2, B, F] nbr2_rtt, nbr2_mask [f2, f1, 2, B]

so the batch lies in the lanes, a masked mean over a hop's fan-out is a
sum of ``f`` slabs along the leading axis (elementwise: nothing crosses a
lane or a sublane), and the endpoints of an edge are ``x[0]`` and
``x[1]``. The device sampler (train/fused_sampling.py) draws, gathers and
hands over its tensors in this order, 13.1M feature rows a step that are
never laid out again; the host sampler (data/graph_sampler.py) is numpy
and batch-major, and its callers turn a batch over once at their own edge
(:func:`nodes_last`; ``gnn_trainer.apply_indexed`` transposes the index
arrays before its gathers). There is one model and one contract: no flag,
no second path.

TPU mapping:
- The model is dense math over static shapes: masked means reduce the
  leading fan-out axes, and the SAGE combine steps are bf16 matmuls that
  tile onto the MXU. No scatter, no segment ops, no dynamic shapes — and
  batches shard over ``data`` (the trailing node axis) with zero ambiguity.
- Probe RTTs ride along as per-neighbor edge features (the signal the graph
  exists to carry): a neighbor's vector is [node_feat, log-rtt]. The mean
  of ``[features | rtt]`` is ``[mean(features) | mean(rtt)]`` column by
  column, so a hop is aggregated first (the RTTs as ``[f, ...]`` scalars
  with the batch in the lanes) and the 9-wide rows are put together on the
  aggregated nodes, a fan-out fewer than the sampled slots.
- The edge head concatenates both endpoint embeddings → 2-layer MLP →
  logit. Per-edge cost is O(f1·f2) gathers + a handful of matmuls,
  embarrassingly batch-parallel → pjit over the ``data`` axis.
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp


def masked_mean(x, mask):
    """Mean over the leading (fan-out) axis of ``x [f, *nodes]`` or
    ``x [f, *nodes, D]``, counting only the slots ``mask [f, *nodes]``
    marks 1 (padded fan-out: a mask holds 0s and 1s).

    The masked values are added up in float32 whatever ``x``'s dtype. The
    mask is applied in ``x``'s dtype (exact: a value times 0 or 1) so that
    the widening is the sum's own, one pass over the slots; widened before
    the product, the compiler hoists it over the reshape of a gather's
    rows, where it is a float32 copy of every sampled row.
    """
    weights = jnp.expand_dims(mask, tuple(range(mask.ndim, x.ndim)))
    total = jnp.sum(x * weights.astype(x.dtype), axis=0, dtype=jnp.float32)
    return total / jnp.maximum(jnp.sum(weights, axis=0), 1.0)


def nodes_last(center_feat, nbr1_feat, nbr1_rtt, nbr1_mask,
               nbr2_feat, nbr2_rtt, nbr2_mask):
    """A batch-major neighbourhood (``EdgeBatch.astuple()[:-1]``: ``[B, 2,
    f1(, f2)(, F)]``) in the model's layout: the node axes reversed, the
    feature axis where it was."""
    def rows(x):
        return jnp.moveaxis(x.T, 0, -1)

    return (rows(center_feat), rows(nbr1_feat), nbr1_rtt.T, nbr1_mask.T,
            rows(nbr2_feat), nbr2_rtt.T, nbr2_mask.T)


class SageLayer(nn.Module):
    """One GraphSAGE-mean layer: combine(self, mean of the neighbors)."""

    features: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, h_self, agg):
        # h_self: [*nodes, D]; agg: [*nodes, D'], the neighbors' masked mean
        out = nn.Dense(self.features, dtype=self.dtype, param_dtype=jnp.float32)(
            jnp.concatenate([h_self, agg], axis=-1)
        )
        return nn.relu(out)


class GraphSAGE(nn.Module):
    """2-layer GraphSAGE with an edge-classification head.

    Inputs are a sampled 2-hop neighbourhood in the module's one layout
    (fan-out axes leading, batch trailing); output is the fast-path logit
    per target edge.
    """

    hidden: int = 128
    embed: int = 64
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, center_feat, nbr1_feat, nbr1_rtt, nbr1_mask,
                 nbr2_feat, nbr2_rtt, nbr2_mask):
        def with_rtt(feats, rtt):
            return jnp.concatenate([feats, rtt[..., None]], axis=-1)

        def mean_with_rtt(feats, rtt, mask):
            # Column by column the mean of [features | rtt], on the
            # aggregated nodes.
            return with_rtt(masked_mean(feats.astype(self.dtype), mask),
                            masked_mean(rtt.astype(self.dtype), mask))

        x_center = center_feat.astype(self.dtype)        # [2, B, F]
        x_nbr1 = with_rtt(nbr1_feat.astype(self.dtype),
                          nbr1_rtt.astype(self.dtype))   # [f1, 2, B, F+1]

        layer1 = SageLayer(self.hidden, self.dtype)
        # h1 for the 1-hop neighbors (aggregating their own 2-hop nbrs).
        h1_nbr1 = layer1(
            x_nbr1, mean_with_rtt(nbr2_feat, nbr2_rtt, nbr2_mask)
        )                                                # [f1, 2, B, H]
        # h1 for the centers (aggregating the 1-hop neighbors).
        h1_center = layer1(
            jnp.concatenate(
                [x_center, jnp.zeros(x_center.shape[:-1] + (1,), self.dtype)], axis=-1
            ),
            mean_with_rtt(nbr1_feat, nbr1_rtt, nbr1_mask),
        )                                                # [2, B, H]

        layer2 = SageLayer(self.embed, self.dtype)
        h2_center = layer2(h1_center, masked_mean(h1_nbr1, nbr1_mask))  # [2, B, E]

        # Link-prediction head with explicit pair interactions: product and
        # absolute difference make "endpoints are near each other in
        # embedding space" linearly separable instead of something the MLP
        # must synthesize from raw concatenation.
        h_src, h_dst = h2_center[0], h2_center[1]
        pair = jnp.concatenate(
            [h_src, h_dst, h_src * h_dst, jnp.abs(h_src - h_dst)], axis=-1
        )
        z = nn.Dense(self.hidden, dtype=self.dtype, param_dtype=jnp.float32)(pair)
        z = nn.relu(z)
        logit = nn.Dense(1, dtype=self.dtype, param_dtype=jnp.float32)(z)
        return logit[..., 0].astype(jnp.float32)         # [B]
