"""Blocked exact k-nearest-neighbor graph construction in JAX.

Builds the paper's near-neighbor interaction pattern (Eq. 1): column j is a
near neighbor of row i iff s_j is among the k nearest sources to target t_i.
Distances are computed block-by-block (lax.scan over query blocks) so memory
stays O(block * N) rather than O(N^2).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("k", "block", "exclude_self"))
def knn_graph(targets: jax.Array, sources: jax.Array, k: int,
              block: int = 1024, exclude_self: bool = False,
              valid: jax.Array | None = None
              ) -> Tuple[jax.Array, jax.Array]:
    """Exact kNN of each target among sources.

    Returns ``(idx (M, k), dist2 (M, k))``, squared euclidean distances,
    ascending. With ``exclude_self`` the diagonal (i == j) is excluded
    (source and target sets are the same point set). ``valid`` (N,) bool
    restricts candidates to the masked sources — streaming plans hold
    tombstoned points in their physical source buffer, and a dead slot
    must never be picked as a neighbor.
    """
    m, d = targets.shape
    n = sources.shape[0]
    pad = (-m) % block
    tp = jnp.pad(targets, ((0, pad), (0, 0)))
    s_norm = jnp.sum(sources.astype(jnp.float32) ** 2, axis=1)

    def body(_, tb):
        qb, base = tb
        q32 = qb.astype(jnp.float32)
        # HIGHEST: the TPU's default f32 matmul rounds its inputs to
        # bfloat16 (~3 significant digits), which reorders near neighbors;
        # the exact-kNN contract needs float32 products
        cross = jnp.dot(q32, sources.astype(jnp.float32).T,
                        precision=jax.lax.Precision.HIGHEST)
        d2 = (jnp.sum(q32**2, axis=1)[:, None] + s_norm[None, :]
              - 2.0 * cross)
        if exclude_self:
            rows = base + jnp.arange(qb.shape[0])
            d2 = d2 + (rows[:, None] == jnp.arange(n)[None, :]) * jnp.inf
        if valid is not None:
            d2 = jnp.where(valid[None, :], d2, jnp.inf)
        neg, idx = jax.lax.top_k(-d2, k)
        return None, (idx, -neg)

    blocks = tp.reshape(-1, block, d)
    bases = jnp.arange(blocks.shape[0]) * block
    _, (idx, dist2) = jax.lax.scan(body, None, (blocks, bases))
    idx = idx.reshape(-1, k)[:m]
    dist2 = jnp.maximum(dist2.reshape(-1, k)[:m], 0.0)
    return idx, dist2


def knn_coo(targets: jax.Array, sources: jax.Array, k: int,
            block: int = 1024, exclude_self: bool = False,
            valid: jax.Array | None = None):
    """kNN graph as COO (rows, cols, dist2) arrays, row-major."""
    idx, dist2 = knn_graph(targets, sources, k, block, exclude_self, valid)
    m = idx.shape[0]
    rows = jnp.repeat(jnp.arange(m), k)
    return rows, idx.reshape(-1), dist2.reshape(-1)
