"""Distributed block-sparse interaction via shard_map (DESIGN.md §2, §5).

The paper parallelizes SpMV with pthreads over row blocks; the TPU-native
mapping shards row-blocks over a mesh axis. Because the dual-tree ordering
makes each row-block's column footprint compact, every shard needs only a
small window of the charge vector. The registry backend ("dist") realizes
that window as a minimal halo exchange via :mod:`repro.core.shardplan`;
:func:`spmv_sharded` below keeps the simpler replicate-the-charges
all-gather path as the traced-plan fallback and as the traffic baseline
the halo exchange is measured against.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.compat import shard_map

from repro.core.blocksparse import BSR
from repro.core.registry import NotApplicable, register_backend


def spmv_sharded(bsr: BSR, x: jax.Array, mesh: Mesh, axis: str = "data"
                 ) -> jax.Array:
    """y = A x with row-blocks sharded over ``axis``.

    A row-block count that does not divide the axis size is padded with
    empty row-blocks (column 0, zero tiles — they contribute zero rows
    that are sliced off), so any plan runs on any mesh. Single-vector
    charges only: the local einsum and the final reshape assume ``x`` of
    shape (n,) — reject (n, f) loudly rather than scrambling it.
    """
    if x.ndim != 1:
        raise NotApplicable(f"spmv_sharded supports 1-D charges only, "
                         f"got x.shape={x.shape}")
    n_rb = bsr.vals.shape[0]
    size = mesh.shape[axis]
    pad_rb = (-n_rb) % size
    vals, col_idx = bsr.vals, bsr.col_idx
    if pad_rb:
        # memoize the padded tile tensor on the BSR: serving loops call
        # this every matvec and must not re-copy O(n_rb*nbr*bs^2) data
        cache = getattr(bsr, "_dist_pad", None)
        if cache is not None and cache[0] == size:
            vals, col_idx = cache[1], cache[2]
        else:
            vals = jnp.pad(vals, ((0, pad_rb), (0, 0), (0, 0), (0, 0)))
            col_idx = jnp.pad(col_idx, ((0, pad_rb), (0, 0)))
            if not isinstance(vals, jax.core.Tracer):  # never cache traces
                bsr._dist_pad = (size, vals, col_idx)

    def local(vals, col_idx, xg):
        # vals (n_rb_p/size, nbr, bs, bs); xg fully replicated (all-gathered)
        xb = xg.reshape(-1, bsr.bs)
        seg = xb[col_idx]                            # (rb_l, nbr, bs)
        return jnp.einsum("rnij,rnj->ri", vals, seg)

    f = shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P(axis), P()),
        out_specs=P(axis),
        check_vma=False)
    pad = n_rb * bsr.bs - x.shape[0]
    xp = jnp.pad(x, (0, pad)) if pad else x
    y = f(vals, col_idx, xp)
    return y.reshape(-1)[:bsr.n]


@register_backend("dist")
def _dist_backend(plan, x: jax.Array, *, mesh: Mesh | None = None,
                  axis: str = "data", **_kw) -> jax.Array:
    """InteractionPlan SpMV with row-blocks sharded over a mesh axis.

    Routes through :mod:`repro.core.shardplan`: the plan is sharded once
    (halo exchange analyzed from its ELL schedule, memoized on the plan
    host per mesh shape) and every subsequent call reuses the shards —
    ppermute halos move only the charge window each device actually
    needs, instead of this module's historical full all-gather. Traced
    plans (the plan itself a jit argument) cannot be halo-analyzed on the
    host and fall back to :func:`spmv_sharded`. With no mesh given,
    builds a 1-axis mesh over every host device. Only single-vector
    charges (``x`` of shape (n,)) are supported.
    """
    from repro.core.shardplan import default_mesh, shard

    if mesh is None:
        mesh = default_mesh(axis)
    if isinstance(plan.bsr.col_idx, jax.core.Tracer):
        return spmv_sharded(plan.bsr, x, mesh, axis)
    return shard(plan, mesh, axis=axis).apply(x)
