"""Jit'd public wrappers for the Pallas kernels.

On the CPU backend the kernels run with ``interpret=True`` (the kernel
body is evaluated by the Pallas interpreter, for correctness tests); on
any other backend they compile to Mosaic, and nothing switches an
accelerator to the interpreter. The wrappers handle batching (vmap over
batch/head slices) and padding; the batch-grid SpMV sizes its tiles from
the analytic cost model's hardware config (``core.costmodel
.choose_tiles``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.costmodel import choose_tiles
from repro.core.registry import (register_backend, register_batched_backend,
                                 register_decode_backend)
from repro.kernels import block_attention as _ba
from repro.kernels import bsr_spmv as _bsr
from repro.kernels import decode_attend as _da
from repro.kernels import gamma_score as _gs

# traces of the pallas backends — one per compiled kernel, since the
# backend bodies only run while the enclosing jit is being traced
PALLAS_TRACE_COUNTS = {"batched": 0, "decode": 0}


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


@register_backend("pallas")
def _pallas_backend(plan, x: jax.Array, **_kw) -> jax.Array:
    """InteractionPlan SpMV via the Pallas MXU kernel (batch-grid kernel
    at B=1). Handles (n,) and (n, f) charges and capacity-padded plans —
    dead-slot rows carry zero tiles and stay zero in the output."""
    b = plan.bsr
    return bsr_spmv(b.vals, b.col_idx, x, plan.n,
                    shape_key=plan.spec.shape_key)


_pallas_backend.interpret_only = _interpret


@register_batched_backend("pallas")
def _pallas_batched(spec, data, xs: jax.Array) -> jax.Array:
    """PlanBatch SpMV: the whole batch in ONE batch-grid kernel."""
    PALLAS_TRACE_COUNTS["batched"] += 1
    return bsr_spmv_batched(data.vals, data.col_idx, xs,
                            shape_key=spec.shape_key)


_pallas_batched.interpret_only = _interpret


@functools.partial(jax.jit, static_argnames=("n", "shape_key"))
def bsr_spmv(vals: jax.Array, col_idx: jax.Array, x: jax.Array,
             n: int | None = None,
             shape_key: tuple | None = None) -> jax.Array:
    """ELL-BSR SpMV/SpMM of one matrix (the batch-grid kernel at B=1).
    x (n,) or (n, f); returns the same leading length (``n`` if given).
    One program: the batch axis costs nothing here, where an eager
    ``vals[None]`` would copy every tile."""
    y = bsr_spmv_batched(vals[None], col_idx[None], x[None],
                         shape_key=shape_key)[0]
    return y if n is None else y[:n]


def bsr_spmv_batched(vals: jax.Array, col_idx: jax.Array, xs: jax.Array,
                     shape_key: tuple | None = None) -> jax.Array:
    """Batched ELL-BSR SpMV/SpMM via the batch-grid kernel.

    vals (B, n_rb, nbr, bs, bs); xs (B, n) or (B, n, f); returns the same
    leading charge length as the XLA batched backends (sliced to n).
    Tile sizes (row-superblock, slot-chunk, feature tile) come from the
    hardware config via ``costmodel.choose_tiles``.
    """
    B, n_rb, nbr, bs, _ = vals.shape
    squeeze = xs.ndim == 2
    if squeeze:
        xs = xs[..., None]
    n = xs.shape[1]
    f = xs.shape[-1]
    # pad charges out to the plan's full column-block range (capacity may
    # exceed the live charge length on capacity-padded plans)
    n_cb = max((n + bs - 1) // bs,
               shape_key[4] if shape_key is not None else 0)
    pad = n_cb * bs - n
    if pad:
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
    if shape_key is None:
        shape_key = (n, bs, 8, n_rb, n_cb, nbr)
    rbs, chunk, fc = choose_tiles(shape_key, f)
    y = _bsr.bsr_spmv_batched(vals.astype(jnp.float32),
                              col_idx.astype(jnp.int32),
                              xs.astype(jnp.float32),
                              rbs=rbs, chunk=chunk, fc=fc,
                              interpret=_interpret())
    y = y[:, :n]
    return y[..., 0] if squeeze else y


def block_attention(q, k_sorted, v_sorted, kpos, qpos, idx, *, bq, bk,
                    causal=True):
    """Batched cluster-block-sparse attention.

    q (B,Hq,S,dh); k/v_sorted (B,Hkv,S,dh); kpos (B,Hkv,S); qpos (S,);
    idx (B,Hkv,nqb,n_sel). GQA: q heads grouped onto kv heads."""
    b, hq, s, dh = q.shape
    hkv = k_sorted.shape[1]
    g = hq // hkv
    qg = q.reshape(b * hkv, g, s, dh)
    kf = k_sorted.reshape(b * hkv, s, dh)
    vf = v_sorted.reshape(b * hkv, s, v_sorted.shape[-1])
    pf = kpos.reshape(b * hkv, s)
    idxf = idx.reshape(b * hkv, *idx.shape[2:])

    def one(qs, ks, vs, ps, ix):
        def per_head(qh):
            return _ba.block_attention(qh, ks, vs, ps, qpos, ix,
                                       bq=bq, bk=bk, causal=causal,
                                       interpret=_interpret())
        return jax.vmap(per_head)(qs)

    out = jax.vmap(one)(qg, kf, vf, pf, idxf)
    return out.reshape(b, hq, s, -1)


def decode_attend_fused(q, k, v, pos, cent, qpos, *, n_sel, bk):
    """Cluster decode over plain caches: ``core.clusterkv.decode_select``
    picks the tiles, the Pallas kernel gathers and attends them.
    q (B,Hq,dh); k/v (B,Hkv,S,dh|dv); pos (B,Hkv,S); cent (B,Hkv,S/bk,dh);
    qpos scalar or (B,)."""
    from repro.core import clusterkv as ckv

    PALLAS_TRACE_COUNTS["decode"] += 1
    qp = jnp.broadcast_to(jnp.asarray(qpos, jnp.int32), (q.shape[0],))
    idx = ckv.decode_select(q.astype(jnp.float32), cent.astype(jnp.float32),
                            n_sel)
    return _da.decode_attend_fused(q, k, v, pos, idx, qp, bk=bk,
                                   interpret=_interpret())


@register_decode_backend("pallas")
def _pallas_plan_decode(q, ks, vs, ps, cent, qpos, cfg, *,
                        k_self=None, v_self=None):
    """Plan-ordered decode service attend via the Pallas gather+attend
    kernel. Same contract as the registered ``xla`` decode backend
    (``models.attention._plan_decode_xla``), whose tile selection it
    shares: hole tiles masked out of selection, local-window recency
    boost, optional always-visible self column."""
    from repro.core import clusterkv as ckv

    PALLAS_TRACE_COUNTS["decode"] += 1
    s = ks.shape[2]
    bk = min(cfg.block_k, s)
    qp = qpos.astype(jnp.int32)
    idx = ckv.plan_decode_select(q, ps, cent, qp,
                                 min(cfg.decode_clusters, s // bk), bk,
                                 cfg.local_window_blocks * bk)
    return _da.decode_attend_fused(
        q, ks, vs, ps, idx, qp, k_self, v_self, bk=bk,
        has_self=k_self is not None, interpret=_interpret())


_pallas_plan_decode.interpret_only = _interpret


def gamma_exact(rows: jax.Array, cols: jax.Array, sigma: float,
                bn: int = 256,
                weights: jax.Array | None = None) -> jax.Array:
    """Exact Eq. 4 via the tiled Pallas kernel.

    Pads the coordinate list to a tile multiple with zero-weight entries
    (exactly inert — no far-sentinel correction) and exploits pair
    symmetry to skip the upper tile triangle. ``weights`` supports
    weighted patterns (streaming tombstones carry weight 0)."""
    nnz = rows.shape[0]
    coords = jnp.stack([rows, cols], 1).astype(jnp.float32)
    w = (jnp.ones((nnz,), jnp.float32) if weights is None
         else weights.astype(jnp.float32))
    pad = (-nnz) % bn
    if pad:
        coords = jnp.concatenate([coords, jnp.zeros((pad, 2), jnp.float32)])
        w = jnp.concatenate([w, jnp.zeros((pad,), jnp.float32)])
    total = _gs.gamma_pairs(coords, sigma, bn, weights=w, symmetric=True,
                            interpret=_interpret())
    denom = jnp.float32(nnz) if weights is None else jnp.sum(w)
    return total / (sigma * denom)


def tsne_force(p_vals: jax.Array, col_idx: jax.Array, y: jax.Array,
               n: int | None = None) -> jax.Array:
    """Blockwise t-SNE attractive force via the Pallas kernel (source
    segments gathered by the scalar-prefetched column index)."""
    from repro.kernels import tsne_force as _tf
    f = _tf.tsne_force(p_vals.astype(jnp.float32),
                       col_idx.astype(jnp.int32),
                       y.astype(jnp.float32), interpret=_interpret())
    return f[:n] if n is not None else f
