"""Multi-level near-neighbor interaction computations (paper §2.4).

The interaction ``y = A x`` is computed block-by-block: every kept tile is a
dense (bs, bs) block multiplying a contiguous charge segment — the paper's
"block-segment multiplication". The low-level paths live here and are
published through the backend registry (``repro.core.registry``) under the
names ``csr`` / ``bsr`` / ``bsr_ml``; prefer ``repro.api`` plans over
calling them directly:

  spmv_csr      element-wise gather baseline (scattered/CSR semantics)
  spmv_bsr      flat single-level block path (one einsum over kept tiles)
  spmv_bsr_ml   multi-level path: lax.scan over row-superblocks so the
                working set per step is a superblock stripe (the TPU analog
                of the paper's multi-level cache blocking)
  spmv_pallas   Pallas kernel (kernels/bsr_spmv.py) — one MXU panel
                matmul per row block over DMA-gathered charge segments;
                registered as ``pallas`` by kernels/ops.py

Iterative-application value updates (t-SNE attractive force, mean shift) are
computed *blockwise dense* from the current coordinates — the TPU-native
replacement for per-edge gathers (DESIGN.md §2).
"""
from __future__ import annotations

import functools
import warnings
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.blocksparse import BSR
from repro.core.registry import register_backend, register_batched_backend


# ---------------------------------------------------------------------------
# SpMV paths
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n",))
def spmv_csr(vals: jax.Array, rows: jax.Array, cols: jax.Array,
             x: jax.Array, n: int | None = None) -> jax.Array:
    """Gather-based SpMV over COO/CSR edges: y_i = sum_j a_ij x_j."""
    n = n if n is not None else x.shape[0]
    return jnp.zeros((n,) + x.shape[1:], x.dtype).at[rows].add(
        vals[(...,) + (None,) * (x.ndim - 1)] * x[cols])


def _pad_x(x: jax.Array, n_cb: int, bs: int) -> jax.Array:
    pad = n_cb * bs - x.shape[0]
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return x


@functools.partial(jax.jit, static_argnames=("n",))
def spmv_bsr(bsr_vals: jax.Array, col_idx: jax.Array, x: jax.Array,
             n: int) -> jax.Array:
    """Flat block path. bsr_vals (n_rb, nbr, bs, bs); x (n,) or (n, f)."""
    n_rb, nbr, bs, _ = bsr_vals.shape
    squeeze = x.ndim == 1
    if squeeze:
        x = x[:, None]
    xp = _pad_x(x, n_rb, bs)
    xb = xp.reshape(n_rb, bs, -1)                       # (n_cb, bs, f)
    seg = xb[col_idx]                                   # (n_rb, nbr, bs, f)
    y = jnp.einsum("rnij,rnjf->rif", bsr_vals, seg)
    y = y.reshape(n_rb * bs, -1)[:n]
    return y[:, 0] if squeeze else y


@functools.partial(jax.jit, static_argnames=("n", "sb"))
def spmv_bsr_ml(bsr_vals: jax.Array, col_idx: jax.Array, x: jax.Array,
                n: int, sb: int = 8) -> jax.Array:
    """Multi-level block path: scan over row-superblocks (stripes of ``sb``
    row-blocks); each step touches only that stripe's tiles + segments."""
    n_rb, nbr, bs, _ = bsr_vals.shape
    squeeze = x.ndim == 1
    if squeeze:
        x = x[:, None]
    pad_rb = (-n_rb) % sb
    if pad_rb:
        bsr_vals = jnp.pad(bsr_vals, ((0, pad_rb), (0, 0), (0, 0), (0, 0)))
        col_idx = jnp.pad(col_idx, ((0, pad_rb), (0, 0)))
    xp = _pad_x(x, n_rb, bs)
    xb = xp.reshape(n_rb, bs, -1)

    v = bsr_vals.reshape(-1, sb, nbr, bs, bs)
    c = col_idx.reshape(-1, sb, nbr)

    def step(_, vc):
        vt, ct = vc
        seg = xb[ct]                                    # (sb, nbr, bs, f)
        return None, jnp.einsum("rnij,rnjf->rif", vt, seg)

    _, ys = jax.lax.scan(step, None, (v, c))
    y = ys.reshape(-1, bs, ys.shape[-1]).reshape(-1, ys.shape[-1])[:n]
    return y[:, 0] if squeeze else y


# -- batched paths (PlanBatch: stacked plans, one kernel) -------------------


def _flat_gather_segments(xs: jax.Array, col_idx: jax.Array,
                          bs: int) -> jax.Array:
    """Charge segments for every (lane, row-block, tile) of a batch.

    ``xs`` (B, n, f), ``col_idx`` (B, n_rb, nbr) -> (B, n_rb, nbr, bs, f).
    The naive formulation — ``vmap`` of the single-plan ``xb[col_idx]`` —
    leaves XLA a *batched* gather, which the CPU backend lowers to scalar
    loops (~10x slower than the compute it feeds). Flattening the batch
    into one segment table and offsetting the indices per lane turns it
    back into the plain row gather the single-plan path enjoys.
    """
    B = xs.shape[0]
    n_cb = (xs.shape[1] + bs - 1) // bs
    pad = n_cb * bs - xs.shape[1]
    if pad:
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
    flat = xs.reshape(B * n_cb, bs, -1)
    idx = (col_idx + (jnp.arange(B) * n_cb)[:, None, None]).reshape(-1)
    seg = flat[idx]
    return seg.reshape(col_idx.shape + seg.shape[1:])


def _tiles_times_segments(vals: jax.Array, seg: jax.Array) -> jax.Array:
    """(..., nbr, bs, bs) tiles x (..., nbr, bs, f) segments ->
    (..., bs, f), summed over the tile slots.

    NOT an einsum: XLA lowers ``...ij,...jf`` to a dot_general whose
    preferred operand layout *transposes the whole tile tensor on every
    call* (constants get it folded once — arguments pay it each time; at
    batch sizes that copy is 10x the useful compute). The elementwise
    broadcast-multiply + reduce (f == 1) and the layout-preserving
    ``batch_matmul`` (f > 1) keep the tiles in their stored layout.
    """
    lead = vals.shape[:-3]
    nbr, bs = vals.shape[-3], vals.shape[-1]
    f = seg.shape[-1]
    if f == 1:
        y = (vals * seg[..., None, :, 0]).sum(axis=(-3, -1))
        return y[..., None]
    out = jax.lax.batch_matmul(vals.reshape(-1, bs, bs),
                               seg.reshape(-1, bs, f))
    return out.reshape(lead + (nbr, bs, f)).sum(axis=-3)


@jax.jit
def spmv_bsr_batched(vals: jax.Array, col_idx: jax.Array,
                     xs: jax.Array) -> jax.Array:
    """Flat block path over a stacked batch: ``vals`` (B, n_rb, nbr, bs,
    bs), ``xs`` (B, n) or (B, n, f); one gather + one tile contraction
    for every plan in the batch."""
    B, n_rb, nbr, bs, _ = vals.shape
    squeeze = xs.ndim == 2
    if squeeze:
        xs = xs[..., None]
    n = xs.shape[1]
    seg = _flat_gather_segments(xs, col_idx, bs)
    y = _tiles_times_segments(vals, seg)
    y = y.reshape(B, n_rb * bs, -1)[:, :n]
    return y[..., 0] if squeeze else y


@functools.partial(jax.jit, static_argnames=("sb",))
def spmv_bsr_ml_batched(vals: jax.Array, col_idx: jax.Array,
                        xs: jax.Array, sb: int = 8) -> jax.Array:
    """Multi-level batched path: scan over row-superblock stripes (every
    lane's stripe s together), flat-gathering each stripe's segments —
    the working set per step is one stripe *across the batch*."""
    B, n_rb, nbr, bs, _ = vals.shape
    squeeze = xs.ndim == 2
    if squeeze:
        xs = xs[..., None]
    n = xs.shape[1]
    pad_rb = (-n_rb) % sb
    if pad_rb:
        vals = jnp.pad(vals, ((0, 0), (0, pad_rb), (0, 0), (0, 0), (0, 0)))
        col_idx = jnp.pad(col_idx, ((0, 0), (0, pad_rb), (0, 0)))
    n_cb = (n + bs - 1) // bs
    pad = n_cb * bs - n
    if pad:
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
    flat = xs.reshape(B * n_cb, bs, -1)
    off = (jnp.arange(B) * n_cb)[:, None, None]
    v = jnp.swapaxes(vals.reshape(B, -1, sb, nbr, bs, bs), 0, 1)
    c = jnp.swapaxes((col_idx + off).reshape(B, -1, sb, nbr), 0, 1)

    def step(_, vc):
        vt, ct = vc                          # (B,sb,nbr,bs,bs) (B,sb,nbr)
        seg = flat[ct.reshape(-1)].reshape(ct.shape + flat.shape[1:])
        return None, _tiles_times_segments(vt, seg)

    _, ys = jax.lax.scan(step, None, (v, c))        # (n_sb, B, sb, bs, f)
    y = jnp.swapaxes(ys, 0, 1).reshape(B, -1, ys.shape[-1])[:, :n]
    return y[..., 0] if squeeze else y


@register_batched_backend("bsr")
def _bsr_batched(spec, data, xs: jax.Array) -> jax.Array:
    return spmv_bsr_batched(data.vals, data.col_idx, xs)


@register_batched_backend("bsr_ml")
def _bsr_ml_batched(spec, data, xs: jax.Array) -> jax.Array:
    return spmv_bsr_ml_batched(data.vals, data.col_idx, xs, spec.sb)


# -- registry backends (plan, x) -> y, cluster index space ------------------


@register_backend("csr")
def _csr_backend(plan, x: jax.Array, **_kw) -> jax.Array:
    """Per-edge gather baseline over the plan's reordered COO pattern."""
    rows, cols, vals = plan.coo_device()
    return spmv_csr(vals, rows, cols, x, plan.n)


@register_backend("bsr")
def _bsr_backend(plan, x: jax.Array, **_kw) -> jax.Array:
    b = plan.bsr
    return spmv_bsr(b.vals, b.col_idx, x, plan.n)


@register_backend("bsr_ml")
def _bsr_ml_backend(plan, x: jax.Array, **_kw) -> jax.Array:
    b = plan.bsr
    return spmv_bsr_ml(b.vals, b.col_idx, x, plan.n, b.sb)


def spmv(bsr: BSR, x: jax.Array, path: str = "bsr") -> jax.Array:
    """Deprecated shim: string-dispatched SpMV over a bare BSR.

    Use ``repro.api.build_plan(...).matvec(x, backend=...)`` instead —
    plans carry the COO, host state, and autotune context this shim
    cannot reconstruct. ``path`` accepts any name in
    ``core.registry.backend_names()`` (``csr``/``bsr``/``bsr_ml``/
    ``pallas``/``dist``), but only the pure-storage paths work on a bare
    BSR: ``csr`` needs the plan's COO, ``dist`` needs a mesh-sharded
    plan, and ``backend="auto"`` needs the plan's structural key — all
    raise or misbehave here. See ``docs/backends.md``.
    """
    warnings.warn("interact.spmv(bsr, x, path) is deprecated; use "
                  "repro.api plans and the backend registry",
                  DeprecationWarning, stacklevel=2)
    from repro.api import InteractionPlan
    from repro.core.registry import get_backend
    return get_backend(path)(InteractionPlan.from_bsr(bsr), x)


# ---------------------------------------------------------------------------
# Iterative applications: blockwise-dense value recomputation
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n",))
def tsne_attractive(p_vals: jax.Array, col_idx: jax.Array, nbr_mask: jax.Array,
                    y: jax.Array, n: int) -> jax.Array:
    """t-SNE attractive force (paper §3.1), blockwise.

    F_i = sum_j p_ij q_ij (y_i - y_j), q_ij = 1/(1 + |y_i - y_j|^2), with
    p the (fixed-profile) kNN-based affinity stored as dense tiles. Values
    p_ij q_ij are recomputed dense per tile from the current embedding y.
    """
    n_rb, nbr, bs, _ = p_vals.shape
    d = y.shape[1]
    yp = _pad_x(y, n_rb, bs).reshape(n_rb, bs, d)
    ysrc = yp[col_idx]                                   # (n_rb, nbr, bs, d)
    ytgt = yp[:, None, :, None, :]                       # (n_rb, 1, bs, 1, d)
    diff = ytgt - ysrc[:, :, None, :, :]                 # (n_rb, nbr, bs_t, bs_s, d)
    q = 1.0 / (1.0 + jnp.sum(diff * diff, axis=-1))
    w = p_vals * q                       # p tile is (target, source) = (t, s)
    f = jnp.einsum("rnts,rntsd->rtd", w, diff)
    return f.reshape(-1, d)[:n]


@functools.partial(jax.jit, static_argnames=("h2", "n"))
def meanshift_step(w_pattern: jax.Array, col_idx: jax.Array,
                   sources_blocked: jax.Array, t: jax.Array,
                   h2: float, n: int) -> jax.Array:
    """One mean-shift iteration (paper §3.2), blockwise.

    New mean m_i = sum_j w_ij s_j / sum_j w_ij with Gaussian weights
    w_ij = exp(-|t_i - s_j|^2 / h2) over the (fixed) neighbor pattern;
    weights are recomputed dense per tile from current targets t.
    ``w_pattern`` (n_rb, nbr, bs, bs) is the 0/1 neighbor-pattern tile.
    ``sources_blocked`` (n_cb, bs, d) are sources in cluster order.
    """
    n_rb, nbr, bs, _ = w_pattern.shape
    d = t.shape[1]
    tp = _pad_x(t, n_rb, bs).reshape(n_rb, bs, d)
    s = sources_blocked[col_idx]                         # (n_rb, nbr, bs, d)
    diff = tp[:, None, :, None, :] - s[:, :, None, :, :]
    w = jnp.exp(-jnp.sum(diff * diff, axis=-1) / h2) * w_pattern
    num = jnp.einsum("rnts,rnsd->rtd", w, s)
    den = jnp.sum(w, axis=(1, 3))[..., None]
    m = num / jnp.maximum(den, 1e-12)
    return m.reshape(-1, d)[:n]
