"""Cluster-sparse attention — the paper's pipeline as an LM attention backend.

Mapping (DESIGN.md §3): attention's score matrix *is* a near-neighbor
interaction matrix (queries = targets, keys = sources). The paper's
reordering pipeline is applied per (batch, kv-head):

  1. low-dimensional embedding of the keys onto their top-d principal axes
     (core.embedding, paper §2.4 step 1);
  2. hierarchical clustering by Morton order in the embedding space
     (core.hierarchy, step 2) -> keys permuted into cluster order;
  3. the interaction is computed *block-sparse with dense blocks*: for each
     128-wide query tile only the top-B key tiles (by centroid score) are
     kept, and each kept (q-tile, k-tile) pair is a dense MXU block
     (steps 3-4: multi-level storage + block-segment interaction).

Causality is preserved exactly *within* the computed blocks via gathered
key positions; block selection always boosts blocks containing the local
causal window so recent tokens are never dropped. Like the paper's method
(and kNN attention generally) the set of computed blocks is an
approximation of full attention; tests bound the error against dense
attention on clustered data.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.embedding import pca_project_det as _pca_project
from repro.core.hierarchy import morton_codes

NEG_INF = -1e30


def masked_softmax(logit: jax.Array, mask: jax.Array) -> jax.Array:
    """Softmax over the last axis with a guarded normalizer.

    Bitwise-identical to ``jax.nn.softmax`` whenever at least one column
    of ``mask`` is live — masked entries carry ``NEG_INF`` logits whose
    ``exp`` underflows to exactly ``+0.0`` — but returns exact zeros
    instead of a uniform row when EVERY column is masked (an
    early-position decode whose selected tiles are all holes/future:
    ``exp(NEG_INF - NEG_INF) == 1`` would weight garbage rows uniformly).
    The guard is ``sparse_block_attention``'s ``jnp.maximum(l, 1e-30)``
    applied to the flat-softmax form."""
    logit = jnp.where(mask, logit, NEG_INF)
    m = jnp.max(logit, axis=-1, keepdims=True)
    e = jnp.exp(logit - jax.lax.stop_gradient(m))
    e = jnp.where(mask, e, 0.0)
    return e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)


def decode_logits(qh: jax.Array, ksel: jax.Array) -> jax.Array:
    """Scaled q·k logits for one (batch, kv-head) slice: qh (g,dh) float32,
    ksel (c,dh) float32 -> (g,c)."""
    scale = jnp.sqrt(jnp.asarray(qh.shape[-1], jnp.float32))
    return qh @ ksel.T / scale


# ---------------------------------------------------------------------------
# per-head orderings as a PlanBatch (the plan API as the ordering asset)
# ---------------------------------------------------------------------------
#
# ``cluster_perm`` below re-derives a throwaway Morton sort on every call —
# fine inside a traced training step, but the serving path (prefill + many
# decode steps over one cache) wants the ordering to be an *asset*: built
# once per (batch, kv-head), reused across calls, refreshable when the cache
# churns, and checkpointable with the model. That asset is exactly an
# ``api.PlanBatch``: one plan per head, stacked on a shared spec.


def kv_plan_batch(k: jax.Array, *, d: int = 3, bits: int = 10,
                  leaf_size: int = 64, knn: int = 8,
                  with_bsr: bool = False, capacity: int = None):
    """One ``InteractionPlan`` per (batch, kv-head) over the keys, stacked
    as an ``api.PlanBatch`` — the per-head ordering `select_blocks`
    consumes (see :func:`plan_batch_perm`).

    Host-side (concrete keys: prefill/serving, not inside a traced step).
    ``with_bsr=True`` additionally dresses each head's kNN pattern into
    storage, so the same batch serves batched near-neighbor matvecs over
    the key sets; the default builds ordering-only members (cheap).

    ``capacity`` over-allocates every member to the given (pow2-unified)
    slot count with Morton-spread holes, so generated tokens stream in
    through ``api.update_plan``'s insert tier instead of re-sorting — the
    decode service builds every session at ``capacity=max_seq`` and all
    sessions share one ``PlanSpec`` (and one compiled decode kernel).
    """
    from repro import api

    kn = np.asarray(k, np.float32)
    s, dh = kn.shape[-2:]
    flat = kn.reshape((-1, s, dh))
    return api.build_plan_batch(flat, k=min(knn, s - 1), d=min(d, dh),
                                bits=bits, leaf_size=leaf_size,
                                with_bsr=with_bsr, backend="bsr",
                                capacity=capacity)


def plan_batch_perm(pb, lead: Tuple[int, ...]) -> jax.Array:
    """Stacked cluster ordering of a :func:`kv_plan_batch` result, shaped
    ``lead + (S,)`` (e.g. ``(B, Hkv, S)``) — a drop-in for the permutation
    :func:`cluster_perm` derives privately per call."""
    pi = pb.data.pi
    want = int(np.prod(lead)) if lead else 1
    if pi.shape[0] != want:
        raise ValueError(
            f"PlanBatch has {pi.shape[0]} members, lead shape {lead} "
            f"needs {want} (one plan per (batch, kv-head))")
    return pi.reshape(tuple(lead) + (pi.shape[-1],)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# steps 1+2: embed + cluster order (embedding shared with core.embedding —
# the same §2.4 step-1 projection the InteractionPlan pipeline uses)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("d", "bits"))
def cluster_perm(k: jax.Array, d: int = 3, bits: int = 10) -> jax.Array:
    """Cluster ordering of keys ``k`` (..., S, dh) -> perm (..., S).

    perm[i] = index (into original order) of the i-th key in cluster order.
    """
    lead = k.shape[:-2]
    flat = k.reshape((-1,) + k.shape[-2:])

    def one(kh):
        y = _pca_project(kh, d)
        return jnp.argsort(morton_codes(y, bits)).astype(jnp.int32)

    return jax.vmap(one)(flat).reshape(lead + (k.shape[-2],))


def permute_kv(k: jax.Array, v: jax.Array, pos: jax.Array, perm: jax.Array):
    """Apply cluster order along the S axis of k, v (B, H, S, dh), pos (B, H, S)."""
    take = lambda a: jnp.take_along_axis(a, perm[..., None], axis=-2)
    return take(k), take(v), jnp.take_along_axis(pos, perm, axis=-1)


# ---------------------------------------------------------------------------
# step 3: block centroids + top-B causal selection
# ---------------------------------------------------------------------------


def block_centroids(k_sorted: jax.Array, bk: int) -> jax.Array:
    """(B, H, S, dh) -> (B, H, S/bk, dh) mean key per cluster tile."""
    b, h, s, dh = k_sorted.shape
    return k_sorted.reshape(b, h, s // bk, bk, dh).mean(axis=3)


@functools.partial(jax.jit, static_argnames=("n_sel", "bq", "causal"))
def select_blocks(q_cent: jax.Array, k_cent: jax.Array,
                  kpos_min: jax.Array, kpos_max: jax.Array,
                  qpos_min: jax.Array, qpos_max: jax.Array,
                  n_sel: int, bq: int, causal: bool = True,
                  local_window: int = 128) -> jax.Array:
    """Top-``n_sel`` key tiles per query tile.

    q_cent (B,H,nqb,dh), k_cent (B,H,nkb,dh); kpos_min/max (B,H,nkb) are the
    min/max original positions inside each (cluster-sorted) key tile;
    qpos_min/max (nqb,). Returns idx (B,H,nqb,n_sel) int32.
    """
    scores = jnp.einsum("bhqd,bhkd->bhqk", q_cent, k_cent)
    if causal:
        # key tile fully in the future of the whole query tile -> never valid
        invalid = kpos_min[:, :, None, :] > qpos_max[None, None, :, None]
        scores = jnp.where(invalid, NEG_INF, scores)
        # boost tiles holding the local causal window (recent tokens)
        recent = (kpos_max[:, :, None, :] >=
                  (qpos_min[None, None, :, None] - local_window))
        near = recent & ~invalid
        scores = jnp.where(near, scores + 1e4, scores)
    _, idx = jax.lax.top_k(scores, n_sel)
    return idx.astype(jnp.int32)


# ---------------------------------------------------------------------------
# step 4: block-segment interaction (online-softmax over selected tiles)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("bq", "bk", "causal"))
def sparse_block_attention(q: jax.Array, k_sorted: jax.Array,
                           v_sorted: jax.Array, pos_sorted: jax.Array,
                           qpos: jax.Array, idx: jax.Array,
                           bq: int, bk: int, causal: bool = True
                           ) -> jax.Array:
    """Block-sparse attention with dense MXU tiles (pure-JAX reference path;
    the Pallas kernel in kernels/block_attention.py implements the same
    contract).

    q (B,Hq,S,dh); k_sorted/v_sorted (B,Hkv,S,dh) in cluster order;
    pos_sorted (B,Hkv,S) original positions; qpos (S,) query positions;
    idx (B,Hkv,nqb,n_sel) selected key tiles per query tile.
    Hq must be a multiple of Hkv (GQA).
    """
    b, hq, s, dh = q.shape
    hkv = k_sorted.shape[1]
    g = hq // hkv
    nqb = s // bq
    n_sel = idx.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(dh, jnp.float32))

    qb = q.reshape(b, hkv, g, nqb, bq, dh)
    kb = k_sorted.reshape(b, hkv, s // bk, bk, dh)
    vb = v_sorted.reshape(b, hkv, s // bk, bk, v_sorted.shape[-1])
    pb = pos_sorted.reshape(b, hkv, s // bk, bk)
    qp = qpos.reshape(nqb, bq)

    def gather_tiles(x, i):                    # x (nkb, ...) i (nqb, n_sel)
        return x[i]                            # (nqb, n_sel, ...)

    def per_bh(qg, kt, vt, pt, it):
        # qg (g,nqb,bq,dh)  kt/vt (nkb,bk,dh)  pt (nkb,bk)  it (nqb,n_sel)
        ksel = gather_tiles(kt, it)            # (nqb, n_sel, bk, dh)
        vsel = gather_tiles(vt, it)
        psel = gather_tiles(pt, it)            # (nqb, n_sel, bk)

        def over_sel(carry, xs):
            m, l, acc = carry
            kt_, vt_, pt_ = xs                 # (nqb,bk,dh),(nqb,bk,dh),(nqb,bk)
            logit = jnp.einsum("gqtd,qsd->gqts", qg, kt_) * scale
            if causal:
                mask = pt_[None, :, None, :] <= qp[None, :, :, None]
                logit = jnp.where(mask, logit, NEG_INF)
            m_new = jnp.maximum(m, logit.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(logit - m_new[..., None])
            l = l * alpha + p.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "gqts,qsd->gqtd", p, vt_.astype(jnp.float32))
            return (m_new, l, acc), None

        m0 = jnp.full((g, nqb, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((g, nqb, bq), jnp.float32)
        a0 = jnp.zeros((g, nqb, bq, v_sorted.shape[-1]), jnp.float32)
        xs = (jnp.swapaxes(ksel, 0, 1), jnp.swapaxes(vsel, 0, 1),
              jnp.swapaxes(psel, 0, 1))        # scan over n_sel
        (m, l, acc), _ = jax.lax.scan(over_sel, (m0, l0, a0), xs)
        return acc / jnp.maximum(l, 1e-30)[..., None]

    out = jax.vmap(jax.vmap(per_bh))(qb, kb, vb, pb, idx)
    return out.reshape(b, hq, s, v_sorted.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# decode: top-c cluster selection + gathered attention
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n_sel",))
def decode_select(q: jax.Array, centroids: jax.Array, n_sel: int) -> jax.Array:
    """q (B,Hq,dh) grouped to kv heads scores centroids (B,Hkv,nkb,dh);
    returns idx (B,Hkv,n_sel)."""
    b, hq, dh = q.shape
    hkv = centroids.shape[1]
    qg = q.reshape(b, hkv, hq // hkv, dh).mean(axis=2)
    # multiply+reduce, not einsum: the grouped query is a single row per
    # kv head, and an M=1 contraction is strength-reduced shape-dependently
    # by XLA:CPU — the elementwise form scores identically per-slice and
    # batched
    scores = jnp.sum(qg[:, :, None, :] * centroids, -1)
    _, idx = jax.lax.top_k(scores, n_sel)
    return idx.astype(jnp.int32)


def plan_decode_select(q: jax.Array, ps: jax.Array, cent: jax.Array,
                       qpos: jax.Array, n_sel: int, bk: int,
                       window: int) -> jax.Array:
    """Tile selection over a PLAN-ordered cache (the decode service).

    q (B,Hq,dh); ps (B,Hkv,S) time position per plan slot (``INT32_MAX``
    marks a capacity hole); cent (B,Hkv,S/bk,dh); qpos (B,). Tiles with no
    live position (all > qpos) never win, and tiles holding a position
    within ``window`` of qpos (the causal frontier) are boosted ahead of
    the rest. Returns idx (B,Hkv,n_sel) int32."""
    b, hq, dh = q.shape
    hkv, s = ps.shape[1], ps.shape[2]
    pt = ps.reshape(b, hkv, s // bk, bk)
    qp = qpos.astype(jnp.int32)
    live = pt <= qp[:, None, None, None]              # causal AND not-a-hole
    tile_has = live.any(-1)                           # (B,Hkv,nkb)
    qg = q.reshape(b, hkv, hq // hkv, dh).mean(axis=2).astype(jnp.float32)
    scores = jnp.sum(qg[:, :, None, :] * cent.astype(jnp.float32), -1)
    scores = jnp.where(tile_has, scores, NEG_INF)
    recent = jnp.where(live, pt, -1).max(-1)
    near = recent >= (qp[:, None, None] - window)
    scores = jnp.where(near & tile_has, scores + 1e4, scores)
    _, idx = jax.lax.top_k(scores, n_sel)
    return idx.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("bk",))
def decode_attend(q: jax.Array, k: jax.Array, v: jax.Array,
                  pos: jax.Array, qpos: jax.Array, idx: jax.Array,
                  bk: int) -> jax.Array:
    """Single-token attention over gathered cluster tiles.

    q (B,Hq,dh); k/v (B,Hkv,S,dh); pos (B,Hkv,S); idx (B,Hkv,c) tile ids.
    Returns (B,Hq,dh). Entries with pos > qpos are masked (cache slots not
    yet filled, or future positions).
    """
    b, hq, dh = q.shape
    hkv, s = k.shape[1], k.shape[2]
    g = hq // hkv
    dv = v.shape[-1]
    nkb = s // bk
    kb = k.reshape(b, hkv, nkb, bk, dh)
    vb = v.reshape(b, hkv, nkb, bk, dv)
    pb = pos.reshape(b, hkv, nkb, bk)

    def per_bh(qh, kt, vt, pt, it):
        # qh (g,dh)  kt (nkb,bk,dh)  vt (nkb,bk,dv)  pt (nkb,bk)  it (c,)
        ksel = kt[it].reshape(-1, dh)          # (c*bk, dh)
        vsel = vt[it].reshape(-1, dv)
        psel = pt[it].reshape(-1)
        logit = decode_logits(qh.astype(jnp.float32),
                              ksel.astype(jnp.float32))
        # guarded: an early-position decode can select only holes/future
        # tiles, and an unguarded softmax would weight them uniformly
        w = masked_softmax(logit, psel[None, :] <= qpos)
        return (w @ vsel.astype(jnp.float32)).astype(q.dtype)

    out = jax.vmap(jax.vmap(per_bh))(
        q.reshape(b, hkv, g, dh), kb, vb, pb, idx)
    return out.reshape(b, hq, dv)
