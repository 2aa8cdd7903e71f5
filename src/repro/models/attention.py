"""Attention backends.

Layout convention: q/k/v are (B, H, S, dh); positions are int32.

  dense_attention   naive full logits — tiny smoke tests only
  flash_attention   lax.scan over key tiles with online softmax (GQA-aware,
                    causal and sliding-window masks) — the memory-sane
                    full-attention path used by train/prefill lowerings
  decode_attention  single-token einsum over the whole cache (logits are
                    O(S), never O(S^2)); GSPMD shards the cache seq axis
  clusterkv_*       the paper's technique (core/clusterkv): cluster-sorted
                    keys, top-B dense tiles per query tile; sharded decode
                    combines per-shard partial softmax (flash-decode style)
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.compat import shard_map

from repro.configs.base import ClusterKVConfig
from repro.core import clusterkv as ckv
from repro.core.registry import register_decode_backend

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: jax.Array, pos: jax.Array, theta: float = 1e4) -> jax.Array:
    """Rotary embedding. x (..., S, dh), pos (..., S) broadcastable."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * freqs      # (..., S, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# full-attention paths
# ---------------------------------------------------------------------------


def _mask(logit, qpos, kpos, causal: bool, window: int):
    ok = jnp.ones(logit.shape[-2:], bool)
    if causal:
        ok = kpos[None, :] <= qpos[:, None]
    if window:
        ok = ok & (kpos[None, :] > qpos[:, None] - window)
    return jnp.where(ok, logit, NEG_INF)


def dense_attention(q, k, v, qpos, kpos, *, causal=True, window=0):
    b, hq, s, dh = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, s, dh)
    logit = jnp.einsum("bhgsd,bhtd->bhgst", qg.astype(jnp.float32),
                       k.astype(jnp.float32)) / jnp.sqrt(float(dh))
    logit = _mask(logit, qpos, kpos, causal, window)
    w = jax.nn.softmax(logit, axis=-1)
    o = jnp.einsum("bhgst,bhtd->bhgsd", w, v.astype(jnp.float32))
    return o.reshape(b, hq, s, v.shape[-1]).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block"))
def flash_attention(q, k, v, qpos, kpos, *, causal=True, window=0,
                    block: int = 512):
    """Blockwise online-softmax attention, scan over key tiles."""
    b, hq, s, dh = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = 1.0 / jnp.sqrt(float(dh))
    nb = -(-skv // block)
    pad = nb * block - skv
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    posp = jnp.pad(kpos, (0, pad), constant_values=jnp.iinfo(jnp.int32).max)
    qg = q.reshape(b, hkv, g, s, dh).astype(jnp.float32)

    kb = kp.reshape(b, hkv, nb, block, dh)
    vb = vp.reshape(b, hkv, nb, block, v.shape[-1])
    pb = posp.reshape(nb, block)

    pad_pos = jnp.iinfo(jnp.int32).max

    def step(carry, xs):
        m, l, acc = carry
        kt, vt, pt = xs                       # (b,hkv,block,dh), ..., (block,)
        logit = jnp.einsum("bhgsd,bhtd->bhgst", qg,
                           kt.astype(jnp.float32)) * scale
        ok = jnp.broadcast_to(pt[None, :] != pad_pos, (s, block))
        if causal:
            ok = ok & (pt[None, :] <= qpos[:, None])
        if window:
            ok = ok & (pt[None, :] > qpos[:, None] - window)
        logit = jnp.where(ok, logit, NEG_INF)
        m_new = jnp.maximum(m, logit.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logit - m_new[..., None])
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgst,bhtd->bhgsd", p, vt.astype(jnp.float32))
        return (m_new, l, acc), None

    dv = v.shape[-1]
    m0 = jnp.full((b, hkv, g, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, s), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, s, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (jnp.moveaxis(kb, 2, 0), jnp.moveaxis(vb, 2, 0), pb))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    return o.reshape(b, hq, s, dv).astype(q.dtype)


def decode_attention(q, k, v, kpos, qpos, *, window=0):
    """q (B,Hq,dh) one token; cache k/v (B,Hkv,S,dh); kpos (B,S) or (S,).

    Entries with kpos > qpos are masked (unfilled cache slots / future)."""
    b, hq, dh = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    if kpos.ndim == 1:
        kpos = jnp.broadcast_to(kpos, (b, kpos.shape[0]))
    qg = q.reshape(b, hkv, g, dh).astype(jnp.float32)
    logit = jnp.einsum("bhgd,bhtd->bhgt", qg,
                       k.astype(jnp.float32)) / jnp.sqrt(float(dh))
    ok = kpos[:, None, None, :] <= qpos
    if window:
        ok = ok & (kpos[:, None, None, :] > qpos - window)
    logit = jnp.where(ok, logit, NEG_INF)
    w = jax.nn.softmax(logit, axis=-1)
    o = jnp.einsum("bhgt,bhtd->bhgd", w, v.astype(jnp.float32))
    return o.reshape(b, hq, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# cluster-sparse backend (the paper's technique)
# ---------------------------------------------------------------------------


def clusterkv_attention(q, k, v, qpos, kpos, cfg: ClusterKVConfig, *,
                        causal=True, plan_batch=None):
    """Block-sparse attention over cluster-sorted keys (train/prefill).

    The paper reorders BOTH matrix dimensions (pi_t and pi_s). Keys are
    always cluster-sorted; for non-causal attention (encoder/cross/t-SNE
    style) queries are cluster-sorted too — per head — so query tiles are
    cluster-coherent and centroid selection is sharp; outputs are scattered
    back to original order. For causal LM attention queries stay in time
    order (the local-window boost supplies recency; sorting queries would
    scramble the causal frontier).

    ``plan_batch`` (an ``api.PlanBatch`` from ``ckv.kv_plan_batch(k)``,
    or the stacked (B, Hkv, Skv) ordering array extracted from one)
    supplies the per-head key ordering as a persistent plan asset instead
    of the private per-call Morton sort — the serving path builds it once
    at prefill, refreshes/checkpoints it with the cache, and every
    subsequent call skips the embed+sort work. The array form is traced
    data, so the decode service passes each session's orderings into ONE
    compiled prefill shared by every spec-identical session. Key entries
    with ``kpos == INT32_MAX`` are treated as holes (capacity slots not
    yet streamed into) and never attended.
    """
    b, hq, s, dh = q.shape
    hkv = k.shape[1]
    bq = min(cfg.block_q, s)
    bk = min(cfg.block_k, s)
    nqb, nkb = s // bq, k.shape[2] // bk
    n_sel = min(cfg.blocks_per_query, nkb)

    if kpos.ndim == 1:
        kposb = jnp.broadcast_to(kpos, (b, hkv, kpos.shape[0]))
    else:
        kposb = kpos
    if plan_batch is None:
        perm = ckv.cluster_perm(k, d=cfg.embed_dim)
    elif hasattr(plan_batch, "data"):
        perm = ckv.plan_batch_perm(plan_batch, (b, hkv))
    else:
        perm = jnp.asarray(plan_batch).astype(jnp.int32)
    k_s, v_s, pos_s = ckv.permute_kv(k, v, kposb, perm)
    cent = ckv.block_centroids(k_s, bk)
    posb = pos_s.reshape(b, hkv, nkb, bk)
    kpmin = posb.min(-1)
    # hole slots carry the INT32_MAX sentinel: they must not inflate the
    # tile's max position (that would make every holey tile look "recent"
    # and soak up the local-window boost)
    kpmax = jnp.where(posb == jnp.iinfo(jnp.int32).max, -1, posb).max(-1)

    if not causal:
        # pi_t: query cluster sort per kv-head group (positions irrelevant)
        g = hq // hkv
        q_grp = q.reshape(b, hkv, g, s, dh).mean(axis=2)    # (B,Hkv,S,dh)
        qperm = ckv.cluster_perm(q_grp, d=cfg.embed_dim)    # (B,Hkv,S)
        qperm_h = jnp.repeat(qperm, g, axis=1)              # (B,Hq,S)
        q_s = jnp.take_along_axis(q, qperm_h[..., None], axis=-2)
        qc = q_s.reshape(b, hkv, g, nqb, bq, dh).mean(axis=(2, 4))
        zero = jnp.zeros((nqb,), jnp.int32)
        idx = ckv.select_blocks(qc.astype(jnp.float32),
                                cent.astype(jnp.float32), kpmin, kpmax,
                                zero, zero, n_sel, bq, causal=False)
        out_s = _tile_attention(q_s, k_s, v_s, pos_s, qpos, idx, bq, bk,
                                False, cfg)
        inv = jnp.argsort(qperm_h, axis=-1)
        return jnp.take_along_axis(out_s, inv[..., None], axis=-2)

    qpmin = qpos.reshape(nqb, bq).min(-1)
    qpmax = qpos.reshape(nqb, bq).max(-1)
    qc = q.reshape(b, hkv, hq // hkv, nqb, bq, dh).mean(axis=(2, 4))
    idx = ckv.select_blocks(qc.astype(jnp.float32), cent.astype(jnp.float32),
                            kpmin, kpmax, qpmin, qpmax, n_sel, bq,
                            causal=causal,
                            local_window=cfg.local_window_blocks * bk)
    return _tile_attention(q, k_s, v_s, pos_s, qpos, idx, bq, bk, causal, cfg)


def _tile_attention(q, k_s, v_s, pos_s, qpos, idx, bq, bk, causal,
                    cfg: ClusterKVConfig):
    """Dense-tile interaction: Pallas kernel when requested, jnp otherwise."""
    if cfg.use_pallas:
        from repro.kernels import ops as kops
        return kops.block_attention(q, k_s, v_s, pos_s, qpos, idx,
                                    bq=bq, bk=bk, causal=causal)
    return ckv.sparse_block_attention(q, k_s, v_s, pos_s, qpos, idx, bq, bk,
                                      causal=causal)


def clusterkv_decode(q, k, v, kpos, qpos, cfg: ClusterKVConfig):
    """Single-token decode: top-c tiles by centroid score, gathered attend.

    ``cfg.use_pallas`` routes the select+gather+attend chain through the
    fused Mosaic kernel (``kernels/decode_attend.py``) instead of the two
    unfused XLA ops — equal to float32 rounding, selected tiles stream
    from HBM exactly once."""
    b, hq, dh = q.shape
    hkv, s = k.shape[1], k.shape[2]
    bk = min(cfg.block_k, s)
    if s % bk:
        # cache length not tile-aligned (e.g. ad-hoc growth in examples):
        # fall back to dense decode — correct, just not sparse
        kp = kpos if kpos.ndim == 1 else kpos[0, 0]
        return decode_attention(q, k, v, kp, qpos)
    nkb = s // bk
    n_sel = min(cfg.decode_clusters, nkb)
    if kpos.ndim == 1:
        kpos = jnp.broadcast_to(kpos, (b, hkv, kpos.shape[0]))
    cent = ckv.block_centroids(k, bk)
    if cfg.use_pallas:
        from repro.kernels import ops as kops
        return kops.decode_attend_fused(q, k, v, kpos, cent, qpos,
                                        n_sel=n_sel, bk=bk)
    idx = ckv.decode_select(q.astype(jnp.float32), cent.astype(jnp.float32),
                            n_sel)
    return ckv.decode_attend(q, k, v, kpos, qpos, idx, bk)


def clusterkv_plan_decode(q, ks, vs, ps, cent, qpos, cfg: ClusterKVConfig, *,
                          k_self=None, v_self=None):
    """Single-token decode over PLAN-ordered caches (the decode service).

    q (B,Hq,dh); ks/vs (B,Hkv,S,dh) keys/values already in plan (cluster)
    order; ps (B,Hkv,S) int32 original time position of each plan slot,
    with ``INT32_MAX`` marking capacity holes not yet streamed into;
    cent (B,Hkv,S/bk,dh) per-tile centroids maintained incrementally by
    the service; qpos (B,) per-slot decode positions.

    ``k_self``/``v_self`` (B,Hkv,dh) optionally carry the CURRENT token's
    key/value as an always-visible extra column: the service lands each
    generated token into the plan one tick later (insert-tier streaming is
    host-side), so self-attention must not depend on the landing.

    No embed/sort/full-centroid work happens here — that is the point:
    everything order-derived is serving state, this is pure gather+attend.

    Dispatches through the decode-backend registry: ``cfg.decode_backend``
    names ``"xla"`` (the unfused select/gather/attend below) or
    ``"pallas"`` (the fused Mosaic kernel); ``"auto"`` asks the analytic
    cost model (``core.costmodel.choose_decode_backend``) — the same
    ``repro.cost/v1`` model that ranks the SpMV backends — which prices
    the fused kernel's single launch and once-only tile traffic against
    the XLA path's gather round-trip (and its interpret-mode slowdown on
    CPU, where the XLA path keeps winning).
    """
    from repro.core.registry import get_decode_backend

    name = cfg.decode_backend
    if name == "auto":
        from repro.core import costmodel
        from repro.kernels import ops as kops

        b, hq, dh = q.shape
        hkv, s = ks.shape[1], ks.shape[2]
        bk = min(cfg.block_k, s)
        feat = costmodel.DecodeFeatures(
            batch=b, hq=hq, hkv=hkv, s=s, dh=dh, dv=vs.shape[-1], bk=bk,
            n_sel=min(cfg.decode_clusters, s // bk))
        name = costmodel.choose_decode_backend(
            feat, interpret=kops._interpret())
    return get_decode_backend(name)(q, ks, vs, ps, cent, qpos, cfg,
                                    k_self=k_self, v_self=v_self)


@register_decode_backend("xla")
def _plan_decode_xla(q, ks, vs, ps, cent, qpos, cfg: ClusterKVConfig, *,
                     k_self=None, v_self=None):
    """The unfused reference: top-k select, vmapped tile gather, attend."""
    b, hq, dh = q.shape
    hkv, s = ks.shape[1], ks.shape[2]
    g = hq // hkv
    dv = vs.shape[-1]
    bk = min(cfg.block_k, s)
    nkb = s // bk
    n_sel = min(cfg.decode_clusters, nkb)
    big = jnp.iinfo(jnp.int32).max

    pt = ps.reshape(b, hkv, nkb, bk)
    qp = qpos.astype(jnp.int32)                       # (B,)
    idx = ckv.plan_decode_select(q, ps, cent, qp, n_sel, bk,
                                 cfg.local_window_blocks * bk)

    kb = ks.reshape(b, hkv, nkb, bk, dh)
    vb = vs.reshape(b, hkv, nkb, bk, dv)
    if k_self is None:
        k_self = jnp.zeros((b, hkv, dh), ks.dtype)
        v_self = jnp.zeros((b, hkv, dv), vs.dtype)
        self_pos = jnp.full((b, hkv), big, jnp.int32)   # masked out
    else:
        self_pos = jnp.broadcast_to(qp[:, None], (b, hkv))

    def per_h(qh, kt, vt, pt_, it, ksf, vsf, spos, qp_):
        # qh (g,dh)  kt (nkb,bk,dh)  vt (nkb,bk,dv)  pt_ (nkb,bk)  it (c,)
        ksel = jnp.concatenate([kt[it].reshape(-1, dh), ksf[None, :]], 0)
        vsel = jnp.concatenate([vt[it].reshape(-1, dv), vsf[None, :]], 0)
        psel = jnp.concatenate([pt_[it].reshape(-1), spos[None]], 0)
        logit = ckv.decode_logits(qh.astype(jnp.float32),
                                  ksel.astype(jnp.float32))
        # guarded (see ckv.masked_softmax): a just-admitted slot can select
        # nothing but holes when no self column rides along
        w = ckv.masked_softmax(logit, psel[None, :] <= qp_)
        return (w @ vsel.astype(jnp.float32)).astype(q.dtype)

    out = jax.vmap(jax.vmap(per_h, in_axes=(0, 0, 0, 0, 0, 0, 0, 0, None)),
                   in_axes=(0, 0, 0, 0, 0, 0, 0, 0, 0))(
        q.reshape(b, hkv, g, dh), kb, vb, pt, idx,
        k_self, v_self, self_pos, qp)
    return out.reshape(b, hq, dv)


def clusterkv_percall_decode(q, k, v, kpos, qpos, cfg: ClusterKVConfig):
    """Per-call clusterkv decode for per-slot position vectors (qpos (B,)).

    Re-derives the Morton ordering and ALL tile centroids of the whole
    cache on every generated token — the baseline cost the plan-cached
    service amortizes away. Kept as the continuous-batching analogue of
    :func:`clusterkv_decode` (whose scalar-qpos contract serves the
    single-sequence cache path)."""
    b, hq, dh = q.shape
    hkv, s = k.shape[1], k.shape[2]
    bk = min(cfg.block_k, s)
    if kpos.ndim == 1:
        kpos = jnp.broadcast_to(kpos, (b, hkv, s))
    if s % bk:
        return decode_attention(q, k, v, kpos[:, 0],
                                qpos[:, None, None, None])
    perm = ckv.cluster_perm(k, d=cfg.embed_dim)       # per call — the cost
    ks, vs, ps = ckv.permute_kv(k, v, kpos, perm)
    cent = ckv.block_centroids(ks.astype(jnp.float32), bk)
    return clusterkv_plan_decode(q, ks, vs, ps, cent, qpos, cfg)


def clusterkv_decode_sharded(q, k, v, kpos, qpos, cfg: ClusterKVConfig,
                             mesh: Mesh, axis: str = "data"):
    """Long-context decode with the cache sequence sharded over ``axis``.

    Every shard selects its local top-c cluster tiles, computes a partial
    softmax (m, l, o), and partials combine with pmax/psum — flash-decode
    with the paper's cluster selection inside each shard. No cross-shard
    gathers ever touch the cache.
    """
    b, hq, dh = q.shape
    hkv, s = k.shape[1], k.shape[2]
    shards = mesh.shape[axis]
    s_local = s // shards
    bk = min(cfg.block_k, s_local)
    n_sel = min(cfg.decode_clusters, s_local // bk)
    if kpos.ndim == 1:
        kpos = jnp.broadcast_to(kpos, (b, hkv, s))

    def local(qh, kl, vl, pl):
        # kl/vl (b, hkv, s_local, dh); pl (b, hkv, s_local)
        cent = ckv.block_centroids(kl, bk)
        idx = ckv.decode_select(qh.astype(jnp.float32),
                                cent.astype(jnp.float32), n_sel)
        g = hq // hkv
        nkb = s_local // bk
        kb = kl.reshape(b, hkv, nkb, bk, dh)
        vb = vl.reshape(b, hkv, nkb, bk, dh)
        pb = pl.reshape(b, hkv, nkb, bk)

        def per_bh(qg, kt, vt, pt, it):
            ksel = kt[it].reshape(-1, dh).astype(jnp.float32)
            vsel = vt[it].reshape(-1, dh).astype(jnp.float32)
            psel = pt[it].reshape(-1)
            logit = (qg.astype(jnp.float32) @ ksel.T) / jnp.sqrt(float(dh))
            logit = jnp.where(psel[None, :] <= qpos, logit, NEG_INF)
            m = logit.max(axis=-1)
            p = jnp.exp(logit - m[:, None])
            return m, p.sum(-1), p @ vsel

        m, l, o = jax.vmap(jax.vmap(per_bh))(
            qh.reshape(b, hkv, g, dh), kb, vb, pb, idx)
        mm = jax.lax.pmax(m, axis)
        alpha = jnp.exp(m - mm)
        ll = jax.lax.psum(l * alpha, axis)
        oo = jax.lax.psum(o * alpha[..., None], axis)
        out = oo / jnp.maximum(ll, 1e-30)[..., None]
        return out.reshape(b, hq, dh).astype(q.dtype)

    f = shard_map(local, mesh=mesh,
                  in_specs=(P(), P(None, None, axis, None),
                            P(None, None, axis, None), P(None, None, axis)),
                  out_specs=P(), check_vma=False)
    return f(q, k, v, kpos)
