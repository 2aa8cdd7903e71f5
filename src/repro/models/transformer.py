"""Decoder-only transformer LM covering the dense / vlm / moe families
(GQA, optional QKV bias, optional SWA, optional MLA via models.mla,
optional MoE FFN via models.moe, optional cluster-sparse attention).

All layers are stacked and applied with lax.scan; remat wraps the layer
body. Params are (tree, spec-tree) pairs from models.param.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import mla as mla_mod
from repro.models import param as pm
from repro.models.sharding import ShardCtx, NO_SHARD

AUX_COEF = 0.01


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_attn(key, cfg: ModelConfig):
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    pq, sq = pm.linear(ks[0], d, hq * dh, spec=("fsdp", "tp"), bias=cfg.qkv_bias)
    pk, sk = pm.linear(ks[1], d, hkv * dh, spec=("fsdp", "tp"), bias=cfg.qkv_bias)
    pv, sv = pm.linear(ks[2], d, hkv * dh, spec=("fsdp", "tp"), bias=cfg.qkv_bias)
    po, so = pm.linear(ks[3], hq * dh, d, spec=("tp", "fsdp"))
    return ({"wq": pq, "wk": pk, "wv": pv, "wo": po},
            {"wq": sq, "wk": sk, "wv": sv, "wo": so})


def _init_mlp(key, cfg: ModelConfig):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    pg, sg = pm.linear(ks[0], d, f, spec=("fsdp", "tp"))
    pu, su = pm.linear(ks[1], d, f, spec=("fsdp", "tp"))
    pd, sd = pm.linear(ks[2], f, d, spec=("tp", "fsdp"))
    return ({"wg": pg, "wu": pu, "wd": pd}, {"wg": sg, "wu": su, "wd": sd})


def _init_layer(key, cfg: ModelConfig, dense: bool = False):
    ks = jax.random.split(key, 4)
    p, s = {}, {}
    p["ln1"], s["ln1"] = pm.rmsnorm(cfg.d_model)
    p["ln2"], s["ln2"] = pm.rmsnorm(cfg.d_model)
    if cfg.mla is not None:
        p["attn"], s["attn"] = mla_mod.init_mla(ks[0], cfg)
    else:
        p["attn"], s["attn"] = _init_attn(ks[0], cfg)
    if cfg.moe is not None and not dense:
        p["ffn"], s["ffn"] = moe_mod.init_moe(ks[1], cfg)
    else:
        p["ffn"], s["ffn"] = _init_mlp(ks[1], cfg)
    return p, s


def init_lm(cfg: ModelConfig, key) -> Tuple[dict, dict]:
    """Embedding, the stacked layers, final norm and (untied) head. The
    ``first_k_dense`` leading layers, whose FFN is a dense MLP, stack apart
    as ``dense_layers``."""
    ks = jax.random.split(key, 4)
    p, s = {}, {}
    if not cfg.embedding_inputs:
        p["embed"], s["embed"] = pm.embedding(ks[0], cfg.vocab, cfg.d_model)
    nd = cfg.first_k_dense
    if nd:
        p["dense_layers"], s["dense_layers"] = pm.stacked(
            lambda k: _init_layer(k, cfg, dense=True), nd, ks[3])
    p["layers"], s["layers"] = pm.stacked(
        lambda k: _init_layer(k, cfg), cfg.n_layers - nd, ks[1])
    p["ln_f"], s["ln_f"] = pm.rmsnorm(cfg.d_model)
    if not cfg.tie_embeddings:
        p["head"], s["head"] = pm.linear(ks[2], cfg.d_model, cfg.vocab,
                                         spec=("fsdp", "tp"))
    return p, s


# ---------------------------------------------------------------------------
# layer application
# ---------------------------------------------------------------------------


def _project_qkv(lp, x, cfg: ModelConfig, pos, shd: ShardCtx):
    b, s, d = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = pm.apply_linear(lp["wq"], x).reshape(b, s, hq, dh)
    k = pm.apply_linear(lp["wk"], x).reshape(b, s, hkv, dh)
    v = pm.apply_linear(lp["wv"], x).reshape(b, s, hkv, dh)
    rp = pos if pos.ndim == 3 else pos[None, None, :]   # (B,1,S) or (1,1,S)
    q = attn.rope(q.transpose(0, 2, 1, 3), rp, cfg.rope_theta)
    k = attn.rope(k.transpose(0, 2, 1, 3), rp, cfg.rope_theta)
    v = v.transpose(0, 2, 1, 3)
    q = shd.cst(q, "dp", "tp", None, None)
    k = shd.cst(k, "dp", "tp", None, None)
    v = shd.cst(v, "dp", "tp", None, None)
    return q, k, v


def _attend(q, k, v, pos, cfg: ModelConfig, backend: str):
    if backend == "clusterkv" and cfg.clusterkv.enabled:
        return attn.clusterkv_attention(q, k, v, pos, pos, cfg.clusterkv,
                                        causal=True)
    if backend == "dense":
        return attn.dense_attention(q, k, v, pos, pos, causal=True,
                                    window=cfg.swa_window)
    return attn.flash_attention(q, k, v, pos, pos, causal=True,
                                window=cfg.swa_window)


def _apply_mlp(lp, x):
    h = jax.nn.silu(pm.apply_linear(lp["wg"], x)) * pm.apply_linear(lp["wu"], x)
    return pm.apply_linear(lp["wd"], h)


def _layer(lp, x, pos, cfg: ModelConfig, shd: ShardCtx, backend: str,
           dense: bool = False):
    b, s, d = x.shape
    h = pm.apply_rmsnorm(lp["ln1"], x, cfg.norm_eps)
    if cfg.mla is not None:
        a = mla_mod.mla_attention(lp["attn"], h, pos, cfg, shd, backend)
    else:
        q, k, v = _project_qkv(lp["attn"], h, cfg, pos, shd)
        o = _attend(q, k, v, pos, cfg, backend)
        a = pm.apply_linear(lp["attn"]["wo"], o.transpose(0, 2, 1, 3)
                            .reshape(b, s, -1))
    x = shd.cst(x + a, "dp", None, None)
    h = pm.apply_rmsnorm(lp["ln2"], x, cfg.norm_eps)
    if cfg.moe is not None and not dense:
        f, aux = moe_mod.moe_ffn(lp["ffn"], h, cfg, shd)
    else:
        f, aux = _apply_mlp(lp["ffn"], h), jnp.zeros((), jnp.float32)
    x = shd.cst(x + f, "dp", None, None)
    return x, aux


# ---------------------------------------------------------------------------
# forward / loss
# ---------------------------------------------------------------------------


def embed_tokens(p, cfg: ModelConfig, batch: Dict[str, jax.Array],
                 shd: ShardCtx) -> jax.Array:
    if cfg.embedding_inputs:
        h = batch["embeddings"].astype(cfg.dtype)
    else:
        h = p["embed"]["table"][batch["tokens"]].astype(cfg.dtype)
    return shd.cst(h, "dp", None, None)


def forward(p, cfg: ModelConfig, batch: Dict[str, jax.Array], shd: ShardCtx,
            backend: str = "flash") -> Tuple[jax.Array, jax.Array]:
    """Returns (final hidden states (B,S,d), aux loss)."""
    h = embed_tokens(p, cfg, batch, shd)
    s = h.shape[1]
    pos = jnp.arange(s, dtype=jnp.int32)

    def body(dense, carry, lp):
        x, aux = carry
        x, a = _layer(lp, x, pos, cfg, shd, backend, dense)
        return (x, aux + a), None

    carry = (h, jnp.zeros((), jnp.float32))
    if cfg.first_k_dense:
        carry, _ = jax.lax.scan(
            pm.maybe_remat(lambda c, lp: body(True, c, lp), cfg), carry,
            p["dense_layers"])
    (h, aux), _ = jax.lax.scan(
        pm.maybe_remat(lambda c, lp: body(False, c, lp), cfg), carry,
        p["layers"])
    return pm.apply_rmsnorm(p["ln_f"], h, cfg.norm_eps), aux


def lm_head_weight(p, cfg: ModelConfig):
    if cfg.tie_embeddings:
        return p["embed"]["table"].T
    return p["head"]["w"]


def ce_loss(h: jax.Array, w: jax.Array, labels: jax.Array,
            chunk: int = 0) -> jax.Array:
    """Chunked cross-entropy: logits for one token-chunk at a time, so the
    (tokens x vocab) array is never materialized (vocab stays TP-sharded)."""
    b, s, d = h.shape
    hf = h.reshape(b * s, d)
    lf = labels.reshape(b * s)
    t = b * s
    if chunk and chunk < t and t % chunk == 0:
        def step(_, xs):
            hc, lc = xs
            logits = (hc @ w).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
            return None, jnp.sum(lse - gold)
        _, partial = jax.lax.scan(
            step, None, (hf.reshape(-1, chunk, d), lf.reshape(-1, chunk)))
        return partial.sum() / t
    logits = (hf @ w).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, lf[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)


def loss_fn(p, cfg: ModelConfig, batch, shd: ShardCtx,
            backend: str = "flash") -> jax.Array:
    h, aux = forward(p, cfg, batch, shd, backend)
    w = lm_head_weight(p, cfg).astype(cfg.dtype)
    if not cfg.tie_embeddings:
        # gather the (fsdp-sharded) head weight ONCE, keeping only the vocab
        # dim sharded — otherwise every CE chunk all-reduces a full
        # (chunk x vocab-shard) logits block across the data axis
        # (tied heads skip this: the transposed-table gather would conflict
        # with the embedding lookup's sharding)
        w = shd.cst(w, None, "tp")
    return ce_loss(h, w, batch["labels"], cfg.loss_chunk) + AUX_COEF * aux


# ---------------------------------------------------------------------------
# prefill / decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch_size: int, max_seq: int,
               dtype=None) -> Dict[str, Any]:
    dtype = dtype or jnp.dtype(cfg.dtype)
    if cfg.mla is not None:
        return mla_mod.init_cache(cfg, batch_size, max_seq, dtype)
    l, hkv, dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((l, batch_size, hkv, max_seq, dh), dtype),
        "v": jnp.zeros((l, batch_size, hkv, max_seq, dh), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def cache_specs(cfg: ModelConfig, long_context: bool = False):
    """Logical PartitionSpecs for the cache (seq sharded for long ctx)."""
    if cfg.mla is not None:
        return mla_mod.cache_specs(cfg, long_context)
    if long_context:
        kv = P(None, "dp", None, "seq", None)
    else:
        kv = P(None, "dp", "tp", None, None)
    return {"k": kv, "v": kv, "pos": P()}


def prefill(p, cfg: ModelConfig, batch, shd: ShardCtx,
            backend: str = "flash") -> Tuple[Dict, jax.Array]:
    """Forward over the prompt, returning a filled cache + last logits."""
    if cfg.mla is not None:
        return mla_mod.prefill(p, cfg, batch, shd, backend)
    h = embed_tokens(p, cfg, batch, shd)
    b, s, _ = h.shape
    pos = jnp.arange(s, dtype=jnp.int32)

    def body(x, lp):
        hn = pm.apply_rmsnorm(lp["ln1"], x, cfg.norm_eps)
        q, k, v = _project_qkv(lp["attn"], hn, cfg, pos, shd)
        o = _attend(q, k, v, pos, cfg, backend)
        a = pm.apply_linear(lp["attn"]["wo"],
                            o.transpose(0, 2, 1, 3).reshape(b, s, -1))
        x = x + a
        hn = pm.apply_rmsnorm(lp["ln2"], x, cfg.norm_eps)
        if cfg.moe is not None:
            f, _ = moe_mod.moe_ffn(lp["ffn"], hn, cfg, shd)
        else:
            f = _apply_mlp(lp["ffn"], hn)
        return x + f, (k.astype(cfg.dtype), v.astype(cfg.dtype))

    body = pm.maybe_remat(body, cfg)
    h, (ks, vs) = jax.lax.scan(body, h, p["layers"])
    h = pm.apply_rmsnorm(p["ln_f"], h, cfg.norm_eps)
    logits = (h[:, -1] @ lm_head_weight(p, cfg).astype(cfg.dtype)
              ).astype(jnp.float32)
    cache = {"k": ks, "v": vs, "pos": jnp.asarray(s, jnp.int32)}
    return cache, logits


def decode_step(p, cfg: ModelConfig, cache, tokens, shd: ShardCtx,
                backend: str = "flash", sharded_long: bool = False
                ) -> Tuple[jax.Array, Dict]:
    """One decode step. tokens (B, 1); cache from init_cache/prefill.

    cache["pos"] may be a scalar (uniform decode) or a (B,) vector of
    per-sequence positions (continuous batching: every slot writes and
    masks at its own position)."""
    if cfg.mla is not None:
        return mla_mod.decode_step(p, cfg, cache, tokens, shd, backend,
                                   sharded_long)
    if cfg.embedding_inputs:
        # vlm decode consumes token embeddings directly (text continuation)
        h = tokens.astype(cfg.dtype) if tokens.ndim == 3 else \
            p["embed"]["table"][tokens].astype(cfg.dtype)
    else:
        h = p["embed"]["table"][tokens].astype(cfg.dtype)
    b = h.shape[0]
    qpos = cache["pos"]
    per_slot = qpos.ndim == 1
    s_max = cache["k"].shape[3]
    kpos = jnp.arange(s_max, dtype=jnp.int32)
    # rope positions: (S=1,) uniform or (B, 1, S=1) per-slot broadcast
    rope_pos = (qpos[:, None, None] if per_slot
                else qpos[None]).astype(jnp.int32)
    # attention mask positions: scalar or (B, 1, 1, 1)
    mask_qpos = qpos[:, None, None, None] if per_slot else qpos

    def body(x, xs):
        lp, kc, vc = xs                       # kc/vc (B,Hkv,S,dh)
        hn = pm.apply_rmsnorm(lp["ln1"], x, cfg.norm_eps)
        q, k, v = _project_qkv(lp["attn"], hn, cfg, rope_pos, shd)
        if per_slot:
            bi = jnp.arange(b)
            kc = kc.at[bi, :, qpos].set(k[:, :, 0].astype(kc.dtype))
            vc = vc.at[bi, :, qpos].set(v[:, :, 0].astype(vc.dtype))
        else:
            kc = jax.lax.dynamic_update_slice(
                kc, k.astype(kc.dtype), (0, 0, qpos, 0))
            vc = jax.lax.dynamic_update_slice(
                vc, v.astype(vc.dtype), (0, 0, qpos, 0))
        q1 = q[:, :, 0]                        # (B,Hq,dh)
        if backend == "clusterkv" and cfg.clusterkv.enabled:
            if per_slot:
                # continuous batching: per-call ordering over every slot's
                # cache region (the baseline the plan service amortizes)
                o = attn.clusterkv_percall_decode(q1, kc, vc, kpos, qpos,
                                                  cfg.clusterkv)
            elif sharded_long and shd.mesh is not None:
                o = attn.clusterkv_decode_sharded(
                    q1, kc, vc, kpos, qpos, cfg.clusterkv, shd.mesh)
            else:
                o = attn.clusterkv_decode(q1, kc, vc, kpos, qpos,
                                          cfg.clusterkv)
        else:
            o = attn.decode_attention(q1, kc, vc, kpos, mask_qpos,
                                      window=cfg.swa_window)
        a = pm.apply_linear(lp["attn"]["wo"], o.reshape(b, 1, -1))
        x = x + a
        hn = pm.apply_rmsnorm(lp["ln2"], x, cfg.norm_eps)
        if cfg.moe is not None:
            f, _ = moe_mod.moe_ffn(lp["ffn"], hn, cfg, shd)
        else:
            f = _apply_mlp(lp["ffn"], hn)
        return x + f, (kc, vc)

    h, (ks, vs) = jax.lax.scan(body, h, (p["layers"], cache["k"], cache["v"]))
    h = pm.apply_rmsnorm(p["ln_f"], h, cfg.norm_eps)
    logits = (h[:, 0] @ lm_head_weight(p, cfg).astype(cfg.dtype)
              ).astype(jnp.float32)
    new_cache = {"k": ks, "v": vs, "pos": cache["pos"] + 1}
    return logits, new_cache


def plan_prefill(p, cfg: ModelConfig, batch, perms, shd: ShardCtx
                 ) -> jax.Array:
    """Prefill THROUGH per-layer key plans: ``perms`` (L, B, Hkv, S) are
    the sessions' live cluster orderings, driving the ``plan_batch`` path
    of :func:`~repro.models.attention.clusterkv_attention` — so the first
    generated token already comes from the clusterkv kernel the service
    decodes with. Returns last-position logits only (the service keeps its
    cache plan-ordered; the time-ordered cache of :func:`prefill` never
    exists here). MLA models prefill through their latent plans
    (:func:`repro.models.mla.plan_prefill`)."""
    if cfg.mla is not None:
        return mla_mod.plan_prefill(p, cfg, batch, perms, shd)
    if cfg.embedding_inputs:
        raise NotImplementedError(
            "plan prefill serves token decoder-only models")
    h = embed_tokens(p, cfg, batch, shd)
    b, s, _ = h.shape
    pos = jnp.arange(s, dtype=jnp.int32)

    def body(x, xs):
        lp, perm = xs
        hn = pm.apply_rmsnorm(lp["ln1"], x, cfg.norm_eps)
        q, k, v = _project_qkv(lp["attn"], hn, cfg, pos, shd)
        o = attn.clusterkv_attention(q, k, v, pos, pos, cfg.clusterkv,
                                     causal=True, plan_batch=perm)
        a = pm.apply_linear(lp["attn"]["wo"],
                            o.transpose(0, 2, 1, 3).reshape(b, s, -1))
        x = x + a
        hn = pm.apply_rmsnorm(lp["ln2"], x, cfg.norm_eps)
        if cfg.moe is not None:
            f, _ = moe_mod.moe_ffn(lp["ffn"], hn, cfg, shd)
        else:
            f = _apply_mlp(lp["ffn"], hn)
        return x + f, None

    h, _ = jax.lax.scan(body, h, (p["layers"], perms))
    h = pm.apply_rmsnorm(p["ln_f"], h, cfg.norm_eps)
    return (h[:, -1] @ lm_head_weight(p, cfg).astype(cfg.dtype)
            ).astype(jnp.float32)


def plan_decode_step(p, cfg: ModelConfig, pstate, pend, tokens, slot_pos,
                     shd: ShardCtx) -> Tuple[jax.Array, Dict, jax.Array,
                                             jax.Array]:
    """One decode tick over PLAN-ORDERED caches (the ClusterKV service).

    Instead of the time-ordered cache of :func:`decode_step`, the serving
    state keeps each layer's keys/values in their session plan's cluster
    order plus the bookkeeping the sparse decode needs:

      pstate = {"ks","vs": (L,B,Hkv,S,dh) plan-ordered caches,
                "ps": (L,B,Hkv,S) int32 time position per plan slot
                      (INT32_MAX marks capacity holes),
                "cent": (L,B,Hkv,S/bk,dh) f32 per-tile centroids}
      pend   = {"k","v": (L,B,Hkv,dh) LAST tick's key/value,
                "slot": (L,B,Hkv) int32 plan slot the host-side inserter
                        claimed for it (sentinel S = nothing pending),
                "pos": (B,) int32 its time position}

    The host streams each generated token into the session plans through
    ``api.update_plan``'s insert tier *between* ticks; this step only has
    to land the pending k/v rows at their claimed slots (a scatter),
    refresh the one centroid tile each landing touched, and attend with
    the current token's own k/v carried as an extra column (so
    self-attention never waits on the landing). tokens (B,1);
    slot_pos (B,). Returns (logits, new_pstate, k_new, v_new) where
    k_new/v_new (L,B,Hkv,dh) are THIS tick's rows for the host to claim
    slots for. MLA models keep one plan per layer over their latent rows
    (:func:`repro.models.mla.plan_decode_step`).
    """
    if cfg.mla is not None:
        return mla_mod.plan_decode_step(p, cfg, pstate, pend, tokens,
                                        slot_pos, shd)
    if cfg.embedding_inputs:
        raise NotImplementedError(
            "plan decode serves token decoder-only models")
    ckv_cfg = cfg.clusterkv
    h = p["embed"]["table"][tokens].astype(cfg.dtype)
    b = h.shape[0]
    hkv = cfg.n_kv_heads
    s_cap = pstate["ks"].shape[3]
    bk = min(ckv_cfg.block_k, s_cap)
    qpos = slot_pos.astype(jnp.int32)
    rope_pos = qpos[:, None, None]
    nl = pstate["ks"].shape[0]
    li = jnp.arange(nl)[:, None, None]
    bi = jnp.arange(b)[None, :, None]
    hi = jnp.arange(hkv)[None, None, :]
    ppos = jnp.broadcast_to(pend["pos"].astype(jnp.int32)[None, :, None],
                            (nl, b, hkv))

    # land last tick's pending token at its claimed plan slot, one fused
    # scatter across all layers BEFORE the layer scan so the big caches
    # never ride through it as stacked outputs; the sentinel slot == S is
    # out of bounds -> dropped (nothing pending)
    pslot = pend["slot"]
    ks = pstate["ks"].at[li, bi, hi, pslot].set(
        pend["k"].astype(pstate["ks"].dtype), mode="drop")
    vs = pstate["vs"].at[li, bi, hi, pslot].set(
        pend["v"].astype(pstate["vs"].dtype), mode="drop")
    ps = pstate["ps"].at[li, bi, hi, pslot].set(ppos, mode="drop")
    # refresh the ONE centroid tile each landing touched (recomputing an
    # untouched tile's mean is a no-op, so the clipped sentinel is safe);
    # gather the tile FIRST, then widen — never astype the whole cache
    tile = jnp.clip(pslot, 0, s_cap - 1) // bk                # (L,B,Hkv)
    seg_idx = tile[..., None] * bk + jnp.arange(bk)           # (L,B,Hkv,bk)
    seg = jnp.take_along_axis(ks, seg_idx[..., None], axis=3)
    cent = pstate["cent"].at[li, bi, hi, tile].set(
        seg.astype(jnp.float32).mean(3))

    # unrolled layer loop: a lax.scan would materialize per-layer slices
    # of the (L,B,Hkv,S,dh) caches as carried/stacked buffers every tick;
    # unrolled, XLA fuses the static layer slice into the tile gathers and
    # the landing scatter can alias the donated cache buffers in place
    nks, nvs = [], []
    for l in range(nl):
        lp = jax.tree_util.tree_map(lambda a: a[l], p["layers"])
        hn = pm.apply_rmsnorm(lp["ln1"], h, cfg.norm_eps)
        q, k, v = _project_qkv(lp["attn"], hn, cfg, rope_pos, shd)
        q1, k1, v1 = q[:, :, 0], k[:, :, 0], v[:, :, 0]
        o = attn.clusterkv_plan_decode(q1, ks[l], vs[l], ps[l], cent[l],
                                       qpos, ckv_cfg, k_self=k1, v_self=v1)
        a = pm.apply_linear(lp["attn"]["wo"], o.reshape(b, 1, -1))
        h = h + a
        hn = pm.apply_rmsnorm(lp["ln2"], h, cfg.norm_eps)
        if cfg.moe is not None:
            f, _ = moe_mod.moe_ffn(lp["ffn"], hn, cfg, shd)
        else:
            f = _apply_mlp(lp["ffn"], hn)
        h = h + f
        nks.append(k1)
        nvs.append(v1)
    nk, nv = jnp.stack(nks), jnp.stack(nvs)
    h = pm.apply_rmsnorm(p["ln_f"], h, cfg.norm_eps)
    logits = (h[:, 0] @ lm_head_weight(p, cfg).astype(cfg.dtype)
              ).astype(jnp.float32)
    return logits, {"ks": ks, "vs": vs, "ps": ps, "cent": cent}, nk, nv
