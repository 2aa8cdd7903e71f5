"""Multi-head Latent Attention (MiniCPM3 / DeepSeek-V2 style).

Train/prefill expand the latent into full per-head K/V and reuse the shared
attention backends. Decode runs the ABSORBED form: the cache holds only the
(normalized) latent c (rank) + shared RoPE key (dr) per token, query-side
projections are absorbed into the latent space, and attention operates on
the latent directly.

The query comes from a LoRA pair (``q_a``, ``q_ln``, ``q_b``; MiniCPM3)
or, with ``q_lora_rank`` 0, from one direct projection ``wq``
(DeepSeek-V2-Lite). With ``MLAConfig.yarn`` the rope dims take YaRN's
mixed frequencies and the softmax scale gains ``mscale^2``; rope rotates
the two halves of the rope dims, as ``attention.rope`` does, where the
published code first de-interleaves them (with random weights a fixed
permutation of those columns). ``first_k_dense`` leading layers
(``params["dense_layers"]``) take a dense MLP; the rest take the MoE FFN
when the model has one. The serving bodies (prefill, decode, the plan
service) run the MoE through ``moe.moe_serve``: dropless, over the
chip's held experts.

ClusterKV on MLA clusters in the *latent* space: the paper's "embed
first" step is literally MLA's latent projection. The decode service
(:func:`plan_decode_step`) keeps one plan per layer over the latent rows
``[c || k_rope]`` (kv_lora_rank + rope dims, 576 for DeepSeek-V2-Lite),
held once: the values are the first kv_lora_rank columns of the same
rows. Its tick selects tiles by every query head's absorbed score against
the latent centroids, attends over the selected latent rows with the
current token's own row as an extra column, then applies ``W_uv`` and
``wo``. The seq-sharded long-context decode selects on latent blocks too.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.compat import shard_map

from repro.configs.base import ModelConfig
from repro.core import clusterkv as ckv
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import param as pm
from repro.models.sharding import ShardCtx

NEG_INF = -1e30


def _dims(cfg: ModelConfig):
    m = cfg.mla
    return (m.q_lora_rank, m.kv_lora_rank, m.qk_nope_head_dim,
            m.qk_rope_head_dim, m.v_head_dim)


# ---------------------------------------------------------------------------
# YaRN (DeepSeek-V2's DeepseekV2YarnRotaryEmbedding)
# ---------------------------------------------------------------------------


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _correction_dim(rotations: float, dim: int, base: float,
                    max_pos: int) -> float:
    return (dim * math.log(max_pos / (rotations * 2 * math.pi))
            / (2 * math.log(base)))


def rope_inv_freq(cfg: ModelConfig) -> Optional[np.ndarray]:
    """The rope dims' inverse frequencies (dr/2,): None for plain rope;
    with YaRN, ``inv_freq / factor`` below the correction range of
    ``beta_fast``..``beta_slow`` rotations over the original context, the
    plain ``inv_freq`` above it, and a linear ramp between."""
    y = cfg.mla.yarn
    if y is None:
        return None
    dim, base = cfg.mla.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / y.factor
    low = max(math.floor(_correction_dim(y.beta_fast, dim, base,
                                         y.original_max_position)), 0)
    high = min(math.ceil(_correction_dim(y.beta_slow, dim, base,
                                         y.original_max_position)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                      # 1: plain frequency
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def q_factor(cfg: ModelConfig) -> float:
    """What the softmax scale carries beyond ``(dn + dr)^-0.5``: YaRN's
    ``mscale(factor, mscale_all_dim)^2``. Applied to the query, so every
    attention backend's own ``1/sqrt(dn + dr)`` gives the published
    scale."""
    y = cfg.mla.yarn
    return 1.0 if y is None else yarn_mscale(y.factor, y.mscale_all_dim) ** 2


def _rope(x, pos, cfg: ModelConfig):
    return attn.rope(x, pos, cfg.rope_theta, inv_freq=rope_inv_freq(cfg))


# ---------------------------------------------------------------------------
# init / projections
# ---------------------------------------------------------------------------


def init_mla(key, cfg: ModelConfig):
    d, h = cfg.d_model, cfg.n_heads
    qr, kr, dn, dr, dv = _dims(cfg)
    ks = jax.random.split(key, 6)
    p, s = {}, {}
    if qr:
        p["q_a"], s["q_a"] = pm.linear(ks[0], d, qr, spec=("fsdp", None))
        p["q_ln"], s["q_ln"] = pm.rmsnorm(qr)
        p["q_b"], s["q_b"] = pm.linear(ks[1], qr, h * (dn + dr),
                                       spec=(None, "tp"))
    else:
        p["wq"], s["wq"] = pm.linear(ks[0], d, h * (dn + dr),
                                     spec=("fsdp", "tp"))
    p["kv_a"], s["kv_a"] = pm.linear(ks[2], d, kr + dr, spec=("fsdp", None))
    p["kv_ln"], s["kv_ln"] = pm.rmsnorm(kr)
    p["kv_b"], s["kv_b"] = pm.linear(ks[3], kr, h * (dn + dv), spec=(None, "tp"))
    p["wo"], s["wo"] = pm.linear(ks[4], h * dv, d, spec=("tp", "fsdp"))
    return p, s


def _q_proj(lp, x, cfg: ModelConfig, pos):
    """x (B,S,d) -> q_nope (B,H,S,dn), q_rope (B,H,S,dr) (roped), both
    times :func:`q_factor`. pos (S,) shared or (B,S) per sequence."""
    b, s, _ = x.shape
    h = cfg.n_heads
    qr, kr, dn, dr, dv = _dims(cfg)
    if qr:
        q = pm.apply_linear(lp["q_b"],
                            pm.apply_rmsnorm(lp["q_ln"],
                                             pm.apply_linear(lp["q_a"], x)))
    else:
        q = pm.apply_linear(lp["wq"], x)
    q = q.reshape(b, s, h, dn + dr).transpose(0, 2, 1, 3)
    qn, qrope = q[..., :dn], q[..., dn:]
    rp = pos[None, None, :] if pos.ndim == 1 else pos[:, None, :]
    qrope = _rope(qrope, rp, cfg)
    f = q_factor(cfg)
    if f != 1.0:
        qn, qrope = qn * f, qrope * f
    return qn, qrope


def _kv_latent(lp, x, cfg: ModelConfig, pos):
    """x (B,S,d) -> cn (B,S,rank) normalized latent, krope (B,S,dr) roped.
    pos (S,) shared or (B,S) per sequence."""
    qr, kr, dn, dr, dv = _dims(cfg)
    kv = pm.apply_linear(lp["kv_a"], x)
    c, krope = kv[..., :kr], kv[..., kr:]
    cn = pm.apply_rmsnorm(lp["kv_ln"], c, cfg.norm_eps)
    krope = _rope(krope, pos[None, :] if pos.ndim == 1 else pos, cfg)
    return cn, krope


def _expand_kv(lp, cn, cfg: ModelConfig):
    """cn (B,S,rank) -> k_nope (B,H,S,dn), v (B,H,S,dv)."""
    b, s, _ = cn.shape
    h = cfg.n_heads
    qr, kr, dn, dr, dv = _dims(cfg)
    kv = pm.apply_linear(lp["kv_b"], cn).reshape(b, s, h, dn + dv)
    kv = kv.transpose(0, 2, 1, 3)
    return kv[..., :dn], kv[..., dn:]


def _attention(lp, x, pos, cfg: ModelConfig, shd: ShardCtx, backend: str,
               plan_batch=None):
    """Full MLA attention over x (B,S,d) at positions pos (S,): returns
    (out (B,S,d) incl. wo, cn, krope). ``plan_batch`` (B,S) is the latent
    plan's key order, shared by every head (``plan_prefill``)."""
    b, s, _ = x.shape
    h = cfg.n_heads
    qn, qrope = _q_proj(lp, x, cfg, pos)
    cn, krope = _kv_latent(lp, x, cfg, pos)
    kn, v = _expand_kv(lp, cn, cfg)
    q = jnp.concatenate([qn, qrope], axis=-1)
    k = jnp.concatenate(
        [kn, jnp.broadcast_to(krope[:, None], kn.shape[:-1] + (krope.shape[-1],))],
        axis=-1)
    q = shd.cst(q, "dp", "tp", None, None)
    k = shd.cst(k, "dp", "tp", None, None)
    if plan_batch is not None:
        o = attn.clusterkv_attention(
            q, k, v, pos, pos, cfg.clusterkv, causal=True,
            plan_batch=jnp.broadcast_to(plan_batch[:, None], (b, h, s)))
    elif backend == "clusterkv" and cfg.clusterkv.enabled:
        o = attn.clusterkv_attention(q, k, v, pos, pos, cfg.clusterkv,
                                     causal=True)
    elif backend == "dense":
        o = attn.dense_attention(q, k, v, pos, pos, causal=True)
    else:
        o = attn.flash_attention(q, k, v, pos, pos, causal=True)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, -1)
    return pm.apply_linear(lp["wo"], o), cn, krope


def mla_attention(lp, x, pos, cfg: ModelConfig, shd: ShardCtx,
                  backend: str) -> jax.Array:
    """Full (train/prefill) MLA attention, returns (B,S,d) incl. wo."""
    return _attention(lp, x, pos, cfg, shd, backend)[0]


# ---------------------------------------------------------------------------
# cache / prefill / absorbed decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch_size: int, max_seq: int,
               dtype=None) -> Dict[str, Any]:
    dtype = dtype or jnp.dtype(cfg.dtype)
    qr, kr, dn, dr, dv = _dims(cfg)
    return {
        "c": jnp.zeros((cfg.n_layers, batch_size, max_seq, kr), dtype),
        "kr": jnp.zeros((cfg.n_layers, batch_size, max_seq, dr), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def cache_specs(cfg: ModelConfig, long_context: bool = False):
    if long_context:
        c = P(None, "dp", "seq", None)
    else:
        c = P(None, "dp", None, None)
    return {"c": c, "kr": c, "pos": P()}


def _mlp(lp, x):
    h = jax.nn.silu(pm.apply_linear(lp["wg"], x)) * pm.apply_linear(lp["wu"], x)
    return pm.apply_linear(lp["wd"], h)


def _embed(p, cfg, batch):
    return p["embed"]["table"][batch["tokens"]].astype(cfg.dtype)


def _head(p, cfg, h):
    w = (p["embed"]["table"].T if cfg.tie_embeddings else p["head"]["w"])
    return (h @ w.astype(cfg.dtype)).astype(jnp.float32)


def layer_stacks(p, cfg: ModelConfig):
    """The stacked layer groups in depth order, each with whether its FFN
    is a dense MLP: ``dense_layers`` (``first_k_dense``), then
    ``layers``."""
    out = []
    if cfg.first_k_dense:
        out.append((p["dense_layers"], True))
    out.append((p["layers"], cfg.moe is None))
    return out


def serve_ffn(lp, x, cfg: ModelConfig, dense: bool, active=None):
    """The serving FFN of one layer: ``(y, moe stats)``."""
    if dense:
        return _mlp(lp, x), jnp.zeros((len(moe_mod.MOE_STATS),), jnp.int32)
    return moe_mod.moe_serve(lp, x, cfg, active)


def prefill(p, cfg: ModelConfig, batch, shd: ShardCtx,
            backend: str = "flash"):
    """Forward over the prompt: the latent cache, last-position logits.
    With MoE layers the cache also carries ``moe``, the summed
    ``moe.MOE_STATS`` of the prompt's routing."""
    h = _embed(p, cfg, batch)
    b, s, _ = h.shape
    pos = jnp.arange(s, dtype=jnp.int32)

    def body(dense, x, lp):
        hn = pm.apply_rmsnorm(lp["ln1"], x, cfg.norm_eps)
        a, cn, krope = _attention(lp["attn"], hn, pos, cfg, shd, backend)
        x = x + a
        hn = pm.apply_rmsnorm(lp["ln2"], x, cfg.norm_eps)
        f, st = serve_ffn(lp["ffn"], hn, cfg, dense)
        return x + f, (cn.astype(cfg.dtype), krope.astype(cfg.dtype), st)

    cs, krs, sts = [], [], []
    for stack, dense in layer_stacks(p, cfg):
        fn = pm.maybe_remat(lambda x, lp, dense=dense: body(dense, x, lp),
                            cfg)
        h, (c, kr, st) = jax.lax.scan(fn, h, stack)
        cs.append(c)
        krs.append(kr)
        sts.append(st.sum(0))
    h = pm.apply_rmsnorm(p["ln_f"], h, cfg.norm_eps)
    cache = {"c": jnp.concatenate(cs), "kr": jnp.concatenate(krs),
             "pos": jnp.asarray(s, jnp.int32)}
    if cfg.moe is not None:
        cache["moe"] = sum(sts)
    return cache, _head(p, cfg, h[:, -1])


def plan_prefill(p, cfg: ModelConfig, batch, perms, shd: ShardCtx
                 ) -> jax.Array:
    """Prefill through the latent plans: ``perms`` (L, B, 1, S) is each
    layer's latent plan order, shared by every head's expanded keys on
    the ``plan_batch`` path of ``clusterkv_attention``. Returns
    last-position logits only."""
    h = _embed(p, cfg, batch)
    s = h.shape[1]
    pos = jnp.arange(s, dtype=jnp.int32)

    def body(dense, x, xs):
        lp, perm = xs
        hn = pm.apply_rmsnorm(lp["ln1"], x, cfg.norm_eps)
        x = x + _attention(lp["attn"], hn, pos, cfg, shd, "clusterkv",
                           plan_batch=perm[:, 0])[0]
        hn = pm.apply_rmsnorm(lp["ln2"], x, cfg.norm_eps)
        return x + serve_ffn(lp["ffn"], hn, cfg, dense)[0], None

    lo = 0
    for stack, dense in layer_stacks(p, cfg):
        n = jax.tree.leaves(stack)[0].shape[0]
        h, _ = jax.lax.scan(lambda x, xs, dense=dense: body(dense, x, xs),
                            h, (stack, perms[lo:lo + n]))
        lo += n
    h = pm.apply_rmsnorm(p["ln_f"], h, cfg.norm_eps)
    return _head(p, cfg, h[:, -1])


def _absorbed_scores_attend(lp, qn, qrope, cc, krc, kpos, qpos, cfg,
                            shd: ShardCtx, backend: str, sharded_long: bool):
    """Absorbed-form attention over latent cache.

    qn (B,H,dn), qrope (B,H,dr); cc (B,S,rank); krc (B,S,dr).
    Returns o_lat (B,H,rank)."""
    qr_, kr_, dn, dr, dv = _dims(cfg)
    h = cfg.n_heads
    wkv = lp["kv_b"]["w"].reshape(kr_, h, dn + dv)
    wk = wkv[..., :dn]                                   # (rank, H, dn)
    q_lat = jnp.einsum("bhd,rhd->bhr", qn.astype(jnp.float32),
                       wk.astype(jnp.float32))
    scale = 1.0 / math.sqrt(dn + dr)

    if backend == "clusterkv" and cfg.clusterkv.enabled and shd.mesh is not None \
            and sharded_long:
        return _latent_decode_sharded(q_lat, qrope, cc, krc, kpos, qpos,
                                      cfg, shd, scale)
    logits = (jnp.einsum("bhr,bsr->bhs", q_lat, cc.astype(jnp.float32))
              + jnp.einsum("bhd,bsd->bhs", qrope.astype(jnp.float32),
                           krc.astype(jnp.float32))) * scale
    ok = kpos[None, None, :] <= qpos
    logits = jnp.where(ok, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhs,bsr->bhr", w, cc.astype(jnp.float32))


def _latent_decode_sharded(q_lat, qrope, cc, krc, kpos, qpos, cfg,
                           shd: ShardCtx, scale):
    """ClusterKV decode on the latent cache, seq sharded over 'data':
    per-shard latent-block centroids -> top-c -> partial softmax -> psum."""
    mesh = shd.mesh
    axis = "data"
    b, s, rank = cc.shape
    hq = q_lat.shape[1]
    shards = mesh.shape[axis]
    s_local = s // shards
    bk = min(cfg.clusterkv.block_k, s_local)
    n_sel = min(cfg.clusterkv.decode_clusters, s_local // bk)

    def local(ql, qr2, cl, krl, pl):
        nkb = cl.shape[1] // bk
        cb = cl.reshape(b, nkb, bk, rank)
        krb = krl.reshape(b, nkb, bk, -1)
        pb = pl.reshape(nkb, bk)
        cent_c = cb.mean(axis=2)                          # (b, nkb, rank)
        cent_k = krb.mean(axis=2)
        sc = (jnp.einsum("bhr,bkr->bhk", ql, cent_c.astype(jnp.float32))
              + jnp.einsum("bhd,bkd->bhk", qr2.astype(jnp.float32),
                           cent_k.astype(jnp.float32)))
        sc = sc.mean(axis=1)                              # (b, nkb) shared sel
        _, idx = jax.lax.top_k(sc, n_sel)

        def per_b(qlb, qrb, cbb, krbb, it):
            csel = cbb[it].reshape(-1, rank).astype(jnp.float32)
            ksel = krbb[it].reshape(-1, krbb.shape[-1]).astype(jnp.float32)
            psel = pb.reshape(-1)[(it[:, None] * bk
                                   + jnp.arange(bk)[None, :]).reshape(-1)]
            lg = (qlb @ csel.T + qrb.astype(jnp.float32) @ ksel.T) * scale
            lg = jnp.where(psel[None, :] <= qpos, lg, NEG_INF)
            m = lg.max(axis=-1)
            pexp = jnp.exp(lg - m[:, None])
            return m, pexp.sum(-1), pexp @ csel

        m, l, o = jax.vmap(per_b)(ql, qr2, cb, krb, idx)
        mm = jax.lax.pmax(m, axis)
        alpha = jnp.exp(m - mm)
        ll = jax.lax.psum(l * alpha, axis)
        oo = jax.lax.psum(o * alpha[..., None], axis)
        return oo / jnp.maximum(ll, 1e-30)[..., None]

    f = shard_map(local, mesh=mesh,
                  in_specs=(P(), P(), P(None, axis, None),
                            P(None, axis, None), P(axis)),
                  out_specs=P(), check_vma=False)
    return f(q_lat, qrope, cc, krc, kpos)


def decode_step(p, cfg: ModelConfig, cache, tokens, shd: ShardCtx,
                backend: str = "flash", sharded_long: bool = False):
    h = _embed(p, cfg, {"tokens": tokens})
    b = h.shape[0]
    qpos = cache["pos"]
    s_max = cache["c"].shape[2]
    kpos = jnp.arange(s_max, dtype=jnp.int32)
    qr_, kr_, dn, dr, dv = _dims(cfg)
    nheads = cfg.n_heads

    def body(dense, x, xs):
        lp, cc, krc = xs                      # cc (B,S,rank), krc (B,S,dr)
        hn = pm.apply_rmsnorm(lp["ln1"], x, cfg.norm_eps)
        qn, qrope = _q_proj(lp["attn"], hn, cfg, qpos[None].astype(jnp.int32))
        cn1, kr1 = _kv_latent(lp["attn"], hn, cfg,
                              qpos[None].astype(jnp.int32))
        cc = jax.lax.dynamic_update_slice(cc, cn1.astype(cc.dtype),
                                          (0, qpos, 0))
        krc = jax.lax.dynamic_update_slice(krc, kr1.astype(krc.dtype),
                                           (0, qpos, 0))
        o_lat = _absorbed_scores_attend(
            lp["attn"], qn[:, :, 0], qrope[:, :, 0], cc, krc, kpos, qpos,
            cfg, shd, backend, sharded_long)
        wkv = lp["attn"]["kv_b"]["w"].reshape(kr_, nheads, dn + dv)
        wv = wkv[..., dn:]
        o = jnp.einsum("bhr,rhd->bhd", o_lat, wv.astype(jnp.float32))
        a = pm.apply_linear(lp["attn"]["wo"],
                            o.reshape(b, 1, -1).astype(cfg.dtype))
        x = x + a
        hn = pm.apply_rmsnorm(lp["ln2"], x, cfg.norm_eps)
        x = x + serve_ffn(lp["ffn"], hn, cfg, dense)[0]
        return x, (cc, krc)

    cs, krs, lo = [], [], 0
    for stack, dense in layer_stacks(p, cfg):
        n = jax.tree.leaves(stack)[0].shape[0]
        h, (c, kr) = jax.lax.scan(
            lambda x, xs, dense=dense: body(dense, x, xs), h,
            (stack, cache["c"][lo:lo + n], cache["kr"][lo:lo + n]))
        cs.append(c)
        krs.append(kr)
        lo += n
    h = pm.apply_rmsnorm(p["ln_f"], h, cfg.norm_eps)
    return _head(p, cfg, h[:, 0]), {"c": jnp.concatenate(cs),
                                    "kr": jnp.concatenate(krs),
                                    "pos": cache["pos"] + 1}


# ---------------------------------------------------------------------------
# the plan service's tick over latent plans
# ---------------------------------------------------------------------------

# device-side counters the plan tick keeps in ``pstate["stats"]``
COUNTERS = moe_mod.MOE_STATS + ("decode_rows_attended", "decode_ticks")
_LO_BITS = 20


def bump_counters(stats: jax.Array, inc: jax.Array) -> jax.Array:
    """Add ``inc`` (N,) int32, each under 2^31 - 2^20, to counters kept
    as (N, 2) int32 ``[high, low]`` words (value = high * 2^20 + low,
    low < 2^20): exact far past int32, with no 64-bit type on the
    device."""
    low = stats[:, 1] + inc
    return jnp.stack([stats[:, 0] + (low >> _LO_BITS),
                      low & ((1 << _LO_BITS) - 1)], axis=1)


def read_counters(stats) -> Dict[str, int]:
    """The values of :func:`bump_counters` words, by ``COUNTERS`` name."""
    st = np.asarray(stats).astype(np.int64)
    return {n: int(hi) * (1 << _LO_BITS) + int(lo)
            for n, (hi, lo) in zip(COUNTERS, st)}


def latent_dim(cfg: ModelConfig) -> int:
    """Width of one latent row ``[c || k_rope]``."""
    return cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim


def row_width(cfg: ModelConfig) -> int:
    """Width the plan state stores a latent row at: ``latent_dim`` padded
    with zero columns to a multiple of 128. At 576 the TPU's tiling pads
    the minor dim anyway, and a gather of 576-wide rows makes XLA copy
    the whole cache first; at 640 the tick gathers in place. Zero columns
    change no score, centroid or value."""
    return -(-latent_dim(cfg) // 128) * 128


def _widen(x, width: int):
    pad = width - x.shape[-1]
    return x if pad == 0 else jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def latent_attention(lp, hn, lat, ps, cent, qpos, cfg: ModelConfig,
                     layer=None):
    """One layer's absorbed attention for one token per sequence over
    plan-ordered latent rows. hn (B,1,d) normed input; lat (B,1,S,W)
    latent rows ``[c || k_rope]`` stored ``W = row_width`` wide (with
    ``layer``: the (L,B,1,S,W) stack, read in place at that layer); ps
    (B,1,S); cent (B,1,S/bk,W); qpos (B,). The absorbed query
    ``[W_uk^T q_nope || q_rope]`` of every head selects tiles and attends
    (``attention.clusterkv_plan_decode``, values the rows' first
    kv_lora_rank columns) with this token's own row as an extra column;
    ``W_uv`` and ``wo`` follow.

    Returns (out (B,1,d), the token's row (B,1,R) at ``latent_dim``,
    q_lat (B,H,W) as the backend saw it)."""
    qr, kr, dn, dr, dv = _dims(cfg)
    b, nh = hn.shape[0], cfg.n_heads
    qn, qrope = _q_proj(lp, hn, cfg, qpos[:, None])
    cn1, kr1 = _kv_latent(lp, hn, cfg, qpos[:, None])
    row = jnp.concatenate([cn1, kr1], -1).astype(lat.dtype)     # (B,1,R)
    wkv = lp["kv_b"]["w"].reshape(kr, nh, dn + dv)
    # the backend divides logits by sqrt(R); the model's scale is
    # (dn + dr)^-0.5 (its YaRN factor rides on the query already)
    width = lat.shape[-1]
    q_lat = _widen(jnp.concatenate(
        [jnp.einsum("bhd,rhd->bhr", qn[:, :, 0].astype(jnp.float32),
                    wkv[..., :dn].astype(jnp.float32)),
         qrope[:, :, 0].astype(jnp.float32)], -1), width) \
        * math.sqrt(width / (dn + dr))
    o_lat = attn.clusterkv_plan_decode(q_lat, lat, None, ps, cent, qpos,
                                       cfg.clusterkv,
                                       k_self=_widen(row, width), dv=kr,
                                       layer=layer)
    o = jnp.einsum("bhr,rhd->bhd", o_lat, wkv[..., dn:].astype(jnp.float32))
    out = pm.apply_linear(lp["wo"], o.reshape(b, 1, -1).astype(hn.dtype))
    return out, row, q_lat


def plan_decode_step(p, cfg: ModelConfig, pstate, pend, tokens, slot_pos,
                     shd: ShardCtx):
    """One decode tick over PLAN-ORDERED latent rows (the ClusterKV
    service on MLA). The twin of ``transformer.plan_decode_step``, with one
    plan per layer over the latent and no separate values:

      pstate = {"ks": (L,B,1,S,W) latent rows [c || k_rope] in plan
                      order, zero-padded to W = row_width,
                "ps": (L,B,1,S) int32 time position per plan slot
                      (INT32_MAX marks capacity holes),
                "cent": (L,B,1,S/bk,W) f32 per-tile centroids,
                "stats": (len(COUNTERS), 2) int32 counters}
      pend   = {"k": (L,B,1,R) LAST tick's latent rows,
                "slot": (L,B,1) plan slot claimed for each (sentinel S),
                "pos": (B,) their time positions,
                "active": (B,) bool slots serving a request (counters)}

    Each layer: the absorbed query ``[W_uk^T q_nope || q_rope]`` of every
    head selects tiles against the latent centroids and attends over the
    selected latent rows plus the token's own row; ``W_uv`` and ``wo``
    follow. Returns (logits, new_pstate, rows (L,B,1,R), None)."""
    ckv_cfg = cfg.clusterkv
    h = p["embed"]["table"][tokens].astype(cfg.dtype)
    b = h.shape[0]
    nl, _, hkv, s_cap, width = pstate["ks"].shape
    bk = min(ckv_cfg.block_k, s_cap)
    nkb = s_cap // bk
    n_sel = min(ckv_cfg.decode_clusters, nkb)
    qpos = slot_pos.astype(jnp.int32)
    active = pend["active"]
    li = jnp.arange(nl)[:, None, None]
    bi = jnp.arange(b)[None, :, None]
    hi = jnp.arange(hkv)[None, None, :]
    ppos = jnp.broadcast_to(pend["pos"].astype(jnp.int32)[None, :, None],
                            (nl, b, hkv))

    # land last tick's rows at their claimed plan slots (one scatter for
    # all layers; sentinel S drops) and refresh each touched tile's mean
    pslot = pend["slot"]
    ks = pstate["ks"].at[li, bi, hi, pslot].set(
        _widen(pend["k"], width).astype(pstate["ks"].dtype), mode="drop")
    ps = pstate["ps"].at[li, bi, hi, pslot].set(ppos, mode="drop")
    tile = jnp.clip(pslot, 0, s_cap - 1) // bk
    seg_idx = tile[..., None] * bk + jnp.arange(bk)
    seg = jnp.take_along_axis(ks, seg_idx[..., None], axis=3)
    cent = pstate["cent"].at[li, bi, hi, tile].set(
        seg.astype(jnp.float32).mean(3))

    layers = [(jax.tree.map(lambda a, i=i: a[i], stack), dense)
              for stack, dense in layer_stacks(p, cfg)
              for i in range(jax.tree.leaves(stack)[0].shape[0])]
    moe_st = jnp.zeros((len(moe_mod.MOE_STATS),), jnp.int32)
    attended = jnp.zeros((), jnp.int32)
    rows = []
    for l, (lp, dense) in enumerate(layers):
        hn = pm.apply_rmsnorm(lp["ln1"], h, cfg.norm_eps)
        a, row, q_lat = latent_attention(lp["attn"], hn, ks, ps[l],
                                         cent[l], qpos, cfg, layer=l)
        h = h + a
        hn = pm.apply_rmsnorm(lp["ln2"], h, cfg.norm_eps)
        f, st = serve_ffn(lp["ffn"], hn, cfg, dense, active[:, None])
        h = h + f
        moe_st = moe_st + st
        # live rows in the tiles the backend selects (the same selection)
        idx = ckv.plan_decode_select(q_lat, ps[l], cent[l], qpos, n_sel, bk,
                                     ckv_cfg.local_window_blocks * bk)
        pt = ps[l].reshape(b, hkv, nkb, bk)
        psel = jnp.take_along_axis(pt, idx[..., None], axis=2)
        live = (psel <= qpos[:, None, None, None]).sum((1, 2, 3))
        attended = attended + jnp.sum(jnp.where(active, live, 0),
                                      dtype=jnp.int32)
        rows.append(row)
    h = pm.apply_rmsnorm(p["ln_f"], h, cfg.norm_eps)
    logits = _head(p, cfg, h[:, 0])
    inc = jnp.concatenate([moe_st, jnp.stack([attended,
                                              jnp.ones((), jnp.int32)])])
    new = {"ks": ks, "ps": ps, "cent": cent,
           "stats": bump_counters(pstate["stats"], inc)}
    return logits, new, jnp.stack(rows), None
