"""Plain reference for a DeepSeek-V2 decoder: the full causal forward pass
in float32, with no cache, no kernels, no latent absorption and no
selection.

It imports nothing of the program. It is given the weights the benchmark
made (bfloat16, widened here to float32 one layer at a time), the
configuration's sizes and the token sequences the service read and
produced, and returns the logits at the positions that produced each
served token. It follows the published DeepSeek-V2 description
(arXiv:2405.04434 and the model's ``modeling_deepseek.py``):

- RMSNorm before attention and FFN;
- latent attention with a direct query projection: the latent ``c`` and
  the shared rope key from ``kv_a``, RMSNorm on ``c`` alone, per-head
  keys and values expanded from ``c`` through ``kv_b`` (the plain form,
  not the absorbed one), the rope key shared by every head;
- YaRN rope: inverse frequencies mixed between the plain ones and those
  slowed by ``factor`` along a linear ramp over the correction range of
  ``beta_fast``..``beta_slow`` rotations in the original context, cos and
  sin scaled by ``mscale(factor, mscale) / mscale(factor,
  mscale_all_dim)``, softmax scale ``(dn + dr)^-0.5 *
  mscale(factor, mscale_all_dim)^2``;
- the first ``first_k_dense`` layers with a SiLU-gated dense MLP, the
  rest with a softmax router over all routed experts, greedy top-k, gates
  renormalised only where ``norm_topk_prob`` says, times
  ``routed_scaling_factor``, and the shared experts as one MLP;
- an untied head. Every product runs at ``Precision.HIGHEST``.

Departures, both shared with the program:

- rope rotates the two halves of the rope dims; the published code
  first de-interleaves the rope columns (``view(d/2, 2).transpose``),
  which with random weights is a fixed permutation of those columns;
- the expert share: the weights hold only this chip's experts
  (``first_held`` on); each token's pairs routed to them are computed
  and what experts held elsewhere would add is left out.

``quant="fp8"`` is the control: the same pass with every weight matrix
(router, experts, embedding and head included) rounded to float8 e4m3,
scaled per output channel, the step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


class Sizes(NamedTuple):
    """The numbers of the configuration the forward needs (hashable)."""
    heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rms_eps: float
    rope_theta: float
    yarn: tuple           # (factor, original_max_position, beta_fast,
                          #  beta_slow, mscale, mscale_all_dim)
    top_k: int
    first_held: int
    norm_topk_prob: bool
    routed_scaling_factor: float


def _fp8(w: jax.Array, axis: int) -> jax.Array:
    """``w`` rounded to float8 e4m3 with one scale per slice along
    ``axis`` (its largest magnitude maps to the format's largest)."""
    top = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.where(top > 0, top / 448.0, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, base: float, yarn: tuple) -> np.ndarray:
    """YaRN's inverse frequencies for ``dim`` rope dims (float64)."""
    factor, orig, beta_fast, beta_slow, _, _ = yarn

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    freq = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    return freq / factor * (1.0 - mask) + freq * mask


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, inv_freq, gain):
    """x (..., S, dr) at positions 0..S-1, rotating the two halves."""
    s, dr = x.shape[-2:]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang) * gain, jnp.sin(ang) * gain
    x1, x2 = x[..., :dr // 2], x[..., dr // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mm(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def _mlp(x, wg, wu, wd):
    return _mm(jax.nn.silu(_mm(x, wg)) * _mm(x, wu), wd)


@functools.partial(jax.jit, static_argnames=("sizes", "quant"))
def logits_at(params, tokens: jax.Array, rows: jax.Array, *, sizes: Sizes,
              quant: str = "") -> jax.Array:
    """Logits (B, m, vocab) at positions ``rows`` (B, m) of the sequences
    ``tokens`` (B, S)."""
    if quant not in ("", "fp8"):
        raise ValueError(f"unknown quant {quant!r}")
    q8 = (lambda w, axis: _fp8(w, axis)) if quant else (lambda w, axis: w)
    z = sizes
    hq, kr, dn, dr, dv = (z.heads, z.kv_lora_rank, z.qk_nope_head_dim,
                          z.qk_rope_head_dim, z.v_head_dim)
    factor, _, _, _, msc, msc_all = z.yarn
    inv_freq = jnp.asarray(yarn_inv_freq(dr, z.rope_theta, z.yarn),
                           jnp.float32)
    gain = _mscale(factor, msc) / _mscale(factor, msc_all)
    scale = (dn + dr) ** -0.5 * _mscale(factor, msc_all) ** 2
    b, s = tokens.shape
    mask = jnp.tril(jnp.ones((s, s), bool))

    def attention(h, a):
        q = _mm(h, q8(a["wq"]["w"], 0)).reshape(b, s, hq, dn + dr)
        q = q.transpose(0, 2, 1, 3)
        kv = _mm(h, q8(a["kv_a"]["w"], 0))
        c = _rms(kv[..., :kr], a["kv_ln"]["scale"], z.rms_eps)
        kpe = _rope(kv[..., kr:], inv_freq, gain)               # (b, s, dr)
        kvb = _mm(c, q8(a["kv_b"]["w"], 0)).reshape(b, s, hq, dn + dv)
        kvb = kvb.transpose(0, 2, 1, 3)
        k = jnp.concatenate([kvb[..., :dn], jnp.broadcast_to(
            kpe[:, None], (b, hq, s, dr))], -1)
        qq = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], inv_freq,
                                                  gain)], -1)
        logit = jnp.einsum("bhqd,bhkd->bhqk", qq, k,
                           precision=HIGHEST) * scale
        p = jax.nn.softmax(jnp.where(mask, logit, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", p, kvb[..., dn:],
                       precision=HIGHEST)
        return _mm(o.transpose(0, 2, 1, 3).reshape(b, s, hq * dv),
                   q8(a["wo"]["w"], 0))

    def experts(h, f):
        probs = jax.nn.softmax(_mm(h, q8(f["router"]["w"], 0)), axis=-1)
        top, idx = jax.lax.top_k(probs, z.top_k)
        if z.norm_topk_prob:
            top = top / top.sum(-1, keepdims=True)
        top = top * z.routed_scaling_factor
        held = f["wg"].shape[0]
        ids = z.first_held + jnp.arange(held)
        gate = jnp.sum(jnp.where(idx[..., None] == ids, top[..., None], 0.0),
                       axis=-2)                                  # (b, s, held)
        wg, wu, wd = (q8(f["wg"], 1), q8(f["wu"], 1), q8(f["wd"], 1))
        hid = jax.nn.silu(jnp.einsum("bsd,edf->ebsf", h, wg,
                                     precision=HIGHEST)) \
            * jnp.einsum("bsd,edf->ebsf", h, wu, precision=HIGHEST)
        out = jnp.einsum("ebsf,efd->ebsd", hid, wd, precision=HIGHEST)
        y = jnp.einsum("ebsd,bse->bsd", out, gate, precision=HIGHEST)
        sh = f["shared"]
        return y + _mlp(h, q8(sh["wg"], 0), q8(sh["wu"], 0),
                        q8(sh["wd"], 0))

    def layer(dense, x, lp):
        lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
        x = x + attention(_rms(x, lp["ln1"]["scale"], z.rms_eps), lp["attn"])
        h = _rms(x, lp["ln2"]["scale"], z.rms_eps)
        f = lp["ffn"]
        if dense:
            y = _mlp(h, q8(f["wg"]["w"], 0), q8(f["wu"]["w"], 0),
                     q8(f["wd"]["w"], 0))
        else:
            y = experts(h, f)
        return x + y, None

    # the rows looked up, widened (and rounded per row) on their own: the
    # whole table in float32 would not fit beside the weights
    x = q8(params["embed"]["table"][tokens].astype(jnp.float32), -1)
    if "dense_layers" in params:
        x, _ = jax.lax.scan(functools.partial(layer, True), x,
                            params["dense_layers"])
    x, _ = jax.lax.scan(functools.partial(layer, False), x, params["layers"])
    x = _rms(x, params["ln_f"]["scale"].astype(jnp.float32), z.rms_eps)
    x = jnp.take_along_axis(x, rows[..., None], axis=1)          # (B, m, d)
    return _mm(x, q8(params["head"]["w"].astype(jnp.float32), 0))
