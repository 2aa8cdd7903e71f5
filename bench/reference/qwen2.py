"""Plain reference for a Qwen2 decoder: the full causal forward pass in
float32, with no cache, no kernels and no selection.

It imports nothing of the program. It is given the weights the benchmark
made (bfloat16, widened here to float32), the configuration's sizes and
the token sequences the service read and produced, and returns the
logits at the positions that produced each served token. It follows the
published Qwen2 description: RMSNorm before attention and MLP, rotary
embedding on the two halves of each head, grouped-query attention with
biases on q, k and v, a SiLU-gated MLP, and the head tied to the
embedding. Every product runs at ``Precision.HIGHEST``.

``quant="fp8"`` is the control: the same pass with every weight matrix
rounded to float8 e4m3, scaled per output channel (per row of the
embedding), the step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_MATRICES = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")


def _fp8(w: jax.Array, axis: int) -> jax.Array:
    """``w`` rounded to float8 e4m3 with one scale per slice along
    ``axis`` (its largest magnitude maps to the format's largest)."""
    top = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.where(top > 0, top / 448.0, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    """x (B, H, S, dh) at positions 0..S-1."""
    s, dh = x.shape[-2:]
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mm(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("sizes", "quant"))
def logits_at(params, tokens: jax.Array, rows: jax.Array, *, sizes: tuple,
              quant: str = "") -> jax.Array:
    """Logits (B, m, vocab) at positions ``rows`` (B, m) of the sequences
    ``tokens`` (B, S). ``sizes`` is ``(heads, kv_heads, rms_eps,
    rope_theta)``."""
    hq, hkv, eps, theta = sizes
    b, s = tokens.shape
    table = params["embed"]["table"].astype(jnp.float32)
    if quant == "fp8":
        table = _fp8(table, 1)
    elif quant:
        raise ValueError(f"unknown quant {quant!r}")
    d = table.shape[1]
    dh = d // hq
    mask = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lp):
        lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
        w = {n: (lp["attn"] if n in ("wq", "wk", "wv", "wo") else lp["ffn"])
             [n]["w"] for n in _MATRICES}
        if quant == "fp8":
            w = {n: _fp8(v, 0) for n, v in w.items()}
        a = lp["attn"]
        h = _rms(x, lp["ln1"]["scale"], eps)
        q = (_mm(h, w["wq"]) + a["wq"]["b"]).reshape(b, s, hq, dh)
        k = (_mm(h, w["wk"]) + a["wk"]["b"]).reshape(b, s, hkv, dh)
        v = (_mm(h, w["wv"]) + a["wv"]["b"]).reshape(b, s, hkv, dh)
        q = _rope(q.transpose(0, 2, 1, 3), theta)
        k = _rope(k.transpose(0, 2, 1, 3), theta)
        v = v.transpose(0, 2, 1, 3)
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
        logit = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                           precision=HIGHEST) / jnp.sqrt(jnp.float32(dh))
        p = jax.nn.softmax(jnp.where(mask, logit, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", p, v, precision=HIGHEST)
        x = x + _mm(o.transpose(0, 2, 1, 3).reshape(b, s, d), w["wo"])
        h = _rms(x, lp["ln2"]["scale"], eps)
        f = jax.nn.silu(_mm(h, w["wg"])) * _mm(h, w["wu"])
        return x + _mm(f, w["wd"]), None

    x = table[tokens]
    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x, params["ln_f"]["scale"].astype(jnp.float32), eps)
    x = jnp.take_along_axis(x, rows[..., None], axis=1)       # (B, m, d)
    return _mm(x, table.T)
