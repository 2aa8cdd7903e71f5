"""Pallas TPU kernel: fused cluster-tile gather + single-token attend.

The decode-side twin of ``bsr_spmv``'s batch-grid kernel. The unfused XLA
path gathers the selected k/v tiles (a vmapped take that writes them back
to HBM) and then runs the attend over the copy. This kernel attends
straight off the cache: grid ``(batch member, kv head, selected tile)``,
where the tile index arrives by scalar prefetch, so the Pallas pipeline
DMAs each selected ``(bk, d)`` tile from HBM into VMEM exactly once
(double-buffered against the previous tile's compute) and nothing else of
the cache moves. The softmax runs online across the tiles (running max,
normalizer and weighted sum in VMEM scratch) and is guarded: a selection
with no live position yields exact zeros, like
``core.clusterkv.masked_softmax``.

Tile *selection* stays in XLA — ``core.clusterkv.decode_select`` (plain
caches) or ``plan_decode_select`` (the decode service's plan-ordered
caches: holes masked, local-window boost) — a top-k over ``S/bk``
centroid scores, shared with the XLA backends, so both backends attend the
same tiles. With ``has_self`` the current token's own k/v join the
softmax as an always-visible extra column (the service lands each token in
the plan one tick later).

Every block keeps its last two dims whole (k/v tiles ``(bk, d)``,
positions ``(1, bk)``), which the chip's tiling rule accepts at any head
width; a manual DMA slice of a 64-wide head is refused. Agreement with
the XLA reference (one softmax over the concatenated selection) is a
float32 tolerance, not bitwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(idx_ref, qpos_ref, q_ref, k_ref, v_ref, p_ref, kself_ref,
            vself_ref, o_ref, m_scr, l_scr, acc_scr, *, n_sel, has_self):
    b = pl.program_id(0)
    j = pl.program_id(2)
    qp = qpos_ref[b]
    qf = q_ref[0, 0].astype(jnp.float32)              # (g, dh)
    scale = jnp.sqrt(jnp.asarray(qf.shape[-1], jnp.float32))

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def update(logit, mask, v):
        """Fold one block of columns into the running softmax."""
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(jnp.where(mask, logit, NEG_INF),
                                            axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        e = jnp.where(mask, jnp.exp(logit - m_new), 0.0)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(e, axis=-1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jnp.dot(
            e, v, precision=_HIGHEST, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    kf = k_ref[0, 0].astype(jnp.float32)              # (bk, dh)
    logit = jax.lax.dot_general(
        qf, kf, (((1,), (1,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32) / scale   # (g, bk)
    update(logit, p_ref[0, 0, 0] <= qp, v_ref[0, 0].astype(jnp.float32))

    @pl.when(j == n_sel - 1)
    def _finish():
        if has_self:
            ks = kself_ref[0, 0].astype(jnp.float32)  # (1, dh)
            l_self = jnp.sum(qf * ks, axis=-1, keepdims=True) / scale
            update(l_self, jnp.ones(l_self.shape, bool),
                   vself_ref[0, 0].astype(jnp.float32))
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "has_self", "interpret"))
def decode_attend_fused(q, k, v, pos, idx, qpos, k_self=None, v_self=None,
                        *, bk: int, has_self: bool = False,
                        interpret: bool = False) -> jax.Array:
    """Gather+attend over selected tiles. q (B,Hq,dh); k/v (B,Hkv,S,dh|dv);
    pos (B,Hkv,S) int32 (entries > qpos are masked); idx (B,Hkv,c) int32
    selected tile ids; qpos (B,) int32; k_self/v_self (B,Hkv,dh|dv), used
    only with ``has_self``. Returns (B,Hq,dv) in q's dtype."""
    b, hq, dh = q.shape
    hkv, s = k.shape[1], k.shape[2]
    g = hq // hkv
    dv = v.shape[-1]
    n_sel = idx.shape[-1]
    if s % bk or s // bk < n_sel:
        raise ValueError(f"cache length {s} needs {n_sel} whole {bk}-tiles")
    if not has_self:
        k_self = jnp.zeros((b, hkv, dh), k.dtype)
        v_self = jnp.zeros((b, hkv, dv), v.dtype)

    def tile(bi, hi, j, ix, qp):
        return (bi, hi, ix[bi, hi, j], 0)

    def row(bi, hi, j, ix, qp):
        return (bi, hi, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, n_sel),
        in_specs=[
            pl.BlockSpec((1, 1, g, dh), row),
            pl.BlockSpec((1, 1, bk, dh), tile),
            pl.BlockSpec((1, 1, bk, dv), tile),
            pl.BlockSpec((1, 1, 1, 1, bk),
                         lambda bi, hi, j, ix, qp: (bi, hi, ix[bi, hi, j],
                                                    0, 0)),
            pl.BlockSpec((1, 1, 1, dh), row),
            pl.BlockSpec((1, 1, 1, dv), row),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dv), row),
        scratch_shapes=[pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, dv), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, n_sel=n_sel, has_self=has_self),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(idx.astype(jnp.int32), qpos.astype(jnp.int32),
      q.reshape(b, hkv, g, dh), k, v,
      pos.astype(jnp.int32).reshape(b, hkv, s // bk, 1, bk),
      k_self.reshape(b, hkv, 1, dh), v_self.reshape(b, hkv, 1, dv))
    return out.reshape(b, hq, dv)
