"""Pallas TPU kernel: ELL-BSR block-sparse matrix x dense (multi-)vector.

The paper's bottom-level "block-segment multiplication" (§2.4) on the MXU,
over a batch of same-spec plans (``PlanBatch``; a single plan is B=1).

Grid ``(batch member, row superblock, feature tile, slot chunk)``. Each
step holds ``rbs`` row blocks' worth of ``chunk`` ELL tiles in VMEM, reads
their column indices from an SMEM block, DMA-gathers the matching
``(bs, fc)`` charge segments straight from HBM into VMEM scratch, and
accumulates ``sum_c A[r, c] @ x[idx[r, c]]`` into the ``(bs, fc)`` output
tile. Nothing of size ``n`` is resident: VMEM and SMEM per step are
bounded by the tile sizes (``costmodel.choose_tiles``) whatever the plan
size, and each step's segments cross HBM exactly once.

Layout rules the chip imposes and this form follows:

* charges are tiled to ``fc``, a multiple of 128 lanes (a charge vector,
  ``f == 1``, rides lane 0 of a 128-wide tile);
* column indices arrive per step in SMEM blocks, never as one scalar
  prefetch of the whole ``(B, n_rb, nbr)`` index array;
* the slot reduction is split into chunks, so VMEM does not grow with the
  ELL width;
* a ``(bs, bs)`` tile with ``bs < 128`` is presented lane-dense
  (:func:`packing`), so it is not padded to 128 lanes in VMEM.

The float sum therefore associates per chunk and per tile, not in the
order of the XLA ``bsr``/``bsr_ml`` backends: agreement with them is a
tolerance, not bitwise. Padding slots carry zero tiles (index 0), padded
rows and feature columns are zero, so no masking is needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def packing(bs: int):
    """``(Q, R, W)``: a ``(bs, bs)`` tile is stored as ``(R, W)`` rows of
    ``W >= 128`` lanes, ``Q`` tile rows per packed row (tile row
    ``r*Q + q`` is packed row ``r``, lanes ``q*bs:(q+1)*bs``). A 32x32
    float32 tile becomes one (8, 128) register tile instead of a
    (32, 128) one with three quarters of its lanes padding."""
    q = max(1, LANES // bs)
    if (bs < LANES and LANES % bs) or (bs >= LANES and bs % LANES) \
            or bs % q:
        raise ValueError(f"the Pallas SpMV tiles bs in (16, 32, 64) or a "
                         f"multiple of {LANES}; got bs={bs}")
    return q, bs // q, bs * q


def _kernel(idx_ref, vals_ref, x_hbm, y_ref, xbuf, sem, *, rbs, chunk,
            fc, bs, q):
    b = pl.program_id(0)
    fi = pl.program_id(2)
    t = pl.program_id(3)

    def segment(j):                 # j = r * chunk + c
        return pltpu.make_async_copy(
            x_hbm.at[b, idx_ref[0, 0, 0, 0, j], :, pl.ds(fi * fc, fc)],
            xbuf.at[j], sem.at[0])

    def start(j, carry):
        segment(j).start()
        return carry

    def wait(j, carry):
        segment(j).wait()
        return carry

    jax.lax.fori_loop(0, rbs * chunk, start, 0)

    @pl.when(t == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    jax.lax.fori_loop(0, rbs * chunk, wait, 0)
    for r in range(rbs):
        def slot(c, acc):
            tile = vals_ref[0, r, c]                  # (R, W) packed
            seg = xbuf[r * chunk + c]                 # (bs, fc)
            return tuple(
                a + jnp.dot(tile[:, k * bs:(k + 1) * bs], seg,
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
                for k, a in enumerate(acc))
        zero = jnp.zeros(y_ref.shape[3:], jnp.float32)
        acc = jax.lax.fori_loop(0, chunk, slot, (zero,) * q)
        for k in range(q):
            y_ref[0, r, k] += acc[k]


@functools.partial(jax.jit,
                   static_argnames=("rbs", "chunk", "fc", "interpret"))
def bsr_spmv_batched(vals: jax.Array, col_idx: jax.Array, xs: jax.Array,
                     *, rbs: int = 1, chunk: int | None = None,
                     fc: int = LANES, interpret: bool = False) -> jax.Array:
    """Batch-grid ELL-BSR SpMV/SpMM over stacked same-spec members.

    vals (B, n_rb, nbr, bs, bs) float32; col_idx (B, n_rb, nbr) int32;
    xs (B, n, f) or (B, n) float32 with n a whole number of column blocks.
    Returns (B, n_rb*bs, f) [or (B, n_rb*bs) for 1-D charges].

    ``rbs`` row blocks and ``chunk`` ELL slots share one grid step;
    charges tile to ``fc`` columns (a multiple of 128).
    """
    B, n_rb, nbr, bs, _ = vals.shape
    q, rows, width = packing(bs)
    squeeze = xs.ndim == 2
    if squeeze:
        xs = xs[..., None]
    n, f = xs.shape[1], xs.shape[2]
    if n % bs:
        raise ValueError(f"charge length {n} is not a multiple of bs={bs}")
    if fc % LANES:
        raise ValueError(f"feature tile {fc} is not a multiple of {LANES}")
    chunk = min(chunk or nbr, max(nbr, 1))

    pad_rb = (-n_rb) % rbs
    pad_c = (-nbr) % chunk
    if pad_rb or pad_c:   # zero tiles gathering column block 0 add nothing
        vals = jnp.pad(vals, ((0, 0), (0, pad_rb), (0, pad_c), (0, 0),
                              (0, 0)))
        col_idx = jnp.pad(col_idx, ((0, 0), (0, pad_rb), (0, pad_c)))
    n_rb_p, nbr_p = n_rb + pad_rb, nbr + pad_c
    pad_f = (-f) % fc
    if pad_f:
        xs = jnp.pad(xs, ((0, 0), (0, 0), (0, pad_f)))
    f_p = f + pad_f
    xs = xs.reshape(B, n // bs, bs, f_p)
    n_sb, n_ch = n_rb_p // rbs, nbr_p // chunk
    # one SMEM row of rbs*chunk indices per grid step (a block's last two
    # dims must be whole array dims or (8, 128) multiples, SMEM included)
    col_idx = col_idx.reshape(B, n_sb, rbs, n_ch, chunk) \
        .transpose(0, 1, 3, 2, 4).reshape(B, n_sb, n_ch, 1, rbs * chunk)

    vals = vals.reshape(B, n_rb_p, nbr_p, rows, width)

    grid = (B, n_sb, f_p // fc, n_ch)
    y = pl.pallas_call(
        functools.partial(_kernel, rbs=rbs, chunk=chunk, fc=fc, bs=bs,
                          q=q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, 1, rbs * chunk),
                         lambda b, i, fi, t: (b, i, t, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, rbs, chunk, rows, width),
                         lambda b, i, fi, t: (b, i, t, 0, 0)),
            # charges stay in HBM; only the indexed segments are DMA'd
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        # output rows in packed order (q, r); unshuffled below
        out_specs=pl.BlockSpec((1, rbs, q, rows, fc),
                               lambda b, i, fi, t: (b, i, 0, 0, fi)),
        out_shape=jax.ShapeDtypeStruct((B, n_rb_p, q, rows, f_p),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((rbs * chunk, bs, fc), jnp.float32),
                        pltpu.SemaphoreType.DMA((1,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(col_idx, vals, xs)
    y = y.transpose(0, 1, 3, 2, 4).reshape(B, n_rb_p * bs, f_p)
    y = y[:, :n_rb * bs, :f]
    return y[..., 0] if squeeze else y
