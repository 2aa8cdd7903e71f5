"""Pallas TPU kernel: ELL-BSR block-sparse matrix x dense (multi-)vector.

The paper's bottom-level "block-segment multiplication" (§2.4) on the MXU,
over a batch of same-spec plans (``PlanBatch``; a single plan is B=1).

Grid ``(batch member, row superblock, feature tile, slot chunk)``. Each
step holds ``rbs`` row blocks' worth of ``chunk`` ELL slots in VMEM, reads
their column indices from an SMEM block, DMA-gathers the matching
``(bs, fc)`` charge segments straight from HBM into VMEM scratch, and
adds ``sum_c A[r, c] @ x[idx[r, c]]`` into the ``(bs, fc)`` output tile.
Nothing of size ``n`` is resident: VMEM and SMEM per step are bounded by
the tile sizes (``costmodel.choose_tiles``) whatever the plan size, and
each step's segments cross HBM exactly once.

Panel form. The wrapper presents each row block's tiles as one panel
``(bs, nbr * bs)``, ``panel[i, c*bs + j] = A[c][i, j]``, and the gathered
segments sit one under the other in a ``(rbs * chunk * bs, fc)`` scratch.
So a row block's chunk of slots is ONE matmul, ``(bs, chunk*bs) @
(chunk*bs, fc)``, and the MXU sums over the slots itself: no loop over
slots, no per-tile dots.

Layout rules the chip imposes and this form follows:

* charges are tiled to ``fc``, a multiple of 128 lanes (a charge vector,
  ``f == 1``, rides lane 0 of a 128-wide tile);
* column indices arrive per step in SMEM blocks, never as one scalar
  prefetch of the whole ``(B, n_rb, nbr)`` index array;
* the lane rule: a panel block is ``chunk * bs`` lanes wide, so ``chunk``
  is the whole ELL width (a whole-dim block, no padding) or ``chunk *
  bs`` is a multiple of 128 and the ELL width pads with zero slots to a
  whole number of chunks (:func:`panel_chunk`);
* the slot reduction may be split into chunks, so VMEM does not grow
  with the ELL width.

The float sum therefore associates per chunk inside the MXU, not in the
order of the XLA ``bsr``/``bsr_ml`` backends: agreement with them is a
tolerance, not bitwise. Padding slots carry zero tiles (index 0), padded
rows and feature columns are zero, so no masking is needed.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def panel_chunk(nbr: int, bs: int, chunk: int | None = None) -> int:
    """The slots per grid step that the lane rule allows: ``chunk``
    (default: all ``nbr``) rounded up to whole 128-lane panel columns,
    or the whole ELL width ``nbr`` where the rounding reaches it (a
    whole-dim block, which needs no padding)."""
    if bs % 8:
        raise ValueError(f"the Pallas SpMV tiles bs a multiple of 8; "
                         f"got bs={bs}")
    nbr = max(nbr, 1)
    unit = LANES // math.gcd(bs, LANES)      # slots per 128 lanes
    chunk = min(chunk or nbr, nbr)
    return min(-(-chunk // unit) * unit, nbr)


def _kernel(idx_ref, panel_ref, x_hbm, y_ref, xbuf, sem, *, rbs, chunk,
            fc, bs):
    b = pl.program_id(0)
    fi = pl.program_id(2)
    t = pl.program_id(3)
    width = chunk * bs

    def segment(j):                 # j = r * chunk + c
        return pltpu.make_async_copy(
            x_hbm.at[b, idx_ref[0, 0, 0, 0, j], :, pl.ds(fi * fc, fc)],
            xbuf.at[pl.ds(pl.multiple_of(j * bs, bs), bs)], sem.at[0])

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
        y_ref[0, r] += jnp.dot(panel_ref[0, r],
                               xbuf[r * width:(r + 1) * width, :],
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("rbs", "chunk", "fc", "interpret"))
def bsr_spmv_batched(vals: jax.Array, col_idx: jax.Array, xs: jax.Array,
                     *, rbs: int = 1, chunk: int | None = None,
                     fc: int = LANES, interpret: bool = False) -> jax.Array:
    """Batch-grid ELL-BSR SpMV/SpMM over stacked same-spec members.

    vals (B, n_rb, nbr, bs, bs) float32; col_idx (B, n_rb, nbr) int32;
    xs (B, n, f) or (B, n) float32 with n a whole number of column blocks.
    Returns (B, n_rb*bs, f) [or (B, n_rb*bs) for 1-D charges].

    ``rbs`` row blocks and ``chunk`` ELL slots share one grid step
    (``chunk`` rounded up to the lane rule, :func:`panel_chunk`);
    charges tile to ``fc`` columns (a multiple of 128).
    """
    B, n_rb, nbr, bs, _ = vals.shape
    squeeze = xs.ndim == 2
    if squeeze:
        xs = xs[..., None]
    n, f = xs.shape[1], xs.shape[2]
    if n % bs:
        raise ValueError(f"charge length {n} is not a multiple of bs={bs}")
    if fc % LANES:
        raise ValueError(f"feature tile {fc} is not a multiple of {LANES}")
    chunk = panel_chunk(nbr, bs, chunk)

    pad_rb = (-n_rb) % rbs
    pad_c = (-nbr) % chunk
    # panel[b, r, i, c*bs + j] = vals[b, r, c, i, j]; padding slots are
    # zero tiles gathering column block 0, which add nothing
    panels = vals.transpose(0, 1, 3, 2, 4).reshape(B, n_rb, bs, nbr * bs)
    if pad_rb or pad_c:
        panels = jnp.pad(panels, ((0, 0), (0, pad_rb), (0, 0),
                                  (0, pad_c * bs)))
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

    grid = (B, n_sb, f_p // fc, n_ch)
    y = pl.pallas_call(
        functools.partial(_kernel, rbs=rbs, chunk=chunk, fc=fc, bs=bs),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, 1, rbs * chunk),
                         lambda b, i, fi, t: (b, i, t, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, rbs, bs, chunk * bs),
                         lambda b, i, fi, t: (b, i, 0, t)),
            # charges stay in HBM; only the indexed segments are DMA'd
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, rbs, bs, fc),
                               lambda b, i, fi, t: (b, i, 0, fi)),
        out_shape=jax.ShapeDtypeStruct((B, n_rb_p, bs, f_p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((rbs * chunk * bs, fc), jnp.float32),
                        pltpu.SemaphoreType.DMA((1,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(col_idx, panels, xs)
    y = y.reshape(B, n_rb_p * bs, f_p)[:, :n_rb * bs, :f]
    return y[..., 0] if squeeze else y
