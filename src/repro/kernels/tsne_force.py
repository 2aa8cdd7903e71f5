"""Pallas TPU kernel: blockwise t-SNE attractive force (paper §3.1).

The paper's iterative hot loop: F_i = sum_j p_ij q_ij (y_i - y_j) with
q_ij = 1/(1 + |y_i - y_j|^2) over the kNN pattern. Values q are recomputed
DENSE per kept tile from the current embedding — the TPU-native
replacement for the per-edge gather loop (DESIGN.md §2).

Grid ``(row block, ELL slot)``; the source segment's block index comes
from the scalar-prefetched column index, so the Pallas pipeline DMAs each
``(bs, 128)`` segment once. The embedding is padded to 128 lanes (zero
columns change no distance), and the tile arithmetic is 2-D matmuls:
``|y_t - y_s|^2 = |y_t|^2 + |y_s|^2 - 2 y_t y_s^T`` and
``F_t = rowsum(w) y_t - w y_s`` with ``w = p * q``. Padding slots carry
zero P tiles, so their force contributions vanish. The whole ``(n_rb,
nbr)`` column index rides in SMEM, which bounds ``n_rb * nbr`` (the
t-SNE sizes of paper §3.1 fit many times over).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
_HIGHEST = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))          # contract the last dims: A @ B^T


def _kernel(idx_ref, p_ref, yt_ref, ys_ref, f_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        f_ref[...] = jnp.zeros_like(f_ref)

    p = p_ref[0, 0].astype(jnp.float32)               # (bs, bs)
    yt = yt_ref[...]                                  # (bs, 128)
    ys = ys_ref[...]
    ones = jnp.ones((1, yt.shape[1]), jnp.float32)
    cross = jax.lax.dot_general(yt, ys, _NT, precision=_HIGHEST,
                                preferred_element_type=jnp.float32)
    nt = jnp.sum(yt * yt, axis=1, keepdims=True)      # (bs, 1)
    ns = jax.lax.dot_general(ones, ys * ys, _NT, precision=_HIGHEST,
                             preferred_element_type=jnp.float32)  # (1, bs)
    d2 = jnp.maximum(nt + ns - 2.0 * cross, 0.0)
    w = p / (1.0 + d2)
    f_ref[...] += jnp.sum(w, axis=1, keepdims=True) * yt - jnp.dot(
        w, ys, precision=_HIGHEST, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def tsne_force(p_vals: jax.Array, col_idx: jax.Array, y: jax.Array,
               *, interpret: bool = False) -> jax.Array:
    """p_vals (n_rb, nbr, bs, bs); col_idx (n_rb, nbr) int32;
    y (n_cb*bs, d) current embedding (padded to block multiple), d <= 128.
    Returns F (n_rb*bs, d)."""
    n_rb, nbr, bs, _ = p_vals.shape
    n, d = y.shape
    if d > LANES:
        raise ValueError(f"embedding dimension {d} exceeds {LANES}")
    rows = max(n, n_rb * bs)
    yp = jnp.pad(y.astype(jnp.float32), ((0, rows - n), (0, LANES - d)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_rb, nbr),
        in_specs=[
            pl.BlockSpec((1, 1, bs, bs), lambda i, j, idx: (i, j, 0, 0)),
            pl.BlockSpec((bs, LANES), lambda i, j, idx: (i, 0)),
            pl.BlockSpec((bs, LANES), lambda i, j, idx: (idx[i, j], 0)),
        ],
        out_specs=pl.BlockSpec((bs, LANES), lambda i, j, idx: (i, 0)),
    )
    f = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_rb * bs, LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(col_idx, p_vals, yp, yp)
    return f[:, :d]
