"""Plain reference for the plan's interaction: exact k nearest neighbours
and the SpMV over them, in float64 on the host.

It imports nothing of the program and takes nothing the program made: it
is given the points (which the benchmark generated) and an answer
``(x, y)`` of the program, and says by how much ``y`` misses
``A x``, where ``A`` averages each point's k nearest other points
(weight 1/k per edge, the configuration's values).

Candidates come from one pass on the device: squared distances at full
float32 precision and a ``top_k`` of ``k + extra`` per row. Each
candidate's distance is then recomputed on the host in float64 as
``|x_i - x_j|^2``, which decides the neighbour set. Where the k-th and a
further candidate lie within float32 rounding of each other (a tie that
the program's float32 distances may break either way) every admissible
choice is tried and the one nearest the program's answer is taken: a row
is wrong only if no admissible neighbour set gives its answer.
"""
from __future__ import annotations

import functools
import itertools
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

# float32 rounding of an expanded squared distance |q|^2 + |s|^2 - 2 q.s
# over 128 dimensions measured below 1e-6 of |q|^2 + |s|^2 on the 128-d
# data; candidates whose float64 distances lie closer than twice that to
# the k-th one are ties the program's float32 distances may break either
# way
TIE_RTOL = 2e-6


@functools.partial(jax.jit, static_argnames=("m", "block"))
def _candidates(points: jax.Array, m: int, block: int) -> jax.Array:
    n, d = points.shape
    sq = jnp.sum(points * points, axis=1)

    def body(_, start):
        q = jax.lax.dynamic_slice(points, (start, 0), (block, d))
        qs = jax.lax.dynamic_slice(sq, (start,), (block,))
        d2 = qs[:, None] + sq[None, :] - 2.0 * jnp.dot(
            q, points.T, precision=jax.lax.Precision.HIGHEST)
        rows = start + jnp.arange(block)
        d2 = jnp.where(rows[:, None] == jnp.arange(n)[None, :], jnp.inf, d2)
        return None, jax.lax.top_k(-d2, m)[1]

    _, idx = jax.lax.scan(body, None, jnp.arange(0, n, block))
    return idx.reshape(n, m)


def candidates(points: jax.Array, k: int, extra: int = 16) -> np.ndarray:
    """(n, k + extra) nearest other points of each point by float32
    distance, on the device."""
    n = points.shape[0]
    block = next(b for b in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1)
                 if n % b == 0)
    return np.asarray(_candidates(points, k + extra, block))


class ExactGraph:
    """The exact k-nearest-neighbour sets of a point set, ties kept open.

    ``certain`` (CSR, n x n, ones) holds every neighbour a row must have.
    ``open`` lists groups of tied rows: ``(rows, need, choices)``, each
    row taking ``need`` more neighbours from its ``choices`` (R, m).
    """

    def __init__(self, points: np.ndarray, cand: np.ndarray, k: int,
                 block: int = 8192, threads: int = 8):
        x = np.asarray(points, np.float64)
        n = x.shape[0]
        self.n, self.k = n, k
        self.x = x
        self.sq = (x * x).sum(1)
        with ThreadPoolExecutor(threads) as pool:
            parts = list(pool.map(lambda lo: self._block(cand, lo, block),
                                  range(0, n, block)))
        rows = np.concatenate([p[0] for p in parts])
        cols = np.concatenate([p[1] for p in parts])
        groups: Dict[tuple, list] = {}
        for p in parts:
            for key, (r, c) in p[2].items():
                groups.setdefault(key, []).append((r, c))
        self.open: List[Tuple[np.ndarray, int, np.ndarray]] = [
            (np.concatenate([r for r, _ in g]), key[1],
             np.concatenate([c for _, c in g]))
            for key, g in sorted(groups.items())]
        self.widened = sum(p[3] for p in parts)
        self.certain = sp.csr_matrix(
            (np.ones(len(rows)), (rows, cols)), shape=(n, n))

    @property
    def tied_rows(self) -> int:
        return sum(len(r) for r, _, _ in self.open)

    def _block(self, cand: np.ndarray, lo: int, block: int):
        x, sq, k = self.x, self.sq, self.k
        r = np.arange(lo, min(lo + block, self.n))
        c = cand[r]
        diff = x[r][:, None, :] - x[c]
        d = np.einsum("rmd,rmd->rm", diff, diff)
        order = np.argsort(d, axis=1, kind="stable")
        d = np.take_along_axis(d, order, 1)
        c = np.take_along_axis(c, order, 1)
        kth = d[:, k - 1]
        slack = TIE_RTOL * (sq[r] + sq[c].max(1))
        lo_t = (d < (kth - slack)[:, None]).sum(1)       # certain: a prefix
        hi_t = (d <= (kth + slack)[:, None]).sum(1)      # ties: [lo_t, hi_t)
        short = hi_t >= c.shape[1]       # the tie may run past the
        rows, cols, groups, widened = [], [], {}, 0     # candidates
        for i in np.nonzero(short)[0]:
            widened += 1
            ri, ci, key, ch = self._widen(int(r[i]))
            rows.append(ri)
            cols.append(ci)
            if key is not None:
                groups.setdefault(key, []).append(ch)
        ok = ~short
        take = np.arange(c.shape[1])[None, :] < np.where(
            hi_t - lo_t == k - lo_t, hi_t, lo_t)[:, None]
        rr, cc = np.nonzero(take & ok[:, None])
        rows.append(r[rr])
        cols.append(c[rr, cc])
        tied = ok & (hi_t - lo_t > k - lo_t)
        out = {}
        for key in set(zip((hi_t - lo_t)[tied], (k - lo_t)[tied])):
            sel = np.nonzero(tied & (hi_t - lo_t == key[0])
                             & (k - lo_t == key[1]))[0]
            span = lo_t[sel][:, None] + np.arange(key[0])[None, :]
            out[tuple(int(v) for v in key)] = (
                r[sel], np.take_along_axis(c[sel], span, 1))
        for key, chs in groups.items():
            rr_, cc_ = out.get(key, (np.zeros(0, int),
                                     np.zeros((0, key[0]), int)))
            out[key] = (np.concatenate([rr_] + [np.array([h[0]])
                                                for h in chs]),
                        np.concatenate([cc_] + [h[1][None] for h in chs]))
        return (np.concatenate(rows), np.concatenate(cols), out, widened)

    def _widen(self, i: int):
        """Brute force over every point for one row whose tie runs past
        its candidates."""
        x, sq, k = self.x, self.sq, self.k
        diff = x - x[i]
        d = np.einsum("nd,nd->n", diff, diff)
        d[i] = np.inf
        order = np.argsort(d, kind="stable")
        ds = d[order]
        slack = TIE_RTOL * (sq[i] + sq.max())
        lo_t = int((ds < ds[k - 1] - slack).sum())
        hi_t = int((ds <= ds[k - 1] + slack).sum())
        if hi_t - lo_t == k - lo_t:
            return np.full(hi_t, i), order[:hi_t], None, None
        return (np.full(lo_t, i), order[:lo_t], (hi_t - lo_t, k - lo_t),
                (i, order[lo_t:hi_t]))

    def spmv_error(self, x: np.ndarray, y: np.ndarray) -> float:
        """Largest ``|y - A x| / (|A| |x|)`` over every entry, with each
        tied row given its best admissible neighbour set."""
        x = np.asarray(x, np.float64)
        y = np.asarray(y, np.float64)
        if x.ndim == 1:
            x, y = x[:, None], y[:, None]
        if y.shape != x.shape or not np.all(np.isfinite(y)):
            return float("inf")
        inv_k = 1.0 / self.k
        want = (self.certain @ x) * inv_k
        scale = (self.certain @ np.abs(x)) * inv_k
        err = np.abs(y - want) / np.maximum(scale, 1e-300)
        worst = err.max(1)
        for rows, need, choices in self.open:
            xs = x[choices]                                 # (R, m, f)
            best = np.full(len(rows), np.inf)
            for pick in itertools.combinations(range(choices.shape[1]),
                                               need):
                sel = xs[:, list(pick)]
                w = want[rows] + sel.sum(1) * inv_k
                s = scale[rows] + np.abs(sel).sum(1) * inv_k
                e = (np.abs(y[rows] - w) / np.maximum(s, 1e-300)).max(1)
                best = np.minimum(best, e)
            worst[rows] = best
        return float(worst.max())


def spmv_bf16(points_cand: np.ndarray, k: int, x: jax.Array) -> jax.Array:
    """The reference SpMV one precision below the configuration's: charges
    rounded to bfloat16, sums in float32, over the first ``k`` candidates
    of each row. It stands where the program's answer stands to show that
    the comparison fails it."""
    idx = jnp.asarray(points_cand[:, :k])
    xb = x.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.take(xb, idx, axis=0).sum(1) / k
