"""Sharded plans: per-device row-block BSR shards with halo exchange.

``shard(plan, mesh)`` transforms an :class:`repro.api.InteractionPlan` into
a :class:`ShardedPlan` whose row-blocks are partitioned contiguously over a
mesh axis. Because the cluster ordering makes every row-block's column
footprint compact (the paper's whole point — §2.4 step 2), the charge
window each device needs is its *own* charge shard plus a small **halo** of
neighboring blocks. The halo is computed exactly from the ELL schedule
(``col_idx`` under ``nbr_mask``), so on banded/clustered patterns each
matvec moves only the halo blocks between neighbor devices
(``lax.ppermute``) instead of all-gathering the full charge vector the way
``core.dist.spmv_sharded`` does.

Exchange modes, chosen per plan by :func:`analyze_shards`:

  halo       left/right halos (each capped at one shard) moved by one
             ppermute per side, plus an optional **hot set**: the few
             column blocks referenced from outside any window (stray
             cross-cluster kNN edges) are replicated to every device with
             one psum — so a handful of long-range tiles costs
             ``2 * n_hot`` blocks instead of forcing a full gather
  ring       a dense band wider than one shard: whole neighbor shards are
             fetched hop-by-hop; still less traffic than replication
             while ``hops_lo + hops_hi < n_dev - 1``
  allgather  scattered patterns with near-global support: windows + hot
             set would move more than replication, so fall back to one
             all-gather (identical traffic to ``spmv_sharded``)

The column indices of each shard are remapped to *window-local* coordinates
on the host at shard time, so the device loop is a gather + one einsum with
no index arithmetic. ``unshard()`` reverses the transform bit-exactly.

Lifecycle: ``ShardedPlan.refresh(x_new)`` composes with the PR 2 plan
lifecycle — a patch-tier refresh updates only the shards owning migrated
row-blocks (no global rebuild of the shard arrays); rebucket/rebuild tiers
(or a patch whose new columns escape the halo window) fall back to a full
re-shard of the refreshed plan.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import make_mesh, shard_map
from repro.core import costmodel
from repro.core.blocksparse import BSR
from repro.core.registry import NotApplicable

__all__ = ["ShardSpec", "ShardedPlan", "analyze_shards", "shard",
           "default_mesh"]


@functools.lru_cache(maxsize=None)
def default_mesh(axis: str = "data") -> Mesh:
    """1-axis mesh over every local device (shared by `shard` and the
    `dist` registry backend, so their memoized shards agree)."""
    return make_mesh((jax.device_count(),), (axis,))


@dataclass(frozen=True)
class ShardSpec:
    """Host-side halo analysis of a BSR over an ``n_dev``-way row split.

    All quantities are in *column-block* units (one block = ``bs`` charges).
    ``transfer_blocks`` is the number of charge blocks each device moves
    per matvec — the quantity the halo exchange minimizes (replication via
    all-gather costs ``(n_dev - 1) * rb_per``). Hot-set blocks are billed
    at 2x: a psum ring both sends and receives each contribution.
    """
    axis: str
    n_dev: int
    rb_per: int            # row-blocks owned per device (after padding)
    n_rb_pad: int          # rb_per * n_dev
    halo_lo: int           # left-halo width (max over devices, <= rb_per)
    halo_hi: int           # right-halo width (max over devices, <= rb_per)
    hops_lo: int           # whole-shard hops left (ring mode)
    hops_hi: int           # whole-shard hops right (ring mode)
    n_hot: int             # replicated out-of-window column blocks
    mode: str              # halo | ring | allgather
    win: int               # halo-window length per device, in blocks

    @property
    def transfer_blocks(self) -> int:
        if self.mode == "halo":
            return self.halo_lo + self.halo_hi + 2 * self.n_hot
        if self.mode == "ring":
            return (self.hops_lo + self.hops_hi) * self.rb_per
        return (self.n_dev - 1) * self.rb_per

    @property
    def allgather_blocks(self) -> int:
        return (self.n_dev - 1) * self.rb_per

    def window_base(self, dev: int) -> int:
        """First global column-block of device ``dev``'s halo window."""
        if self.mode == "halo":
            return dev * self.rb_per - self.halo_lo
        if self.mode == "ring":
            return (dev - self.hops_lo) * self.rb_per
        return 0


def _support(bsr: BSR, rb_per: int, n_dev: int):
    """Per-device sorted unique column support from the ELL schedule."""
    out = []
    for d in range(n_dev):
        r0, r1 = d * rb_per, min((d + 1) * rb_per, bsr.n_rb)
        out.append(bsr.rowblock_cols(r0, r1) if r0 < r1
                   else np.empty(0, np.int64))
    return out


def analyze_shards(bsr: BSR, n_dev: int, axis: str = "data"
                   ) -> Tuple[ShardSpec, np.ndarray]:
    """Exchange plan for ``bsr`` row-sharded ``n_dev`` ways.

    Reads the ELL schedule on the host (concrete arrays required) and
    costs three covers of every device's column support — capped halo +
    replicated hot set, whole-shard ring hops, full all-gather — picking
    the cheapest. Returns ``(spec, hot)`` where ``hot`` is the sorted
    global column blocks of the hot set (empty outside halo mode).
    """
    n_rb = bsr.n_rb
    rb_per = -(-n_rb // n_dev)
    n_rb_pad = rb_per * n_dev
    no_hot = np.empty(0, np.int64)

    if n_dev == 1:
        return ShardSpec(axis=axis, n_dev=1, rb_per=rb_per,
                         n_rb_pad=n_rb_pad, halo_lo=0, halo_hi=0,
                         hops_lo=0, hops_hi=0, n_hot=0, mode="halo",
                         win=rb_per), no_hot

    support = _support(bsr, rb_per, n_dev)

    # candidate 1: halo capped at one shard per side + hot set for the rest
    halo_lo = halo_hi = 0
    far = []
    for d, cols in enumerate(support):
        if cols.size == 0:
            continue
        r0, r1 = d * rb_per, (d + 1) * rb_per
        near = cols[(cols >= r0 - rb_per) & (cols < r1 + rb_per)]
        far.append(cols[(cols < r0 - rb_per) | (cols >= r1 + rb_per)])
        if near.size:
            halo_lo = max(halo_lo, r0 - int(near.min()))
            halo_hi = max(halo_hi, int(near.max()) - (r1 - 1))
    halo_lo, halo_hi = max(halo_lo, 0), max(halo_hi, 0)
    hot = (np.unique(np.concatenate(far)) if far else no_hot
           ).astype(np.int64)
    blocks_halo = halo_lo + halo_hi + 2 * len(hot)

    # candidate 2: uncapped whole-shard ring hops (wide dense bands)
    span_lo = span_hi = 0
    for d, cols in enumerate(support):
        if cols.size == 0:
            continue
        r0, r1 = d * rb_per, (d + 1) * rb_per
        span_lo = max(span_lo, r0 - int(cols.min()))
        span_hi = max(span_hi, int(cols.max()) - (r1 - 1))
    hops_lo, hops_hi = -(-span_lo // rb_per), -(-span_hi // rb_per)
    ring_ok = hops_lo + hops_hi < n_dev - 1
    blocks_ring = (hops_lo + hops_hi) * rb_per if ring_ok else None

    blocks_ag = (n_dev - 1) * rb_per
    # all three candidates are priced in seconds on the configured
    # interconnect by the shared analytic cost model (a monotone map of
    # the block counts, so decisions match the historical block compare)
    cost_halo = costmodel.exchange_cost(blocks_halo, bsr.bs)
    cost_ring = costmodel.exchange_cost(blocks_ring, bsr.bs)
    cost_ag = costmodel.exchange_cost(blocks_ag, bsr.bs)
    best = min(c for c in (cost_halo, cost_ring, cost_ag) if c is not None)
    if best == cost_halo and cost_halo < cost_ag:
        return ShardSpec(axis=axis, n_dev=n_dev, rb_per=rb_per,
                         n_rb_pad=n_rb_pad, halo_lo=halo_lo,
                         halo_hi=halo_hi, hops_lo=0, hops_hi=0,
                         n_hot=len(hot), mode="halo",
                         win=halo_lo + rb_per + halo_hi), hot
    if cost_ring is not None and best == cost_ring and cost_ring < cost_ag:
        return ShardSpec(axis=axis, n_dev=n_dev, rb_per=rb_per,
                         n_rb_pad=n_rb_pad, halo_lo=min(span_lo, rb_per),
                         halo_hi=min(span_hi, rb_per), hops_lo=hops_lo,
                         hops_hi=hops_hi, n_hot=0, mode="ring",
                         win=(hops_lo + 1 + hops_hi) * rb_per), no_hot
    return ShardSpec(axis=axis, n_dev=n_dev, rb_per=rb_per,
                     n_rb_pad=n_rb_pad, halo_lo=0, halo_hi=0, hops_lo=0,
                     hops_hi=0, n_hot=0, mode="allgather",
                     win=n_rb_pad), no_hot


def _row_bases(spec: ShardSpec, rows: np.ndarray) -> np.ndarray:
    """Window base of each row-block's owning device."""
    base = np.array([spec.window_base(d) for d in range(spec.n_dev)],
                    np.int64)
    return base[rows // spec.rb_per]


def _remap_cols(col: np.ndarray, mask: np.ndarray, base: np.ndarray,
                spec: ShardSpec, hot: np.ndarray):
    """Global column-blocks -> window-local slots, given per-row bases.

    Real columns inside the row's halo window map to ``col - base``; real
    columns outside it map to ``win + index-in-hot``. Padded slots (mask
    False) map to slot 0 — their tiles are zero, so whatever segment they
    gather contributes nothing. Returns ``(local, covered)``: ``covered``
    is False where a *real* column escapes both window and hot set (the
    incremental refresh uses it to detect overflow; at shard time the
    analysis guarantees full coverage).
    """
    local = col.astype(np.int64) - base[:, None]
    in_win = (local >= 0) & (local < spec.win)
    if spec.n_hot:
        pos = np.searchsorted(hot, col)
        in_hot = (pos < spec.n_hot) & (
            hot[np.clip(pos, 0, spec.n_hot - 1)] == col)
    else:
        pos = np.zeros(col.shape, np.int64)
        in_hot = np.zeros(col.shape, bool)
    out = np.where(in_win, np.clip(local, 0, spec.win - 1),
                   np.where(in_hot, spec.win + pos, 0)).astype(np.int32)
    return out, in_win | in_hot | ~mask


def _local_cols(col_idx: np.ndarray, mask: np.ndarray, spec: ShardSpec,
                hot: np.ndarray) -> np.ndarray:
    """Remap the full (row-padded) ELL schedule to window-local slots."""
    n_rb_pad = spec.n_rb_pad
    padded = np.zeros((n_rb_pad, col_idx.shape[1]), np.int64)
    padded[:col_idx.shape[0]] = col_idx
    mask_full = np.zeros(padded.shape, bool)
    mask_full[:mask.shape[0]] = mask
    out, covered = _remap_cols(padded, mask_full,
                               _row_bases(spec, np.arange(n_rb_pad)),
                               spec, hot)
    assert covered.all(), "halo analysis must cover every real column"
    return out


def _hot_routing(spec: ShardSpec, hot: np.ndarray
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-device scatter routes for the hot-set psum.

    Device ``d`` owns the hot blocks lying in its row range; it writes its
    local block ``hot_local`` into slot ``hot_dst`` of the shared buffer
    (padded routes target the extra drop slot ``n_hot``).
    """
    owner = hot // spec.rb_per
    counts = np.bincount(owner, minlength=spec.n_dev)
    max_own = int(counts.max(initial=0))
    hot_local = np.zeros((spec.n_dev, max_own), np.int32)
    hot_dst = np.full((spec.n_dev, max_own), spec.n_hot, np.int32)
    for d in range(spec.n_dev):
        mine = np.nonzero(owner == d)[0]
        hot_local[d, :len(mine)] = hot[mine] - d * spec.rb_per
        hot_dst[d, :len(mine)] = mine
    return hot_local, hot_dst


class ShardedPlan:
    """Per-device row-block BSR shards of an InteractionPlan.

    Arrays are laid out with :class:`~jax.sharding.NamedSharding` over
    ``mesh`` so each device owns its row-blocks' tiles and (window-local)
    column schedule; ``apply``/``matvec`` run the halo exchange chosen by
    ``spec``. The wrapped ``plan`` keeps serving permutation helpers,
    stats, and the refresh lifecycle.
    """

    def __init__(self, plan, mesh: Mesh, spec: ShardSpec,
                 vals: jax.Array, lcol: jax.Array, mask: jax.Array,
                 hot: np.ndarray, hot_local: jax.Array,
                 hot_dst: jax.Array):
        self.plan = plan
        self.mesh = mesh
        self.spec = spec
        self.vals = vals          # (n_rb_pad, nbr, bs, bs), P(axis)
        self.lcol = lcol          # (n_rb_pad, nbr) window-local, P(axis)
        self.mask = mask          # (n_rb_pad, nbr) bool, P(axis)
        self.hot = hot            # (n_hot,) sorted global blocks, host
        self.hot_local = hot_local  # (n_dev, max_own) owner routes, P(axis)
        self.hot_dst = hot_dst      # (n_dev, max_own) buffer slots, P(axis)
        self.shard_patches = 0    # incremental refreshes applied in place
        self.reshards = 0         # full re-shards (tier escalation)
        self._fn = None

    # -- compute -----------------------------------------------------------

    def _local_matvec(self):
        spec, bs = self.spec, self.plan.bsr.bs
        axis, n_dev, rb_per = spec.axis, spec.n_dev, spec.rb_per
        fwd = [(i, (i + 1) % n_dev) for i in range(n_dev)]   # from left
        bwd = [((i + 1) % n_dev, i) for i in range(n_dev)]   # from right

        def local(vals, lcol, hot_local, hot_dst, xs):
            # xs: this device's charge shard, (rb_per * bs,)
            if spec.mode == "allgather":
                win = jax.lax.all_gather(xs, axis, tiled=True)
            elif spec.mode == "ring":
                parts, cur = [], xs
                for _ in range(spec.hops_lo):
                    cur = jax.lax.ppermute(cur, axis, fwd)
                    parts.insert(0, cur)
                parts.append(xs)
                cur = xs
                for _ in range(spec.hops_hi):
                    cur = jax.lax.ppermute(cur, axis, bwd)
                    parts.append(cur)
                win = jnp.concatenate(parts)
            else:                           # halo: minimal slice exchange
                parts = []
                if spec.halo_lo:
                    parts.append(jax.lax.ppermute(
                        xs[rb_per * bs - spec.halo_lo * bs:], axis, fwd))
                parts.append(xs)
                if spec.halo_hi:
                    parts.append(jax.lax.ppermute(
                        xs[:spec.halo_hi * bs], axis, bwd))
                win = jnp.concatenate(parts) if len(parts) > 1 else xs
            if spec.n_hot:
                # replicate the hot set: each owner scatters its blocks
                # into a shared buffer slot, one psum merges them (each
                # slot written by exactly one device; slot n_hot drops
                # the padded routes)
                xb_own = xs.reshape(rb_per, bs)
                buf = jnp.zeros((spec.n_hot + 1, bs), xs.dtype)
                buf = buf.at[hot_dst[0]].set(xb_own[hot_local[0]])
                buf = jax.lax.psum(buf, axis)
                win = jnp.concatenate([win, buf[:spec.n_hot].reshape(-1)])
            xb = win.reshape(spec.win + spec.n_hot, bs)
            seg = xb[lcol]                               # (rb_l, nbr, bs)
            return jnp.einsum("rnij,rnj->ri", vals, seg).reshape(-1)

        return shard_map(local, mesh=self.mesh,
                         in_specs=(P(axis),) * 5,
                         out_specs=P(axis), check_vma=False)

    def apply(self, x: jax.Array) -> jax.Array:
        """``y = A' x`` in cluster order via the sharded halo path."""
        x = jnp.asarray(x)
        if x.ndim != 1:
            raise NotApplicable(f"sharded plans take 1-D charges, got "
                             f"shape {x.shape}")
        if self._fn is None:
            self._fn = jax.jit(self._local_matvec())
        bs = self.plan.bsr.bs
        pad = self.spec.n_rb_pad * bs - x.shape[0]
        xp = jnp.pad(x, (0, pad)) if pad else x
        return self._fn(self.vals, self.lcol, self.hot_local,
                        self.hot_dst, xp)[:self.plan.n]

    def matvec(self, x: jax.Array) -> jax.Array:
        """``y = A x`` in original order (permute ∘ apply ∘ unpermute)."""
        return self.plan.unpermute(self.apply(self.plan.permute(x)))

    def solve(self, b: jax.Array, *, shift: float = 0.0,
              precond: "str | None" = None,
              tol: "float | None" = None,
              maxiter: "int | None" = None):
        """CG on the sharded matvec: each iteration runs the compiled
        halo-exchange SpMV, the dot products reduce over the device axis
        (mesh-sharded arrays psum implicitly). 1-D right-hand sides only
        (the sharded apply's contract); see ``docs/solvers.md``."""
        from repro.solvers.krr import solve as _solve
        return _solve(self, b, shift=shift, precond=precond, tol=tol,
                      maxiter=maxiter)

    # -- introspection -----------------------------------------------------

    @property
    def n(self) -> int:
        return self.plan.n

    @property
    def transfer_fraction(self) -> float:
        """Charge blocks received per device, as a fraction of what a full
        all-gather of the (padded) charge vector would move."""
        ag = self.spec.allgather_blocks
        return self.spec.transfer_blocks / ag if ag else 0.0

    def unshard(self) -> BSR:
        """Reconstruct the unsharded BSR from the shard arrays (bit-exact
        inverse of :func:`shard`: unpad rows, window-local / hot-slot ->
        global columns, padded slots restored to column 0)."""
        b = self.plan.bsr
        spec = self.spec
        vals = np.asarray(self.vals)[:b.n_rb]
        lcol = np.asarray(self.lcol)[:b.n_rb].astype(np.int64)
        mask = np.asarray(self.mask)[:b.n_rb]
        col = lcol + _row_bases(spec, np.arange(b.n_rb))[:, None]
        if spec.n_hot:
            far = lcol >= spec.win
            col[far] = self.hot[np.clip(lcol[far] - spec.win, 0,
                                        spec.n_hot - 1)]
        col = np.where(mask, col, 0)
        return BSR(bs=b.bs, sb=b.sb, n=b.n, n_rb=b.n_rb, n_cb=b.n_cb,
                   col_idx=jnp.asarray(col.astype(np.int32)),
                   nbr_mask=jnp.asarray(mask), vals=jnp.asarray(vals),
                   fill=b.fill, max_nbr=b.max_nbr)

    # -- lifecycle (compose with repro.api.refresh_plan) -------------------

    def _register(self) -> "ShardedPlan":
        """Enter this ShardedPlan into its plan's shard memo (the same
        cache ``shard()`` and the ``dist`` backend consult)."""
        cache = getattr(self.plan.host, "shard_cache", None)
        if cache is not None:
            cache[(self.spec.n_dev, self.spec.axis)] = self
        return self

    def _handoff(self, prev: "ShardedPlan", patched: int = 0,
                 resharded: int = 0) -> "ShardedPlan":
        """Carry lineage telemetry (and, when the exchange program is
        unchanged, the compiled fn) from ``prev`` onto this plan."""
        self.shard_patches = prev.shard_patches + patched
        self.reshards = prev.reshards + resharded
        if self._fn is None and self.spec == prev.spec:
            self._fn = prev._fn
        return self._register()

    def _absorb(self, new_plan, in_place_actions: Tuple[str, ...]
                ) -> "ShardedPlan":
        """Fold an already-updated wrapped plan into the shard arrays.

        When the update was one of ``in_place_actions`` (layout-preserving
        tiers that record ``last_patch_rb``), only the shards owning the
        touched row-blocks are scattered into — devices whose rows were
        untouched keep their arrays, and no halo re-analysis happens,
        *provided* the new columns still fit the existing halo window.
        Everything else (rebucket/rebuild/compact/capacity growth, or a
        window overflow) re-shards the new plan from scratch.
        """
        st = new_plan.refresh_stats
        touched = new_plan.host.last_patch_rb
        same_layout = (
            st.last_action in in_place_actions and touched is not None
            and new_plan.bsr is not None and self.plan.bsr is not None
            and new_plan.bsr.n_rb == self.plan.bsr.n_rb
            and new_plan.bsr.max_nbr == self.plan.bsr.max_nbr)
        if not same_layout:
            return shard(new_plan, self.mesh, axis=self.spec.axis
                         )._handoff(self, resharded=1)
        if len(touched) == 0:      # nothing changed: shards already valid
            return ShardedPlan(new_plan, self.mesh, self.spec, self.vals,
                               self.lcol, self.mask, self.hot,
                               self.hot_local, self.hot_dst
                               )._handoff(self)

        spec = self.spec
        b = new_plan.bsr
        col_np = np.asarray(b.col_idx[touched]).astype(np.int64)
        mask_np = np.asarray(b.nbr_mask[touched])
        local, covered = _remap_cols(col_np, mask_np,
                                     _row_bases(spec, touched), spec,
                                     self.hot)
        if not covered.all():
            # a changed row grew support beyond window + hot set
            return shard(new_plan, self.mesh, axis=self.spec.axis
                         )._handoff(self, resharded=1)
        ti = jnp.asarray(touched)
        return ShardedPlan(
            new_plan, self.mesh, spec,
            self.vals.at[ti].set(b.vals[ti]),
            self.lcol.at[ti].set(jnp.asarray(local)),
            self.mask.at[ti].set(jnp.asarray(mask_np)),
            self.hot, self.hot_local, self.hot_dst
        )._handoff(self, patched=1)

    def refresh(self, x_new, *, policy: Optional[str] = None
                ) -> "ShardedPlan":
        """Refresh the wrapped plan, then update shards incrementally.

        A patch-tier refresh (permutation and ELL shapes kept) scatters
        only the migrated row-blocks' tiles/columns into the owning shards
        — devices whose rows did not move keep their arrays untouched and
        no halo re-analysis or global rebuild happens, *provided* the new
        columns still fit the existing halo window. Rebucket/rebuild (or a
        window overflow) re-shard the refreshed plan from scratch.
        """
        return self._absorb(self.plan.refresh(x_new, policy=policy),
                            ("patch",))

    # -- streaming (compose with repro.api.update_plan) --------------------

    def update(self, *, insert=None, delete=None,
               policy: Optional[str] = None) -> "ShardedPlan":
        """One streaming step on the wrapped plan, shards kept in sync.

        Append/tombstone tiers touch a recorded set of row-blocks at a
        fixed layout, so only the shards owning them are scattered into —
        exactly the refresh patch path. A compaction (or capacity growth,
        which changes ``n_rb``, or a halo-window overflow from a streamed
        row's new columns) re-shards the updated plan on the same mesh.
        """
        from repro import api

        return self._absorb(
            api.update_plan(self.plan, insert=insert, delete=delete,
                            policy=policy),
            ("append", "tombstone"))

    def absorb(self, new_plan) -> "ShardedPlan":
        """Absorb an externally-updated successor of the wrapped plan —
        the shard-local half of a double-buffer swap.

        ``repro.core.doublebuf.DoubleBufferedPlan`` maintains the host
        plan (in-place tiers on the caller thread, layout repairs on a
        background thread); after a swap, the sharded view absorbs the
        successor here. In-place steps (append/tombstone/patch, recorded
        ``last_patch_rb`` at an unchanged layout) scatter only the
        touched shards; a swapped-in rebucket/compact re-shards — on the
        same mesh, carrying the compiled matvec when the shard spec is
        unchanged (shard-local swap, no recompilation).
        """
        return self._absorb(new_plan, ("append", "tombstone", "patch"))

    def insert(self, x_new, *, policy: Optional[str] = None):
        """Streamed insert; returns ``(sharded_plan, physical_indices)``."""
        sp = self.update(insert=x_new, policy=policy)
        return sp, sp.plan.host.last_inserted_idx

    def delete(self, idx, *, policy: Optional[str] = None) -> "ShardedPlan":
        """Streamed delete (tombstone) of physical rows ``idx``."""
        return self.update(delete=idx, policy=policy)

    def __repr__(self) -> str:
        s = self.spec
        return (f"ShardedPlan(n={self.plan.n}, devices={s.n_dev}, "
                f"rb_per={s.rb_per}, mode={s.mode!r}, "
                f"halo=({s.halo_lo},{s.halo_hi}), hot={s.n_hot}, "
                f"transfer={self.transfer_fraction:.2f}x-allgather)")


def shard(plan, mesh: Optional[Mesh] = None, axis: str = "data"
          ) -> ShardedPlan:
    """Shard ``plan``'s row-blocks over ``mesh`` (default: every device).

    Analyzes the ELL schedule for the minimal halo exchange (plus hot
    set), remaps the column schedule to window-local coordinates, and
    places tiles/columns with a row-sharded
    :class:`~jax.sharding.NamedSharding`. Requires a concrete
    (non-traced) plan with a BSR.

    Memoized per ``(device count, axis)`` on the plan host — repeated
    calls (including the ``dist`` registry backend's) return the same
    ShardedPlan instead of re-analyzing and re-placing the tiles.
    """
    if plan.bsr is None:
        raise ValueError("profile-only plan has no BSR to shard "
                         "(rebuild with with_bsr=True)")
    if isinstance(plan.bsr.col_idx, jax.core.Tracer):
        raise ValueError("shard() analyzes the ELL schedule on the host; "
                         "call it outside jit")
    if mesh is None:
        mesh = default_mesh(axis)
    if axis not in mesh.shape:
        raise ValueError(f"mesh has no axis {axis!r} (axes: "
                         f"{tuple(mesh.axis_names)}); pass axis=")
    cache = getattr(plan.host, "shard_cache", None)
    key = (mesh.shape[axis], axis)
    if cache is not None:
        sp = cache.get(key)
        if sp is not None and sp.plan.bsr is plan.bsr and sp.mesh == mesh:
            return sp
    b = plan.bsr
    spec, hot = analyze_shards(b, mesh.shape[axis], axis)
    col_np = np.asarray(b.col_idx)
    mask_np = np.zeros((spec.n_rb_pad, b.max_nbr), bool)
    mask_np[:b.n_rb] = np.asarray(b.nbr_mask)
    lcol = _local_cols(col_np, mask_np[:b.n_rb], spec, hot)
    hot_local, hot_dst = _hot_routing(spec, hot)
    pad_rb = spec.n_rb_pad - b.n_rb
    vals = (jnp.pad(b.vals, ((0, pad_rb), (0, 0), (0, 0), (0, 0)))
            if pad_rb else b.vals)
    sh = NamedSharding(mesh, P(axis))
    return ShardedPlan(plan, mesh, spec,
                       jax.device_put(vals, sh),
                       jax.device_put(jnp.asarray(lcol), sh),
                       jax.device_put(jnp.asarray(mask_np), sh),
                       hot,
                       jax.device_put(jnp.asarray(hot_local), sh),
                       jax.device_put(jnp.asarray(hot_dst), sh)
                       )._register()
