"""Mixture-of-Experts FFN: sort-based capacity routing, static shapes.

Routing is computed locally per data shard inside shard_map (no cross-shard
sort); expert weights are sharded over the tensor axis on their hidden dim
("expert TP" — robust to expert counts not divisible by the mesh, e.g.
granite's 40 experts on a 16-way axis), with the row-parallel down-proj
combined by an explicit psum. Optional EP (experts over the tensor axis with
all-to-all token exchange) is provided for divisible counts.

Dropped-token semantics (training, :func:`moe_ffn`): tokens beyond an
expert's capacity (ceil(T*k/E * capacity_factor)) are dropped
(Switch-style); the residual stream carries them unchanged.

Serving (:func:`moe_serve`) is dropless and told which experts this chip
holds (``MoEConfig.experts_held`` from ``first_held``): it routes over all
``n_experts``, computes the held experts' part of the result for the
tokens routed to them, and leaves out what experts held elsewhere would
add (expert parallelism without its exchange).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro import compat
from repro.compat import shard_map

from repro.configs.base import ModelConfig
from repro.models import param as pm
from repro.models.sharding import ShardCtx, ep_axis, resolve_spec, tp_axis

CAPACITY_FACTOR = 1.25


def init_moe(key, cfg: ModelConfig):
    m = cfg.moe
    d, e, f = cfg.d_model, m.n_experts, m.d_ff_expert
    eh = m.held                      # expert weights: only the held share
    ks = jax.random.split(key, 6)
    scale_in = 1.0 / math.sqrt(d)
    scale_out = 1.0 / math.sqrt(f)
    ep = "ep" if m.expert_parallel else None
    tp_in = None if m.expert_parallel else "tp"
    p = {
        "router": {"w": jax.random.normal(ks[0], (d, e), jnp.float32) * scale_in},
        "wg": jax.random.normal(ks[1], (eh, d, f), jnp.float32) * scale_in,
        "wu": jax.random.normal(ks[2], (eh, d, f), jnp.float32) * scale_in,
        "wd": jax.random.normal(ks[3], (eh, f, d), jnp.float32) * scale_out,
    }
    s = {
        "router": {"w": P("fsdp", None)},
        "wg": P(ep, "fsdp", tp_in),
        "wu": P(ep, "fsdp", tp_in),
        "wd": P(ep, tp_in, "fsdp"),
    }
    if m.n_shared_experts:
        fs = f * m.n_shared_experts
        p["shared"] = {
            "wg": jax.random.normal(ks[4], (d, fs), jnp.float32) * scale_in,
            "wu": jax.random.normal(ks[5], (d, fs), jnp.float32) * scale_in,
            "wd": jax.random.normal(ks[4], (fs, d), jnp.float32) / math.sqrt(fs),
        }
        s["shared"] = {"wg": P("fsdp", "tp"), "wu": P("fsdp", "tp"),
                       "wd": P("tp", "fsdp")}
    return p, s


def _route_local(xf, eidx, gates, wg, wu, wd, capacity: int,
                 psum_axis: Optional[str]):
    """Sort-based dispatch within one shard.

    xf (T, d); eidx/gates (T, k); wg/wu (E, d, f_local); wd (E, f_local, d).
    """
    t, k = eidx.shape
    e = wg.shape[0]
    d = xf.shape[-1]
    flat_e = eidx.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=e)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(t * k) - starts[sorted_e]
    keep = rank < capacity
    dest = sorted_e * capacity + jnp.minimum(rank, capacity - 1)
    token_of = order // k

    vals = xf[token_of] * keep[:, None].astype(xf.dtype)
    buf = jnp.zeros((e * capacity, d), xf.dtype).at[dest].add(vals)
    bufe = buf.reshape(e, capacity, d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", bufe, wg.astype(xf.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", bufe, wu.astype(xf.dtype))
    out = jnp.einsum("ecf,efd->ecd", h, wd.astype(xf.dtype)).reshape(-1, d)
    if psum_axis is not None:
        out = jax.lax.psum(out, psum_axis)
    g = (gates.reshape(-1)[order] * keep)[:, None].astype(xf.dtype)
    y = jnp.zeros_like(xf).at[token_of].add(out[dest] * g)
    return y


def _route_ep(xf, eidx, gates, wg, wu, wd, capacity: int, ep_axis: str):
    """EP: experts sharded over ``ep_axis``; tokens exchanged by all_to_all."""
    t, k = eidx.shape
    d = xf.shape[-1]
    e_local = wg.shape[0]
    n_dev = compat.axis_size(ep_axis)
    e = e_local * n_dev
    cap = capacity
    flat_e = eidx.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=e)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(t * k) - starts[sorted_e]
    keep = rank < cap
    dest = sorted_e * cap + jnp.minimum(rank, cap - 1)
    token_of = order // k

    vals = xf[token_of] * keep[:, None].astype(xf.dtype)
    buf = jnp.zeros((e * cap, d), xf.dtype).at[dest].add(vals)
    # exchange: (n_dev, e_local*cap, d) -> all_to_all over devices
    buf = buf.reshape(n_dev, e_local * cap, d)
    buf = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=0,
                             tiled=False)
    # now (n_dev, e_local*cap, d): rows from every peer for MY experts
    bufe = buf.reshape(n_dev, e_local, cap, d)
    h = jax.nn.silu(jnp.einsum("necd,edf->necf", bufe, wg.astype(xf.dtype)))
    h = h * jnp.einsum("necd,edf->necf", bufe, wu.astype(xf.dtype))
    out = jnp.einsum("necf,efd->necd", h, wd.astype(xf.dtype))
    out = out.reshape(n_dev, e_local * cap, d)
    out = jax.lax.all_to_all(out, ep_axis, split_axis=0, concat_axis=0,
                             tiled=False)
    out = out.reshape(e * cap, d)
    g = (gates.reshape(-1)[order] * keep)[:, None].astype(xf.dtype)
    y = jnp.zeros_like(xf).at[token_of].add(out[dest] * g)
    return y


def load_balance_loss(probs, eidx, n_experts: int):
    """Switch-style aux loss: E * sum_e f_e * p_e."""
    t = probs.shape[0]
    f = jnp.zeros((n_experts,), jnp.float32).at[eidx.reshape(-1)].add(1.0)
    f = f / jnp.maximum(f.sum(), 1.0)
    pbar = probs.mean(axis=0)
    return n_experts * jnp.sum(f * pbar)


def _route(p, x: jax.Array, m):
    """Softmax router over all ``n_experts``, greedy top-k. The gates are
    the top-k probabilities, renormalised to sum 1 where the model says
    so (``norm_topk_prob``)."""
    probs = jax.nn.softmax(x.astype(jnp.float32) @ p["router"]["w"], axis=-1)
    gates, eidx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return probs, gates, eidx


def _shared(p, x):
    sh = p["shared"]
    h = jax.nn.silu(x @ sh["wg"].astype(x.dtype)) * (x @ sh["wu"].astype(x.dtype))
    return h @ sh["wd"].astype(x.dtype)


# serving-side counters of one moe_serve call (int32, summed over tokens)
MOE_STATS = ("moe_experts_read", "moe_pairs_local", "moe_pairs_total",
             "moe_pairs_dropped")


def moe_serve(p, x: jax.Array, cfg: ModelConfig, active=None
              ) -> Tuple[jax.Array, jax.Array]:
    """The serving MoE FFN: dropless, over this chip's held experts.

    x (..., d); ``active`` (...) bool marks the tokens the counters count
    (None: every token). Every token is routed over all ``n_experts``;
    each (token, expert) pair whose expert is held here is dispatched to
    it (sorted by expert, capacity = the token count, so no expert can
    overflow and no pair is dropped) and combined with its gate; pairs
    for experts held elsewhere are left out. Shared experts run once, on
    every token.

    Returns ``(y, stats)`` with ``stats`` int32 in ``MOE_STATS`` order:
    distinct held experts the active tokens reached, their pairs routed
    here, all their pairs, and the pairs routed here that no expert
    computed (0 unless the dispatch is wrong)."""
    m = cfg.moe
    shape = x.shape
    d = shape[-1]
    xf = x.reshape(-1, d)
    t, k, eh = xf.shape[0], m.top_k, m.held
    _, gates, eidx = _route(p, xf, m)
    le = eidx.reshape(-1) - m.first_held               # held-local index
    local = (le >= 0) & (le < eh)
    le = jnp.where(local, le, eh)                      # elsewhere: sorts last
    order = jnp.argsort(le, stable=True)
    sorted_e = le[order]
    counts = jnp.bincount(le, length=eh + 1)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(t * k) - starts[sorted_e]
    cap = t
    keep = (sorted_e < eh) & (rank < cap)
    dest = jnp.where(keep, sorted_e * cap + rank, eh * cap)   # eh*cap: drop
    token_of = order // k
    buf = jnp.zeros((eh * cap, d), x.dtype).at[dest].set(
        xf[token_of], mode="drop").reshape(eh, cap, d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(x.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["wu"].astype(x.dtype))
    out = jnp.einsum("ecf,efd->ecd", h, p["wd"].astype(x.dtype))
    out = out.reshape(eh * cap, d)
    g = (gates.reshape(-1)[order] * keep).astype(x.dtype)[:, None]
    y = jnp.zeros_like(xf).at[token_of].add(
        out[jnp.minimum(dest, eh * cap - 1)] * g)
    if "shared" in p:
        y = y + _shared(p, xf)

    act = (jnp.ones((t,), bool) if active is None
           else jnp.broadcast_to(active, shape[:-1]).reshape(t))
    pair_act = act[token_of]
    loc = pair_act & (sorted_e < eh)
    reached = jnp.zeros((eh + 1,), jnp.int32).at[sorted_e].max(
        loc.astype(jnp.int32))[:eh]
    i32 = lambda v: jnp.sum(v, dtype=jnp.int32)
    stats = jnp.stack([i32(reached), i32(loc), i32(pair_act),
                       i32(loc & ~keep)])
    return y.reshape(shape), stats


def moe_ffn(p, x: jax.Array, cfg: ModelConfig, shd: ShardCtx
            ) -> Tuple[jax.Array, jax.Array]:
    """x (B, S, d) -> (y (B, S, d), aux_loss scalar)."""
    m = cfg.moe
    if m.held != m.n_experts:
        raise NotImplementedError(
            "the training MoE holds every expert; a held share serves only "
            "(moe_serve)")
    b, s, d = x.shape
    probs, gates, eidx = _route(p, x, m)
    aux = load_balance_loss(probs.reshape(-1, m.n_experts).astype(jnp.float32),
                            eidx.reshape(-1, m.top_k), m.n_experts)

    n_dp = 1
    tp = tp_axis(shd.mesh) if shd.mesh is not None else None
    if shd.mesh is not None:
        dp_ax = resolve_spec(P("dp"), shd.mesh)[0]
        for a in (dp_ax if isinstance(dp_ax, tuple) else (dp_ax,)):
            n_dp *= shd.mesh.shape[a]
    if shd.mesh is None or b % n_dp != 0:
        # single-device path, or batch too small to shard (e.g. decode B=1):
        # route locally with replicated compute
        t = b * s
        cap = max(1, math.ceil(t * m.top_k * m.capacity_factor
                               / m.n_experts))
        y = _route_local(x.reshape(t, d), eidx.reshape(t, -1),
                         gates.reshape(t, -1).astype(x.dtype),
                         p["wg"], p["wu"], p["wd"], cap, None)
        y = y.reshape(b, s, d)
    else:
        mesh = shd.mesh
        dp = resolve_spec(P("dp"), mesh)[0]
        t_local = b * s // n_dp
        cap = max(1, math.ceil(t_local * m.top_k * m.capacity_factor
                               / m.n_experts))

        epax = ep_axis(mesh)
        dp_axes = dp if isinstance(dp, tuple) else (dp,)
        # sequence dim sharded over tp when tp exists and ep not already in dp
        seq_ax = tp if (tp is not None and tp not in dp_axes) else None
        n_seq = mesh.shape[seq_ax] if seq_ax is not None else 1
        if m.expert_parallel and epax is not None \
                and m.n_experts % mesh.shape[epax] == 0 and s % n_seq == 0:
            # tokens enter fully sharded (batch over dp, seq over tp when
            # distinct) so EP compute is never replicated; all_to_all over
            # the expert axis exchanges token rows with the experts' owners
            cap_ep = max(1, math.ceil(b * s // (n_dp * n_seq) * m.top_k
                                      * m.capacity_factor / m.n_experts))

            def body(xl, el, gl, wg, wu, wd):
                tl = xl.shape[0] * xl.shape[1]
                y = _route_ep(xl.reshape(tl, d), el.reshape(tl, -1),
                              gl.reshape(tl, -1).astype(xl.dtype),
                              wg, wu, wd, cap_ep, epax)
                return y.reshape(xl.shape)
            f = shard_map(body, mesh=mesh,
                          in_specs=(P(dp, seq_ax), P(dp, seq_ax),
                                    P(dp, seq_ax),
                                    P(epax, None, None),
                                    P(epax, None, None),
                                    P(epax, None, None)),
                          out_specs=P(dp, seq_ax), check_vma=False)
        else:
            def body(xl, el, gl, wg, wu, wd):
                tl = xl.shape[0] * xl.shape[1]
                y = _route_local(xl.reshape(tl, d), el.reshape(tl, -1),
                                 gl.reshape(tl, -1).astype(xl.dtype),
                                 wg, wu, wd, cap, tp)
                return y.reshape(xl.shape)
            f = shard_map(body, mesh=mesh,
                          in_specs=(P(dp), P(dp), P(dp),
                                    P(None, None, tp),
                                    P(None, None, tp),
                                    P(None, tp, None)),
                          out_specs=P(dp), check_vma=False)
        y = f(x, eidx, gates, p["wg"], p["wu"], p["wd"])

    if "shared" in p:
        y = y + _shared(p, x)
    return y, aux
