"""ClusterKV decode service: plan-cached continuous batching.

``ClusterKVEngine`` extends the reference :class:`~repro.train.serve_loop.
Engine` with plans as first-class serving state. The per-call clusterkv
decode path re-derives the cluster ordering of every slot's cache each
tick (a Morton sort per token); the service instead

  - builds one ordering ``PlanBatch`` per layer at ADMISSION
    (:func:`repro.core.clusterkv.kv_plan_batch` over the prefilled keys,
    ``capacity=max_seq``) and keeps the slot's KV cache in PLAN order,
  - streams each generated key into those plans through the PR 4 insert
    tier (:class:`~repro.serve.streaming.LockstepInserter` — claim a
    Morton-leaf slot host-side, scatter device-side; never re-sort),
  - admits by SPEC UNIFICATION: every session is built to the same pow2
    capacity and plan config, so ``PlanSpec`` equality guarantees a new
    session re-enters the one compiled decode step. ``decode_traces``
    counts retraces at trace time; the service gate is that it stays 1
    across arbitrary admission churn.

``mode="percall"`` runs the same engine over the baseline per-call
clusterkv decode (``backend="clusterkv"``) for A/B benchmarking.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.configs.base import ModelConfig
from repro.core import clusterkv as ckv
from repro.models.sharding import NO_SHARD
from repro.serve.session import Session, SessionStore
from repro.serve.streaming import LockstepInserter
from repro.spans import span
from repro.train.serve_loop import Engine, Request

_BIG = np.iinfo(np.int32).max


@functools.partial(jax.jit, static_argnames=("slot", "bk"))
def _device_trim(pstate, rows, slot: int, bk: int):
    """Zero the trimmed plan rows of one engine slot and recompute its
    centroids. ``rows`` (L, Hkv, nd) plan-order rows (sentinel S: skip)."""
    ks, vs, ps = pstate["ks"], pstate["vs"], pstate["ps"]
    l, _, h, s, dh = ks.shape
    li = jnp.arange(l)[:, None, None]
    hi = jnp.arange(h)[None, :, None]
    ks = ks.at[li, slot, hi, rows].set(0.0, mode="drop")
    vs = vs.at[li, slot, hi, rows].set(0.0, mode="drop")
    ps = ps.at[li, slot, hi, rows].set(_BIG, mode="drop")
    cent = pstate["cent"].at[:, slot].set(
        ks[:, slot].astype(jnp.float32).reshape(l, h, s // bk, bk, dh).mean(3))
    return {"ks": ks, "vs": vs, "ps": ps, "cent": cent}


@functools.partial(jax.jit, static_argnames=("slot", "bk"))
def _device_regather(pstate, gather, slot: int, bk: int):
    """Reorder one engine slot's plan-ordered rows after a host rebucket:
    ``gather`` (L, Hkv, S) maps new plan row -> old plan row."""
    l, _, h, s, dh = pstate["ks"].shape
    ks = jnp.take_along_axis(pstate["ks"][:, slot], gather[..., None], axis=2)
    vs = jnp.take_along_axis(pstate["vs"][:, slot], gather[..., None], axis=2)
    ps = jnp.take_along_axis(pstate["ps"][:, slot], gather, axis=2)
    cent = ks.astype(jnp.float32).reshape(l, h, s // bk, bk, dh).mean(3)
    return {"ks": pstate["ks"].at[:, slot].set(ks),
            "vs": pstate["vs"].at[:, slot].set(vs),
            "ps": pstate["ps"].at[:, slot].set(ps),
            "cent": pstate["cent"].at[:, slot].set(cent)}


class ClusterKVEngine(Engine):
    """Continuous batching with plan-cached clusterkv decode.

    mode="plan"     plan-ordered caches + insert-streamed session plans
                    (ONE decode trace for the service's lifetime)
    mode="percall"  baseline: time-ordered cache, per-tick Morton sort
                    (``Engine`` with backend="clusterkv")
    """

    def __init__(self, cfg: ModelConfig, params, slots: int = 4,
                 max_seq: int = 512, prefill_bucket: int = 64,
                 mode: str = "plan", knn: int = 8,
                 plan_prefill: bool = False):
        if mode not in ("plan", "percall"):
            raise ValueError(f"unknown service mode {mode!r}")
        if not cfg.clusterkv.enabled:
            cfg = dataclasses.replace(
                cfg, clusterkv=dataclasses.replace(cfg.clusterkv,
                                                   enabled=True))
        if mode == "plan" and cfg.mla is not None:
            raise NotImplementedError("plan service serves GQA caches")
        self.mode = mode
        self.knn = knn
        self.plan_prefill = plan_prefill
        self.decode_traces = 0
        self.tokens_out = 0
        self._pf_plan: Dict[int, callable] = {}
        backend = "clusterkv" if mode == "percall" else "flash"
        super().__init__(cfg, params, slots=slots, max_seq=max_seq,
                         prefill_bucket=prefill_bucket, backend=backend)
        self.store = SessionStore()
        bk = min(self.cfg.clusterkv.block_k, max_seq)
        if max_seq % bk:
            raise ValueError("max_seq must be a multiple of block_k")
        self.bk = bk
        self.L = self.cfg.n_layers
        self.Hkv = self.cfg.n_kv_heads
        self.dh = self.cfg.head_dim
        if mode == "plan":
            dt = jnp.dtype(self.cfg.dtype)
            shape = (self.L, slots, self.Hkv)
            self.pstate = {
                "ks": jnp.zeros(shape + (max_seq, self.dh), dt),
                "vs": jnp.zeros(shape + (max_seq, self.dh), dt),
                "ps": jnp.full(shape + (max_seq,), _BIG, jnp.int32),
                "cent": jnp.zeros(shape + (max_seq // bk, self.dh),
                                  jnp.float32),
            }
            self._pend_k = jnp.zeros(shape + (self.dh,), dt)
            self._pend_v = jnp.zeros(shape + (self.dh,), dt)
            self._pend_phys = np.full(shape, -1, np.int64)
            self._pend_pos = np.zeros(slots, np.int32)
            self._slot_sess: List[Optional[Session]] = [None] * slots
            self._tier_totals = {"appends": 0, "tombstones": 0,
                                 "rebuckets": 0, "grows": 0,
                                 "compactions": 0}
            # per-slot plan generation: bumped whenever a session's plan
            # objects are replaced (trim/rebucket/restore — the engine's
            # swaps); every inserter claim is validated against it
            self._plan_gen = [0] * slots
            self.inserter = LockstepInserter(
                self.L, slots, self.Hkv, max_seq, self.dh,
                self.cfg.clusterkv.embed_dim, knn)
            # donate the plan state so the pend-landing scatter can alias
            # the cache buffers instead of copying them every tick (a
            # backend that can't donate just warns and copies)
            self._plan_decode = jax.jit(self._plan_decode_step,
                                        donate_argnums=(1,))

    # -- jitted pieces ------------------------------------------------------

    def _decode_step(self, params, cache, tokens, slot_pos):
        self.decode_traces += 1        # runs at TRACE time: counts compiles
        return super()._decode_step(params, cache, tokens, slot_pos)

    def _plan_decode_step(self, params, pstate, pend, tokens, slot_pos):
        self.decode_traces += 1        # runs at TRACE time: counts compiles
        return self.mod.plan_decode_step(params, self.cfg, pstate, pend,
                                         tokens, slot_pos, NO_SHARD)

    def _plan_prefill_fn(self, length: int):
        if length not in self._pf_plan:
            def fn(params, tokens, perms):
                return self.mod.plan_prefill(params, self.cfg,
                                             {"tokens": tokens}, perms,
                                             NO_SHARD)
            self._pf_plan[length] = jax.jit(fn)
        return self._pf_plan[length]

    # -- admission ----------------------------------------------------------

    def _install(self, s: int, req: Request, cache_1, blen: int):
        """Plan-mode admission: build the session's per-layer plan batches
        over the prefilled keys (capacity = max_seq, so every admission
        re-unifies to the SAME spec) and stage the slot's plan-ordered
        decode state. Returns plan-path logits when ``plan_prefill`` is
        set (the clusterkv_attention(plan_batch=) wiring), else None."""
        if self.mode != "plan":
            return super()._install(s, req, cache_1, blen)
        if blen <= self.knn:
            raise ValueError(
                f"prefill bucket {blen} must exceed knn={self.knn} (spec "
                "unification pins every member's k to knn)")
        # the prefill batch is one request, so these are the slot's bytes
        with span("admit.kv_out",
                  bytes=cache_1["k"].nbytes + cache_1["v"].nbytes):
            # (L, Hkv, blen, dh)
            k_np = np.asarray(cache_1["k"][:, 0], np.float32)
            v_np = np.asarray(cache_1["v"][:, 0], np.float32)
        S = self.max_seq
        with span("admit.plans", layers=self.L):
            plans = [ckv.kv_plan_batch(jnp.asarray(k_np[l]),
                                       d=self.cfg.clusterkv.embed_dim,
                                       knn=self.knn, capacity=S)
                     for l in range(self.L)]
        with span("admit.kv_in"):
            # physical row p < blen holds the key of time position p; tail
            # rows are capacity holes (INT32_MAX position sentinel)
            pi = np.stack([np.asarray(pb.data.pi)
                           for pb in plans])                   # (L,Hkv,S)
            k_pad = np.zeros((self.L, self.Hkv, S, self.dh), np.float32)
            v_pad = np.zeros((self.L, self.Hkv, S, self.dh), np.float32)
            k_pad[:, :, :blen], v_pad[:, :, :blen] = k_np, v_np
            ks = np.take_along_axis(k_pad, pi[..., None], axis=2)
            vs = np.take_along_axis(v_pad, pi[..., None], axis=2)
            ps = np.where(pi < blen, pi, _BIG).astype(np.int32)
            cent = ks.reshape(self.L, self.Hkv, S // self.bk, self.bk,
                              self.dh).mean(3)
            dt = self.pstate["ks"].dtype
            self.pstate = {
                "ks": self.pstate["ks"].at[:, s].set(jnp.asarray(ks, dt)),
                "vs": self.pstate["vs"].at[:, s].set(jnp.asarray(vs, dt)),
                "ps": self.pstate["ps"].at[:, s].set(jnp.asarray(ps)),
                "cent": self.pstate["cent"].at[:, s].set(
                    jnp.asarray(cent)),
            }
        self._pend_phys[:, s] = -1
        self._plan_gen[s] = 0
        self.inserter.attach(s, plans, generation=0)
        sess = Session(rid=req.rid, slot=s, blen=blen, plans=plans)
        self.store.admit(sess)
        self._slot_sess[s] = sess
        if self.plan_prefill:
            # re-run prefill THROUGH the plans: per-head live orderings
            # drive clusterkv_attention's plan_batch path, so the first
            # generated token already comes from the clusterkv kernel
            perms = np.stack([
                np.stack([pi[l, h][pi[l, h] < blen]
                          for h in range(self.Hkv)])
                for l in range(self.L)]).astype(np.int32)  # (L,Hkv,blen)
            plen = len(req.tokens)
            padded = np.zeros(blen, np.int32)
            padded[-plen:] = req.tokens
            pf = self._plan_prefill_fn(blen)
            return pf(self.params, jnp.asarray(padded[None]),
                      jnp.asarray(perms[:, None]))
        return None

    def _release(self, s: int, req: Request) -> None:
        if self.mode != "plan":
            return
        sess = self._slot_sess[s]
        if sess is None:
            return
        self.store.counters["flushed_edges"] += self.inserter.flush(s)
        self.inserter.detach(s)
        self._pend_phys[:, s] = -1
        self._slot_sess[s] = None
        for pb in sess.plans:
            for host in pb.hosts:
                for key in self._tier_totals:
                    self._tier_totals[key] += getattr(host.refresh, key, 0)
        self.store.retire(sess.rid)

    # -- the tick -----------------------------------------------------------

    def step(self) -> int:
        n = self._plan_step() if self.mode == "plan" else super().step()
        self.tokens_out += n
        return n

    def _pend_slots(self) -> np.ndarray:
        """Plan-order landing rows of the pending tokens, resolved against
        the CURRENT member orderings (physical slots are stable across
        trims/rebuckets; plan rows are not). Sentinel max_seq = none."""
        out = np.full((self.L, self.slots, self.Hkv), self.max_seq, np.int32)
        for s in range(self.slots):
            sess = self._slot_sess[s]
            if sess is None:
                continue
            for l in range(self.L):
                for h in range(self.Hkv):
                    p = self._pend_phys[l, s, h]
                    if p >= 0:
                        out[l, s, h] = sess.plans[l].hosts[h].inv[p]
        return out

    def _plan_step(self) -> int:
        self._admit()
        active = [s for s, r in enumerate(self.slot_req) if r is not None]
        if not active:
            return 0
        with span("decode", active=len(active)):
            tokens = np.zeros((self.slots, 1), np.int32)
            for s in active:
                tokens[s, 0] = self.slot_req[s].output[-1]
            pend = {"k": self._pend_k, "v": self._pend_v,
                    "slot": jnp.asarray(self._pend_slots()),
                    "pos": jnp.asarray(self._pend_pos)}
            with span("decode.dispatch"):
                logits, self.pstate, nk, nv = self._plan_decode(
                    self.params, self.pstate, pend, jnp.asarray(tokens),
                    jnp.asarray(self.slot_pos))
                nxt = np.asarray(jnp.argmax(logits, -1))
            # stream this tick's keys into the session plans: the host
            # claims each one's Morton-leaf slot now; the device lands it
            # next tick
            with span("decode.claim"):
                phys = self.inserter.insert(
                    active, nk,
                    generations={s: self._plan_gen[s] for s in active})
            self._pend_phys = phys
            self._pend_k, self._pend_v = nk, nv
            self._pend_pos = self.slot_pos.copy()
            for s in active:
                sess = self._slot_sess[s]
                sess.phys_hist[int(self.slot_pos[s])] = phys[:, s, :].copy()
                self.slot_pos[s] += 1
                self.slot_req[s].output.append(int(nxt[s]))
            self.store.counters["inserts"] += len(active)
            self.ticks += 1
        return len(active)

    # -- session surgery ----------------------------------------------------

    def trim(self, rid: int, positions: Sequence[int]) -> None:
        """Tombstone the given TIME positions out of a live session: the
        member plans take the PR 4 tombstone tier (capacity keeps the
        spec, so no retrace), the device rows are zeroed + re-holed."""
        sess = self.store.get(rid)
        if sess is None:
            raise KeyError(f"no live session {rid}")
        s = sess.slot
        self.store.counters["flushed_edges"] += self.inserter.flush(s)
        del_rows = np.zeros((self.L, self.Hkv, len(positions)), np.int64)
        for i, pos in enumerate(sorted(set(int(p) for p in positions))):
            if pos >= int(self.slot_pos[s]):
                raise ValueError(f"position {pos} not decoded yet")
            if pos < sess.blen:
                del_rows[:, :, i] = pos
            else:
                del_rows[:, :, i] = sess.phys_hist.pop(pos)
                if (int(self._pend_pos[s]) == pos
                        and self._pend_phys[0, s, 0] >= 0):
                    self._pend_phys[:, s] = -1    # never lands
        new_plans = []
        plan_rows = np.zeros_like(del_rows, dtype=np.int32)
        for l in range(self.L):
            idxs = [del_rows[l, h] for h in range(self.Hkv)]
            pb = sess.plans[l].update(delete=idxs, policy="tombstone")
            for h in range(self.Hkv):
                plan_rows[l, h] = pb.hosts[h].inv[del_rows[l, h]]
            new_plans.append(pb)
        sess.plans = new_plans
        self._plan_gen[s] += 1                 # hosts were replaced:
        self.inserter.attach(s, new_plans,     # swap in a new generation
                             generation=self._plan_gen[s])
        self.pstate = _device_trim(self.pstate, jnp.asarray(plan_rows),
                                   s, self.bk)
        self.store.counters["deletes"] += del_rows.shape[-1]

    def rebucket(self, rid: int) -> None:
        """Force the rebucket tier on a live session: re-sort every member
        ordering by its maintained Morton codes (host), re-gather the
        slot's plan-ordered device rows to match. Shapes are untouched, so
        the decode step does not retrace."""
        sess = self.store.get(rid)
        if sess is None:
            raise KeyError(f"no live session {rid}")
        s = sess.slot
        self.store.counters["flushed_edges"] += self.inserter.flush(s)
        S = self.max_seq
        gathers = np.zeros((self.L, self.Hkv, S), np.int64)
        new_plans = []
        for l, pb in enumerate(sess.plans):
            cfg = pb.spec.config
            members = []
            for h, host in enumerate(pb.hosts):
                if host.codes is None:
                    codes, lo, hi = api._stream_codes(host, cfg)
                    host.codes, host.code_lo, host.code_hi = codes, lo, hi
                r2, c2, v2 = host.coo
                pi2, inv2, r2n, c2n = api._stream_rebucket(
                    host.pi, host.codes, r2, c2, S)
                gathers[l, h] = host.inv[pi2]   # new plan row -> old row
                host.pi, host.inv = pi2, inv2
                host.coo = (r2n, c2n, v2)
                host.coo_dev = None
                host.tree = None
                host.gamma = None
                host.shard_cache = {}
                host.refresh = dataclasses.replace(
                    host.refresh, rebuckets=host.refresh.rebuckets + 1,
                    last_action="rebucket")
                members.append(api.InteractionPlan(
                    cfg, S, None, jnp.asarray(pi2), jnp.asarray(inv2), host))
            new_plans.append(api.PlanBatch.from_plans(members, capacity=S))
        sess.plans = new_plans
        self._plan_gen[s] += 1
        self.inserter.attach(s, new_plans, generation=self._plan_gen[s])
        self.pstate = _device_regather(self.pstate, jnp.asarray(gathers),
                                       s, self.bk)
        self.store.counters["rebuckets"] += 1

    # -- drain / snapshot / resume ------------------------------------------

    def snapshot(self, ckpt, step: int, name: str = "sessions",
                 blocking: bool = True) -> None:
        """Flush, pack every live session's device rows + request state
        into its ``aux`` payload, and hand the SessionStore to
        ``Checkpointer.save_plan``."""
        self.store.counters["flushed_edges"] += self.inserter.flush_all()
        # bf16 has no npz representation: widen to f32 (lossless); resume
        # casts back to the cache dtype
        f32 = jnp.float32
        ks = np.asarray(self.pstate["ks"].astype(f32))
        vs = np.asarray(self.pstate["vs"].astype(f32))
        ps = np.asarray(self.pstate["ps"])
        cent = np.asarray(self.pstate["cent"])
        pend_k = np.asarray(self._pend_k.astype(f32))
        pend_v = np.asarray(self._pend_v.astype(f32))
        for sess in self.store.sessions.values():
            s = sess.slot
            req = self.slot_req[s]
            hist_pos = np.asarray(sorted(sess.phys_hist), np.int64)
            hist_phys = (np.stack([sess.phys_hist[int(p)] for p in hist_pos])
                         if hist_pos.size
                         else np.zeros((0, self.L, self.Hkv), np.int64))
            sess.aux = {
                "ks": ks[:, s], "vs": vs[:, s], "ps": ps[:, s],
                "cent": cent[:, s],
                "pend_k": pend_k[:, s], "pend_v": pend_v[:, s],
                "pend_phys": self._pend_phys[:, s].copy(),
                "pend_pos": np.asarray(self._pend_pos[s], np.int32),
                "slot_pos": np.asarray(self.slot_pos[s], np.int32),
                "prompt": np.asarray(req.tokens, np.int32),
                "output": np.asarray(req.output, np.int32),
                "max_new": np.asarray(req.max_new, np.int32),
                "eos_id": np.asarray(
                    -1 if req.eos_id is None else req.eos_id, np.int32),
                "hist_pos": hist_pos, "hist_phys": hist_phys,
            }
        ckpt.save_plan(step, self.store, name=name, blocking=blocking)

    def resume(self, store: SessionStore) -> None:
        """Adopt a restored SessionStore: rebind every session to its slot
        and rebuild the device state, pending token, and request from its
        ``aux`` payload. Decode continues bit-exactly."""
        if self.mode != "plan":
            raise ValueError("resume requires mode='plan'")
        self.store = store
        dt = self.pstate["ks"].dtype
        for sess in store.sessions.values():
            s, aux = sess.slot, sess.aux
            sess.phys_hist = {int(p): aux["hist_phys"][i]
                              for i, p in enumerate(aux["hist_pos"])}
            self.pstate = {
                "ks": self.pstate["ks"].at[:, s].set(
                    jnp.asarray(aux["ks"], dt)),
                "vs": self.pstate["vs"].at[:, s].set(
                    jnp.asarray(aux["vs"], dt)),
                "ps": self.pstate["ps"].at[:, s].set(jnp.asarray(aux["ps"])),
                "cent": self.pstate["cent"].at[:, s].set(
                    jnp.asarray(aux["cent"])),
            }
            self._pend_k = self._pend_k.at[:, s].set(
                jnp.asarray(aux["pend_k"], dt))
            self._pend_v = self._pend_v.at[:, s].set(
                jnp.asarray(aux["pend_v"], dt))
            self._pend_phys[:, s] = aux["pend_phys"]
            self._pend_pos[s] = int(aux["pend_pos"])
            self.slot_pos[s] = int(aux["slot_pos"])
            eos = int(aux["eos_id"])
            req = Request(rid=sess.rid, tokens=np.asarray(aux["prompt"]),
                          max_new=int(aux["max_new"]),
                          eos_id=None if eos < 0 else eos,
                          output=[int(t) for t in aux["output"]])
            self.slot_req[s] = req
            self._slot_sess[s] = sess
            self._plan_gen[s] = 0              # restored plans: fresh
            self.inserter.attach(s, sess.plans, generation=0)

    # -- telemetry ----------------------------------------------------------

    def report(self) -> dict:
        """Machine-readable service telemetry (JSON-safe)."""
        rep = {
            "mode": self.mode, "backend": self.backend,
            "slots": self.slots, "max_seq": self.max_seq,
            "ticks": self.ticks, "tokens_out": self.tokens_out,
            "decode_traces": self.decode_traces,
            "prefill_traces": len(self._prefills) + len(self._pf_plan),
        }
        if self.mode == "plan":
            rep.update(self.store.report())
            tiers = dict(self._tier_totals)      # retired sessions
            for sess in self.store.sessions.values():
                for pb in sess.plans:
                    for host in pb.hosts:
                        for key in tiers:
                            tiers[key] += getattr(host.refresh, key, 0)
            rep["insert_tiers"] = tiers
        return rep
