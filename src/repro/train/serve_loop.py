"""Continuous-batching serving loop.

Production-serving structure over the model decode step: a fixed pool of
``slots`` (the static decode batch the step was compiled for), a request
queue, and an engine loop that

  - admits queued requests into free slots (prefilling their prompt into
    the slot's cache region),
  - runs ONE batched decode step for all active slots per tick,
  - retires slots on EOS/max-tokens and immediately backfills them.

Static shapes throughout: the decode step is compiled once for
(slots, max_seq); prefill is compiled per admitted prompt-length bucket
(lengths are rounded up to ``prefill_bucket`` to bound recompiles).

Single-host reference implementation; the sharded version places the slot
axis on "dp" and the cache per cache_specs (the dry-run decode cells prove
those lowerings).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import model_api
from repro.models.sharding import NO_SHARD
from repro.spans import span


@dataclasses.dataclass
class Request:
    rid: int
    tokens: np.ndarray                 # prompt token ids (1-D)
    max_new: int = 32
    eos_id: Optional[int] = None
    # filled by the engine:
    output: List[int] = dataclasses.field(default_factory=list)
    t_submit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0


class Engine:
    def __init__(self, cfg: ModelConfig, params, slots: int = 4,
                 max_seq: int = 512, prefill_bucket: int = 64,
                 backend: str = "flash"):
        if cfg.family not in ("dense", "moe"):
            raise NotImplementedError(
                "reference engine supports decoder-only token models")
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_seq = max_seq
        self.bucket = prefill_bucket
        self.backend = backend
        mod = model_api.module_for(cfg)
        self.mod = mod
        self.cache = mod.init_cache(cfg, slots, max_seq)
        # per-slot positions replace the scalar cache pos
        self.slot_pos = np.zeros(slots, np.int32)
        self.slot_req: List[Optional[Request]] = [None] * slots
        self.queue: Deque[Request] = deque()
        self._decode = jax.jit(self._decode_step)
        self._prefills: Dict[int, Callable] = {}
        # which axis of each cache entry is the sequence axis, read off the
        # family's own cache spec (slot install copies along it)
        self._seq_axes = model_api.cache_seq_axes(cfg)
        self.ticks = 0

    # -- jitted pieces ------------------------------------------------------

    def _decode_step(self, params, cache, tokens, slot_pos):
        """One token for every slot, each writing and masking at ITS OWN
        position (cache['pos'] as a (slots,) vector — decode_step's
        continuous-batching contract)."""
        cache = dict(cache, pos=slot_pos)
        logits, new_cache = self.mod.decode_step(
            params, self.cfg, cache, tokens, NO_SHARD, self.backend)
        return logits, new_cache

    def _prefill_fn(self, length: int):
        if length not in self._prefills:
            def fn(params, tokens):
                cfg = dataclasses.replace(self.cfg)
                return self.mod.prefill(params, cfg, {"tokens": tokens},
                                        NO_SHARD, self.backend)
            self._prefills[length] = jax.jit(fn)
        return self._prefills[length]

    # -- engine -------------------------------------------------------------

    def submit(self, req: Request) -> None:
        req.t_submit = time.time()
        self.queue.append(req)

    def _install(self, s: int, req: Request, cache_1, blen: int):
        """Install an admitted request's prefilled state into slot ``s``.

        The base engine copies every seq-scaling cache entry (per
        ``model_api.cache_seq_axes`` — not a hardcoded key list) into the
        slot's cache region. Subclasses may stage entirely different
        serving state and return replacement first-token logits (else
        None to keep the prefill's)."""
        for key, ax in self._seq_axes.items():
            seg = cache_1[key][:, 0]             # e.g. (L, H, blen, dh)
            start = [0] * self.cache[key].ndim
            start[1] = s                         # slot on the batch axis
            self.cache[key] = jax.lax.dynamic_update_slice(
                self.cache[key], seg[:, None], tuple(start))
        return None

    def _release(self, s: int, req: Request) -> None:
        """Hook: slot ``s`` just retired ``req`` (subclass teardown)."""

    def _admit(self) -> None:
        for s in range(self.slots):
            if self.slot_req[s] is not None or not self.queue:
                continue
            req = self.queue.popleft()
            plen = len(req.tokens)
            blen = -(-plen // self.bucket) * self.bucket
            with span("admit", rid=req.rid, blen=blen):
                padded = np.zeros(blen, np.int32)
                padded[-plen:] = req.tokens      # left-pad into the bucket
                with span("admit.prefill"):
                    pf = self._prefill_fn(blen)
                    cache_1, logits = jax.block_until_ready(
                        pf(self.params, jnp.asarray(padded[None])))
                override = self._install(s, req, cache_1, blen)
                if override is not None:
                    logits = override
                self.slot_pos[s] = blen
                with span("admit.first_token"):
                    tok = int(jnp.argmax(logits[0]))
                req.output.append(tok)
                req.t_first = time.time()
                self.slot_req[s] = req

    def _retire(self) -> None:
        for s, req in enumerate(self.slot_req):
            if req is None:
                continue
            done = (len(req.output) >= req.max_new
                    or (req.eos_id is not None
                        and req.output[-1] == req.eos_id)
                    or int(self.slot_pos[s]) >= self.max_seq - 1)
            if done:
                req.t_done = time.time()
                self.slot_req[s] = None
                self.slot_pos[s] = 0
                self._release(s, req)

    def step(self) -> int:
        """One engine tick: admit, decode all active slots, retire."""
        self._admit()
        active = [s for s, r in enumerate(self.slot_req) if r is not None]
        if not active:
            return 0
        tokens = np.zeros((self.slots, 1), np.int32)
        for s in active:
            tokens[s, 0] = self.slot_req[s].output[-1]
        logits, self.cache = self._decode(
            self.params, self.cache, jnp.asarray(tokens),
            jnp.asarray(self.slot_pos))
        nxt = np.asarray(jnp.argmax(logits, -1))
        for s in active:
            self.slot_pos[s] += 1
            self.slot_req[s].output.append(int(nxt[s]))
        self.ticks += 1
        return len(active)

    def run(self, until_empty: bool = True, max_ticks: int = 10_000) -> None:
        while (self.queue or any(r is not None for r in self.slot_req)) \
                and self.ticks < max_ticks:
            self.step()
            self._retire()
