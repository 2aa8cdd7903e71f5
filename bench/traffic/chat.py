"""Traffic kind ``chat``: open-loop chat requests into the ClusterKV
decode service (``ClusterKVEngine``).

Set-up makes the model's weights on the device from the seed, starts the
service with the cell's slots, ``max_seq`` and prefill bucket, and serves
one request of every prefill bucket the mix can use (every slot taking
one), so that each prefill, admission plan build and the decode step are
compiled before the window.

The mix is the same for every seed: ``rate * seconds`` requests whose
prompt and output lengths are the quantiles of clipped log-normal
distributions and whose gaps are the quantiles of an exponential one,
scaled to fill the window, each of the three in an order the cell's
``mix_seed`` draws. A tail over some tens of requests moves by tens of
percent with the order of arrivals, so every run replays one schedule;
the run seed draws the weights and the prompt tokens, which change no
length. Requests are sent at their due times whatever the
service is doing (an open loop), between ticks: a request that falls due
during a tick is sent when the tick returns, and its wait still counts
from its due time. The window closes when every request due in it has
finished, or ``drain_s`` after its nominal end, when those still open
count as failed.

The check runs the plain reference (``bench/reference/qwen2.py``) over
every finished request's prompt, as the service read it (left-padded to
its bucket), and its served tokens, and reads how far each served token's
logit lies below the reference's best at that position.

Parameters (``bench/workloads/<cell>.json``): ``rate`` requests per
second, ``slots``, ``max_seq``, ``bucket``, ``prompt`` and ``output``
(``median``, ``sigma``, ``min``, ``max`` of the log-normal lengths),
``drain_s``.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import math
import statistics
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import stats
from bench.harness import BenchError, Check, Ctx, Window
from bench.reference import qwen2 as reference

TOKENS, FAULT, WEIGHTS = range(3)
REF_BATCH = 4           # sequences per reference call


@dataclasses.dataclass
class State:
    engine: Any
    params: Any
    info: Dict[str, Any]
    fault: Dict[str, Any]


# -- the model ------------------------------------------------------------


def _sizes(cfg: dict) -> tuple:
    return (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["intermediate_size"], cfg["vocab_size"])


@functools.partial(jax.jit, static_argnames=("sizes",))
def make_params(key, sizes: tuple):
    """The weights, in the layout the service reads, as the configuration
    file's ``weights.init`` states, in bfloat16: one call on the device."""
    n_l, d, hq, hkv, ff, vocab = sizes
    dh = d // hq
    keys = iter(jax.random.split(key, 16))

    def mat(shape, fan_in):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / math.sqrt(fan_in)).astype(jnp.bfloat16)

    def vec(shape, mean):
        return (mean + 0.1 * jax.random.normal(next(keys), shape,
                                               jnp.float32)
                ).astype(jnp.bfloat16)

    def proj(width):
        return {"w": mat((n_l, d, width), d), "b": vec((n_l, width), 0.0)}

    return {
        "embed": {"table": mat((vocab, d), d)},
        "layers": {
            "ln1": {"scale": vec((n_l, d), 1.0)},
            "ln2": {"scale": vec((n_l, d), 1.0)},
            "attn": {"wq": proj(hq * dh), "wk": proj(hkv * dh),
                     "wv": proj(hkv * dh),
                     "wo": {"w": mat((n_l, hq * dh, d), hq * dh)}},
            "ffn": {"wg": {"w": mat((n_l, d, ff), d)},
                    "wu": {"w": mat((n_l, d, ff), d)},
                    "wd": {"w": mat((n_l, ff, d), ff)}},
        },
        "ln_f": {"scale": vec((d,), 1.0)},
    }


def model_config(cfg: dict):
    """The service's ``ModelConfig``, every number from the file."""
    from repro.configs.base import ClusterKVConfig, ModelConfig
    svc = cfg["service"]
    ckv = ClusterKVConfig(enabled=True, embed_dim=svc["embed_dim"],
                          block_q=svc["block_k"], block_k=svc["block_k"],
                          decode_clusters=svc["decode_clusters"],
                          decode_backend=svc["decode_backend"])
    dt = cfg["weights"]["dtype"]
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        qkv_bias=cfg["qkv_bias"], tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        dtype=dt, param_dtype=dt, clusterkv=ckv, remat=False)


def _same_layout(mcfg, params) -> None:
    """The weights made here have the tree, shapes and types the service's
    own initialiser gives: otherwise the run measures something else."""
    from repro.models import model_api
    want = jax.eval_shape(lambda k: model_api.init(mcfg, k)[0],
                          jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: params)
    spec = jax.tree.map(lambda a: (a.shape, str(a.dtype)), want)
    have = jax.tree.map(lambda a: (a.shape, str(a.dtype)), got)
    if spec != have:
        raise BenchError(f"weights do not match the service's layout: "
                         f"{spec} vs {have}")


# -- the mix --------------------------------------------------------------


def _lengths(spec: dict, q: np.ndarray) -> np.ndarray:
    z = np.array([statistics.NormalDist().inv_cdf(x) for x in q])
    v = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def mix(params: dict, seconds: float):
    """(due offsets, prompt lengths, output lengths) of the requests due
    in a window of ``seconds``: one fixed set of sizes and gaps, in the
    order ``params["mix_seed"]`` draws."""
    rng = np.random.default_rng(params["mix_seed"])
    m = max(1, int(round(params["rate"] * seconds)))
    q = (np.arange(m) + 0.5) / m
    prompts = rng.permutation(_lengths(params["prompt"], q))
    outputs = rng.permutation(_lengths(params["output"], q))
    gaps = rng.permutation(-np.log1p(-q))
    gaps *= seconds / gaps.sum()
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    return due, prompts, outputs


def _bucket(n: int, bucket: int) -> int:
    return -(-n // bucket) * bucket


# -- set-up ----------------------------------------------------------------


def setup(ctx: Ctx) -> State:
    from repro.serve.engine import ClusterKVEngine
    from repro.train.serve_loop import Request
    cfg, p = ctx.config, ctx.params
    mcfg = model_config(cfg)
    params = make_params(ctx.key(WEIGHTS), _sizes(cfg))
    _same_layout(mcfg, params)
    jax.block_until_ready(params)
    eng = ClusterKVEngine(mcfg, params, slots=p["slots"],
                          max_seq=p["max_seq"], prefill_bucket=p["bucket"],
                          mode=cfg["service"]["mode"],
                          knn=cfg["service"]["knn"])
    fault = _plant(ctx, eng)
    # one request per prefill bucket the mix can reach, every slot busy
    lo = _bucket(p["prompt"]["min"], p["bucket"])
    hi = _bucket(p["prompt"]["max"], p["bucket"])
    lens = list(range(lo, hi + 1, p["bucket"]))
    lens += lens[:max(0, p["slots"] - len(lens))]
    rng = ctx.rng(TOKENS, 1)
    warm = [Request(rid=-1 - i, max_new=3,
                    tokens=rng.integers(0, cfg["vocab_size"], n)
                    .astype(np.int32)) for i, n in enumerate(lens)]
    for r in warm:
        eng.submit(r)
    while eng.queue or any(r is not None for r in eng.slot_req):
        eng.step()
        eng._retire()
    rep = eng.report()
    ctx.say(f"service {mcfg.name} L={mcfg.n_layers} d={mcfg.d_model} "
            f"heads={mcfg.n_heads}/{mcfg.n_kv_heads} vocab={mcfg.vocab}; "
            f"{p['slots']} slots, max_seq {p['max_seq']}, buckets {lens}; "
            f"warm-up served {len(warm)} requests, decode traces "
            f"{rep['decode_traces']}")
    return State(engine=eng, params=params, info={}, fault=fault)


def _plant(ctx: Ctx, eng) -> Dict[str, Any]:
    """A planted fault in the service's decode step (tests of the check):
    ``unchanged`` hands the plan state back as it came, so no generated
    key or value ever lands; ``altered`` makes one tick of the window
    serve another token in one slot."""
    fault = {"armed": False, "tick": 0}
    if ctx.fault is None:
        return fault
    if ctx.fault == "unchanged":
        def same(params, pstate, pend, tokens, slot_pos):
            out = eng._plan_decode_step(params, pstate, pend, tokens,
                                        slot_pos)
            return out[0], pstate, out[2], out[3]
        eng._plan_decode = jax.jit(same)
        return fault
    if ctx.fault == "altered":
        rng = ctx.rng(FAULT)
        at, token = int(rng.integers(3, 12)), int(
            rng.integers(ctx.config["vocab_size"]))
        decode = eng._plan_decode
        bump = jax.jit(lambda logits, s: logits.at[s, token].set(1e9))
        jax.block_until_ready(bump(jnp.zeros(
            (ctx.params["slots"], ctx.config["vocab_size"])), 0))

        def altered(*args):
            logits, *rest = decode(*args)
            if fault["armed"]:
                fault["tick"] += 1
                if fault["tick"] == at:
                    s = next(i for i, r in enumerate(eng.slot_req)
                             if r is not None)
                    logits = bump(logits, s)
            return (logits, *rest)
        eng._plan_decode = altered
        return fault
    raise ValueError(f"unknown fault {ctx.fault!r}")


# -- the window --------------------------------------------------------------


def window(ctx: Ctx, st: State, seconds: float) -> Window:
    from repro.train.serve_loop import Request
    cfg, p = ctx.config, ctx.params
    eng = st.engine
    offsets, prompts, outputs = mix(p, seconds)
    rng = ctx.rng(TOKENS)
    reqs = [Request(rid=i, max_new=int(o),
                    tokens=rng.integers(0, cfg["vocab_size"], int(n))
                    .astype(np.int32))
            for i, (n, o) in enumerate(zip(prompts, outputs))]
    n = len(reqs)
    first: List[Any] = [None] * n
    done: List[Any] = [None] * n
    sent_at = np.zeros(n)
    steps = []                                   # (seconds, admitted)
    st.fault["armed"] = True
    t0 = time.perf_counter()
    due = t0 + offsets
    close, give_up = t0 + seconds, t0 + seconds + p["drain_s"]
    sent = 0
    while True:
        now = time.perf_counter()
        while sent < n and due[sent] <= now:
            eng.submit(reqs[sent])
            sent_at[sent] = now
            sent += 1
        busy = bool(eng.queue) or any(r is not None for r in eng.slot_req)
        if not busy:
            if sent == n:
                break
            with jax.profiler.TraceAnnotation("bench/await-arrival"):
                time.sleep(due[sent] - now)
            continue
        if now > give_up:
            break
        admits = eng.store.counters["admits"]
        name = ("bench/step.admit" if eng.queue and None in eng.slot_req
                else "bench/step")
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            eng.step()
        t1 = time.perf_counter()
        steps.append((t1 - t, eng.store.counters["admits"] - admits))
        for r in eng.slot_req:
            if r is not None and r.rid >= 0 and first[r.rid] is None:
                first[r.rid] = t1
        eng._retire()
        for r in reqs[:sent]:
            if done[r.rid] is None and r.t_done:
                done[r.rid] = t1
    end = time.perf_counter()
    st.fault["armed"] = False
    late = sent_at[:sent] - due[:sent]
    ctx.say(f"{n} requests due in {seconds:g} s ({p['rate']} /s), "
            f"{sum(d is not None for d in done)} finished; sent late by "
            f"median {np.median(late) * 1e3:.1f} ms, max "
            f"{late.max() * 1e3:.1f} ms; window {end - t0:.2f} s")
    failed = sum(d is None for d in done)
    ttft = stats.due_percentile(due, first, 90, gave_up=end)
    st.info["admit_step_ms"] = _admit_step_ms(steps)
    finished = [r for r in reqs if done[r.rid] is not None]
    tokens = sum(len(r.output) for r in finished)
    return Window(attempted=n, failed=failed, seconds=end - t0,
                  e2e={"ttft_p90_ms": ttft * 1e3},
                  counts={"requests": n, "tokens": tokens,
                          "steps": len(steps)},
                  answers=finished)


def _admit_step_ms(steps) -> Any:
    """Host time of the ticks that admitted, per request admitted, above
    the median tick that admitted nothing."""
    plain = [s for s, a in steps if a == 0]
    admitted = sum(a for _, a in steps)
    if not plain or not admitted:
        return None
    base = statistics.median(plain)
    return sum(s - base for s, a in steps if a) / admitted * 1e3


# -- the check ---------------------------------------------------------------


def check(ctx: Ctx, st: State, win: Window):
    served = win.answers
    win.answers = None
    st.engine = None                         # free the program's state
    gc.collect()
    wrong = sum(len(r.output) != r.max_new or
                not all(0 <= t < ctx.config["vocab_size"] for t in r.output)
                for r in served)
    quant = {None: "", "fp8": "fp8"}.get(ctx.control)
    if quant is None:
        raise ValueError(f"unknown control {ctx.control!r}")
    t = time.perf_counter()
    gap = widest_gaps(ctx, st.params, served, (quant,))[quant]
    ctx.say(f"checked {len(served)} requests, "
            f"{sum(len(r.output) for r in served)} served tokens in "
            f"{time.perf_counter() - t:.1f} s"
            + (f" (control {ctx.control}: the gap of its own first token)"
               if quant else ""))
    return [Check("logit_gap", gap, ctx.limits["logit_gap"]),
            Check("wrong_length", float(wrong), 0.0)]


def widest_gaps(ctx: Ctx, params, served, quants=("",)) -> Dict[str, float]:
    """For each ``quant`` in ``quants``: the widest gap by which a token
    lies below the float32 reference's best logit at its position. With
    ``""`` the token is the one served; with a control's name it is the
    one that control puts first at the same position."""
    cfg, p = ctx.config, ctx.params
    if not served:
        return {q: math.inf for q in quants}
    width = _bucket(_bucket(p["prompt"]["max"], p["bucket"])
                    + p["output"]["max"], 128)
    m = p["output"]["max"]
    sizes = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
             float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]))
    widest = {q: 0.0 for q in quants}
    for lo in range(0, len(served), REF_BATCH):
        part = served[lo:lo + REF_BATCH]
        tokens = np.zeros((REF_BATCH, width), np.int32)
        rows = np.zeros((REF_BATCH, m), np.int32)
        want = np.zeros((REF_BATCH, m), np.int32)
        valid = np.zeros((REF_BATCH, m), bool)
        for b, r in enumerate(part):
            blen = _bucket(len(r.tokens), p["bucket"])
            out = np.asarray(r.output, np.int32)
            tokens[b, blen - len(r.tokens):blen] = r.tokens
            tokens[b, blen:blen + len(out) - 1] = out[:-1]
            rows[b, :len(out)] = blen - 1 + np.arange(len(out))
            want[b, :len(out)] = out
            valid[b, :len(out)] = True
        ref = reference.logits_at(params, jnp.asarray(tokens),
                                  jnp.asarray(rows), sizes=sizes)
        best = ref.max(-1)
        for q in quants:
            if q:
                pick = jnp.argmax(reference.logits_at(
                    params, jnp.asarray(tokens), jnp.asarray(rows),
                    sizes=sizes, quant=q), -1)
            else:
                pick = jnp.asarray(want)
            got = jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
            gap = np.asarray(best - got)[valid]
            widest[q] = max(widest[q], float(gap.max()))
    return widest


def readings(ctx: Ctx, st: State, win: Window, controls=("fp8",)) -> dict:
    """The numbers compared, for the program and for each control, over
    one window's finished requests (the limit-setting probe)."""
    gaps = widest_gaps(ctx, st.params, win.answers, ("",) + tuple(controls))
    return {"logit_gap": gaps[""],
            **{f"logit_gap.{c}": gaps[c] for c in controls}}


def reseed(ctx: Ctx, st: State) -> None:
    """New weights from ``ctx.seed`` in the running service (the probe's
    next seed): the service takes its weights as an argument of every
    call, so nothing compiles again."""
    st.params = make_params(ctx.key(WEIGHTS), _sizes(ctx.config))
    st.engine.params = st.params
