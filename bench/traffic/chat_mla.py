"""Traffic kind ``chat_mla``: open-loop chat requests into the ClusterKV
decode service (``ClusterKVEngine``) serving a DeepSeek-V2 model: latent
attention (MLA) with one plan per layer over the latent rows, and an
expert layer that holds the chip's share of the routed experts.

Everything but the model is the ``chat`` kind's, by import: the mix (one
schedule for every seed), the open-loop window and its due-time tail,
``admit_step_ms``, the planted faults, and the probe hooks ``reseed`` and
``readings``. Set-up makes this model's weights on the device from the
seed and warms one request of every prefill bucket, as ``chat`` does.

The window also reads the service's device-side counters before and after
(one fetch each, never per tick) and reports their growth in ``info``:
the decode ticks, the held experts the ticks' tokens reached, the latent
rows attended, the (token, expert) pairs routed and dropped.

The check runs the plain reference (``bench/reference/deepseek_v2.py``)
over every finished request's prompt, as the service read it (left-padded
to its bucket), and its served tokens, and reads how far each served
token's logit lies below the reference's best at that position; it also
holds the service to dropless routing (``moe_pairs_dropped``, prefill and
decode, over the whole run) and every request to its length.

Parameters: as ``chat``.
"""
from __future__ import annotations

import functools
import gc
import math
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import BenchError, Check, Ctx, Window
from bench.reference import deepseek_v2 as reference
from bench.traffic import chat

TOKENS, FAULT, WEIGHTS = chat.TOKENS, chat.FAULT, chat.WEIGHTS
REF_BATCH = 2           # sequences per reference call: its attention and
                        # expert activations (float32) fit beside the
                        # weights and, in the limits probe, the service
DECODE_MODULE = "jit__plan_decode_step"   # the service's tick program
State = chat.State


# -- the model ------------------------------------------------------------


def _sizes(cfg: dict) -> tuple:
    """What ``make_params`` needs, hashable: (layers, dense layers,
    hidden, heads, kv_lora_rank, nope, rope, v dims, dense width, expert
    width, router outputs, held experts, shared experts, vocab)."""
    return (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["reduced"]["n_routed_experts"], cfg["n_routed_experts"],
            cfg["n_shared_experts"], cfg["vocab_size"])


@functools.partial(jax.jit, static_argnames=("sizes",))
def make_params(key, sizes: tuple):
    """The weights, in the layout the service reads, as the configuration
    file's ``weights.init`` states, in bfloat16: one call on the device."""
    (n_l, n_dense, d, h, kr, dn, dr, dv, ff, fe, n_exp, held, n_shared,
     vocab) = sizes
    n_moe = n_l - n_dense
    keys = iter(jax.random.split(key, 32))

    def mat(shape, fan_in):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / math.sqrt(fan_in)).astype(jnp.bfloat16)

    def norm(shape):
        return {"scale": (1.0 + 0.1 * jax.random.normal(
            next(keys), shape, jnp.float32)).astype(jnp.bfloat16)}

    def layers(n, ffn):
        return {
            "ln1": norm((n, d)), "ln2": norm((n, d)),
            "attn": {"wq": {"w": mat((n, d, h * (dn + dr)), d)},
                     "kv_a": {"w": mat((n, d, kr + dr), d)},
                     "kv_ln": norm((n, kr)),
                     "kv_b": {"w": mat((n, kr, h * (dn + dv)), kr)},
                     "wo": {"w": mat((n, h * dv, d), h * dv)}},
            "ffn": ffn(n),
        }

    def dense(n):
        return {"wg": {"w": mat((n, d, ff), d)},
                "wu": {"w": mat((n, d, ff), d)},
                "wd": {"w": mat((n, ff, d), ff)}}

    def experts(n):
        fs = fe * n_shared
        return {"router": {"w": mat((n, d, n_exp), d)},
                "wg": mat((n, held, d, fe), d),
                "wu": mat((n, held, d, fe), d),
                "wd": mat((n, held, fe, d), fe),
                "shared": {"wg": mat((n, d, fs), d),
                           "wu": mat((n, d, fs), d),
                           "wd": mat((n, fs, d), fs)}}

    params = {
        "embed": {"table": mat((vocab, d), d)},
        "layers": layers(n_moe, experts),
        "ln_f": norm((d,)),
        "head": {"w": mat((d, vocab), d)},
    }
    if n_dense:
        params["dense_layers"] = layers(n_dense, dense)
    return params


def model_config(cfg: dict):
    """The service's ``ModelConfig``, every number from the file."""
    from repro.configs.base import (ClusterKVConfig, MLAConfig, ModelConfig,
                                    MoEConfig, YarnConfig)
    svc = cfg["service"]
    rs = cfg["rope_scaling"]
    if (rs["type"] != "yarn" or rs["mscale"] != rs["mscale_all_dim"]
            or cfg["q_lora_rank"] is not None
            or cfg["routed_scaling_factor"] != 1):
        raise BenchError("chat_mla serves YaRN MLA with a direct q "
                         "projection, cos/sin scale 1 and unscaled gates")
    ckv = ClusterKVConfig(enabled=True, embed_dim=svc["embed_dim"],
                          block_q=svc["block_k"], block_k=svc["block_k"],
                          decode_clusters=svc["decode_clusters"],
                          decode_backend=svc["decode_backend"])
    dt = cfg["weights"]["dtype"]
    return ModelConfig(
        name=cfg["name"], family="moe",
        n_layers=cfg["num_hidden_layers"],
        first_k_dense=cfg["first_k_dense_replace"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        mla=MLAConfig(
            q_lora_rank=0, kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"],
            yarn=YarnConfig(
                factor=float(rs["factor"]),
                original_max_position=rs["original_max_position_embeddings"],
                beta_fast=float(rs["beta_fast"]),
                beta_slow=float(rs["beta_slow"]),
                mscale_all_dim=float(rs["mscale_all_dim"]))),
        moe=MoEConfig(
            n_experts=cfg["reduced"]["n_routed_experts"],
            top_k=cfg["num_experts_per_tok"],
            d_ff_expert=cfg["moe_intermediate_size"],
            n_shared_experts=cfg["n_shared_experts"],
            norm_topk_prob=cfg["norm_topk_prob"],
            experts_held=cfg["n_routed_experts"],
            first_held=cfg["deployment"]["first_expert"]),
        dtype=dt, param_dtype=dt, clusterkv=ckv, remat=False)


def reference_sizes(cfg: dict) -> reference.Sizes:
    rs = cfg["rope_scaling"]
    return reference.Sizes(
        heads=cfg["num_attention_heads"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rms_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(cfg["rope_theta"]),
        yarn=(float(rs["factor"]), rs["original_max_position_embeddings"],
              float(rs["beta_fast"]), float(rs["beta_slow"]),
              float(rs["mscale"]), float(rs["mscale_all_dim"])),
        top_k=cfg["num_experts_per_tok"],
        first_held=cfg["deployment"]["first_expert"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]))


# -- set-up ----------------------------------------------------------------


def setup(ctx: Ctx) -> State:
    from repro.serve.engine import ClusterKVEngine
    from repro.train.serve_loop import Request
    cfg, p = ctx.config, ctx.params
    mcfg = model_config(cfg)
    params = make_params(ctx.key(WEIGHTS), _sizes(cfg))
    chat._same_layout(mcfg, params)
    jax.block_until_ready(params)
    eng = ClusterKVEngine(mcfg, params, slots=p["slots"],
                          max_seq=p["max_seq"], prefill_bucket=p["bucket"],
                          mode=cfg["service"]["mode"],
                          knn=cfg["service"]["knn"])
    fault = chat._plant(ctx, eng)
    # one request per prefill bucket the mix can reach, every slot busy
    lo = chat._bucket(p["prompt"]["min"], p["bucket"])
    hi = chat._bucket(p["prompt"]["max"], p["bucket"])
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
            f"heads={mcfg.n_heads} latent={eng.dh} experts "
            f"{mcfg.moe.held}/{mcfg.moe.n_experts} held, top-"
            f"{mcfg.moe.top_k}; vocab={mcfg.vocab}; {p['slots']} slots, "
            f"max_seq {p['max_seq']}, buckets {lens}; warm-up served "
            f"{len(warm)} requests, decode traces {rep['decode_traces']}")
    return State(engine=eng, params=params,
                 info={"decode_module": DECODE_MODULE}, fault=fault)


# -- the window --------------------------------------------------------------


def window(ctx: Ctx, st: State, seconds: float) -> Window:
    """``chat``'s window, with the service's counters read around it."""
    before = st.engine.counters()
    win = chat.window(ctx, st, seconds)
    after = st.engine.counters()
    st.info.update({k: v - before.get(k, 0) for k, v in after.items()})
    st.info["dropped_total"] = (after["moe_pairs_dropped"]
                                + after.get("prefill_moe_pairs_dropped", 0))
    ctx.say("counters in the window: " + ", ".join(
        f"{k} {v}" for k, v in st.info.items() if isinstance(v, int)))
    return win


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
            + (f" (control {ctx.control}: the gap of its own token)"
               if quant else ""))
    return [Check("logit_gap", gap, ctx.limits["logit_gap"]),
            Check("wrong_length", float(wrong), ctx.limits["wrong_length"]),
            Check("moe_pairs_dropped", float(st.info["dropped_total"]),
                  ctx.limits["moe_pairs_dropped"])]


def widest_gaps(ctx: Ctx, params, served, quants=("",)) -> Dict[str, float]:
    """For each ``quant`` in ``quants``: the widest gap by which a token
    lies below the float32 reference's best logit at its position. With
    ``""`` the token is the one served; with a control's name it is the
    one that control puts first at the same position."""
    cfg, p = ctx.config, ctx.params
    if not served:
        return {q: math.inf for q in quants}
    width = chat._bucket(chat._bucket(p["prompt"]["max"], p["bucket"])
                         + p["output"]["max"], 128)
    m = p["output"]["max"]
    sizes = reference_sizes(cfg)
    widest = {q: 0.0 for q in quants}
    for lo in range(0, len(served), REF_BATCH):
        part = served[lo:lo + REF_BATCH]
        tokens = np.zeros((REF_BATCH, width), np.int32)
        rows = np.zeros((REF_BATCH, m), np.int32)
        want = np.zeros((REF_BATCH, m), np.int32)
        valid = np.zeros((REF_BATCH, m), bool)
        for b, r in enumerate(part):
            blen = chat._bucket(len(r.tokens), p["bucket"])
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
            **{f"logit_gap.{c}": gaps[c] for c in controls},
            "moe_pairs_dropped": st.info["dropped_total"]}


def reseed(ctx: Ctx, st: State) -> None:
    """New weights from ``ctx.seed`` in the running service (the probe's
    next seed). The old weights go first: two sets do not fit beside the
    service's state."""
    st.params = st.engine.params = None
    gc.collect()
    st.params = make_params(ctx.key(WEIGHTS), _sizes(ctx.config))
    st.engine.params = st.params
