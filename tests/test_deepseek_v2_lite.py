"""DeepSeek-V2-Lite on the ClusterKV plan service, at CPU size (``REDUCED``:
one dense layer then three MoE layers, 8 routed experts of which 4 are
held, YaRN on).

The plain reference is ``bench/reference/deepseek_v2.py`` (imported by
file path: it imports nothing of the program), the float32 forward the
benchmark checks the chip against."""
import dataclasses
import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ClusterKVConfig
from repro.configs.deepseek_v2_lite import CONFIG, REDUCED
from repro.core import costmodel
from repro.models import mla, model_api, moe
from repro.models.sharding import NO_SHARD
from repro.serve import ClusterKVEngine
from repro.train.serve_loop import Request

ROOT = Path(__file__).resolve().parents[1]
MAX_SEQ = 128   # block_k 16 -> 8 tiles; decode_clusters 8 selects them all,
                # so the sparse plan decode is exact against a dense forward


def _reference():
    spec = importlib.util.spec_from_file_location(
        "deepseek_v2_reference", ROOT / "bench" / "reference" / "deepseek_v2.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _sizes(cfg):
    m, y, e = cfg.mla, cfg.mla.yarn, cfg.moe
    return REF.Sizes(cfg.n_heads, m.kv_lora_rank, m.qk_nope_head_dim,
                     m.qk_rope_head_dim, m.v_head_dim, cfg.norm_eps,
                     cfg.rope_theta,
                     (y.factor, y.original_max_position, y.beta_fast,
                      y.beta_slow, y.mscale_all_dim, y.mscale_all_dim),
                     e.top_k, e.first_held, e.norm_topk_prob, 1.0)


@pytest.fixture(scope="module")
def setup():
    # float32 on both sides: the comparisons below are of logits, and the
    # program's own rounding should be all that separates them
    cfg = REDUCED.with_(
        dtype="float32", param_dtype="float32",
        clusterkv=ClusterKVConfig(enabled=True, block_q=16, block_k=16,
                                  decode_clusters=8))
    params, _ = model_api.init(cfg, jax.random.PRNGKey(1))
    return cfg, params


def _requests(lengths, max_new):
    rng = np.random.default_rng(0)
    return [Request(rid=i, tokens=rng.integers(0, REDUCED.vocab, n)
                    .astype(np.int32), max_new=m)
            for i, (n, m) in enumerate(zip(lengths, max_new))]


def _serve(cfg, params, reqs, **kw):
    eng = ClusterKVEngine(cfg, params, slots=2, max_seq=MAX_SEQ,
                          prefill_bucket=16, mode="plan", knn=4, **kw)
    seen = []                        # (request ids, positions, logits)
    decode = eng._plan_decode

    def record(params, pstate, pend, tokens, slot_pos):
        out = decode(params, pstate, pend, tokens, slot_pos)
        seen.append(([None if r is None else r.rid for r in eng.slot_req],
                     np.array(slot_pos), np.asarray(out[0])))
        return out
    eng._plan_decode = record
    for r in reqs:
        eng.submit(r)
    while eng.queue or any(r is not None for r in eng.slot_req):
        eng.step()
        eng._retire()
    return eng, seen


def _ref_logits(cfg, params, req, bucket=16):
    """The reference's logits at every position that produced one of the
    request's tokens, over the prompt as the service read it."""
    blen = -(-len(req.tokens) // bucket) * bucket
    out = np.asarray(req.output, np.int32)
    toks = np.zeros((1, blen + len(out)), np.int32)
    toks[0, blen - len(req.tokens):blen] = req.tokens
    toks[0, blen:blen + len(out) - 1] = out[:-1]
    rows = (blen - 1 + np.arange(len(out)))[None]
    with jax.default_matmul_precision("float32"):
        return blen, np.asarray(REF.logits_at(
            params, jnp.asarray(toks), jnp.asarray(rows),
            sizes=_sizes(cfg)))[0]


def test_engine_serves_mla_in_plan_mode(setup):
    """The plan service takes an MLA model: one latent plan per layer
    (Hkv 1, rows kv_lora_rank + rope dims wide), no value copy, one decode
    trace, and its counters: nothing dropped, every tick counted."""
    cfg, params = setup
    reqs = _requests([20, 33, 9], [10, 7, 12])
    eng, _ = _serve(cfg, params, reqs)
    assert (eng.Hkv, eng.dh) == (1, 32 + 8)
    assert "vs" not in eng.pstate
    # stored zero-padded to a multiple of 128 columns (mla.row_width)
    assert eng.pstate["ks"].shape == (cfg.n_layers, 2, 1, MAX_SEQ, 128)
    assert not np.asarray(eng.pstate["ks"][..., 40:]).any()
    assert eng.report()["decode_traces"] == 1
    c = eng.counters()
    decoded = sum(len(r.output) - 1 for r in reqs)
    moe_layers = cfg.n_layers - cfg.first_k_dense
    assert c["decode_ticks"] == eng.ticks
    assert c["moe_pairs_total"] == decoded * cfg.moe.top_k * moe_layers
    assert 0 < c["moe_pairs_local"] < c["moe_pairs_total"]
    assert 0 < c["moe_experts_read"] <= eng.ticks * moe_layers * cfg.moe.held
    assert c["moe_pairs_dropped"] == 0
    assert c["prefill_moe_pairs_dropped"] == 0
    # every context is under 8 tiles, so each active token attends all its
    # live rows: positions 0..pos-1 (its own row rides as the extra column)
    assert c["decode_rows_attended"] > decoded * cfg.n_layers * 16


def test_plan_decode_matches_reference_logits(setup):
    """Prefill, then plan-mode decode through the engine, against the
    plain reference's full forward, on logits.

    Tolerance 2e-4 absolute on logits of magnitude ~1: both sides run in
    float32; what separates them is summation order (absorbed latent
    attention against expanded K/V, the MoE's dispatch against a dense sum
    over held experts), ~3e-6 here."""
    cfg, params = setup
    reqs = _requests([20, 33, 9], [10, 7, 12])
    _, seen = _serve(cfg, params, reqs)
    for r in reqs:
        blen, ref = _ref_logits(cfg, params, r)
        np.testing.assert_array_equal(ref.argmax(-1), r.output)
        # row j of ref is the logits at position blen - 1 + j
        got = {int(pos[s]): logits[s] for rids, pos, logits in seen
               for s, rid in enumerate(rids) if rid == r.rid}
        assert sorted(got) == list(range(blen, blen + len(r.output) - 1))
        np.testing.assert_allclose(
            np.stack([got[q] for q in sorted(got)]), ref[1:], atol=2e-4,
            rtol=0)


def test_plan_prefill_matches_plain_prefill(setup):
    """``plan_prefill`` prefills through the latent plans (one order per
    layer, shared by every head's expanded keys); with every tile
    selected its first tokens equal the plain prefill's."""
    cfg, params = setup
    plain = _requests([20, 33], [4, 4])
    _serve(cfg, params, plain)
    planned = _requests([20, 33], [4, 4])
    _serve(cfg, params, planned, plan_prefill=True)
    assert [r.output for r in plain] == [r.output for r in planned]


def test_absorbed_latent_decode_matches_expanded(setup):
    """The tick's absorbed attention over plan-ordered latent rows (rows
    shuffled, holes, the token's own row as the extra column) equals
    expanded-K/V attention at the last position. Tolerance 1e-5: float32,
    only the summation order differs."""
    cfg, params = setup
    lp = jax.tree.map(lambda a: a[1], params["layers"])["attn"]
    b, s, s_cap = 2, 23, 32
    x = jax.random.normal(jax.random.PRNGKey(3), (b, s, cfg.d_model))
    pos = jnp.arange(s, dtype=jnp.int32)
    full = mla.mla_attention(lp, x, pos, cfg, NO_SHARD, "dense")[:, -1]
    cn, kr = mla._kv_latent(lp, x[:, :-1], cfg, pos[:-1])
    lat = jnp.concatenate([cn, kr], -1)                  # (b, s-1, R)
    order = np.random.default_rng(0).permutation(s_cap)  # plan order
    phys = np.where(order < s - 1, order, 0)
    rows = jnp.where((order < s - 1)[None, :, None], lat[:, phys], 0.0)
    ps = jnp.asarray(np.where(order < s - 1, order,
                              np.iinfo(np.int32).max), jnp.int32)
    ps = jnp.broadcast_to(ps, (b, 1, s_cap))
    bk = 16
    cent = rows.reshape(b, 1, s_cap // bk, bk, -1).mean(3)
    qpos = jnp.full((b,), s - 1, jnp.int32)
    out, _, _ = mla.latent_attention(lp, x[:, -1:], rows[:, None], ps,
                                     cent, qpos, cfg)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(full),
                               atol=1e-5, rtol=1e-5)


def test_yarn_inv_freq_and_softmax_scale():
    """YaRN at the published numbers (64 rope dims, theta 1e4, factor 40
    over 4,096 positions, beta 32/1): the correction range is dims 10..23,
    plain frequencies below it, ``1/40`` of them above, a linear ramp
    between; softmax scale ``192^-0.5 * (0.1 * 0.707 * ln 40 + 1)^2``."""
    inv = mla.rope_inv_freq(CONFIG)
    plain = 1e4 ** (-np.arange(0, 64, 2) / 64)
    assert inv.shape == (32,)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    ramp = 6 / 13                                      # dim 16 of 10..23
    np.testing.assert_allclose(inv[16], 0.01 * (1 - ramp) + 0.01 / 40 * ramp,
                               rtol=1e-6)
    np.testing.assert_allclose(inv[31], 10 ** -3.875 / 40, rtol=1e-6)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert mla.q_factor(CONFIG) == pytest.approx(mscale ** 2)
    assert mla.q_factor(CONFIG) == pytest.approx(1.589627, abs=1e-6)
    np.testing.assert_allclose(REF.yarn_inv_freq(64, 1e4, (40, 4096, 32, 1,
                                                           0.707, 0.707)),
                               inv, rtol=1e-6)
    # the program's rope turns each half-pair by position * inv_freq
    x = jnp.zeros((1, 1, 64)).at[0, 0, 16].set(1.0)
    y = mla._rope(x, jnp.asarray([7]), CONFIG)
    np.testing.assert_allclose(
        [float(y[0, 0, 16]), float(y[0, 0, 48])],
        [math.cos(7 * inv[16]), math.sin(7 * inv[16])], atol=1e-6)


def _moe_cfg(held, first):
    return REDUCED.with_(dtype="float32", moe=dataclasses.replace(
        REDUCED.moe, experts_held=held, first_held=first))


def _moe_params(seed=0):
    uncut = _moe_cfg(0, 0)
    p, _ = moe.init_moe(jax.random.PRNGKey(seed), uncut)
    return uncut, p


def _share(p, first, held):
    return dict(p, wg=p["wg"][first:first + held], wu=p["wu"][first:first + held],
                wd=p["wd"][first:first + held])


def test_expert_shares_sum_to_the_uncut_layer():
    """The share test: each chip's part of the MoE layer (its held
    experts' outputs for the tokens routed to them, plus the shared
    experts every chip computes alike) summed over all shares, with the
    shared experts counted once, is the uncut layer."""
    uncut, p = _moe_params()
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 7, uncut.d_model))
    whole, st = moe.moe_serve(p, x, uncut)
    shared = moe._shared(p, x.reshape(-1, uncut.d_model)).reshape(x.shape)
    parts = []
    for first in (0, 4):
        cfg = _moe_cfg(4, first)
        y, s = moe.moe_serve(_share(p, first, 4), x, cfg)
        parts.append((y, s))
    total = sum(y for y, _ in parts) - (len(parts) - 1) * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=1e-5, rtol=1e-5)
    names = moe.MOE_STATS
    local = [int(s[names.index("moe_pairs_local")]) for _, s in parts]
    assert sum(local) == int(st[names.index("moe_pairs_total")]) == 21 * 2
    assert all(int(s[names.index("moe_pairs_dropped")]) == 0 for _, s in parts)


def test_dropless_when_every_token_picks_one_expert():
    """Every token routes to expert 0 (and, top-2, to expert 1): the
    training path's capacity, ceil(T k / E * 1.25) = 5 of 16, would drop
    most of them; the serving layer computes every pair. The gates are the
    raw top-k probabilities: ``norm_topk_prob`` is false, so a layer that
    renormalised them would differ."""
    cfg = _moe_cfg(4, 0)
    _, p = _moe_params(1)
    p = _share(p, 0, 4)
    d = cfg.d_model
    w = jnp.zeros((d, cfg.moe.n_experts)).at[:, 0].set(0.02)
    p = dict(p, router={"w": w})
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (16, d))) + 0.1
    y, st = moe.moe_serve(p, x, cfg)
    probs = jax.nn.softmax(x @ w, axis=-1)
    assert float(probs[:, :2].sum(-1).max()) < 0.999   # renorm would show

    def mlp(e, v):
        h = jax.nn.silu(v @ p["wg"][e]) * (v @ p["wu"][e])
        return h @ p["wd"][e]
    want = probs[:, :1] * mlp(0, x) + probs[:, 1:2] * mlp(1, x) \
        + moe._shared(p, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5,
                               rtol=1e-5)
    names = moe.MOE_STATS
    assert int(st[names.index("moe_pairs_local")]) == 32
    assert int(st[names.index("moe_pairs_dropped")]) == 0
    assert int(st[names.index("moe_experts_read")]) == 2


def test_shared_latent_rows_take_the_xla_decode():
    """Values read off the key rows (one latent array) rank only the XLA
    decode: the fused kernel takes keys and values as two arrays."""
    feat = costmodel.DecodeFeatures(batch=16, hq=16, hkv=1, s=2048, dh=576,
                                    dv=512, bk=128, n_sel=16, shared_kv=True)
    assert costmodel.choose_decode_backend(feat, interpret=False) == "xla"
    rank = costmodel.rank_decode_backends(feat, interpret=False)
    assert rank["ranking"] == ["xla"]


def test_config_is_the_published_model_with_the_expert_share():
    """CONFIG: 27 layers (1 dense), every published width, the router's 64
    outputs, top-6 unnormalised, and 8 held experts: 3.11 B parameters."""
    shapes = jax.eval_shape(lambda k: model_api.init(CONFIG, k)[0],
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert n == 3_110_989_312
    ffn = shapes["layers"]["ffn"]
    assert ffn["router"]["w"].shape == (26, 2048, 64)
    assert ffn["wg"].shape == (26, 8, 2048, 1408)
    assert ffn["shared"]["wg"].shape == (26, 2048, 2816)
    assert shapes["dense_layers"]["ffn"]["wg"]["w"].shape == (1, 2048, 10944)
    assert shapes["layers"]["attn"]["wq"]["w"].shape == (26, 2048, 16 * 192)
    assert shapes["layers"]["attn"]["kv_a"]["w"].shape == (26, 2048, 576)
    assert mla.latent_dim(CONFIG) == 576


def test_counters_stay_exact_past_int32():
    """The tick's counters are two int32 words each: 3,000 ticks of
    900,000 attended rows (past 2^31) read back exactly."""
    stats = jnp.zeros((len(mla.COUNTERS), 2), jnp.int32)
    inc = jnp.asarray([5, 7, 9, 0, 900_000, 1], jnp.int32)
    step = jax.jit(mla.bump_counters)
    for _ in range(3000):
        stats = step(stats, inc)
    got = mla.read_counters(stats)
    assert got["decode_rows_attended"] == 2_700_000_000 > 2 ** 31
    assert got["decode_ticks"] == 3000
    assert got["moe_experts_read"] == 15_000
