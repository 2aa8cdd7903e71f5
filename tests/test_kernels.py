"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core.blocksparse import random_bsr
from repro.core.interact import spmv_bsr_ml_batched
from repro.kernels import ops, ref
from repro.kernels.block_attention import block_attention as ba_kernel
from repro.kernels.bsr_spmv import bsr_spmv_batched as batch_kernel
from repro.kernels.bsr_spmv import panel_chunk
from repro.kernels.gamma_score import gamma_pairs


@pytest.mark.parametrize("n,bs,nbr,f", [
    (256, 16, 3, 1), (512, 32, 5, 4), (512, 64, 2, 8), (256, 128, 2, 2),
])
def test_bsr_spmv_shapes(n, bs, nbr, f):
    bsr = random_bsr(n * bs, n, bs, nbr)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((n, f)), jnp.float32)
    pad = bsr.n_rb * bs - n
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    got = batch_kernel(bsr.vals[None], bsr.col_idx[None], xp[None],
                       interpret=True)[0]
    want = ref.bsr_spmv_ref(bsr.vals, bsr.col_idx, xp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bsr_spmv_dtypes(dtype):
    bsr = random_bsr(11, 256, 32, 4)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((256, 2)), jnp.float32).astype(dtype)
    got = ops.bsr_spmv(bsr.vals, bsr.col_idx, x, 256)
    want = ref.bsr_spmv_ref(bsr.vals, bsr.col_idx,
                            x.astype(jnp.float32))[:256]
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("S,dh,bq,bk,nsel,causal", [
    (128, 16, 16, 16, 3, True),
    (256, 32, 32, 32, 4, True),
    (256, 64, 64, 32, 2, False),
    (128, 32, 16, 32, 4, True),
])
def test_block_attention_shapes(S, dh, bq, bk, nsel, causal):
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((S, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((S, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((S, dh)), jnp.float32)
    kpos = jnp.asarray(rng.permutation(S), jnp.int32)
    qpos = jnp.arange(S, dtype=jnp.int32)
    idx = jnp.asarray(rng.integers(0, S // bk, (S // bq, nsel)), jnp.int32)
    got = ba_kernel(q, k, v, kpos, qpos, idx, bq=bq, bk=bk, causal=causal,
                    interpret=True)
    want = ref.block_attention_ref(q, k, v, kpos, qpos, idx, bq=bq, bk=bk,
                                   causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_block_attention_batched_wrapper_matches_core():
    """ops.block_attention (vmapped kernel) == core.clusterkv reference."""
    from repro.core import clusterkv as ckv
    rng = np.random.default_rng(3)
    B, Hq, Hkv, S, dh, bq, bk, nsel = 2, 4, 2, 128, 16, 32, 32, 3
    q = jnp.asarray(rng.standard_normal((B, Hq, S, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, Hkv, S, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, Hkv, S, dh)), jnp.float32)
    kpos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, Hkv, S))
    qpos = jnp.arange(S, dtype=jnp.int32)
    idx = jnp.asarray(rng.integers(0, S // bk, (B, Hkv, S // bq, nsel)),
                      jnp.int32)
    got = ops.block_attention(q, k, v, kpos, qpos, idx, bq=bq, bk=bk)
    want = ckv.sparse_block_attention(q, k, v, kpos, qpos, idx, bq, bk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("nnz,bn", [(128, 64), (300, 128), (512, 256)])
def test_gamma_pairs_shapes(nnz, bn):
    rng = np.random.default_rng(4)
    coords = jnp.asarray(rng.integers(0, 100, (nnz, 2)), jnp.float32)
    pad = (-nnz) % bn
    if pad:
        far = jnp.full((pad, 2), 1e9) + jnp.arange(pad)[:, None] * 1e6
        padded = jnp.concatenate([coords, far.astype(jnp.float32)])
    else:
        padded = coords
    got = float(gamma_pairs(padded, 7.0, bn, interpret=True)) - pad
    want = float(ref.gamma_pairs_ref(coords, 7.0))
    assert got == pytest.approx(want, rel=1e-4)


# -- batch-grid kernel: edge shapes, all matching bsr_ml batched ------------
#
# The kernel contracts a row block's chunk of ELL slots as one panel matmul
# on the MXU, where the XLA ``bsr_ml`` path sums every slot in one
# batch_matmul: the same products, associated differently. float32 rounds
# each add at ~6e-8 relative, so with a handful of O(1) slots the two agree
# to ~1e-6; 1e-5 is the bound (bitwise parity would forbid the chip's form).
_TOL = dict(rtol=1e-5, atol=1e-5)


def _random_batch(B, n_cb, bs, nbr, seed=0):
    vals, idxs = [], []
    for b in range(B):
        bsr = random_bsr(seed + b, n_cb * bs, bs, nbr)
        vals.append(np.asarray(bsr.vals))
        idxs.append(np.asarray(bsr.col_idx))
    return (jnp.asarray(np.stack(vals), jnp.float32),
            jnp.asarray(np.stack(idxs), jnp.int32))


@pytest.mark.parametrize("B,n_cb,bs,nbr,f,rbs,fct,chunk", [
    (1, 8, 16, 4, 1, 1, None, 3),     # degenerate single member
    (3, 8, 16, 4, 1, 4, None, 3),     # row-superblocked, scalar charges
    (3, 8, 16, 4, 3, 2, 2, 3),        # f not a multiple of the feature tile
    (2, 8, 16, 4, 5, 3, 4, 3),        # rbs not dividing n_rb (row padding)
    (2, 8, 32, 5, 300, 2, 1, 3),      # three 128-lane feature tiles
    (2, 16, 32, 10, 4, 2, None, 3),   # 3*32 lanes: chunk 3 -> 4, nbr 10 -> 12
    (1, 32, 16, 20, 2, 1, None, 5),   # 5*16 lanes: chunk 5 -> 8, nbr 20 -> 24
    (2, 8, 16, 4, 3, 2, None, None),  # whole-dim panel, 4*16 < 128 lanes
    (2, 8, 32, 3, 1, 1, None, None),  # whole-dim panel, 3*32 < 128 lanes
    (1, 8, 64, 6, 5, 2, None, 1),     # bs 64: chunk 1 -> 2, three chunks
    (2, 8, 16, 4, 257, 1, 1, None),   # whole-dim panel, three f tiles
    (1, 4, 64, 1, 1, 2, None, None),  # bs 64, one slot, f = 1
])
def test_batch_kernel_matches_bsr_ml(B, n_cb, bs, nbr, f, rbs, fct, chunk):
    """``fct`` counts 128-lane feature tiles (None: the default tile);
    ``chunk`` is the requested slot chunk, which the lane rule rounds."""
    vals, col_idx = _random_batch(B, n_cb, bs, nbr)
    rng = np.random.default_rng(9)
    shape = (B, n_cb * bs) if f == 1 else (B, n_cb * bs, f)
    xs = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    fc = 128 * (fct or 1)
    got = batch_kernel(vals, col_idx, xs, rbs=rbs, chunk=chunk, fc=fc,
                       interpret=True)
    want = spmv_bsr_ml_batched(vals, col_idx, xs, 8)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_TOL)


@pytest.mark.parametrize("nbr,bs,chunk,want", [
    (158, 32, None, 158),   # the whole ELL width: a whole-dim block
    (158, 32, 80, 80),      # 80 * 32 lanes: already whole lane tiles
    (158, 32, 3, 4),        # rounded up to one 128-lane column
    (4, 16, 3, 4),          # 8 slots would pass the width: whole-dim
    (20, 16, 5, 8),
    (6, 64, 1, 2),
    (3, 128, 1, 1),
])
def test_panel_chunk_lane_rule(nbr, bs, chunk, want):
    got = panel_chunk(nbr, bs, chunk)
    assert got == want
    assert got == nbr or got * bs % 128 == 0


def _holey_batch():
    """Pow2-padded capacity with interleaved streaming holes and ELL
    padding slots (ell_slack widens max_nbr beyond the live columns)."""
    rng = np.random.default_rng(3)
    xs = [rng.standard_normal((120, 8)).astype(np.float32)
          for _ in range(3)]
    pb = api.build_plan_batch(xs, k=8, bs=16, sb=4, backend="bsr",
                              ell_slack=4, capacity=128)
    kills = [rng.choice(120, 17, replace=False) for _ in range(3)]
    return pb.delete(kills)


def test_batch_backend_holes_and_padding_bit_match():
    pb = _holey_batch()
    rng = np.random.default_rng(4)
    for shape in [(pb.batch, pb.capacity), (pb.batch, pb.capacity, 3)]:
        xs = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        want = api._batch_apply_kernel(pb.spec, pb.data, xs, "bsr_ml",
                                       "apply")
        got = api._batch_apply_kernel(pb.spec, pb.data, xs, "pallas",
                                      "apply")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_TOL)


def test_single_plan_pallas_dead_slots_stay_zero():
    """The pallas single-plan backend handles capacity-padded plans with
    streaming holes: dead-slot rows carry zero tiles, so their output rows
    must be exactly zero (and live rows must match the bsr path)."""
    rng = np.random.default_rng(5)
    x = rng.standard_normal((120, 8)).astype(np.float32)
    plan = api.build_plan(jnp.asarray(x), k=8, bs=16, sb=4, backend="bsr",
                          capacity=128)
    plan = plan.delete(rng.choice(120, 13, replace=False))
    for shape in [(plan.n,), (plan.n, 4)]:
        q = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        y_pl = np.asarray(plan.apply(q, backend="pallas"))
        y_ref = np.asarray(plan.apply(q, backend="bsr"))
        np.testing.assert_allclose(y_pl, y_ref, rtol=1e-5, atol=1e-5)
        dead = ~plan.permute(plan.alive)
        assert dead.any()
        assert not np.any(y_pl[dead])            # exactly zero, no residue


def test_batched_pallas_64_members_one_kernel():
    """64-member PlanBatch matvec: ONE compiled kernel (trace-counted),
    matching the bsr_ml batched backend to float32 rounding."""
    rng = np.random.default_rng(6)
    xs = [rng.standard_normal((64, 8)).astype(np.float32)
          for _ in range(64)]
    pb = api.build_plan_batch(xs, k=6, bs=16, sb=4, backend="bsr")
    x = jnp.asarray(rng.standard_normal((64, pb.capacity)), jnp.float32)
    ops.PALLAS_TRACE_COUNTS["batched"] = 0
    got = pb.matvec(x, backend="pallas")
    for _ in range(2):                           # re-dispatch, no re-trace
        got = pb.matvec(x, backend="pallas")
    assert ops.PALLAS_TRACE_COUNTS["batched"] == 1
    want = pb.matvec(x, backend="bsr_ml")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_TOL)


@pytest.mark.parametrize("n,bs,k,d", [(256, 16, 6, 2), (512, 32, 10, 3)])
def test_tsne_force_kernel(n, bs, k, d):
    """Kernel vs jnp oracle vs core.interact blockwise path."""
    from repro.core import blocksparse, interact
    rng = np.random.default_rng(0)
    rows = np.repeat(np.arange(n), k)
    cols = rng.integers(0, n, n * k)
    key = rows.astype(np.int64) * n + cols
    _, first = np.unique(key, return_index=True)
    rows, cols = rows[first], cols[first]
    pv = rng.random(len(rows)).astype(np.float32)
    bsr = blocksparse.build_bsr(rows, cols, pv, n, bs=bs)
    y = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    got = ops.tsne_force(bsr.vals, bsr.col_idx, y, n)
    want_core = interact.tsne_attractive(bsr.vals, bsr.col_idx,
                                         bsr.nbr_mask, y, n)
    yp = jnp.pad(y, ((0, bsr.n_rb * bs - n), (0, 0)))
    want_ref = ref.tsne_force_ref(bsr.vals, bsr.col_idx, yp)[:n]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_core),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# fused decode attention (kernels/decode_attend.py)
# ---------------------------------------------------------------------------
#
# The references are the JITTED pure-JAX ops the decode service would
# otherwise run. Both attend the same selected tiles (the selection is
# shared code); the kernel folds them into an online softmax tile by tile
# where the reference takes one softmax over the concatenated selection, so
# the float32 results agree to rounding (~1e-6 here; bound 1e-5) and a
# bfloat16 output to one rounding of the output dtype (2^-8 relative).


def _assert_decode_close(got, want):
    assert got.dtype == want.dtype
    tol = 1e-5 if got.dtype == jnp.float32 else 2 ** -7
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def _plain_decode_case(seed, B, hq, hkv, S, dh, bk, dtype):
    from repro.core import clusterkv as ckv
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, hq, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, hkv, S, dh)),
                    jnp.float32).astype(dtype)
    v = jnp.asarray(rng.standard_normal((B, hkv, S, dh)),
                    jnp.float32).astype(dtype)
    pos = jnp.asarray(np.stack([np.stack([rng.permutation(S)
                                          for _ in range(hkv)])
                                for _ in range(B)]), jnp.int32)
    cent = ckv.block_centroids(k, bk)
    return q, k, v, pos, cent


@pytest.mark.parametrize("hq,hkv,dtype", [
    (1, 1, jnp.float32),           # g == 1: the strength-reduction trap
    (4, 2, jnp.float32),
    (8, 2, jnp.float32),
    (4, 4, jnp.bfloat16),          # g == 1 again, bf16 cache
    (6, 2, jnp.bfloat16),
])
def test_decode_fused_bitwise_plain(hq, hkv, dtype):
    """Fused kernel == jitted decode_select + decode_attend."""
    from repro.core import clusterkv as ckv
    S, dh, bk, n_sel = 128, 32, 32, 2
    q, k, v, pos, cent = _plain_decode_case(11, 2, hq, hkv, S, dh, bk,
                                            dtype)
    for qpos in (S - 1, S // 3):
        got = ops.decode_attend_fused(q, k, v, pos, cent, qpos,
                                      n_sel=n_sel, bk=bk)
        idx = ckv.decode_select(q, cent.astype(jnp.float32), n_sel)
        want = ckv.decode_attend(q, k, v, pos, qpos, idx, bk)
        _assert_decode_close(got, want)


@pytest.mark.parametrize("hq,hkv,has_self", [
    (2, 2, True),                  # g == 1
    (2, 2, False),
    (4, 2, True),
    (8, 2, False),
    (8, 1, True),
])
def test_decode_fused_bitwise_plan_holey(hq, hkv, has_self):
    """Plan mode vs the jitted xla decode backend over capacity-padded
    caches: hole slots (pos == INT32_MAX) carry garbage k/v and must be
    invisible; the self column must ride along untouched."""
    import functools

    from repro.configs.base import ClusterKVConfig
    from repro.models import attention as attn

    B, S, dh, bk = 3, 128, 32, 32
    cfg = ClusterKVConfig(enabled=True, block_k=bk, decode_clusters=2,
                          decode_backend="pallas")
    rng = np.random.default_rng(13)
    big = np.iinfo(np.int32).max
    q = jnp.asarray(rng.standard_normal((B, hq, dh)), jnp.bfloat16)
    ks = jnp.asarray(rng.standard_normal((B, hkv, S, dh)), jnp.bfloat16)
    vs = jnp.asarray(rng.standard_normal((B, hkv, S, dh)), jnp.bfloat16)
    qpos = jnp.asarray(rng.integers(8, 96, (B,)), jnp.int32)
    ps = np.full((B, hkv, S), big, np.int64)
    for b in range(B):
        live = int(qpos[b])                  # plan rows streamed so far
        for h in range(hkv):
            rows = rng.choice(S, live, replace=False)
            ps[b, h, rows] = rng.permutation(live)
    ps = jnp.asarray(ps, jnp.int32)
    from repro.core import clusterkv as ckv
    cent = ckv.block_centroids(ks.astype(jnp.float32), bk)
    k_self = jnp.asarray(rng.standard_normal((B, hkv, dh)), jnp.bfloat16)
    v_self = jnp.asarray(rng.standard_normal((B, hkv, dh)), jnp.bfloat16)

    ref = jax.jit(functools.partial(attn._plan_decode_xla, cfg=cfg))
    if has_self:
        want = ref(q, ks, vs, ps, cent, qpos, k_self=k_self, v_self=v_self)
        got = attn.clusterkv_plan_decode(q, ks, vs, ps, cent, qpos, cfg,
                                         k_self=k_self, v_self=v_self)
    else:
        want = ref(q, ks, vs, ps, cent, qpos)
        got = attn.clusterkv_plan_decode(q, ks, vs, ps, cent, qpos, cfg)
    _assert_decode_close(got, want)


def test_decode_fused_one_trace():
    """Re-dispatching the fused decode at a fixed shape must not re-trace
    (the serve tick calls it every token)."""
    q, k, v, pos, cent = _plain_decode_case(17, 2, 4, 2, 128, 32, 32,
                                            jnp.float32)

    @jax.jit
    def tick(q, k, v, pos, cent, qpos):
        return ops.decode_attend_fused(q, k, v, pos, cent, qpos,
                                       n_sel=2, bk=32)

    ops.PALLAS_TRACE_COUNTS["decode"] = 0
    for qpos in (40, 50, 60):                # dynamic arg, same shape
        tick(q, k, v, pos, cent, jnp.full((2,), qpos, jnp.int32)
             ).block_until_ready()
    assert ops.PALLAS_TRACE_COUNTS["decode"] == 1


def test_decode_fused_rejects_ragged_cache():
    q, k, v, pos, cent = _plain_decode_case(19, 1, 2, 1, 128, 32, 32,
                                            jnp.float32)
    with pytest.raises(ValueError, match="whole"):
        ops.decode_attend_fused(q, k[:, :, :100], v[:, :, :100],
                                pos[:, :, :100], cent, 99, n_sel=2, bk=32)
