"""Iterative solvers on the plan operator (ISSUE 10): batched CG with
telemetry, block-Jacobi preconditioning sliced from the plan's own BSR
tiles, KRR fit/predict, Lanczos eigensolves, and spectral embedding —
verified against dense references across single plans, PlanBatch
lockstep, sharded operators, and streamed plans mid-lifecycle.

Runs on any device count (1 under plain pytest, 8 under the CI
``multidevice`` job) — the sharded-CG leg exercises whatever mesh the
process has.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import registry
from repro.data.pipeline import feature_mixture
from repro.solvers import (RBFValues, cg, krr_fit, krr_fit_batch,
                           lanczos_eigsh, normalized_operator, redress_rbf,
                           solve, spectral_embedding)
from repro.solvers.precond import (block_jacobi, diag_tiles, diag_vector,
                                   jacobi)

N, D, K = 256, 16, 8
SHIFT = 5.0           # comfortably above |lambda_min| of the truncated W


@pytest.fixture(scope="module")
def x():
    return feature_mixture(N, D, n_clusters=8, seed=0)


@pytest.fixture(scope="module")
def plan(x):
    return api.build_plan(x, k=K, bs=16, sb=4, backend="bsr",
                          symmetrize=True, values=RBFValues())


def dense_shifted(p, shift=SHIFT):
    return np.asarray(p.bsr.to_dense()) + shift * np.eye(p.n)


def dense_solve_original(p, b, shift=SHIFT):
    """Dense reference in ORIGINAL index order."""
    pi, inv = np.asarray(p.pi), np.asarray(p.inv)
    sol = np.linalg.solve(dense_shifted(p, shift), np.asarray(b)[pi])
    return sol[inv]


# ---------------------------------------------------------------------------
# cg core
# ---------------------------------------------------------------------------


def test_cg_matches_dense():
    rng = np.random.default_rng(0)
    q = rng.standard_normal((24, 24)).astype(np.float32)
    a = q @ q.T + 24 * np.eye(24, dtype=np.float32)
    b = rng.standard_normal(24).astype(np.float32)
    res = cg(lambda v: jnp.asarray(a) @ v, jnp.asarray(b), tol=1e-6,
             maxiter=200)
    ref = np.linalg.solve(a, b)
    assert bool(res.converged)
    np.testing.assert_allclose(np.asarray(res.x), ref, rtol=2e-4, atol=1e-5)


def test_cg_multirhs_axis():
    """(B, n, t) lanes with axis=-2: every (lane, target) column solved."""
    rng = np.random.default_rng(1)
    a = np.stack([np.eye(16, dtype=np.float32) * (3 + i) for i in range(2)])
    b = rng.standard_normal((2, 16, 3)).astype(np.float32)
    res = cg(lambda v: jnp.einsum("bij,bjt->bit", jnp.asarray(a), v),
             jnp.asarray(b), axis=-2, tol=1e-6, maxiter=50)
    assert res.x.shape == (2, 16, 3)
    assert res.iters.shape == (2, 3)
    for i in range(2):
        np.testing.assert_allclose(np.asarray(res.x[i]), b[i] / (3 + i),
                                   rtol=1e-4)


def test_cg_telemetry_and_early_exit():
    """Lanes freeze individually: a trivial lane converges at iteration
    1 while a harder lane keeps running; its frozen history is NaN."""
    rng = np.random.default_rng(2)
    q = rng.standard_normal((32, 32)).astype(np.float32)
    hard = q @ q.T + 1e-1 * np.eye(32, dtype=np.float32)
    easy = np.eye(32, dtype=np.float32)
    a = jnp.stack([jnp.asarray(easy), jnp.asarray(hard)])
    b = jnp.asarray(rng.standard_normal((2, 32)), jnp.float32)
    res = cg(lambda v: jnp.einsum("bij,bj->bi", a, v), b, tol=1e-5,
             maxiter=400)
    it = np.asarray(res.iters)
    assert it[0] == 1 and it[1] > it[0]
    hist = np.asarray(res.history)
    assert hist.shape == (2, 401)
    # the easy lane ran exactly 1 iteration: entries past it are NaN
    assert np.isnan(hist[0, 2:]).all()
    assert np.isfinite(hist[1, :it[1] + 1]).all()
    # the recorded final residual is the history's last finite entry
    np.testing.assert_allclose(hist[1, it[1]], np.asarray(res.resid)[1],
                               rtol=1e-6)
    assert bool(np.asarray(res.converged).all())


def test_cg_zero_rhs_converges_immediately():
    res = cg(lambda v: 2.0 * v, jnp.zeros(8), tol=1e-5, maxiter=10)
    assert bool(res.converged) and int(res.iters) == 0
    np.testing.assert_array_equal(np.asarray(res.x), np.zeros(8))


# ---------------------------------------------------------------------------
# preconditioner extraction (satellite: bitwise against the dense matrix)
# ---------------------------------------------------------------------------


def test_diag_tiles_bitwise_match_dense(plan):
    """Block-Jacobi tiles must equal the diagonal blocks sliced from the
    densified operator BITWISE — extraction is a masked read of the very
    same ELL slots the dense path sums."""
    tiles = np.asarray(diag_tiles(plan.spec, plan.data))
    n_rb, bs = plan.spec.n_rb, plan.spec.bs
    dense = np.zeros((n_rb * bs, n_rb * bs), np.float32)
    d0 = np.asarray(plan.bsr.to_dense())
    dense[:d0.shape[0], :d0.shape[1]] = d0
    for rb in range(n_rb):
        sl = slice(rb * bs, (rb + 1) * bs)
        np.testing.assert_array_equal(tiles[rb], dense[sl, sl])


def test_diag_tiles_dead_slots_get_identity():
    """Capacity-padded plan with deleted points: dead slots must carry
    identity rows (never singular blocks), live blocks stay bitwise."""
    x = feature_mixture(200, D, n_clusters=4, seed=3)
    p = api.build_plan(x, k=K, bs=16, sb=4, backend="bsr", capacity=256,
                      symmetrize=True, values=RBFValues())
    p = p.update(delete=np.arange(0, 40))
    assert p.host.alive is not None and not bool(
        np.asarray(p.host.alive).all())
    tiles = np.asarray(diag_tiles(p.spec, p.data))
    n_rb, bs, cap = p.spec.n_rb, p.spec.bs, p.spec.capacity
    dense = np.zeros((n_rb * bs, n_rb * bs), np.float32)
    d0 = np.asarray(p.bsr.to_dense())
    dense[:d0.shape[0], :d0.shape[1]] = d0
    alive_cl = np.zeros(n_rb * bs, bool)
    alive_cl[:cap] = np.asarray(p.host.alive)[np.asarray(p.pi)]
    for rb in range(n_rb):
        sl = slice(rb * bs, (rb + 1) * bs)
        blk = dense[sl, sl].copy()
        a = alive_cl[sl]
        blk[~a, :] = 0.0
        blk[:, ~a] = 0.0
        blk[~a, ~a] = 1.0
        np.testing.assert_array_equal(tiles[rb], blk)
    # dead-slot identity rows keep every block SPD under the KRR-regime
    # shift (the truncated kernel itself is indefinite, so the shift must
    # clear its spectral floor — SHIFT does)
    L = np.linalg.cholesky(tiles + SHIFT * np.eye(bs, dtype=np.float32))
    assert np.isfinite(L).all()


def test_block_jacobi_inverts_diag_blocks(plan):
    """apply(r) == (D + shift I)^-1 r block-by-block."""
    rng = np.random.default_rng(4)
    r = jnp.asarray(rng.standard_normal(plan.n), jnp.float32)
    z = np.asarray(block_jacobi(plan.spec, plan.data, SHIFT)(r))
    tiles = np.asarray(diag_tiles(plan.spec, plan.data))
    bs = plan.spec.bs
    rp = np.zeros(plan.spec.n_rb * bs, np.float32)
    rp[:plan.n] = np.asarray(r)
    ref = np.concatenate([
        np.linalg.solve(tiles[i] + SHIFT * np.eye(bs), rp[i*bs:(i+1)*bs])
        for i in range(plan.spec.n_rb)])[:plan.n]
    np.testing.assert_allclose(z, ref, rtol=2e-4, atol=1e-5)


def test_jacobi_matches_pointwise_diag(plan):
    rng = np.random.default_rng(5)
    r = jnp.asarray(rng.standard_normal(plan.n), jnp.float32)
    z = np.asarray(jacobi(plan.spec, plan.data, SHIFT)(r))
    d = np.asarray(diag_vector(plan.spec, plan.data)) + SHIFT
    np.testing.assert_allclose(z, np.asarray(r) / d, rtol=1e-5)


# ---------------------------------------------------------------------------
# preconditioner registry (mirrors the backend registry)
# ---------------------------------------------------------------------------


def test_registry_defaults_registered():
    names = api.preconditioner_names()
    for name in ("block_jacobi", "jacobi", "identity"):
        assert name in names


def test_registry_unknown_has_did_you_mean():
    with pytest.raises(ValueError, match="block_jacobi"):
        api.get_preconditioner("blck_jacobi")


def test_registry_duplicate_raises():
    with pytest.raises(ValueError, match="already registered"):
        api.register_preconditioner("block_jacobi")(lambda s, d, sh: None)
    # overwrite with the original is allowed (and restores state)
    orig = api.get_preconditioner("block_jacobi")
    api.register_preconditioner("block_jacobi", orig, overwrite=True)


# ---------------------------------------------------------------------------
# config validation (satellite)
# ---------------------------------------------------------------------------


def test_config_validates_solver_knobs():
    with pytest.raises(ValueError, match="cg_tol"):
        api.PlanConfig(k=K, bs=16, sb=4, cg_tol=0.0)
    with pytest.raises(ValueError, match="cg_maxiter"):
        api.PlanConfig(k=K, bs=16, sb=4, cg_maxiter=0)
    with pytest.raises(ValueError, match="preconditioner"):
        api.PlanConfig(k=K, bs=16, sb=4, precond="no_such_precond")
    cfg = api.PlanConfig(k=K, bs=16, sb=4, cg_tol=1e-4, cg_maxiter=32,
                         precond="jacobi")
    assert cfg.cg_tol == 1e-4 and cfg.precond == "jacobi"


# ---------------------------------------------------------------------------
# plan.solve: single, streamed, batch, sharded
# ---------------------------------------------------------------------------


def test_plan_solve_matches_dense(plan):
    rng = np.random.default_rng(6)
    b = jnp.asarray(rng.standard_normal(plan.n), jnp.float32)
    res = plan.solve(b, shift=SHIFT, tol=1e-6, maxiter=400)
    assert bool(res.converged)
    ref = dense_solve_original(plan, b)
    np.testing.assert_allclose(np.asarray(res.x), ref, rtol=1e-3, atol=1e-5)


def test_plan_solve_multirhs(plan):
    rng = np.random.default_rng(7)
    b = jnp.asarray(rng.standard_normal((plan.n, 3)), jnp.float32)
    res = plan.solve(b, shift=SHIFT, tol=1e-6, maxiter=400)
    assert res.x.shape == (plan.n, 3) and res.iters.shape == (3,)
    for t in range(3):
        ref = dense_solve_original(plan, np.asarray(b[:, t]))
        np.testing.assert_allclose(np.asarray(res.x[:, t]), ref,
                                   rtol=1e-3, atol=1e-5)


def test_streamed_plan_solve_mid_lifecycle():
    """Solve after delete+insert tiers: converges to the dense reference
    of the CURRENT pattern; dead slots return exactly zero."""
    rng = np.random.default_rng(8)
    x0 = feature_mixture(300, D, n_clusters=8, seed=9)
    p = api.build_plan(x0, k=K, bs=16, sb=4, backend="bsr", capacity=384,
                      symmetrize=True, values=RBFValues())
    p = p.update(insert=feature_mixture(30, D, n_clusters=8, seed=10))
    p = p.update(delete=rng.choice(300, 40, replace=False))
    assert p.host.alive is not None
    alive = np.asarray(p.host.alive)
    b = np.where(alive, rng.standard_normal(p.n), 0.0).astype(np.float32)
    res = p.solve(jnp.asarray(b), shift=SHIFT, tol=1e-6, maxiter=400)
    assert bool(res.converged)
    ref = dense_solve_original(p, b)
    np.testing.assert_allclose(np.asarray(res.x), ref, rtol=1e-3, atol=1e-5)
    assert np.all(np.asarray(res.x)[~alive] == 0.0)


def test_batch_solve_matches_members():
    rng = np.random.default_rng(11)
    xs = [feature_mixture(N, D, n_clusters=8, seed=s) for s in range(4)]
    batch = api.build_plan_batch(xs, k=K, bs=16, sb=4, backend="bsr",
                                 symmetrize=True, values=RBFValues())
    b = jnp.asarray(rng.standard_normal((4, batch.capacity)), jnp.float32)
    res = batch.solve(b, shift=SHIFT, tol=1e-6, maxiter=400)
    assert bool(np.asarray(res.converged).all())
    assert res.iters.shape == (4,)
    for i, m in enumerate(batch.members()):
        ref = dense_solve_original(m, np.asarray(b[i]))
        np.testing.assert_allclose(np.asarray(res.x[i]), ref,
                                   rtol=1e-3, atol=1e-5)


def test_batch_solve_single_trace():
    """B member systems under ONE compiled solver kernel: the backend
    traces exactly once however many members ride the batch."""
    xs = [feature_mixture(N, D, n_clusters=8, seed=s) for s in range(3)]
    batch = api.build_plan_batch(xs, k=K, bs=16, sb=4, backend="bsr",
                                 symmetrize=True, values=RBFValues())
    b = jnp.ones((3, batch.capacity), jnp.float32)
    calls = []

    @api.register_backend("test_solver_counter")
    def _counting(p, v, **kw):
        calls.append(1)
        return api.get_backend("bsr")(p, v)

    try:
        jax.block_until_ready(batch.solve(
            b, shift=SHIFT, backend="test_solver_counter", maxiter=64).x)
        jax.block_until_ready(batch.solve(
            b, shift=SHIFT, backend="test_solver_counter", maxiter=64).x)
    finally:
        registry._BACKENDS.pop("test_solver_counter", None)
    assert len(calls) == 1


def test_sharded_solve_matches_single(plan):
    """CG over the halo-exchange matvec (psum'd dots under the mesh) on
    whatever mesh the process has — 8 devices in the CI multidevice job."""
    rng = np.random.default_rng(12)
    b = jnp.asarray(rng.standard_normal(plan.n), jnp.float32)
    sp = plan.shard()
    res = sp.solve(b, shift=SHIFT, tol=1e-6, maxiter=400)
    assert bool(res.converged)
    ref = np.asarray(plan.solve(b, shift=SHIFT, tol=1e-6, maxiter=400).x)
    np.testing.assert_allclose(np.asarray(res.x), ref, rtol=1e-3,
                               atol=1e-5)


def test_block_jacobi_beats_identity_iterations(plan):
    rng = np.random.default_rng(13)
    b = jnp.asarray(rng.standard_normal(plan.n), jnp.float32)
    it_bj = int(plan.solve(b, shift=SHIFT, precond="block_jacobi",
                           maxiter=400).iters)
    it_id = int(plan.solve(b, shift=SHIFT, precond="identity",
                           maxiter=400).iters)
    assert it_bj < it_id


# ---------------------------------------------------------------------------
# lanczos / eigs
# ---------------------------------------------------------------------------


def test_lanczos_eigsh_matches_dense():
    rng = np.random.default_rng(14)
    q = rng.standard_normal((64, 64)).astype(np.float32)
    a = (q + q.T) / 2
    # eigenvalues 4 and 5 of this matrix are 4% apart, so without restarts
    # the 4th Ritz vector's residual after the default m=32 steps depends
    # on the start vector: 5e-3 from the PRNG stream JAX drew before
    # jax_threefry_partitionable became the default, 1.8e-2 from today's.
    # m=48 converges every pair to ~1e-6 whatever the stream.
    w, u = lanczos_eigsh(lambda v: jnp.asarray(a) @ v, 64, 4, m=48, seed=0)
    ref = np.linalg.eigvalsh(a)[::-1][:4]
    np.testing.assert_allclose(np.asarray(w), ref, rtol=1e-4, atol=1e-4)
    # Ritz vectors are orthonormal and satisfy the eigen equation
    g = np.asarray(u).T @ np.asarray(u)
    np.testing.assert_allclose(g, np.eye(4), atol=1e-3)
    resid = a @ np.asarray(u) - np.asarray(u) * np.asarray(w)
    assert np.abs(resid).max() < 1e-2


def test_plan_eigs_matches_dense(plan):
    w, u = plan.eigs(k=3, seed=0)
    dense = np.asarray(plan.bsr.to_dense())
    ref = np.linalg.eigvalsh(dense)[::-1][:3]
    np.testing.assert_allclose(np.asarray(w), ref, rtol=1e-3, atol=1e-3)
    # eigenvectors come back in ORIGINAL order: check the eigen equation
    # through the original-order matvec
    av = np.asarray(plan.matvec(u))
    np.testing.assert_allclose(av, np.asarray(u) * np.asarray(w), atol=5e-3)


# ---------------------------------------------------------------------------
# spectral embedding on the KDE-weighted similarity graph
# ---------------------------------------------------------------------------


def test_redress_rbf_pins_bandwidth(plan):
    p2 = redress_rbf(plan, bandwidth=0.9)
    vals = np.asarray(p2.coo[2])
    assert (vals > 0).all() and (vals <= 1.0).all()
    # symmetric operator: <y, Ax> == <x, Ay>
    rng = np.random.default_rng(15)
    a = jnp.asarray(rng.standard_normal(p2.n), jnp.float32)
    b = jnp.asarray(rng.standard_normal(p2.n), jnp.float32)
    lhs = float(jnp.vdot(b, p2.matvec(a)))
    rhs = float(jnp.vdot(a, p2.matvec(b)))
    np.testing.assert_allclose(lhs, rhs, rtol=1e-4)


def test_normalized_operator_spectrum_bounded(plan):
    n_op, deg = normalized_operator(plan)
    assert deg.shape == (plan.n,) and bool(jnp.all(deg >= 0))
    w, _ = lanczos_eigsh(n_op, plan.n, 2, seed=1)
    # D^-1/2 W D^-1/2 of a nonnegative graph has spectrum in [-1, 1]
    assert float(np.asarray(w).max()) <= 1.0 + 1e-4


def test_spectral_embedding_separates_two_clusters():
    """Two weakly-bridged components: the 2-D embedding must recover the
    plant by nearest centroid. (Bridged, not disconnected — a fully
    disconnected graph has eigenvalue 1 with multiplicity 2, and a
    single-vector Krylov method cannot split a degenerate eigenspace.)"""
    rng = np.random.default_rng(17)
    c = rng.standard_normal((2, 4)).astype(np.float32)
    labels = np.arange(256) % 2
    x = (c[labels] + 0.45 * rng.standard_normal((256, 4))).astype(np.float32)
    w, y = spectral_embedding(x, n_components=2, k=8, bs=16, sb=4,
                              backend="bsr", drop_first=False, seed=2)
    assert y.shape == (256, 2)
    y = np.asarray(y)
    y = y / np.maximum(np.linalg.norm(y, axis=1, keepdims=True), 1e-12)
    cents = np.stack([y[labels == i].mean(0) for i in range(2)])
    pred = (((y[:, None, :] - cents[None]) ** 2).sum(-1)).argmin(1)
    acc = max((pred == labels).mean(), (pred == (1 - labels)).mean())
    assert acc > 0.95


# ---------------------------------------------------------------------------
# kernel ridge regression
# ---------------------------------------------------------------------------


def test_krr_fit_matches_dense(plan, x):
    rng = np.random.default_rng(18)
    w_true = rng.standard_normal(D).astype(np.float32)
    y = np.tanh(x @ w_true).astype(np.float32)
    model = krr_fit(plan, jnp.asarray(y), lam=0.5, tol=1e-6, maxiter=400)
    assert bool(model.result.converged)
    shift = float(np.asarray(model.self_weight)) + 0.5
    ref = dense_solve_original(plan, y, shift=shift)
    np.testing.assert_allclose(np.asarray(model.alpha), ref, rtol=1e-3,
                               atol=1e-5)
    # in-sample prediction is K alpha = (W + sw I) alpha
    yhat = np.asarray(model.predict())
    ref_hat = (np.asarray(plan.matvec(model.alpha))
               + float(np.asarray(model.self_weight))
               * np.asarray(model.alpha))
    np.testing.assert_allclose(yhat, ref_hat, rtol=1e-5)


def test_krr_predict_out_of_sample(plan, x):
    rng = np.random.default_rng(19)
    y = np.tanh(x @ rng.standard_normal(D).astype(np.float32))
    model = krr_fit(plan, jnp.asarray(y.astype(np.float32)), lam=0.5)
    x_new = feature_mixture(32, D, n_clusters=8, seed=20)
    out = np.asarray(model.predict(x_new))
    assert out.shape == (32,) and np.isfinite(out).all()
    # prediction AT a training point through the cross-kernel stays close
    # to that point's in-sample neighbor contribution (same truncation)
    out_tr = np.asarray(model.predict(x[:8]))
    assert np.isfinite(out_tr).all()


def test_krr_fit_batch_lockstep_multitarget():
    rng = np.random.default_rng(21)
    xs = [feature_mixture(N, D, n_clusters=8, seed=30 + s) for s in range(3)]
    batch = api.build_plan_batch(xs, k=K, bs=16, sb=4, backend="bsr",
                                 symmetrize=True, values=RBFValues())
    ys = jnp.asarray(rng.standard_normal((3, batch.capacity, 2)),
                     jnp.float32)
    model = krr_fit_batch(batch, ys, lam=0.5, tol=1e-6, maxiter=400)
    assert model.alpha.shape == (3, batch.capacity, 2)
    assert bool(np.asarray(model.result.converged).all())
    sw = np.asarray(model.self_weight)
    assert sw.shape == (3,)          # per-lane Gershgorin shift
    for i, m in enumerate(batch.members()):
        for t in range(2):
            ref = dense_solve_original(m, np.asarray(ys[i, :, t]),
                                       shift=float(sw[i]) + 0.5)
            np.testing.assert_allclose(np.asarray(model.alpha[i, :, t]),
                                       ref, rtol=1e-3, atol=1e-5)


def test_krr_rejects_nonpositive_lam(plan):
    with pytest.raises(ValueError, match="lam"):
        krr_fit(plan, jnp.ones(plan.n), lam=0.0)


def test_solve_validates_rhs_shape(plan):
    with pytest.raises(ValueError, match="rows"):
        solve(plan, jnp.ones(plan.n + 1), shift=SHIFT)
