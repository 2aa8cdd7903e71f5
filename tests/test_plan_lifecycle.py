"""Plan lifecycle (ISSUE 2): refresh tiers, stable partial reorder, BSR
patching, drift-measure edge cases, pytree round-trips under jit/vmap,
and checkpoint save -> restore -> matvec equivalence."""
import dataclasses
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.checkpoint.ckpt import Checkpointer
from repro.compat import make_mesh
from repro.core import blocksparse, hierarchy, interact, measures
from repro.core.ordering import stable_partial_reorder
from repro.data.pipeline import feature_mixture

N, D, K = 512, 32, 8


@pytest.fixture(scope="module")
def points():
    return feature_mixture(N, D, n_clusters=8, seed=0)


@pytest.fixture(scope="module")
def plan(points):
    return api.build_plan(points, k=K, bs=16, sb=4, backend="bsr",
                          ell_slack=8)


def _teleport(x, frac, seed=1):
    """Move a fraction of points onto other clusters' locations."""
    rng = np.random.default_rng(seed)
    x2 = x.copy()
    mv = rng.choice(len(x), size=max(int(len(x) * frac), 1), replace=False)
    x2[mv] = x[(mv + len(x) // 2) % len(x)]
    x2[mv] += 0.01 * rng.standard_normal((len(mv), x.shape[1])
                                         ).astype(np.float32)
    return x2, mv


def _detected(plan, x_new):
    """Original indices the refresh migration detector flags (a teleport
    landing in the SAME leaf cell is — by design — not a migration)."""
    host, cfg = plan.host, plan.config
    y_new = np.asarray(api.apply_pca_map(jnp.asarray(x_new),
                                         jnp.asarray(host.embed_mean),
                                         jnp.asarray(host.embed_axes)))
    shift = api._cmp_shift(plan.n, y_new.shape[1], cfg.bits, host.tree,
                           cfg.leaf_size)
    return np.nonzero(api._cell_migration(host.y_last, y_new, cfg.bits,
                                          shift))[0]


# ---------------------------------------------------------------------------
# refresh tiers
# ---------------------------------------------------------------------------


def test_refresh_noop_when_nothing_moved(plan, points):
    p2 = plan.refresh(points)
    st = p2.refresh_stats
    assert st.last_action == "patch"
    assert st.last_migrated_frac == 0.0
    # untouched structure is shared, not copied
    assert p2.bsr is plan.bsr
    np.testing.assert_array_equal(p2.host.pi, plan.host.pi)


def test_refresh_patch_small_migration(plan, points):
    x2, mv = _teleport(points, 0.03)
    p2 = plan.refresh(x2, policy="patch")
    st = p2.refresh_stats
    assert st.last_action == "patch" and st.patches == 1
    # permutation untouched by the cheap tier
    np.testing.assert_array_equal(p2.host.pi, plan.host.pi)

    # patched storage is self-consistent: bsr path == csr over its own COO
    xq = jnp.asarray(np.random.default_rng(2).standard_normal(N),
                     jnp.float32)
    ref = np.asarray(p2.apply(xq, backend="csr"))
    got = np.asarray(p2.apply(xq, backend="bsr"))
    assert np.abs(got - ref).max() <= 1e-4

    # detected-migrated rows got their *exact* fresh kNN
    det = _detected(plan, x2)
    assert len(det) > 0 and set(det) <= set(mv)
    fresh = api.build_plan(x2, k=K, bs=16, sb=4, backend="bsr")
    r2, c2, _ = p2.coo
    ro, co = p2.host.pi[r2], p2.host.pi[c2]
    fr, fc, _ = fresh.coo
    fro, fco = fresh.host.pi[fr], fresh.host.pi[fc]
    for i in det:
        assert set(co[ro == i]) == set(fco[fro == i])


def test_refresh_gamma_close_to_rebuild(plan, points):
    x2, _ = _teleport(points, 0.03)
    p2 = plan.refresh(x2)
    rebuilt = api.build_plan(x2, k=K, bs=16, sb=4, backend="bsr")
    assert p2.gamma == pytest.approx(rebuilt.gamma, rel=0.05)


def test_refresh_escalates_with_drift(plan, points):
    x2, _ = _teleport(points, 0.25, seed=3)
    p2 = plan.refresh(x2)
    assert p2.refresh_stats.last_action in ("rebucket", "rebuild")
    # a shuffled cloud is a different ordering problem: full rebuild
    x3 = np.random.default_rng(4).permutation(points).copy()
    p3 = plan.refresh(x3)
    assert p3.refresh_stats.last_action == "rebuild"
    assert p3.refresh_stats.builds == 2


def test_refresh_rebucket_keeps_matvec_semantics(plan, points):
    """After a forced re-bucket, matvec in ORIGINAL order still equals the
    csr reference on the relabeled pattern."""
    x2, _ = _teleport(points, 0.03, seed=5)
    p2 = plan.refresh(x2, policy="rebucket")
    assert p2.refresh_stats.last_action == "rebucket"
    assert sorted(p2.host.pi) == list(range(N))
    xq = jnp.asarray(np.random.default_rng(6).standard_normal(N),
                     jnp.float32)
    r2, c2, v = p2.coo
    rows0, cols0 = p2.host.pi[r2], p2.host.pi[c2]
    want = interact.spmv_csr(jnp.asarray(v), jnp.asarray(rows0),
                             jnp.asarray(cols0), xq, N)
    np.testing.assert_allclose(np.asarray(p2.matvec(xq)),
                               np.asarray(want), rtol=1e-4, atol=1e-4)


def test_refresh_fixed_pattern_reorders_only(points):
    """from_coo plans (externally fixed pattern) refresh their ordering but
    keep edges and values bit-for-bit."""
    rng = np.random.default_rng(7)
    rows = np.repeat(np.arange(N), K)
    cols = rng.integers(0, N, N * K)
    key = rows.astype(np.int64) * N + cols
    _, first = np.unique(key, return_index=True)
    rows, cols = rows[first], cols[first]
    vals = rng.random(len(rows)).astype(np.float32)
    plan = api.InteractionPlan.from_coo(rows, cols, vals, N, x=points,
                                        bs=16, sb=4)
    x2, _ = _teleport(points, 0.3, seed=8)
    p2 = plan.refresh(x2)

    def orig_edges(p):
        r2, c2, v = p.coo
        return sorted(zip(p.host.pi[r2], p.host.pi[c2], v))

    assert orig_edges(p2) == orig_edges(plan)


def test_refresh_policy_validation(plan, points):
    with pytest.raises(ValueError, match="unknown refresh policy"):
        plan.refresh(points, policy="nope")
    with pytest.raises(ValueError, match="same"):
        plan.refresh(points[:-1])
    prof = api.build_plan(points, k=K, ordering="scattered", with_bsr=False)
    with pytest.raises(ValueError, match="not refreshable"):
        prof.refresh(points)


def test_refresh_values_callable_redressed(points):
    """Patched rows get values recomputed through the stored callable."""
    plan = api.build_plan(points, k=K, bs=16, sb=4, backend="bsr",
                          ell_slack=8,
                          values=lambda r, c, d2: 1.0 / (1.0 + d2))
    x2, mv = _teleport(points, 0.03, seed=9)
    det = _detected(plan, x2)
    assert len(det) > 0
    p2 = plan.refresh(x2, policy="patch")
    assert p2.refresh_stats.last_action == "patch"
    r2, c2, v = p2.coo
    ro, co = p2.host.pi[r2], p2.host.pi[c2]
    sel = np.isin(ro, det)
    d2 = ((x2[ro[sel]] - x2[co[sel]]) ** 2).sum(1)
    # knn's |a|^2+|b|^2-2ab distances differ from the direct form by
    # float32 cancellation noise
    np.testing.assert_allclose(v[sel], 1.0 / (1.0 + d2), atol=1e-3)


def test_gamma_drift_monitor(plan, points):
    assert plan.gamma_drift() == 0.0          # pins the reference
    x2, _ = _teleport(points, 0.05, seed=10)
    p2 = plan.refresh(x2, policy="patch")
    assert p2.refresh_stats.gamma0 == pytest.approx(plan.gamma)
    assert isinstance(p2.gamma_drift(), float)


# ---------------------------------------------------------------------------
# building blocks: stable reorder, tree rebucket, patch_bsr, measures
# ---------------------------------------------------------------------------


def test_stable_partial_reorder_properties():
    rng = np.random.default_rng(0)
    n = 200
    keys = rng.integers(0, 50, n)
    pi = np.argsort(keys, kind="stable")
    # unchanged keys -> identical ordering
    np.testing.assert_array_equal(stable_partial_reorder(pi, keys), pi)
    # perturb a few keys: result is sorted, and unmoved points keep their
    # relative order
    keys2 = keys.copy()
    mv = rng.choice(n, 10, replace=False)
    keys2[mv] = rng.integers(0, 50, 10)
    pi2 = stable_partial_reorder(pi, keys2)
    assert sorted(pi2) == list(range(n))
    assert (np.diff(keys2[pi2]) >= 0).all()
    stay = ~np.isin(pi, mv)
    stay2 = ~np.isin(pi2, mv)
    np.testing.assert_array_equal(pi[stay], pi2[stay2])


def test_tree_rebucket_matches_fresh_build():
    rng = np.random.default_rng(1)
    y = rng.standard_normal((300, 3)).astype(np.float32)
    tree = hierarchy.build_tree(y, leaf_size=32)
    y2 = y.copy()
    y2[:30] += 2.0
    re = hierarchy.rebucket(y2, tree, leaf_size=32)
    fresh = hierarchy.build_tree(y2, leaf_size=32)
    # same cells (codes equal), possibly different within-cell tiebreaks
    codes_re = np.asarray(hierarchy.morton_codes(jnp.asarray(y2)))[re.perm]
    codes_fr = np.asarray(hierarchy.morton_codes(jnp.asarray(y2)))[fresh.perm]
    np.testing.assert_array_equal(codes_re, codes_fr)
    assert len(re.levels) == len(fresh.levels)
    for a, b in zip(re.levels, fresh.levels):
        np.testing.assert_array_equal(a, b)


def test_patch_bsr_matches_full_build():
    rng = np.random.default_rng(2)
    n, bs, sb, k = 300, 16, 4, 6
    rows = np.repeat(np.arange(n), k)
    cols = rng.integers(0, n, n * k)
    key = rows.astype(np.int64) * n + cols
    _, first = np.unique(key, return_index=True)
    rows, cols = rows[first], cols[first]
    vals = rng.random(len(rows)).astype(np.float32)
    base = blocksparse.build_bsr(rows, cols, vals, n, bs=bs, sb=sb, slack=2)

    mod = rng.choice(n, 30, replace=False)
    drop = np.isin(rows, mod)
    nr = np.repeat(mod, k)
    nc = rng.integers(0, n, len(nr))
    k2 = nr.astype(np.int64) * n + nc
    _, f2 = np.unique(k2, return_index=True)
    nr, nc = nr[f2], nc[f2]
    r_all = np.concatenate([rows[~drop], nr])
    c_all = np.concatenate([cols[~drop], nc])
    v_all = np.concatenate([vals[~drop],
                            rng.random(len(nr)).astype(np.float32)])
    touched = np.unique(np.concatenate([rows[drop], nr]) // bs)
    patched = blocksparse.patch_bsr(base, r_all, c_all, v_all, touched)
    fresh = blocksparse.build_bsr(r_all, c_all, v_all, n, bs=bs, sb=sb,
                                  max_nbr=base.max_nbr)
    np.testing.assert_array_equal(patched.to_dense(), fresh.to_dense())
    np.testing.assert_array_equal(np.asarray(patched.col_idx),
                                  np.asarray(fresh.col_idx))
    np.testing.assert_array_equal(np.asarray(patched.nbr_mask),
                                  np.asarray(fresh.nbr_mask))
    assert patched.fill == pytest.approx(fresh.fill)


def test_patch_bsr_overflow_raises():
    base = blocksparse.build_bsr(np.array([0]), np.array([0]), None, 64,
                                 bs=16, sb=4)
    assert base.max_nbr == 1
    rows = np.zeros(4, np.int64)
    cols = np.array([0, 16, 32, 48])
    with pytest.raises(ValueError, match="tile slots"):
        blocksparse.patch_bsr(base, rows, cols, None, np.array([0]))


def test_measures_edge_cases():
    empty = np.empty(0, np.int64)
    assert measures.fill_ratio(empty, empty, 64, 16) == 0.0
    assert float(measures.gamma_score(jnp.asarray(empty),
                                      jnp.asarray(empty), 4.0, 64)) == 0.0
    assert float(measures.gamma_exact(jnp.asarray(empty),
                                      jnp.asarray(empty), 4.0)) == 0.0
    assert measures.beta_estimate(empty, empty, 64) == {
        "beta": 0.0, "block": None, "per_block": {}}
    # single-block pattern (n < bs): well-defined, no division by zero
    rows = np.arange(4)
    assert 0 < measures.fill_ratio(rows, rows, 4, 16) <= 1
    assert measures.gamma_drift(None, 1.0) == 0.0
    assert measures.gamma_drift(0.0, 1.0) == 0.0
    assert measures.gamma_drift(2.0, 1.0) == pytest.approx(0.5)
    assert measures.fill_drift(0.5, 0.25) == pytest.approx(0.5)
    assert measures.fill_drift(None, 0.25) == 0.0


# ---------------------------------------------------------------------------
# pytree round-trips under jit / vmap
# ---------------------------------------------------------------------------


def test_plan_pytree_round_trip_jit_vmap(plan, points):
    xq = jnp.asarray(np.random.default_rng(11).standard_normal(N),
                     jnp.float32)
    leaves, treedef = jax.tree_util.tree_flatten(plan)
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    ref = np.asarray(plan.apply(xq, backend="bsr"))
    np.testing.assert_allclose(np.asarray(back.apply(xq, backend="bsr")),
                               ref, rtol=1e-5)

    f = jax.jit(lambda p, v: p.apply(v, backend="bsr"))
    np.testing.assert_allclose(np.asarray(f(plan, xq)), ref, rtol=1e-5)

    X = jnp.asarray(np.random.default_rng(12).standard_normal((4, N)),
                    jnp.float32)
    Y = jax.vmap(lambda v: plan.apply(v, backend="bsr"))(X)
    for i in range(4):
        np.testing.assert_allclose(
            np.asarray(Y[i]), np.asarray(plan.apply(X[i], backend="bsr")),
            rtol=1e-5, atol=1e-5)


def test_refreshed_plan_still_crosses_jit(plan, points):
    x2, _ = _teleport(points, 0.03, seed=13)
    p2 = plan.refresh(x2)
    xq = jnp.asarray(np.random.default_rng(14).standard_normal(N),
                     jnp.float32)
    f = jax.jit(lambda p, v: p.apply(v, backend="bsr"))
    np.testing.assert_allclose(np.asarray(f(p2, xq)),
                               np.asarray(p2.apply(xq, backend="bsr")),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# persistence: save -> restore -> matvec equivalence, refresh-on-restore
# ---------------------------------------------------------------------------


def test_checkpoint_plan_round_trip(plan, points):
    _ = plan.gamma                        # score rides the manifest
    ck = Checkpointer(tempfile.mkdtemp())
    ck.save_plan(7, plan, blocking=True)
    assert ck.plan_steps() == [7]
    assert ck.steps() == []              # no *model* checkpoint here
    p2, step = ck.restore_plan()
    assert step == 7
    xq = jnp.asarray(np.random.default_rng(15).standard_normal(N),
                     jnp.float32)
    # bit-identical matvec after the round trip
    np.testing.assert_array_equal(np.asarray(plan.matvec(xq)),
                                  np.asarray(p2.matvec(xq)))
    assert p2.config == plan.config
    assert p2.host.gamma == plan.host.gamma
    assert p2.tree is not None and p2.tree.n_levels == plan.tree.n_levels
    assert dataclasses.asdict(p2.refresh_stats) == \
        dataclasses.asdict(plan.refresh_stats)


def test_checkpoint_restore_refreshes_on_drift(plan, points):
    ck = Checkpointer(tempfile.mkdtemp())
    ck.save_plan(0, plan, blocking=True)
    # unmoved points: the restored plan validates as fresh
    p_same, _ = ck.restore_plan(refresh_with=points)
    assert p_same.refresh_stats.last_migrated_frac == 0.0
    # drifted points: restore invalidates the stale ordering
    x2 = np.random.default_rng(16).permutation(points).copy()
    p_moved, _ = ck.restore_plan(refresh_with=x2)
    assert p_moved.refresh_stats.last_action == "rebuild"


def test_checkpoint_plans_and_models_gc_independently(plan):
    """Plans saved on a different cadence must not evict (or shadow) model
    checkpoints: each kind keeps its own latest `keep` steps."""
    ck = Checkpointer(tempfile.mkdtemp(), keep=2)
    tree = {"w": jnp.arange(4.0)}
    for s in (10, 20):
        ck.save(s, tree, blocking=True)
    for s in (30, 40, 50):
        ck.save_plan(s, plan, blocking=True)
    assert ck.steps() == [10, 20]        # model ckpts survive plan gc
    assert ck.plan_steps() == [40, 50]   # plans keep their own window
    restored, step = ck.restore(tree)    # default step is a *model* step
    assert step == 20
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))
    _, pstep = ck.restore_plan()
    assert pstep == 50


def test_checkpoint_async_save_plan(plan):
    ck = Checkpointer(tempfile.mkdtemp())
    ck.save_plan(1, plan)                 # async path
    ck.wait()
    p2, _ = ck.restore_plan(step=1)
    assert p2.n == plan.n


def test_plan_config_validated_at_construction():
    """Bad thresholds fail loudly at PlanConfig(), not deep in a refresh."""
    with pytest.raises(ValueError, match="ell_slack"):
        api.PlanConfig(ell_slack=-1)
    with pytest.raises(ValueError, match="patch_frac.*rebuild_frac"):
        api.PlanConfig(patch_frac=0.5, rebuild_frac=0.2)
    with pytest.raises(ValueError, match="drift_tol"):
        api.PlanConfig(drift_tol=-0.1)
    with pytest.raises(ValueError, match="drift_tol"):
        api.PlanConfig(drift_tol=1.5)
    with pytest.raises(ValueError, match="patch_frac"):
        api.PlanConfig(patch_frac=-0.2)
    with pytest.raises(ValueError, match="max_dead_frac"):
        api.PlanConfig(max_dead_frac=0.0)
    with pytest.raises(ValueError, match="grow_frac"):
        api.PlanConfig(grow_frac=-1.0)
    # dataclasses.replace re-validates
    good = api.PlanConfig()
    with pytest.raises(ValueError, match="rebuild_frac"):
        dataclasses.replace(good, rebuild_frac=0.05)
    # build_plan overrides route through the same gate
    with pytest.raises(ValueError, match="ell_slack"):
        api.build_plan(np.zeros((32, 4), np.float32), k=2, ell_slack=-3)


# ---------------------------------------------------------------------------
# restore_plan error paths (descriptive, not opaque tracebacks)
# ---------------------------------------------------------------------------


def test_restore_plan_missing(plan):
    ck = Checkpointer(tempfile.mkdtemp())
    with pytest.raises(FileNotFoundError, match="no plan 'plan'"):
        ck.restore_plan()
    ck.save_plan(3, plan, blocking=True)
    with pytest.raises(FileNotFoundError, match="no plan 'other'"):
        ck.restore_plan(name="other")
    with pytest.raises(FileNotFoundError, match="step 9"):
        ck.restore_plan(step=9)


def test_restore_plan_corrupt_manifest(plan):
    from pathlib import Path
    d = Path(tempfile.mkdtemp())
    ck = Checkpointer(d)
    ck.save_plan(1, plan, blocking=True)
    mf = d / "step_1" / "plan_plan" / "manifest.json"
    mf.write_text("{not json")
    with pytest.raises(ValueError, match="corrupt plan manifest"):
        ck.restore_plan()


def test_restore_plan_array_shape_mismatch(plan):
    import json as _json
    from pathlib import Path
    d = Path(tempfile.mkdtemp())
    ck = Checkpointer(d)
    ck.save_plan(1, plan, blocking=True)
    pd = d / "step_1" / "plan_plan"
    arrays = dict(np.load(pd / "arrays.npz"))

    # truncated pi: capacity disagrees with the manifest
    trunc = dict(arrays)
    trunc["pi"] = trunc["pi"][:-5]
    np.savez(pd / "arrays.npz", **trunc)
    with pytest.raises(ValueError, match="pi.*capacity"):
        ck.restore_plan()

    # missing BSR payload the manifest promises
    nobsr = {k: v for k, v in arrays.items() if k != "bsr_vals"}
    np.savez(pd / "arrays.npz", **nobsr)
    with pytest.raises(ValueError, match="missing arrays.*bsr_vals"):
        ck.restore_plan()

    # tile tensor reshaped behind the manifest's back
    bad = dict(arrays)
    bad["bsr_vals"] = bad["bsr_vals"][:, :-1]
    np.savez(pd / "arrays.npz", **bad)
    with pytest.raises(ValueError, match="bsr_vals shape"):
        ck.restore_plan()

    # manifest edited to a different layout than the arrays
    np.savez(pd / "arrays.npz", **arrays)
    m = _json.loads((pd / "manifest.json").read_text())
    m["bsr"]["max_nbr"] += 1
    (pd / "manifest.json").write_text(_json.dumps(m))
    with pytest.raises(ValueError, match="does not match the manifest"):
        ck.restore_plan()


def test_restore_plan_mesh_validation(plan):
    ck = Checkpointer(tempfile.mkdtemp())
    ck.save_plan(1, plan, blocking=True)
    with pytest.raises(TypeError, match="Mesh or 'auto'"):
        ck.restore_plan(mesh="bogus")
    with pytest.raises(TypeError, match="Mesh or 'auto'"):
        ck.restore_plan(mesh=3)
    mesh = make_mesh((jax.device_count(),), ("data",))
    with pytest.raises(ValueError, match="no axis 'model'"):
        ck.restore_plan(mesh=mesh, axis="model")
    sp, _ = ck.restore_plan(mesh=mesh)       # happy path still works
    assert sp.spec.n_dev == jax.device_count()


# ---------------------------------------------------------------------------
# fixed-source (mean-shift) plans
# ---------------------------------------------------------------------------


def test_sources_mode_build_and_refresh(points):
    rng = np.random.default_rng(17)
    src = points
    t = src + 0.05 * rng.standard_normal(src.shape).astype(np.float32)
    plan = api.build_plan(t, k=K, sources=src, bs=16, sb=4, backend="bsr",
                          ell_slack=8)
    assert plan.host.sources is not None
    # pattern is kNN(targets among sources), self NOT excluded
    r2, c2, _ = plan.coo
    assert len(r2) == N * K

    t2 = t.copy()
    mv = rng.choice(N, 12, replace=False)
    t2[mv] = src[(mv + N // 2) % N]
    det = _detected(plan, t2)
    assert len(det) > 0 and set(det) <= set(mv)
    p2 = plan.refresh(t2, policy="patch")
    # migrated rows' neighbors match a direct kNN against the fixed sources
    from repro.core import knn
    idx, _ = knn.knn_graph(jnp.asarray(t2[det]), jnp.asarray(src), K)
    r2, c2, _ = p2.coo
    ro, co = p2.host.pi[r2], p2.host.pi[c2]
    for j, i in enumerate(det):
        assert set(co[ro == i]) == set(np.asarray(idx[j]))


def test_sources_mode_rejects_mismatch(points):
    with pytest.raises(ValueError, match="sources"):
        api.build_plan(points, k=K, sources=points[:-1])
    with pytest.raises(ValueError, match="symmetrize"):
        api.build_plan(points, k=K, sources=points, symmetrize=True)
