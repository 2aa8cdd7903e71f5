"""Analytic cost model + hardware-config knobs + analytic-first autotune."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import autotune, costmodel
from repro.core.costmodel import HardwareConfig


@pytest.fixture(autouse=True)
def _reset_model_state():
    yield
    costmodel.set_hardware(None)
    autotune.clear_tune_memo()
    autotune.clear_calibration()


def _plan(n=256, bs=16, sb=4):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((n, 8)), jnp.float32)
    return api.build_plan(x, k=8, bs=bs, sb=sb, backend="bsr")


# -- hardware config --------------------------------------------------------


def test_hardware_config_json_roundtrip(tmp_path):
    hw = HardwareConfig(name="test-chip", peak_flops=1e12, hbm_bw=1e11,
                        vmem_bytes=1 << 20)
    p = tmp_path / "hw.json"
    hw.to_json(str(p))
    assert HardwareConfig.from_json(str(p)) == hw
    # knob files with unknown keys fail loudly, not silently
    p.write_text(json.dumps({"peak_flops": 1.0, "warp_size": 32}))
    with pytest.raises(ValueError, match="warp_size"):
        HardwareConfig.from_json(str(p))


def test_set_hardware_accepts_dict_and_resets():
    hw = costmodel.set_hardware({"name": "knobs", "gather_penalty": 2.0})
    assert costmodel.get_hardware() is hw
    assert costmodel.get_hardware().gather_penalty == 2.0
    default = costmodel.set_hardware(None)
    assert default.name == "tpu-v5e"


def test_report_envelope():
    rep = costmodel.make_report("backend_rank", {"winner": "bsr"})
    assert rep["schema"] == costmodel.SCHEMA == "repro.cost/v1"
    assert rep["kind"] == "backend_rank"
    assert rep["hardware"]["peak_flops"] == costmodel.get_hardware().peak_flops
    assert rep["winner"] == "bsr"


# -- per-backend cost shapes ------------------------------------------------


def test_backend_cost_orderings():
    feat = costmodel.plan_features((512, 16, 4, 32, 32, 6), f=1)
    hw = HardwareConfig()
    csr = costmodel.backend_cost(feat, "csr", hw)
    bsr = costmodel.backend_cost(feat, "bsr", hw)
    ml = costmodel.backend_cost(feat, "bsr_ml", hw)
    pallas = costmodel.backend_cost(feat, "pallas", hw)
    # fused kernel moves the least HBM; the per-edge gather path the most
    assert pallas["hbm_bytes"] < bsr["hbm_bytes"] < csr["hbm_bytes"]
    assert ml["launches"] == 8 and bsr["launches"] == 1
    # interpret mode makes pallas unwinnable
    interp = costmodel.backend_cost(feat, "pallas", hw, interpret=True)
    assert interp["seconds"] > bsr["seconds"]


def test_csr_priced_on_true_nnz():
    """The per-edge path pays for real COO edges, not ELL padding: on a
    hub-heavy key (fill ~1%) it must undercut the blocked paths, while
    the dense-equivalent fallback keeps the old blocked-wins ordering."""
    key = (1024, 16, 8, 64, 64, 38)          # kNN hubs: max_nbr >> k
    sparse = costmodel.plan_features(key, nnz=8192)
    dense = costmodel.plan_features(key)     # fallback: every slot full
    hw = HardwareConfig()
    assert costmodel.backend_cost(sparse, "csr", hw)["seconds"] \
        < costmodel.backend_cost(dense, "csr", hw)["seconds"]
    assert costmodel.backend_cost(sparse, "csr", hw)["seconds"] \
        < costmodel.backend_cost(sparse, "bsr", hw)["seconds"]
    assert costmodel.backend_cost(dense, "bsr", hw)["seconds"] \
        < costmodel.backend_cost(dense, "csr", hw)["seconds"]


def test_rank_backends_excludes_inf_calibration():
    feat = costmodel.plan_features((512, 16, 4, 32, 32, 6))
    rep = costmodel.rank_backends(
        feat, ("csr", "bsr", "bsr_ml", "pallas"),
        calibration={"pallas": float("inf"), "csr": 1.0})
    assert "pallas" not in rep["predicted_s"]
    assert rep["winner"] == rep["ranking"][0]
    assert rep["schema"] == costmodel.SCHEMA
    assert rep["winner"] == min(rep["predicted_s"], key=rep["predicted_s"].get)


def test_exchange_cost_monotone_and_none_passthrough():
    assert costmodel.exchange_cost(None, 16) is None
    a = costmodel.exchange_cost(3, 16)
    b = costmodel.exchange_cost(7, 16)
    assert 0 < a < b
    # halved link bandwidth doubles the price
    slow = HardwareConfig(link_bw=HardwareConfig().link_bw / 2)
    assert costmodel.exchange_cost(3, 16, slow) == pytest.approx(2 * a)


def test_choose_tiles_contracts():
    key = (512, 16, 8, 32, 32, 24)
    rbs, chunk, fc = costmodel.choose_tiles(key, f=4)
    assert chunk == 24         # the whole ELL width fits the budget
    assert fc == 128           # feature tiles are whole 128-lane tiles
    assert rbs in (1, 2, 4, 8) and rbs <= 8
    # a starved VMEM budget splits the slot sum and shrinks the superblock;
    # the feature tile never drops below one lane tile
    tiny = HardwareConfig(vmem_bytes=64 * 1024)
    rbs_t, chunk_t, fc_t = costmodel.choose_tiles(key, f=16, hw=tiny)
    assert chunk_t < 24
    assert fc_t == 128
    assert rbs_t <= rbs


def _panel_vmem(bs, rbs, chunk, fc):
    """What choose_tiles promises to hold per grid step: per row block the
    double-buffered panel, the gathered segments and the double-buffered
    output tile, plus one row block's dot operands once more."""
    pad = costmodel._vmem_bytes
    panel, segs = pad(bs, chunk * bs), pad(chunk * bs, fc)
    return rbs * (2 * panel + segs + 2 * pad(bs, fc)) + panel + segs


def test_choose_tiles_counts_lane_padding():
    """A (16, 4) charge segment occupies a (16, 128) VMEM tile: the budget
    holds what the padded panels and segments take, not the nominal
    bytes."""
    hw = HardwareConfig(vmem_bytes=1024 * 1024)
    bs, nbr = 16, 64
    key = (1024, bs, 8, 64, 64, nbr)
    rbs, chunk, fc = costmodel.choose_tiles(key, f=4, hw=hw)
    pad = costmodel._vmem_bytes
    assert pad(bs, 4) == pad(bs, 128) == bs * 128 * 4
    assert _panel_vmem(bs, rbs, chunk, fc) <= hw.vmem_bytes / 2
    assert chunk * bs % 128 == 0
    assert chunk < nbr         # nominal bytes would have kept every slot


@pytest.mark.parametrize("key,f", [
    ((131072, 32, 8, 4096, 4096, 158), 128),   # the interact-f128 cell
    ((131072, 32, 8, 4096, 4096, 158), 1),
    ((1 << 20, 32, 8, 1 << 15, 1 << 15, 16), 128),
    ((1 << 18, 32, 8, 1 << 13, 1 << 13, 160), 300),
    ((1024, 16, 8, 64, 64, 20), 4),
    ((128, 16, 4, 8, 8, 4), 1),                # admission-sized: whole-dim
    ((4096, 64, 8, 64, 64, 1000), 128),        # wide rows: chunks split
])
def test_choose_tiles_meets_lane_rule_within_budget(key, f):
    """Every chunk is the whole ELL width or whole 128-lane panel
    columns, and the panel form's VMEM stays within half the knob."""
    bs, nbr = key[1], key[5]
    rbs, chunk, fc = costmodel.choose_tiles(key, f=f)
    assert chunk == nbr or chunk * bs % 128 == 0
    assert chunk <= nbr
    assert _panel_vmem(bs, rbs, chunk, fc) <= HardwareConfig().vmem_bytes / 2


def test_get_hardware_by_device_kind(monkeypatch):
    class Dev:
        def __init__(self, platform, kind):
            self.platform, self.device_kind = platform, kind

    monkeypatch.delenv("REPRO_HW_CONFIG", raising=False)
    for platform, kind, want in [("tpu", "TPU v5 lite", "tpu-v5e"),
                                 ("cpu", "cpu", "tpu-v5e")]:
        monkeypatch.setattr(costmodel, "_HARDWARE", None)
        monkeypatch.setattr(jax, "devices", lambda: [Dev(platform, kind)])
        assert costmodel.get_hardware().name == want
    monkeypatch.setattr(costmodel, "_HARDWARE", None)
    monkeypatch.setattr(jax, "devices", lambda: [Dev("tpu", "TPU v9")])
    with pytest.raises(ValueError, match="no hardware knobs"):
        costmodel.get_hardware()


# -- analytic-first autotune ------------------------------------------------


def test_tune_backend_reports_ranking_in_memo():
    autotune.clear_tune_memo()
    plan = _plan()
    name, times = autotune.tune_backend(plan, device_count=1)
    assert times and name == min(times, key=times.get)
    (report,) = autotune._TUNE_MEMO.values()
    assert report["schema"] == costmodel.SCHEMA
    assert report["kind"] == "backend_rank"
    assert report["winner"] == name
    assert report["ranking"][0] == name
    # memo hit replays winner + predicted seconds without touching probes
    name2, times2 = autotune.tune_backend(plan, device_count=1)
    assert (name2, times2) == (name, times)


def test_hw_config_flip_changes_decision_without_reprobing(monkeypatch):
    """clear_tune_memo + a different hardware config re-decides purely from
    the model: probes must not run (calibration constants are reused)."""
    plan = _plan(n=256, bs=16, sb=4)     # n_rb=16, sb=4 -> bsr_ml launches 4
    autotune.clear_tune_memo()
    autotune.clear_calibration()
    autotune._CALIB.update({"bsr": 1.0, "bsr_ml": 1.0,
                            "csr": float("inf"), "pallas": float("inf")})

    def boom(*a, **k):
        raise AssertionError("probe ran despite existing calibration")

    monkeypatch.setattr(autotune, "probe_backends", boom)

    costmodel.set_hardware(HardwareConfig(gather_penalty=100.0,
                                          launch_overhead=0.0))
    name_a, _ = autotune.tune_backend(plan, device_count=1)
    assert name_a == "bsr_ml"            # flat path pays the gather penalty

    autotune.clear_tune_memo()
    costmodel.set_hardware(HardwareConfig(gather_penalty=1.0,
                                          launch_overhead=1.0))
    name_b, _ = autotune.tune_backend(plan, device_count=1)
    assert name_b == "bsr"               # striped path pays 4 launches


def test_probe_backends_skips_interpret_pallas():
    plan = _plan(n=128)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(plan.n),
                    jnp.float32)
    times = autotune.probe_backends(plan, x, backends=("bsr", "pallas"),
                                    iters=1, warmup=0)
    assert "pallas" not in times         # interpret-mode: skipped by default
    assert "bsr" in times
    times_inc = autotune.probe_backends(plan, x, backends=("pallas",),
                                        iters=1, warmup=0,
                                        include_interpret=True)
    assert "pallas" in times_inc         # escape hatch still times it


def test_probe_propagates_faults_skips_refusals(monkeypatch):
    """Only the typed NotApplicable refusal is skipped; a backend that
    fails (a kernel the chip's compiler refuses) is an error, never a
    silent fall back to another path."""
    from repro.core import registry

    def broken(plan, x, **_kw):
        raise RuntimeError("compile refused")

    def refuses(plan, x, **_kw):
        raise registry.NotApplicable("does not apply")

    registry._ensure_defaults()
    monkeypatch.setitem(registry._BACKENDS, "broken", broken)
    monkeypatch.setitem(registry._BACKENDS, "refuses", refuses)
    plan = _plan(n=128)
    x = jnp.ones(plan.n, jnp.float32)
    times = autotune.probe_backends(plan, x, backends=("bsr", "refuses"),
                                    iters=1, warmup=0)
    assert set(times) == {"bsr"}
    with pytest.raises(RuntimeError, match="compile refused"):
        autotune.probe_backends(plan, x, backends=("bsr", "broken"),
                                iters=1, warmup=0)


def _decode_feat(**kw):
    base = dict(batch=8, hq=14, hkv=2, s=8192, dh=64, dv=64, bk=128,
                n_sel=4)
    base.update(kw)
    return costmodel.DecodeFeatures(**base)


def test_decode_cost_orderings():
    """Compiled: the fused kernel's once-only tile traffic and single
    launch beat the xla gather round-trip. Interpreted (the CPU CI
    container): the kernel eats interpret_penalty and xla must win —
    that asymmetry is what keeps "auto" correct on both targets."""
    feat = _decode_feat()
    xla = costmodel.decode_cost(feat, "xla")
    pal = costmodel.decode_cost(feat, "pallas")
    assert pal["hbm_bytes"] < xla["hbm_bytes"]
    assert pal["launches"] < xla["launches"]
    assert pal["seconds"] < xla["seconds"]
    pal_i = costmodel.decode_cost(feat, "pallas", interpret=True)
    assert pal_i["seconds"] > xla["seconds"]
    assert costmodel.choose_decode_backend(feat) == "pallas"
    assert costmodel.choose_decode_backend(feat, interpret=True) == "xla"


def test_decode_rank_report_envelope():
    rep = costmodel.rank_decode_backends(_decode_feat())
    assert rep["schema"] == "repro.cost/v1"
    assert rep["kind"] == "decode_rank"
    assert rep["winner"] == rep["ranking"][0]
    assert set(rep["costs"]) == {"xla", "pallas"}
    assert rep["features"]["s"] == 8192
    json.dumps(rep)                                  # JSON-safe


def test_decode_choice_memoized():
    feat = _decode_feat(batch=3)
    costmodel._DECODE_CHOICE.clear()
    a = costmodel.choose_decode_backend(feat)
    assert len(costmodel._DECODE_CHOICE) == 1
    b = costmodel.choose_decode_backend(feat)
    assert a == b and len(costmodel._DECODE_CHOICE) == 1
    costmodel.choose_decode_backend(feat, interpret=True)
    assert len(costmodel._DECODE_CHOICE) == 2
