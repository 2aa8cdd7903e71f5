"""Analytic per-backend cost model + knob-based hardware config.

One model, three consumers:

  * ``core.autotune`` ranks SpMV backends analytically (probes are demoted
    to one-off calibration of the model's constants);
  * ``core.shardplan`` prices halo-vs-ring-vs-allgather exchanges in
    seconds on the configured interconnect instead of raw block counts;
  * ``kernels.ops`` sizes the Pallas batch-grid tiles (row-superblock,
    slot-chunk, feature tile) against the configured VMEM budget.

The hardware is described by a handful of knobs (:class:`HardwareConfig`)
loadable from JSON — point ``REPRO_HW_CONFIG`` at a knob file and every
decision re-derives from the new hardware truth without re-probing.  All
reports emitted here (and by ``launch/roofline.py`` / ``launch/dryrun.py``)
share one machine-readable envelope: ``schema = "repro.cost/v1"`` plus
``kind`` and the hardware knobs that produced the numbers.

Cost shapes come from ``PlanSpec.shape_key`` — ``(capacity, bs, sb, n_rb,
n_cb, max_nbr)`` — which is exactly the structural memo key the autotune
already uses, so a prediction is valid for every plan that would compile
the same kernels.
"""
from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass
from typing import Dict, Iterable, Mapping, Optional, Tuple

SCHEMA = "repro.cost/v1"

# dense bottom tiles are float32 on every path (build_bsr casts)
_ELEM = 4.0
_IDX = 4.0


@dataclass(frozen=True)
class HardwareConfig:
    """Knob-based description of the target chip (defaults: TPU v5e-like,
    the same constants ``launch/analytic.py`` has always used).

    ``launch_overhead`` is the fixed cost of one dispatched kernel / scan
    step; ``gather_penalty`` multiplies HBM bytes moved by *irregular*
    gathers (XLA lowers them far off the streaming-bandwidth roof,
    catastrophically so on CPU); ``edge_cost`` is the per-edge
    serialization of the csr path's scatter-adds (throughput-bound, not
    byte-bound); ``interpret_penalty`` is the slowdown of
    running a Pallas kernel under ``interpret=True`` (the CPU container) —
    on a real MXU it is 1.0 and the fused kernel wins on its merits.
    """
    name: str = "tpu-v5e"
    peak_flops: float = 197e12       # bf16/f32 MXU flops per chip
    hbm_bw: float = 819e9            # HBM bytes/s per chip
    link_bw: float = 50e9            # ICI bytes/s per link
    vmem_bytes: int = 16 * 2 ** 20   # VMEM per core
    mxu_tile: int = 128              # MXU systolic tile edge
    launch_overhead: float = 2e-6    # s per dispatched kernel / scan step
    gather_penalty: float = 4.0      # HBM multiplier on irregular gathers
    edge_cost: float = 2e-10         # s per scattered COO edge (csr path)
    interpret_penalty: float = 1e4   # Pallas interpret-mode slowdown

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Mapping) -> "HardwareConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown hardware knobs {sorted(unknown)}; "
                             f"known: {sorted(known)}")
        return cls(**dict(d))

    def to_json(self, path: str) -> None:
        with open(path, "w") as fh:
            json.dump(self.to_dict(), fh, indent=2)

    @classmethod
    def from_json(cls, path: str) -> "HardwareConfig":
        with open(path) as fh:
            return cls.from_dict(json.load(fh))


_HARDWARE: Optional[HardwareConfig] = None

# knobs per ``jax.Device.device_kind``. Peaks: Google Cloud documentation,
# "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI
# per chip); VMEM is the default scoped-VMEM limit a kernel may use.
DEVICE_KNOBS: Dict[str, HardwareConfig] = {
    "TPU v5 lite": HardwareConfig(),
}


def target_hardware() -> HardwareConfig:
    """The knobs of the chip being planned for, without asking JAX for a
    device: the JSON file named by ``REPRO_HW_CONFIG``, else TPU v5e.
    Dry-run planning and the CPU (which rehearses for the v5e) use it."""
    path = os.environ.get("REPRO_HW_CONFIG")
    return HardwareConfig.from_json(path) if path else HardwareConfig()


def get_hardware() -> HardwareConfig:
    """The active hardware config: ``set_hardware``'s, else
    :func:`target_hardware` on the CPU (the v5e is its rehearsal target)
    or when ``REPRO_HW_CONFIG`` is set, else the knobs of the attached
    accelerator's ``device_kind``. An accelerator with no knobs is an
    error, never a default."""
    global _HARDWARE
    if _HARDWARE is None:
        import jax

        dev = jax.devices()[0]
        if dev.platform == "cpu" or os.environ.get("REPRO_HW_CONFIG"):
            _HARDWARE = target_hardware()
        elif dev.device_kind in DEVICE_KNOBS:
            _HARDWARE = DEVICE_KNOBS[dev.device_kind]
        else:
            raise ValueError(
                f"no hardware knobs for device kind {dev.device_kind!r} "
                f"({dev.platform}); known: {sorted(DEVICE_KNOBS)}. Add its "
                "published peaks to costmodel.DEVICE_KNOBS or point "
                "REPRO_HW_CONFIG at a knob file.")
    return _HARDWARE


def set_hardware(hw: "HardwareConfig | Mapping | str | None"
                 ) -> HardwareConfig:
    """Install a hardware config (object, knob dict, or JSON path).
    ``None`` resets to the environment default. Returns the active config.
    Decisions derived from the model (autotune winners, tile sizes) are
    re-evaluated lazily — clear the autotune memo to force new decisions.
    """
    global _HARDWARE
    if hw is None:
        _HARDWARE = None
        return get_hardware()
    if isinstance(hw, str):
        hw = HardwareConfig.from_json(hw)
    elif isinstance(hw, Mapping):
        hw = HardwareConfig.from_dict(hw)
    _HARDWARE = hw
    return hw


def make_report(kind: str, payload: Mapping,
                hw: Optional[HardwareConfig] = None) -> dict:
    """Shared machine-readable envelope for every cost/roofline/dry-run
    report: ``{"schema", "kind", "hardware", **payload}``."""
    hw = hw or get_hardware()
    out = {"schema": SCHEMA, "kind": kind, "hardware": hw.to_dict()}
    out.update(payload)
    return out


# ---------------------------------------------------------------------------
# per-backend flops / bytes-accessed model
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CostFeatures:
    """Structural features of one SpMV problem (per batch member).

    ``nnz`` is the *true* COO edge count when known. The blocked paths
    compute every ELL slot (``n_rb * max_nbr`` dense tiles, padding
    included) but the per-edge ``csr`` path touches only the real edges
    — on hub-heavy kNN graphs that is a 10-50x work gap the model must
    see, or it never predicts csr winning. ``None`` falls back to the
    dense-equivalent count (every ELL slot full)."""
    capacity: int
    bs: int
    sb: int
    n_rb: int
    n_cb: int
    max_nbr: int
    f: int = 1                     # charge feature columns
    batch: int = 1                 # stacked lanes (PlanBatch)
    nnz: Optional[int] = None      # true COO edges (csr path work)


def plan_features(shape_key: Tuple[int, ...], f: int = 1,
                  batch: int = 1,
                  nnz: Optional[int] = None) -> CostFeatures:
    """``PlanSpec.shape_key`` -> :class:`CostFeatures`."""
    capacity, bs, sb, n_rb, n_cb, max_nbr = shape_key
    return CostFeatures(capacity=capacity, bs=bs, sb=sb, n_rb=n_rb,
                        n_cb=n_cb, max_nbr=int(max_nbr or 0), f=f,
                        batch=batch, nnz=nnz)


def backend_cost(feat: CostFeatures, backend: str,
                 hw: Optional[HardwareConfig] = None, *,
                 interpret: bool = False, n_dev: int = 1,
                 exchange_blocks: int = 0) -> dict:
    """Closed-form flops / HBM bytes / seconds for one backend.

    The roofline estimate is ``max(flops/peak, bytes/hbm_bw)`` plus the
    per-launch overhead and (``dist`` only) the link time of the halo
    exchange. Absolute seconds are calibrated by the autotune (one probe
    per backend, memoized); *relative* order across shapes and hardware
    configs is what the model owns.
    """
    hw = hw or get_hardware()
    B = feat.batch
    tiles = B * feat.n_rb * max(feat.max_nbr, 1)
    flops = 2.0 * tiles * feat.bs * feat.bs * feat.f
    tile_bytes = tiles * feat.bs * feat.bs * _ELEM
    seg_bytes = tiles * feat.bs * feat.f * _ELEM
    out_bytes = B * feat.n_rb * feat.bs * feat.f * _ELEM
    idx_bytes = tiles * _IDX
    link_bytes = 0.0
    launches = 1.0
    edge_s = 0.0
    if backend == "csr":
        # per-edge path over the TRUE nonzeros (the blocked paths pay for
        # every ELL slot; csr skips the padding entirely): each edge moves
        # an index pair and a value, and both the x-gather and the
        # y-scatter-add are irregular (penalized)
        nnz = B * (feat.nnz if feat.nnz is not None
                   else feat.n_rb * max(feat.max_nbr, 1)
                   * feat.bs * feat.bs)
        flops = 2.0 * nnz * feat.f
        hbm = nnz * (_ELEM + 2 * _IDX) \
            + hw.gather_penalty * nnz * 2 * feat.f * _ELEM + out_bytes
        # scatter-adds serialize per edge on top of the byte traffic
        edge_s = nnz * hw.edge_cost
    elif backend == "bsr":
        # one flat kernel; the segment gather indexes the whole charge
        # vector (penalized — XLA gathers run far off the streaming roof)
        hbm = tile_bytes + hw.gather_penalty * seg_bytes + out_bytes \
            + idx_bytes
    elif backend == "bsr_ml":
        # superblock stripes keep each step's gather window resident, so
        # segments stream at full bandwidth — paid for by one dispatched
        # scan step per stripe
        hbm = tile_bytes + seg_bytes + out_bytes + idx_bytes
        launches = float(max(-(-feat.n_rb // max(feat.sb, 1)), 1))
    elif backend == "pallas":
        # fused gather: column indices are scalar-prefetched and segments
        # are cut from the VMEM-resident charge block, so nothing
        # round-trips HBM between gather and dot
        hbm = tile_bytes + seg_bytes + out_bytes + idx_bytes
    elif backend == "dist":
        hbm = (tile_bytes + hw.gather_penalty * seg_bytes + out_bytes) \
            / max(n_dev, 1)
        flops /= max(n_dev, 1)
        link_bytes = float(exchange_blocks) * feat.bs * _ELEM
    else:
        # unknown backends get the generic flat-path estimate
        hbm = tile_bytes + hw.gather_penalty * seg_bytes + out_bytes \
            + idx_bytes
    seconds = max(flops / hw.peak_flops, hbm / hw.hbm_bw) \
        + launches * hw.launch_overhead + link_bytes / hw.link_bw + edge_s
    if backend == "pallas" and interpret:
        seconds *= hw.interpret_penalty
    return {"backend": backend, "flops": flops, "hbm_bytes": hbm,
            "link_bytes": link_bytes, "launches": launches,
            "seconds": seconds}


def rank_backends(feat: CostFeatures, names: Iterable[str], *,
                  hw: Optional[HardwareConfig] = None,
                  calibration: Optional[Mapping[str, float]] = None,
                  interpret: bool = False, n_dev: int = 1) -> dict:
    """Analytic ranking of ``names`` on ``feat`` — a machine-readable
    report (shared envelope) carrying the per-backend cost breakdown, the
    calibrated predicted seconds, and the ranking.

    ``calibration`` maps backend name -> measured/modeled ratio (from one
    probe, memoized by the autotune); missing backends rank with ratio
    1.0, non-finite ratios (probe failed / skipped) are excluded.
    """
    hw = hw or get_hardware()
    calibration = calibration or {}
    costs: Dict[str, dict] = {}
    predicted: Dict[str, float] = {}
    for name in names:
        ratio = float(calibration.get(name, 1.0))
        if ratio != ratio or ratio == float("inf"):   # NaN or inf: excluded
            continue
        c = backend_cost(feat, name, hw, interpret=interpret, n_dev=n_dev)
        costs[name] = c
        predicted[name] = ratio * c["seconds"]
    ranking = sorted(predicted, key=predicted.get)
    return make_report("backend_rank", {
        "features": dataclasses.asdict(feat),
        "costs": costs,
        "calibration": {k: calibration.get(k) for k in predicted},
        "predicted_s": predicted,
        "ranking": ranking,
        "winner": ranking[0] if ranking else None,
    }, hw)


# ---------------------------------------------------------------------------
# iterative-solver pricing (repro.solvers: CG on the plan matvec)
# ---------------------------------------------------------------------------


def _precond_cost(feat: CostFeatures, precond: str,
                  hw: HardwareConfig) -> Tuple[float, float, float, float]:
    """(setup_flops, setup_bytes, apply_flops, apply_bytes) of one
    preconditioner on one solve. Setup runs once per solve (inside the
    solver kernel); apply runs every iteration."""
    B, f = feat.batch, feat.f
    vec = B * feat.capacity * f * _ELEM
    if precond == "block_jacobi":
        blocks = B * feat.n_rb
        # extraction reads every ELL tile once; Cholesky is bs^3/3 per
        # block; each apply is two triangular solves (bs^2 flops per rhs
        # column) streaming the factors
        setup_flops = blocks * feat.bs ** 3 / 3.0
        setup_bytes = B * feat.n_rb * max(feat.max_nbr, 1) \
            * feat.bs * feat.bs * _ELEM
        apply_flops = 2.0 * blocks * feat.bs ** 2 * f
        apply_bytes = blocks * feat.bs * feat.bs * _ELEM + 2 * vec
        return setup_flops, setup_bytes, apply_flops, apply_bytes
    if precond == "jacobi":
        setup_bytes = B * feat.n_rb * max(feat.max_nbr, 1) \
            * feat.bs * feat.bs * _ELEM        # diagonal still reads tiles
        return 0.0, setup_bytes, B * feat.capacity * f, 3 * vec
    # identity / unknown: free
    return 0.0, 0.0, 0.0, 0.0


def solver_cost(feat: CostFeatures, backend: str, *,
                iters: int, precond: str = "block_jacobi",
                hw: Optional[HardwareConfig] = None,
                interpret: bool = False, n_dev: int = 1) -> dict:
    """Closed-form cost of one (batched) CG solve: ``setup + iters *
    per_iteration``.

    Per iteration: one backend matvec (:func:`backend_cost` — the
    dominant term, which is why solver backend choice is *inherited*
    from :func:`rank_backends`), one preconditioner apply, and the CG
    vector work (axpys + dots, ~10 streamed vector passes per
    iteration). Setup: the preconditioner factorization. The ``iters``
    estimate is the caller's (telemetry from a prior solve, or a bound
    from the expected conditioning).
    """
    hw = hw or get_hardware()
    mv = backend_cost(feat, backend, hw, interpret=interpret, n_dev=n_dev)
    su_f, su_b, ap_f, ap_b = _precond_cost(feat, precond, hw)
    vec = feat.batch * feat.capacity * feat.f * _ELEM
    cg_bytes = 10.0 * vec                   # x/r/z/p updates + two dots
    cg_flops = 10.0 * feat.batch * feat.capacity * feat.f
    iter_s = mv["seconds"] \
        + max(ap_f / hw.peak_flops, (ap_b + cg_bytes) / hw.hbm_bw)
    setup_s = max(su_f / hw.peak_flops, su_b / hw.hbm_bw) \
        + hw.launch_overhead
    total = setup_s + iters * iter_s
    return {"backend": backend, "precond": precond, "iters": iters,
            "matvec": mv,
            "setup_flops": su_f, "setup_bytes": su_b,
            "iter_flops": mv["flops"] + ap_f + cg_flops,
            "iter_bytes": mv["hbm_bytes"] + ap_b + cg_bytes,
            "setup_seconds": setup_s, "iter_seconds": iter_s,
            "seconds": total}


def rank_solver_backends(feat: CostFeatures, names: Iterable[str], *,
                         iters: int, precond: str = "block_jacobi",
                         hw: Optional[HardwareConfig] = None,
                         calibration: Optional[Mapping[str, float]] = None,
                         interpret: bool = False, n_dev: int = 1) -> dict:
    """Analytic solver-backend ranking — the ``repro.cost/v1`` envelope,
    kind ``"solver_rank"``. The preconditioner and CG terms are
    backend-independent, so the induced ranking matches
    :func:`rank_backends` on the same features (the matvec owns the
    iteration); what this report adds is honest absolute totals: setup
    amortization and the per-iteration floor the solver pays on top of
    the SpMV."""
    hw = hw or get_hardware()
    calibration = calibration or {}
    costs: Dict[str, dict] = {}
    predicted: Dict[str, float] = {}
    for name in names:
        ratio = float(calibration.get(name, 1.0))
        if ratio != ratio or ratio == float("inf"):
            continue
        c = solver_cost(feat, name, iters=iters, precond=precond, hw=hw,
                        interpret=interpret, n_dev=n_dev)
        costs[name] = c
        predicted[name] = c["setup_seconds"] \
            + iters * (ratio * c["matvec"]["seconds"]
                       + c["iter_seconds"] - c["matvec"]["seconds"])
    ranking = sorted(predicted, key=predicted.get)
    return make_report("solver_rank", {
        "features": dataclasses.asdict(feat),
        "iters": iters,
        "precond": precond,
        "costs": costs,
        "calibration": {k: calibration.get(k) for k in predicted},
        "predicted_s": predicted,
        "ranking": ranking,
        "winner": ranking[0] if ranking else None,
    }, hw)


# ---------------------------------------------------------------------------
# decode-attention pricing (serve tick: models.attention decode backends)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class DecodeFeatures:
    """Structural features of one plan-decode step (whole batch).

    ``s`` is the plan capacity (padded cache length), ``bk`` the tile
    edge, ``n_sel`` the top-c tiles attended per head. The work is
    identical across backends — what differs is how often the selected
    tiles cross HBM and how many launches a tick pays."""
    batch: int
    hq: int
    hkv: int
    s: int
    dh: int
    dv: int
    bk: int
    n_sel: int


def decode_cost(feat: DecodeFeatures, backend: str,
                hw: Optional[HardwareConfig] = None, *,
                interpret: bool = False) -> dict:
    """Closed-form flops / HBM bytes / seconds for one decode backend.

    Both paths score every centroid and attend the same ``n_sel * bk``
    selected rows per (member, kv head). The unfused ``xla`` path pays
    three dispatches (select top-k, gather, attend) and its vmapped tile
    gather is irregular (``gather_penalty``) AND materializes the
    selection back through HBM before the attend re-reads it. The fused
    ``pallas`` kernel is one launch and DMAs each selected tile from HBM
    exactly once, straight into VMEM scratch — but under ``interpret=True``
    (the CPU container) it eats ``interpret_penalty``, which is why
    ``"auto"`` keeps the service on ``xla`` in CI and flips to the kernel
    on a real MXU.
    """
    hw = hw or get_hardware()
    bh = feat.batch * feat.hkv
    nkb = max(feat.s // max(feat.bk, 1), 1)
    sel_rows = bh * feat.n_sel * feat.bk
    sel_bytes = sel_rows * (feat.dh + feat.dv) * _ELEM
    cent_bytes = bh * nkb * feat.dh * _ELEM
    ps_bytes = bh * feat.s * _IDX
    q_bytes = feat.batch * feat.hq * feat.dh * _ELEM
    out_bytes = feat.batch * feat.hq * feat.dv * _ELEM
    flops = 2.0 * bh * nkb * feat.dh \
        + 2.0 * feat.batch * feat.hq * feat.n_sel * feat.bk \
        * (feat.dh + feat.dv)
    base = cent_bytes + ps_bytes + q_bytes + out_bytes
    if backend == "pallas":
        hbm = base + sel_bytes
        launches = 1.0
    else:
        # gather round-trip: irregular read, HBM write-back of the
        # gathered tiles, then the attend streams them back in
        hbm = base + hw.gather_penalty * sel_bytes + 2 * sel_bytes
        launches = 3.0
    seconds = max(flops / hw.peak_flops, hbm / hw.hbm_bw) \
        + launches * hw.launch_overhead
    if backend == "pallas" and interpret:
        seconds *= hw.interpret_penalty
    return {"backend": backend, "flops": flops, "hbm_bytes": hbm,
            "launches": launches, "seconds": seconds}


def rank_decode_backends(feat: DecodeFeatures,
                         names: Iterable[str] = ("xla", "pallas"), *,
                         hw: Optional[HardwareConfig] = None,
                         interpret: bool = False) -> dict:
    """Analytic ranking of decode backends on ``feat`` — the same
    ``repro.cost/v1`` envelope as :func:`rank_backends`, so plan-mode
    backend choice is inspectable with the SpMV tooling."""
    hw = hw or get_hardware()
    costs: Dict[str, dict] = {}
    predicted: Dict[str, float] = {}
    for name in names:
        c = decode_cost(feat, name, hw, interpret=interpret)
        costs[name] = c
        predicted[name] = c["seconds"]
    ranking = sorted(predicted, key=predicted.get)
    return make_report("decode_rank", {
        "features": dataclasses.asdict(feat),
        "costs": costs,
        "predicted_s": predicted,
        "ranking": ranking,
        "winner": ranking[0] if ranking else None,
    }, hw)


_DECODE_CHOICE: Dict[Tuple, str] = {}


def choose_decode_backend(feat: DecodeFeatures, *,
                          interpret: bool = False,
                          hw: Optional[HardwareConfig] = None) -> str:
    """The model's winner for one decode shape, memoized per (shape,
    interpret, hardware) — the serve loop calls this every tick and the
    answer must not cost a ranking each time."""
    hw = hw or get_hardware()
    key = (feat, bool(interpret), hw)
    got = _DECODE_CHOICE.get(key)
    if got is None:
        got = rank_decode_backends(feat, hw=hw,
                                   interpret=interpret)["winner"]
        _DECODE_CHOICE[key] = got
    return got


# ---------------------------------------------------------------------------
# exchange pricing (core.shardplan halo-vs-ring-vs-allgather)
# ---------------------------------------------------------------------------


def exchange_cost(transfer_blocks: "int | None", bs: int,
                  hw: Optional[HardwareConfig] = None) -> Optional[float]:
    """Seconds to move ``transfer_blocks`` charge blocks of ``bs`` float32
    charges over the configured interconnect (``None`` passes through —
    infeasible exchange candidates stay infeasible)."""
    if transfer_blocks is None:
        return None
    hw = hw or get_hardware()
    return float(transfer_blocks) * bs * _ELEM / hw.link_bw


# ---------------------------------------------------------------------------
# Pallas tile sizing (kernels.ops batch-grid kernel)
# ---------------------------------------------------------------------------


def _vmem_bytes(rows: int, cols: int) -> float:
    """VMEM bytes of one float32 ``(rows, cols)`` tile, padded to the
    (8, 128) register tiling."""
    return -(-rows // 8) * 8 * -(-cols // 128) * 128 * _ELEM


def choose_tiles(shape_key: Tuple[int, ...], f: int = 1,
                 hw: Optional[HardwareConfig] = None
                 ) -> Tuple[int, int, int]:
    """Batch-grid tile sizes ``(rbs, chunk, fc)`` under the VMEM knob.

    ``rbs`` row blocks ride one grid step (amortizing grid overhead),
    ``chunk`` ELL slots are contracted per step as one ``(bs, chunk*bs) @
    (chunk*bs, fc)`` panel matmul per row block, and charges are tiled to
    ``fc`` feature columns, a multiple of 128 lanes. ``chunk`` obeys the
    kernel's lane rule (``kernels.bsr_spmv.panel_chunk``): the whole ELL
    width when it fits, which needs no padding, else the width split as
    evenly as the budget allows into chunks of whole 128-lane panel
    columns. VMEM per step is counted with the (8, 128) padding: the
    double-buffered ``(bs, chunk*bs)`` panels, the gathered
    ``(chunk*bs, fc)`` charge segments and the double-buffered ``(bs,
    fc)`` output tiles of each row block, plus one more copy of one row
    block's dot operands (the float32 ``HIGHEST`` dot's scratch). Half
    the VMEM knob is the budget (headroom for the compiler's own
    scratch).
    """
    from repro.kernels.bsr_spmv import panel_chunk  # imports Pallas

    capacity, bs, sb, n_rb, n_cb, max_nbr = shape_key
    hw = hw or get_hardware()
    budget = hw.vmem_bytes / 2
    nbr = max(int(max_nbr or 1), 1)
    fc = -(-max(int(f), 1) // 128) * 128

    def fits(rbs: int, chunk: int, fc_: int) -> bool:
        panel = _vmem_bytes(bs, chunk * bs)
        segs = _vmem_bytes(chunk * bs, fc_)
        per_block = 2 * panel + segs + 2 * _vmem_bytes(bs, fc_)
        return rbs * per_block + panel + segs <= budget

    smallest = panel_chunk(nbr, bs, 1)
    while fc > 128 and not fits(1, smallest, fc):
        fc = max(128, (fc // 2) // 128 * 128)
    n_ch, chunk = 1, panel_chunk(nbr, bs)
    while chunk > smallest and not fits(1, chunk, fc):
        n_ch += 1
        chunk = panel_chunk(nbr, bs, -(-nbr // n_ch))
    rbs = 1
    while rbs * 2 <= min(max(n_rb, 1), 8) and fits(rbs * 2, chunk, fc):
        rbs *= 2
    return rbs, chunk, fc
