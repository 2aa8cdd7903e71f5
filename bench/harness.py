"""The generic half of a benchmark run: find the cell's parts by name, hold
the device to what the cell asks, time the window, read the trace, print.

Nothing here knows a configuration or a traffic kind. A kind module
(``bench/traffic/<kind>.py``) provides three functions:

  setup(ctx)               -> state   data, plan or weights; every shape the
                                      window uses is warmed here; its
                                      ``info`` dict is what metric readers
                                      see of the program's state
  window(ctx, state, s)    -> Window  calls the timed path for ``s`` seconds
                                      and keeps the answers it will check
  check(ctx, state, win)   -> [Check] compares kept answers with the plain
                                      reference, after the program's state
                                      is freed

A per-layer metric is ``bench/metrics/<name>.py`` with ``read(rec)``
returning a number or ``None`` (nothing to read: the metric is left out).
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np


class BenchError(Exception):
    """A run that cannot produce a result (no chip, a missing part)."""


@dataclasses.dataclass
class Check:
    """One number compared with its limit: passes when ``value <= limit``."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


@dataclasses.dataclass
class Window:
    """What one measured window did. ``e2e`` holds the end-to-end metrics
    the kind measured on the host clock; ``counts`` what metric readers
    need (calls, builds); ``answers`` what ``check`` compares."""
    attempted: int
    failed: int
    seconds: float
    e2e: Dict[str, float]
    counts: Dict[str, float] = dataclasses.field(default_factory=dict)
    answers: Any = None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    workload: dict
    end_to_end: List[dict]
    per_layer: List[dict]


@dataclasses.dataclass
class Ctx:
    """What a kind sees of the run."""
    cell: Cell
    seed: int
    rehearse: bool
    control: Optional[str]
    fault: Optional[str]

    @property
    def config(self) -> dict:
        """The configuration, with the workload's rehearsal sizes laid
        over it in a rehearsal."""
        cfg = dict(self.cell.config)
        if self.rehearse:
            cfg.update(self.cell.workload.get("rehearsal", {}))
        return cfg

    @property
    def params(self) -> dict:
        """The traffic's parameters, with the workload's rehearsal ones
        laid over them in a rehearsal."""
        params = dict(self.cell.workload["params"])
        if self.rehearse:
            params.update(self.cell.workload.get("rehearsal_params", {}))
        return params

    @property
    def limits(self) -> dict:
        return self.cell.workload["limits"]

    def rng(self, *stream: int) -> np.random.Generator:
        """A host generator for one named stream of this seed."""
        return np.random.default_rng(np.random.SeedSequence(
            [self.seed, *stream]))

    def key(self, stream: int):
        """A JAX key for one stream of this seed. The seed may exceed 32
        bits, so it goes through a SeedSequence, never straight into
        ``PRNGKey`` (which would drop the high bits)."""
        import jax
        words = np.random.SeedSequence([self.seed, stream]).generate_state(2)
        return jax.random.fold_in(jax.random.PRNGKey(int(words[0])),
                                  int(words[1]))

    @staticmethod
    def say(msg: str) -> None:
        print(f"bench: {msg}", flush=True)


@dataclasses.dataclass
class Record:
    """What a per-layer metric reader sees."""
    config: dict
    params: dict
    trace: Any                      # bench.trace.Summary
    peaks: dict
    info: Dict[str, Any]            # what the kind reports of its state


# -- finding the cell's parts ---------------------------------------------


def _load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise BenchError(f"missing {path}") from None


def load_cell(root: Path, name: str) -> Cell:
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(root / configs[w["config"]]["file"])
    workload = _load_json(root / "bench" / "workloads" / f"{name}.json")
    # a metric listed for the cell, or (with no list) for every cell
    # that reports the end-to-end metric it moves
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"]
                                  in e2e_names else [])]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                workload=workload, end_to_end=e2e, per_layer=per_layer)


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise BenchError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def import_program(root: Path) -> None:
    """Put the checkout's own ``src`` first on the path and make sure the
    system under test comes from there: a checkout without it is no run."""
    src = root / "src"
    sys.path.insert(0, str(src))
    try:
        import repro
    except ImportError as e:
        raise BenchError(f"the system under test is missing: {e}") from None
    where = Path(repro.__path__[0]).resolve()
    if src.resolve() not in where.parents:
        raise BenchError(f"repro imported from {where}, not {src}")


# -- the device -------------------------------------------------------------


def require_chip(chips: int) -> list:
    """The cell's chips, or an error: never a fallback to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    from repro.kernels import ops
    if ops._interpret():
        raise BenchError("the Pallas kernels would run in interpret mode")
    return devs[:chips]


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says). Every program is kept,
    however quick its compile, so a second run of a cell compiles
    nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts programs lowered while armed: each new shape, whether its
    executable is compiled or read from the persistent cache, passes
    through lowering once. With the window armed it must read 0."""
    _EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",)

    def __init__(self):
        from jax import monitoring
        self.armed = False
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if self.armed and event in self._EVENTS:
            self.count += 1


def _pallas_traces() -> int:
    from repro.kernels import ops
    return sum(ops.PALLAS_TRACE_COUNTS.values())


def peak_bytes(devs) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def load_peaks(root: Path, device_kind: str) -> dict:
    """The chip's published peaks; a kind the table lacks is an error."""
    table = _load_json(root / "bench" / "peaks.json")
    if device_kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json (known: "
                         f"{sorted(table['devices'])})")
    return table["devices"][device_kind]


# -- the run ------------------------------------------------------------------


def _process_start() -> float:
    import psutil
    return psutil.Process().create_time()


def run(args, root: Path, t_start: float) -> int:
    cell = load_cell(root, args.workload)
    import_program(root)
    import jax

    t_proc = min(_process_start(), t_start)
    devs = (jax.devices()[:cell.chips] if args.rehearse
            else require_chip(cell.chips))
    dev = devs[0]
    peaks = None if args.rehearse else load_peaks(root, dev.device_kind)
    enable_compile_cache(root)
    counter = CompileCounter()
    kind_name = cell.workload["kind"]
    kind = _load_module(root / "bench" / "traffic" / f"{kind_name}.py",
                        f"bench_kind_{kind_name}")
    ctx = Ctx(cell=cell, seed=args.seed, rehearse=args.rehearse,
              control=args.control, fault=args.fault)
    ctx.say(f"cell {cell.name} seed {args.seed} on {dev.platform} "
            f"{dev.device_kind} x{len(devs)}")
    precision = ctx.config.get("matmul_precision")
    scope = (jax.default_matmul_precision(precision) if precision
             else contextlib.nullcontext())
    trace_dir = Path(tempfile.mkdtemp(prefix="bench-trace-")) \
        if args.trace else None
    try:
        with scope:
            state = kind.setup(ctx)
            setup_s = time.time() - t_proc
            ctx.say(f"set-up {setup_s:.3f} s")
            win = _measure(kind, ctx, state, args.seconds, counter,
                           trace_dir)
            peak = peak_bytes(devs)
            summary = None
            if trace_dir is not None:
                from bench import trace
                summary = trace.summarize(trace.find_xplane(trace_dir),
                                          window="bench/window")
            info = state.info
            checks = list(kind.check(ctx, state, win))
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    checks.append(Check("window_compiles", float(counter.count), 0.0))
    correct = all(c.ok for c in checks)
    rec = Record(config=ctx.config, params=ctx.params, trace=summary,
                 peaks=peaks, info=info)
    result = {
        "correct": correct,
        "attempted": int(win.attempted),
        "failed": int(win.failed),
        "metrics": ({} if args.rehearse else
                    _metrics(cell, win, setup_s, rec, root, args.trace)),
        "device": _device(dev, devs, peak, summary),
    }
    if summary is not None and not args.rehearse:
        result["breakdown"] = summary.breakdown()
    if args.rehearse:
        result["rehearsal"] = {"note": "tiny sizes, no metric",
                               "counts": win.counts}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    for c in checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


def _measure(kind, ctx: Ctx, state, seconds: float, counter: CompileCounter,
             trace_dir: Optional[Path]) -> Window:
    import jax
    traces0 = _pallas_traces()
    profile = contextlib.nullcontext()
    if trace_dir is not None:
        # device operations, and on the host only the spans the benchmark
        # and the runtime mark as important: a window of tens of seconds
        # stays a trace of a few MB
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level, opts.python_tracer_level = 1, 0
        profile = jax.profiler.trace(str(trace_dir), profiler_options=opts)
    with profile:
        counter.armed = True
        try:
            with jax.profiler.TraceAnnotation("bench/window"):
                win = kind.window(ctx, state, seconds)
        finally:
            counter.armed = False
    counter.count += _pallas_traces() - traces0
    return win


def _metrics(cell: Cell, win: Window, setup_s: float, rec: Record,
             root: Path, traced: int) -> dict:
    out = {}
    if not traced:
        for m in cell.end_to_end:
            value = setup_s if m["name"] == "setup_s" else \
                win.e2e.get(m["name"])
            if value is None:
                raise BenchError(f"the window measured no {m['name']}")
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out
    for m in cell.per_layer:
        reader = _load_module(root / "bench" / "metrics" / f"{m['name']}.py",
                              "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(rec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def _device(dev, devs, peak, summary) -> dict:
    out = {"platform": dev.platform, "kind": dev.device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    if summary is not None:
        out["busy_s"] = summary.busy_s
        out["window_s"] = summary.window_s
    return out


def call_until(seconds: float, fn: Callable[[int], None],
               annotation: str) -> tuple:
    """Call ``fn(i)`` (which must wait for its own result) until
    ``seconds`` have passed; the call in flight at the close completes and
    counts. Returns ``(calls, elapsed seconds, per-call seconds)``."""
    import jax
    t0 = time.perf_counter()
    durs = []
    i = 0
    while True:
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(annotation):
            fn(i)
        now = time.perf_counter()
        durs.append(now - t)
        i += 1
        if now - t0 >= seconds:
            return i, now - t0, durs
