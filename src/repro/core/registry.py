"""SpMV backend registry — the pluggable compute layer of an InteractionPlan.

Replaces the old string dispatch in ``core.interact.spmv`` with a registry
keyed by backend name. A backend is a callable

    fn(plan: InteractionPlan, x: jax.Array, **kwargs) -> jax.Array

computing ``y = A x`` in the plan's (cluster-ordered) index space. Built-in
backends register themselves on first use:

  csr       per-edge gather baseline           (core.interact, needs COO)
  bsr       flat single-level block path       (core.interact)
  bsr_ml    multi-level superblock scan        (core.interact)
  pallas    MXU Pallas kernel                  (kernels.ops)
  dist      row-block-sharded SpMV with halo   (core.dist -> core.shardplan;
            exchange for the charge window      shards memoized on the plan)

``core.autotune.tune_backend`` probes this registry to resolve
``backend="auto"`` — device-count-aware: on multi-device meshes ``dist``
wins whenever its halo analysis moves less charge than replication. User
code can ``register_backend`` custom paths and they become visible to
autotuning and ``plan.apply`` immediately.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple


class NotApplicable(ValueError):
    """A backend's typed refusal: it does not apply to this plan or charge
    (a plan with no COO for ``csr``, an (n, f) charge for the 1-D-only
    ``dist`` path). Autotune probes skip a backend that raises this; any
    other exception is a fault and propagates."""


_BACKENDS: Dict[str, Callable] = {}
_BATCHED: Dict[str, Callable] = {}
_DECODE: Dict[str, Callable] = {}
_PRECOND: Dict[str, Callable] = {}
_DEFAULTS_LOADED = False
_DECODE_LOADED = False
_PRECOND_LOADED = False

# modules that register the built-in backends at import time
_DEFAULT_PROVIDERS = ("repro.core.interact", "repro.kernels.ops",
                      "repro.core.dist")
# modules that register the built-in DECODE backends; a separate latch so
# importing the SpMV providers never drags the model stack in, and vice
# versa
_DECODE_PROVIDERS = ("repro.models.attention", "repro.kernels.ops")
# modules that register the built-in PRECONDITIONERS (repro.solvers); its
# own latch keeps the solver subsystem out of plain SpMV imports
_PRECOND_PROVIDERS = ("repro.solvers.precond",)


def register_backend(name: str, fn: Callable | None = None, *,
                     overwrite: bool = False):
    """Register ``fn`` as SpMV backend ``name`` (usable as a decorator).

    Re-registering an existing name raises unless ``overwrite=True`` —
    a silent overwrite turns two libraries picking the same name into a
    wrong-answer bug instead of an import-time error. Re-registering the
    *same* callable is a no-op (module re-imports are harmless).
    """

    def _register(f: Callable) -> Callable:
        prev = _BACKENDS.get(name)
        if prev is not None and prev is not f and not overwrite:
            raise ValueError(
                f"SpMV backend {name!r} is already registered "
                f"({prev.__module__}.{prev.__qualname__}); pass "
                "overwrite=True to replace it deliberately")
        _BACKENDS[name] = f
        return f

    return _register if fn is None else _register(fn)


def register_batched_backend(name: str, fn: Callable | None = None, *,
                             overwrite: bool = False):
    """Register the *batched* implementation of backend ``name``.

    A batched backend is ``fn(spec: PlanSpec, data: PlanData, xs) -> ys``
    computing the cluster-order interaction for a whole stacked batch
    (leading axis) in one kernel. ``PlanBatch`` dispatches to it when
    present; backends without one fall back to a generic ``vmap`` of
    their single-plan path — correct, but XLA (CPU especially) lowers
    vmapped gathers poorly, so hot backends should register a real
    batched kernel (see ``core.interact.spmv_bsr_batched``).
    """

    def _register(f: Callable) -> Callable:
        prev = _BATCHED.get(name)
        if prev is not None and prev is not f and not overwrite:
            raise ValueError(
                f"batched SpMV backend {name!r} is already registered; "
                "pass overwrite=True to replace it deliberately")
        _BATCHED[name] = f
        return f

    return _register if fn is None else _register(fn)


def get_batched_backend(name: str) -> Callable | None:
    """The batched implementation of ``name``, or ``None`` when the
    backend only has a single-plan path (callers vmap it generically)."""
    _ensure_defaults()
    return _BATCHED.get(name)


def _ensure_defaults() -> None:
    global _DEFAULTS_LOADED
    if _DEFAULTS_LOADED:
        return
    import importlib

    for mod in _DEFAULT_PROVIDERS:
        importlib.import_module(mod)
    # only latch after every provider imported: a transient import failure
    # surfaces on this call and is retried on the next, instead of leaving
    # a silently partial registry
    _DEFAULTS_LOADED = True


def get_backend(name: str) -> Callable:
    _ensure_defaults()
    try:
        return _BACKENDS[name]
    except KeyError:
        import difflib

        close = difflib.get_close_matches(name, backend_names(), n=1,
                                          cutoff=0.5)
        hint = f" — did you mean {close[0]!r}?" if close else ""
        raise ValueError(
            f"unknown SpMV backend {name!r}{hint}; "
            f"registered: {backend_names()}"
        ) from None


def backend_names() -> Tuple[str, ...]:
    _ensure_defaults()
    return tuple(sorted(_BACKENDS))


# ---------------------------------------------------------------------------
# decode-attention backends (the serve tick's per-token attend)
# ---------------------------------------------------------------------------
#
# A decode backend is
#
#     fn(q, ks, vs, ps, cent, qpos, cfg, *, k_self=None, v_self=None) -> out
#
# computing ``clusterkv_plan_decode``'s contract over plan-ordered caches
# (see models.attention). Built-ins:
#
#   xla      unfused top-k select + vmapped tile gather + attend
#   pallas   fused Mosaic kernel (kernels.decode_attend) — selection,
#            gather, and softmax in one launch, tiles stream HBM once
#
# ``cfg.decode_backend == "auto"`` resolves through
# ``core.costmodel.choose_decode_backend`` against the same
# ``repro.cost/v1`` model that ranks the SpMV backends.


def register_decode_backend(name: str, fn: Callable | None = None, *,
                            overwrite: bool = False):
    """Register ``fn`` as decode-attention backend ``name`` (decorator-friendly)."""

    def _register(f: Callable) -> Callable:
        prev = _DECODE.get(name)
        if prev is not None and prev is not f and not overwrite:
            raise ValueError(
                f"decode backend {name!r} is already registered "
                f"({prev.__module__}.{prev.__qualname__}); pass "
                "overwrite=True to replace it deliberately")
        _DECODE[name] = f
        return f

    return _register if fn is None else _register(fn)


def _ensure_decode_defaults() -> None:
    global _DECODE_LOADED
    if _DECODE_LOADED:
        return
    import importlib

    for mod in _DECODE_PROVIDERS:
        importlib.import_module(mod)
    _DECODE_LOADED = True


def get_decode_backend(name: str) -> Callable:
    _ensure_decode_defaults()
    try:
        return _DECODE[name]
    except KeyError:
        import difflib

        close = difflib.get_close_matches(name, decode_backend_names(), n=1,
                                          cutoff=0.5)
        hint = f" — did you mean {close[0]!r}?" if close else ""
        raise ValueError(
            f"unknown decode backend {name!r}{hint}; "
            f"registered: {decode_backend_names()}"
        ) from None


def decode_backend_names() -> Tuple[str, ...]:
    _ensure_decode_defaults()
    return tuple(sorted(_DECODE))


# ---------------------------------------------------------------------------
# preconditioners (repro.solvers: the iterative-solver subsystem)
# ---------------------------------------------------------------------------
#
# A preconditioner is a FACTORY
#
#     fn(spec: PlanSpec, data: PlanData, shift: jax.Array) -> apply
#
# factoring an approximation of ``A' + shift*I`` (the plan operator in
# cluster order, diagonal-shifted) and returning ``apply(r) -> z`` with
# ``z ~= (A' + shift I)^-1 r`` over cluster-ordered residuals ``r`` of
# shape (..., capacity) or (..., capacity, f). Factories are called
# *inside* the jitted solver kernel — state (e.g. Cholesky factors of the
# diagonal tiles) is traced, the factory itself is resolved by (static)
# name, so one compiled solver serves a whole PlanBatch. Built-ins
# (registered by ``repro.solvers.precond``):
#
#   identity      no preconditioning (z = r)
#   jacobi        pointwise diagonal scaling
#   block_jacobi  batched Cholesky of the dense diagonal BSR tiles
#                 (dead/hole slots get identity rows, never singular ones)


def register_preconditioner(name: str, fn: Callable | None = None, *,
                            overwrite: bool = False):
    """Register ``fn`` as preconditioner factory ``name`` (decorator-friendly).

    Mirrors :func:`register_backend`: duplicate names raise unless
    ``overwrite=True``; re-registering the same callable is a no-op.
    """

    def _register(f: Callable) -> Callable:
        prev = _PRECOND.get(name)
        if prev is not None and prev is not f and not overwrite:
            raise ValueError(
                f"preconditioner {name!r} is already registered "
                f"({prev.__module__}.{prev.__qualname__}); pass "
                "overwrite=True to replace it deliberately")
        _PRECOND[name] = f
        return f

    return _register if fn is None else _register(fn)


def _ensure_precond_defaults() -> None:
    global _PRECOND_LOADED
    if _PRECOND_LOADED:
        return
    import importlib

    for mod in _PRECOND_PROVIDERS:
        importlib.import_module(mod)
    _PRECOND_LOADED = True


def get_preconditioner(name: str) -> Callable:
    _ensure_precond_defaults()
    try:
        return _PRECOND[name]
    except KeyError:
        import difflib

        close = difflib.get_close_matches(name, preconditioner_names(), n=1,
                                          cutoff=0.5)
        hint = f" — did you mean {close[0]!r}?" if close else ""
        raise ValueError(
            f"unknown preconditioner {name!r}{hint}; "
            f"registered: {preconditioner_names()}"
        ) from None


def preconditioner_names() -> Tuple[str, ...]:
    _ensure_precond_defaults()
    return tuple(sorted(_PRECOND))
