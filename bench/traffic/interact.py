"""Traffic kind ``interact``: one plan, its interaction called over and over.

Set-up builds one ``InteractionPlan`` over the configuration's point set
(values 1/k: each row averages its k nearest neighbours) and warms
``plan.matvec`` on an ``(n, f)`` float32 charge block, which also lets
``backend="auto"`` settle. The window then feeds each answer back in as
the next charges, as a mean-shift iteration over the descriptors does:
every call depends on the last, and each is waited for.

The check compares a sample of the window's answers, drawn from the seed,
plus the last one, entry by entry with ``A x`` over the exact neighbour
sets (``bench/reference/knn_spmv.py``).

Parameters (``bench/workloads/<cell>.json``): ``f`` charges per call,
``early_samples`` answers checked from the window's first ``early_span``
calls.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import data, plan as plans
from bench.harness import Check, Ctx, Window, call_until
from bench.reference import knn_spmv

CHARGES, SAMPLE, FAULT = range(3)


@dataclasses.dataclass
class State:
    points: jax.Array
    plan: Any
    apply: Callable[[jax.Array], jax.Array]
    x0: jax.Array
    info: Dict[str, Any]


def setup(ctx: Ctx) -> State:
    cfg = ctx.config
    f = ctx.params["f"]
    points = data.points(cfg)
    k = cfg["plan"]["k"]
    plan = plans.build(cfg, np.asarray(points),
                       plans.mean_values(k, ctx.fault))
    x0 = jax.random.normal(ctx.key(CHARGES), (cfg["n"], f), jnp.float32)
    jax.block_until_ready(plan.matvec(x0))           # compile; auto settles
    backend = plan.resolve_backend(x=x0)
    apply = _timed_path(ctx, plan, points)
    jax.block_until_ready(apply(apply(x0)))          # every program warm
    info = plans.info(plan)
    ctx.say(f"plan n={info['n']} n_rb={info['n_rb']} "
            f"max_nbr={info['max_nbr']} fill={info['fill']:.6f}; "
            f"backend resolved to {backend}")
    return State(points=points, plan=plan, apply=apply, x0=x0,
                 info=dict(info, backend=backend))


def _timed_path(ctx: Ctx, plan, points) -> Callable:
    """``plan.matvec``, or what a control or a planted fault puts in its
    place."""
    if ctx.control == "bf16":
        cand = knn_spmv.candidates(points, ctx.config["plan"]["k"])
        return lambda x: knn_spmv.spmv_bf16(cand, ctx.config["plan"]["k"], x)
    if ctx.control is not None:
        raise ValueError(f"unknown control {ctx.control!r}")
    if ctx.fault == "unchanged":
        return lambda x: x + 0.0
    if ctx.fault == "altered":                   # one answer row 1 % off
        row = int(ctx.rng(FAULT).integers(plan.n))
        return lambda x: plan.matvec(x).at[row].multiply(1.01)
    if ctx.fault not in (None, "half_neighbours"):
        raise ValueError(f"unknown fault {ctx.fault!r}")
    return plan.matvec


def window(ctx: Ctx, st: State, seconds: float) -> Window:
    p = ctx.params
    picks = set(ctx.rng(SAMPLE).choice(p["early_span"], p["early_samples"],
                                       replace=False).tolist())
    kept, carry = {}, {"x": st.x0}

    def call(i):
        x = carry["x"]
        y = jax.block_until_ready(st.apply(x))
        if i in picks:
            kept[i] = (x, y)
        carry["x"], carry["last"] = y, (i, x, y)

    calls, elapsed, _ = call_until(seconds, call, "bench/matvec")
    i, x, y = carry["last"]
    kept[i] = (x, y)
    return Window(attempted=calls, failed=0, seconds=elapsed,
                  e2e={"matvec_ms": elapsed / calls * 1e3},
                  counts={"calls": calls}, answers=kept)


def check(ctx: Ctx, st: State, win: Window):
    answers = {i: (np.asarray(x), np.asarray(y))
               for i, (x, y) in win.answers.items()}
    win.answers = None
    points = st.points
    st.plan = st.apply = st.x0 = None           # free the program's state
    k = ctx.config["plan"]["k"]
    graph = knn_spmv.ExactGraph(np.asarray(points),
                                knn_spmv.candidates(points, k), k)
    worst = max(graph.spmv_error(x, y) for x, y in answers.values())
    ctx.say(f"checked answers of calls {sorted(answers)}; "
            f"{graph.tied_rows} tied rows, {graph.widened} widened")
    return [Check("spmv_err", worst, ctx.limits["spmv_err"])]
