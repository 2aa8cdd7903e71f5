"""Traffic kind ``build_stream``: plans built one after another over
fresh point sets, as a user does who indexes each new batch of
descriptors.

Set-up makes a pool of point sets from the seed, each with its own
stream, and builds a plan over each once: a plan's tile shapes depend on
its data, so this compiles every shape the window will meet. The window
then calls ``api.build_plan`` over the pool in turn, from an offset drawn
from the seed, and waits for each plan's device arrays. The window closes
when the build in flight at its end completes.

The check reads the window's last plan through one matvec on random
charges and compares the answer with ``A x`` over the exact neighbour
sets of the points it was built from (``bench/reference/knn_spmv.py``):
a wrong neighbour, permutation or tile shows there.

Parameters: ``pool`` point sets, ``check_f`` charges in the check.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import data, plan as plans
from bench.harness import Check, Ctx, Window, call_until
from bench.reference import knn_spmv

CHARGES, OFFSET, FAULT = range(3)


@dataclasses.dataclass
class State:
    points: List[jax.Array]
    hosts: List[np.ndarray]
    build: Callable[[int], Any]
    info: Dict[str, Any]


def _ready(plan) -> None:
    b = plan.bsr
    jax.block_until_ready((plan.pi, plan.inv, b.vals, b.col_idx,
                           b.nbr_mask))


class _ReferencePlan:
    """The reference's own neighbour sets in a plan's place, from points
    one precision below the configuration's (rounded to bfloat16)."""

    def __init__(self, points: jax.Array, k: int):
        self.k = k
        self.cand = knn_spmv.candidates(
            points.astype(jnp.bfloat16).astype(jnp.float32), k)

    def matvec(self, x, backend=None):
        idx = jnp.asarray(self.cand[:, :self.k])
        return jnp.take(x, idx, axis=0).sum(1) / self.k


def setup(ctx: Ctx) -> State:
    cfg = ctx.config
    points = [data.points(cfg, member=j) for j in range(ctx.params["pool"])]
    hosts = [np.asarray(p) for p in points]
    k = cfg["plan"]["k"]
    if ctx.control == "bf16":
        def one(j):
            return _ReferencePlan(points[j], k)
    elif ctx.control is not None:
        raise ValueError(f"unknown control {ctx.control!r}")
    else:
        one = _faulty(ctx, hosts) if ctx.fault else (
            lambda j: plans.build(cfg, hosts[j]))
    info = {}
    for j in range(len(points)):                 # every tile shape warm
        plan = one(j)
        if hasattr(plan, "bsr"):
            _ready(plan)
            info = plans.info(plan)
            ctx.say(f"pool {j}: n_rb={info['n_rb']} "
                    f"max_nbr={info['max_nbr']} fill={info['fill']:.6f}")
    return State(points=points, hosts=hosts, build=one, info=info)


def _faulty(ctx: Ctx, hosts) -> Callable:
    """The build with a planted fault (tests of the check)."""
    cfg = ctx.config
    k = cfg["plan"]["k"]
    if ctx.fault == "unchanged":                 # hand back the plan of
        built = [plans.build(cfg, h) for h in hosts]   # the previous step
        return lambda j: built[j - 1]
    if ctx.fault == "half_neighbours":
        return lambda j: plans.build(
            cfg, hosts[j], plans.mean_values(k, "half_neighbours"))
    if ctx.fault == "altered":                   # one edge value 20 % off
        edge = int(ctx.rng(FAULT).integers(len(hosts[0]) * k))

        def values(rows, cols, d2):
            v = plans.mean_values(k)(rows, cols, d2)
            v[edge] *= 1.2
            return v
        return lambda j: plans.build(cfg, hosts[j], values)
    raise ValueError(f"unknown fault {ctx.fault!r}")


def window(ctx: Ctx, st: State, seconds: float) -> Window:
    pool = len(st.points)
    start = int(ctx.rng(OFFSET).integers(pool))
    last = {}

    def call(i):
        j = (start + i) % pool
        plan = st.build(j)
        if hasattr(plan, "bsr"):
            _ready(plan)
        last["plan"], last["j"] = plan, j

    builds, elapsed, durs = call_until(seconds, call, "bench/build")
    q = np.percentile(durs, [0, 25, 50, 75, 100])
    ctx.say("build seconds min/q1/median/q3/max " +
            " ".join(f"{v:.4f}" for v in q))
    return Window(attempted=builds, failed=0, seconds=elapsed,
                  e2e={"build_s": sum(durs) / builds},
                  counts={"builds": builds}, answers=last)


def check(ctx: Ctx, st: State, win: Window):
    plan, j = win.answers["plan"], win.answers["j"]
    win.answers = None
    n = ctx.config["n"]
    x = jax.random.normal(ctx.key(CHARGES), (n, ctx.params["check_f"]),
                          jnp.float32)
    y = np.asarray(plan.matvec(x, backend="bsr"))
    del plan
    points = st.points[j]
    st.build = None                          # free the program's state
    k = ctx.config["plan"]["k"]
    graph = knn_spmv.ExactGraph(st.hosts[j], knn_spmv.candidates(points, k),
                                k)
    err = graph.spmv_error(np.asarray(x), y)
    ctx.say(f"checked the last build (pool set {j}); {graph.tied_rows} "
            f"tied rows, {graph.widened} widened")
    return [Check("spmv_err", err, ctx.limits["spmv_err"])]
