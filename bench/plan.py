"""How a configuration becomes an ``InteractionPlan``: one place that
every traffic kind over a point-set configuration builds through, so that
two kinds of one configuration time the same plan."""
from __future__ import annotations

from typing import Any, Dict

import numpy as np


def mean_values(k: int, fault: str = None):
    """Edge values 1/k (the plan averages its neighbours). The fault
    ``half_neighbours`` leaves every other edge out and doubles the rest:
    the mean taken over half the neighbours."""
    def values(rows, cols, d2):
        v = np.full(len(rows), 1.0 / k, np.float32)
        if fault == "half_neighbours":
            v[1::2] = 0.0
            v[0::2] *= 2.0
        return v
    return values


def build(config: dict, points_host: np.ndarray, values=None):
    """``api.build_plan`` over ``points_host`` with the configuration's
    plan settings; ``values`` defaults to :func:`mean_values`."""
    from repro import api
    pc = config["plan"]
    return api.build_plan(points_host, k=pc["k"], ordering=pc["ordering"],
                          bs=pc["bs"], sb=pc["sb"], backend=pc["backend"],
                          values=values or mean_values(pc["k"]))


def info(plan) -> Dict[str, Any]:
    """The plan's shapes that per-layer readers use."""
    b = plan.bsr
    return {"n": plan.n, "n_rb": b.n_rb, "max_nbr": b.max_nbr, "bs": b.bs,
            "fill": plan.fill}
