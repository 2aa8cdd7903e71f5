"""Tail statistics of served requests, timed from when each was due.

An open-loop client sends each request at its due time whatever the
service is doing, so a request's wait counts from then, not from when the
service took it in. A request that never answered ranks above every one
that did: it can never improve a tail.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence


def nearest_rank(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile by nearest rank: the smallest value with at
    least ``q`` percent of the values at or below it."""
    if not values:
        raise ValueError("no values")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def due_percentile(due: Sequence[float], first: Sequence[Optional[float]],
                   q: float, gave_up: float) -> float:
    """The ``q``-th percentile of ``first - due`` over all requests due.
    ``first`` is ``None`` for a request that never answered; it ranks above
    every answered one, and where the percentile falls on such a request
    the value is the longest wait seen, ``gave_up - due``, which the true
    wait exceeds."""
    waits = [math.inf if f is None else f - d for d, f in zip(due, first)]
    value = nearest_rank(waits, q)
    if math.isinf(value):
        return max(gave_up - d for d, f in zip(due, first) if f is None)
    return value
