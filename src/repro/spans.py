"""Named host spans at the program's layer boundaries.

``span("admit.plans", layers=24)`` opens ``repro/admit.plans`` as a
``jax.profiler.TraceAnnotation``: while a profiler trace is recording, the
span lands on the host plane of that trace, on the same clock as the
device's operations, with its keyword arguments as event stats. With no
trace recording it only checks whether one is. The program records time
in no other way; whoever wants a duration reads it from a trace.
"""
from __future__ import annotations

import jax


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """Context manager for the host span ``repro/<name>``; ``args`` are
    counts at that boundary (sizes, ids), kept as the event's stats."""
    return jax.profiler.TraceAnnotation("repro/" + name, **args)
