"""``knn_device_ms``: device time of the ``knn_graph`` program per build
in the traced window, from its ``XLA Modules`` events."""

PROGRAM = "jit_knn_graph"


def read(rec):
    builds = rec.trace.calls("bench/build")
    seconds = rec.trace.module_seconds(PROGRAM)
    if not builds or seconds <= 0:
        return None
    return seconds / builds * 1e3
