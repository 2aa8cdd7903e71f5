"""``interact_roofline``: the interaction's own work per call over the
device's busy time per call in the traced window, as a share of the
chip's roofline (``bench/work.py``)."""
from bench import work


def read(rec):
    calls = rec.trace.calls("bench/matvec")
    if not calls or rec.trace.busy_s <= 0:
        return None
    flops, nbytes = work.interact_work(rec.config["n"],
                                       rec.config["plan"]["k"],
                                       rec.params["f"])
    return work.roofline_share(flops, nbytes, rec.trace.busy_s / calls,
                               rec.peaks)
