"""``mla_decode_roofline``: the work of the window's decode ticks
(``bench/work_mla.py``: weights outside the routed experts once per tick,
the held experts the ticks reached, the latent rows attended) over the
device seconds of the service's plan-decode program in the traced window,
as a share of the chip's roofline (``bench/work.py``)."""
from bench import work, work_mla


def read(rec):
    info = rec.info
    if not info.get("decode_ticks") or "decode_module" not in info:
        return None
    seconds = rec.trace.module_seconds(info["decode_module"])
    if seconds <= 0:
        return None
    flops, nbytes = work_mla.decode_work(rec.config, info)
    return work.roofline_share(flops, nbytes, seconds, rec.peaks)
