"""Work counts from shapes, and shares of the chip's peaks.

A roofline share is the least time the chip could take for the work,
``max(bytes / peak bandwidth, flops / peak FLOP/s)``, over the time the
device was busy with it. The work counted is the algorithm's own, whatever
layout or backend carries it, so that a layout that moves less shows.
"""
from __future__ import annotations


def interact_work(n: int, k: int, f: int, itemsize: int = 4) -> tuple:
    """(flops, bytes) of one interaction ``y = A x`` with ``k`` neighbours
    per row and ``f`` charges: a multiply and an add per edge and charge;
    the ``n k`` values read once, the charges read once and the answers
    written once."""
    flops = 2 * n * k * f
    nbytes = n * k * itemsize + 2 * n * f * itemsize
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: compute or bandwidth bound."""
    return max(nbytes / peaks["hbm_bytes_per_s"],
               flops / peaks["flops_per_s"])


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: dict) -> float:
    """Percent of the roofline reached in ``seconds`` of device time."""
    return 100.0 * roofline_seconds(flops, nbytes, peaks) / seconds


def ell_entries(n_rb: int, max_nbr: int, bs: int) -> int:
    """Entries of the stored ELL tiles, padding included."""
    return n_rb * max_nbr * bs * bs
