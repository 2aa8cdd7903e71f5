"""``tile_fill``: stored neighbour entries over the entries of the ELL
tiles, padding included, in percent. A count from the plan's shapes: it
repeats exactly for one point set."""
from bench import work


def read(rec):
    info = rec.info
    if "max_nbr" not in info:
        return None
    entries = work.ell_entries(info["n_rb"], info["max_nbr"], info["bs"])
    return 100.0 * info["n"] * rec.config["plan"]["k"] / entries
