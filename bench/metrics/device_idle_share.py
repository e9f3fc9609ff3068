"""Share of the traced window in which no operation runs on chip 0:
1 - (union of its op intervals) / window."""
from bench import trace as tr


def read(mi):
    if mi.trace is None:
        return None
    try:
        return 100.0 * tr.idle_share(mi.trace, 0)
    except tr.NotInTrace:
        return None
