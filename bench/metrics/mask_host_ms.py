"""Host time of the delivery masks per global batch: the program's
``ltp.masks`` spans (the DES shards' masks tiled onto the packet plan,
or the analytic gather draw and the Early-Close controller) on the
window's host thread."""
from bench import spans as sp


def read(mi):
    if mi.trace is None:
        return None
    ivs = sp.intervals(mi.trace, (sp.MASKS,))
    return sp.per_batch_ms(mi, None if ivs is None else sp.length_ns(ivs))
