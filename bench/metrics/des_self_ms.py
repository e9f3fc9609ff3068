"""Host self time of the runtime's event loop per global batch: the
program's ``ltp.sim.run`` span minus the program spans nested in it
(``ltp.masks``, ``ltp.step.inputs``, ``ltp.step.dispatch``), on the
window's host thread: the DES and the Python around it."""
from bench import spans as sp


def read(mi):
    if mi.trace is None:
        return None
    own = sp.self_intervals(mi.trace, sp.SIM_RUN)
    return sp.per_batch_ms(mi, None if own is None else sp.length_ns(own))
