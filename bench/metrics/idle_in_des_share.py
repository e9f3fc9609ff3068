"""Share of the traced window in which chip 0 runs no op while the host
is in the self time of the runtime's event loop (``ltp.sim.run`` minus
the program spans nested in it): the device waiting for the DES."""
from bench import spans as sp


def read(mi):
    if mi.trace is None or not mi.trace.chip_ops(0):
        return None
    own = sp.self_intervals(mi.trace, sp.SIM_RUN)
    if own is None:
        return None
    idle = sp.idle_intervals(mi.trace, 0)
    return 100.0 * sp.overlap_ns(own, idle) / mi.trace.window_ns
