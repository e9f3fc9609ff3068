"""Device time of the runtime's fused bsp training program per global
batch: the XLA modules of ``step``, summed on chip 0 over the traced
window, over the global batches the window trained."""
from bench import trace as tr

MODULES = r"^jit_step\b"


def read(mi):
    if mi.trace is None or not mi.work.get("global_batches"):
        return None
    mods = tr.matching(mi.trace.chip_modules(0), MODULES)
    if not mods:
        return None
    return sum(e.dur for e in mods) * 1e-6 / mi.work["global_batches"]
