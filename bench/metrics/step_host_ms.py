"""Host time of the training step per global batch: the program's
``ltp.step.inputs`` (the batch, masks, fractions and learning rate made
device arrays) and ``ltp.step.dispatch`` (the jitted call, until it is
enqueued) spans on the window's host thread."""
from bench import spans as sp


def read(mi):
    if mi.trace is None:
        return None
    ivs = sp.intervals(mi.trace, sp.STEP)
    return sp.per_batch_ms(mi, None if ivs is None else sp.length_ns(ivs))
