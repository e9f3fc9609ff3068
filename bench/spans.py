"""Host spans of the program in a trace: a named span's intervals, its
self intervals, and their overlap with the chip's idle time.

The program opens its spans (``ltp.*``) with ``TraceAnnotation`` on the
thread that runs the window, so they are in ``Trace.host`` as
``bench.trace.load`` gives it (events of 10 us or more). Names are
matched by their part before any ``#``. Every function works on plain
``bench.trace`` objects, so a test can build a trace by hand; a span
the trace lacks gives ``None``, never 0. Times are nanoseconds.
"""
from __future__ import annotations

from typing import Iterable, List, Optional, Sequence, Tuple

from bench import trace as tr

Intervals = List[Tuple[float, float]]

# The names are the program's (``repro.obs.spans``), written out here and
# not imported: a program that lacks them must read as None, not fail.
#: the prefix of every span the program opens
PROGRAM = "ltp."
#: the event loop of a run: every host cost of the window is inside it
SIM_RUN = "ltp.sim.run"
#: a round's delivery masks
MASKS = "ltp.masks"
#: the step's inputs and the call of its program
STEP = ("ltp.step.inputs", "ltp.step.dispatch")


def base(name: str) -> str:
    """A span's name without the metadata the profiler may append."""
    return name.split("#", 1)[0]


def intervals(trace: tr.Trace, names: Sequence[str]) -> Optional[Intervals]:
    """The union of the spans named ``names``, clipped to the window, or
    None where the trace has none of them."""
    evs = [e for e in trace.host if base(e.name) in names]
    if not evs:
        return None
    return tr.merge((e.start, e.end) for e in tr.clip(evs, *trace.window))


def subtract(a: Intervals, b: Intervals) -> Intervals:
    """``a`` minus ``b``; both sorted and disjoint, as ``merge`` gives."""
    out, j = [], 0
    for s, f in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < f:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if f > s:
            out.append((s, f))
    return out


def overlap_ns(a: Intervals, b: Intervals) -> float:
    """Length of the intersection of two sorted, disjoint lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        s, f = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if f > s:
            tot += f - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def length_ns(ivs: Iterable[Tuple[float, float]]) -> float:
    return sum(f - s for s, f in ivs)


def self_intervals(trace: tr.Trace, name: str) -> Optional[Intervals]:
    """The span ``name`` minus the union of the program's other spans
    (those nested in it), or None where the trace lacks it."""
    own = intervals(trace, (name,))
    if own is None:
        return None
    others = tr.merge(
        (e.start, e.end) for e in trace.host
        if base(e.name).startswith(PROGRAM) and base(e.name) != name)
    return subtract(own, others)


def idle_intervals(trace: tr.Trace, chip: int = 0) -> Intervals:
    """The window minus the union of chip ``chip``'s ops."""
    busy = tr.merge((e.start, e.end) for e in trace.chip_ops(chip))
    return subtract([trace.window], busy)


def per_batch_ms(mi, ns: Optional[float]) -> Optional[float]:
    """Nanoseconds of a traced window over the global batches it
    trained, in milliseconds; None where there is nothing to read."""
    if ns is None or not mi.work.get("global_batches"):
        return None
    return ns * 1e-6 / mi.work["global_batches"]
