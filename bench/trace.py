"""Device-trace reduction: from a profiler ``.xplane.pb`` to busy time,
module time, the top device ops and the longest idle gaps.

The loader reads the file with ``jax.profiler.ProfileData`` alone. Every
reduction below works on plain ``Event`` lists, so a test can build a
trace by hand. All times are nanoseconds on the trace's clock.

    python bench/trace.py <file.xplane.pb>   # print the trace's layout
"""
from __future__ import annotations

import dataclasses
import glob
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: the benchmark's own host span around the measured window
WINDOW_SPAN = "bench.window"
#: host events shorter than this cannot name an idle gap worth listing
HOST_MIN_NS = 10_000


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float          # ns
    dur: float            # ns
    text: str = ""        # name and string stats, for matching

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    """What the reductions need: per-chip device ops and modules, host
    events, and the window span."""
    ops: Dict[int, List[Event]]        # chip -> XLA op events
    modules: Dict[int, List[Event]]    # chip -> XLA module events
    host: List[Event]                  # the window's host thread (TraceMe)
    window: Tuple[float, float]

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def chip_ops(self, chip: int = 0) -> List[Event]:
        return clip(self.ops.get(chip, []), *self.window)

    def chip_modules(self, chip: int = 0) -> List[Event]:
        return clip(self.modules.get(chip, []), *self.window)


class NotInTrace(LookupError):
    """A reduction found no event of the kind it reads."""


# ----------------------------------------------------------------------------
# interval arithmetic
# ----------------------------------------------------------------------------

def clip(events: Iterable[Event], t0: float, t1: float) -> List[Event]:
    """The parts of ``events`` inside [t0, t1]."""
    out = []
    for e in events:
        s, f = max(e.start, t0), min(e.end, t1)
        if f > s:
            out.append(Event(e.name, s, f - s, e.text))
    return out


def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of closed intervals as sorted, disjoint intervals."""
    out: List[List[float]] = []
    for s, f in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], f)
        else:
            out.append([s, f])
    return [(s, f) for s, f in out]


def union_ns(events: Iterable[Event]) -> float:
    return sum(f - s for s, f in merge((e.start, e.end) for e in events))


def busy_ns(trace: Trace, chip: int = 0) -> float:
    """Union of the intervals in which an op runs on ``chip``."""
    ops = trace.chip_ops(chip)
    if not ops:
        raise NotInTrace(f"no device op on chip {chip}")
    return union_ns(ops)


def idle_share(trace: Trace, chip: int = 0) -> float:
    """1 - busy / window, as a fraction."""
    return 1.0 - busy_ns(trace, chip) / trace.window_ns


def matching(events: Iterable[Event], pattern: str) -> List[Event]:
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e.text or e.name)]


_HLO_RE = re.compile(r"^(%?[\w.\-]+) = .*?\}\)? ([a-z][\w\-]*)\(")


def short_name(name: str) -> str:
    """An XLA op's event name is its HLO text; keep the instruction's
    name and opcode ("%fusion.18 fusion")."""
    m = _HLO_RE.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def top_ops(trace: Trace, k: int = 10, chip: int = 0) -> List[List]:
    """[[name, seconds], ...]: the ops that took most device time."""
    tot: Dict[str, float] = {}
    for e in trace.chip_ops(chip):
        n = short_name(e.name)
        tot[n] = tot.get(n, 0.0) + e.dur
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, v * 1e-9] for n, v in top]


def idle_gaps(trace: Trace, k: int = 10, chip: int = 0) -> List[List]:
    """[[name, seconds], ...]: the longest gaps between device ops, each
    named by the innermost event of the window's host thread that spans
    the gap's midpoint (what the host did while the chip waited), or
    "host" where none does (Python with no span of its own)."""
    t0, t1 = trace.window
    busy = merge((e.start, e.end) for e in trace.chip_ops(chip))
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, f in gaps[:k]:
        mid = 0.5 * (s + f)
        spans = [e for e in trace.host
                 if e.start <= mid <= e.end and e.name != WINDOW_SPAN]
        name = min(spans, key=lambda e: e.dur).name if spans else "host"
        out.append([name, (f - s) * 1e-9])
    return out


# ----------------------------------------------------------------------------
# loading
# ----------------------------------------------------------------------------

_CHIP_RE = re.compile(r"^/device:TPU:(\d+)")


def _event(e) -> Event:
    stats = []
    for kv in e.stats:
        v = kv[1]
        if isinstance(v, str):
            stats.append(v)
    return Event(e.name, float(e.start_ns), float(e.duration_ns),
                 " ".join([e.name] + stats))


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(path: str, chips: Optional[Sequence[int]] = None) -> Trace:
    """Read an ``.xplane.pb``: device ops and modules per TPU chip, host
    events, and the window from the ``bench.window`` host span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        m = _CHIP_RE.match(plane.name)
        if m:
            chip = int(m.group(1))
            if chips is not None and chip not in chips:
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[chip] = [_event(e) for e in line.events]
                elif line.name == "XLA Modules":
                    modules[chip] = [_event(e) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [Event(e.name, float(e.start_ns), float(e.duration_ns),
                             e.name) for e in line.events
                       if e.duration_ns >= HOST_MIN_NS or e.name == WINDOW_SPAN]
                if any(e.name == WINDOW_SPAN for e in evs):
                    host = evs      # the thread that ran the window
    spans = [e for e in host if e.name == WINDOW_SPAN]
    if not spans:
        raise NotInTrace(f"no {WINDOW_SPAN!r} span in {path}")
    w = spans[0]
    return Trace(ops=ops, modules=modules, host=host, window=(w.start, w.end))


def describe(path: str, n: int = 25) -> str:
    """The layout of a trace: planes, lines, and each line's most
    frequent event names with one event's stats."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            count: Dict[str, int] = {}
            for e in evs:
                count[e.name] = count.get(e.name, 0) + 1
            out.append(f"  LINE {line.name!r}: {len(evs)} events")
            for name, c in sorted(count.items(), key=lambda kv: -kv[1])[:n]:
                ex = next(e for e in evs if e.name == name)
                stats = {kv[0]: (kv[1] if not isinstance(kv[1], str)
                                 else kv[1][:160]) for kv in ex.stats}
                out.append(f"    {c:6d} x {name[:120]!r} "
                           f"dur={ex.duration_ns:.0f}ns {stats}")
    return "\n".join(out)


if __name__ == "__main__":
    print(describe(sys.argv[1]))
