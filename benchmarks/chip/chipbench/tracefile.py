"""From a profiler trace to device busy time, kernel time and idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
nothing but JAX, into plain lists of ``Event(name, start_ns, end_ns)``:

* ``ops``: the device's operations (line ``XLA Ops`` of the planes
  ``/device:TPU:<n>``), each fusion, copy and kernel;
* ``modules``: the device's program runs (line ``XLA Modules``), one per
  jitted call, named after the function (``jit__unified_step(...)``);
* ``spans``: the host's ``TraceAnnotation`` spans whose names start with
  ``chipbench.``, on the same clock as the device's events.

The reductions below work on such lists, so a test can build a trace by
hand.  A device op ``name`` is matched by substring, so a kernel is found
by the stable name it is given (``paged_attention_fwd``).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SPAN_PREFIX = "chipbench."


@dataclass(frozen=True)
class Event:
    name: str
    start: float          # ns
    end: float            # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    ops: Dict[int, List[Event]] = field(default_factory=dict)   # by chip
    modules: Dict[int, List[Event]] = field(default_factory=dict)
    spans: List[Event] = field(default_factory=list)
    window: Tuple[float, float] = (0.0, 0.0)   # ns, the traced window

    @property
    def chips(self) -> List[int]:
        return sorted(self.ops)


def load(log_dir: str, window_s: Optional[float] = None) -> Trace:
    """The events of the one trace under ``log_dir``.  The traced
    window runs from the first to the last event of any device or span,
    or is ``window_s`` long ending at the last event when given."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} traces under {log_dir}")
    tr = Trace()
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/device:TPU:"):
            chip = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                dest = {"XLA Ops": tr.ops,
                        "XLA Modules": tr.modules}.get(line.name)
                if dest is not None:
                    dest[chip] = [Event(e.name, e.start_ns,
                                        e.start_ns + e.duration_ns)
                                  for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                tr.spans += [Event(e.name, e.start_ns,
                                   e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX)]
    every = [e for evs in tr.ops.values() for e in evs] + tr.spans
    if every:
        lo = min(e.start for e in every)
        hi = max(e.end for e in every)
        tr.window = (lo if window_s is None else hi - window_s * 1e9, hi)
    return tr


def union(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Merged ``(start, end)`` intervals that the events cover."""
    out: List[Tuple[float, float]] = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            if e.end > out[-1][1]:
                out[-1] = (out[-1][0], e.end)
        else:
            out.append((e.start, e.end))
    return out


def clip(intervals, window) -> List[Tuple[float, float]]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def busy_ns(events: Iterable[Event], window) -> float:
    """Time in ``window`` in which any of ``events`` ran."""
    return sum(b - a for a, b in clip(union(events), window))


def idle_gaps(events: Iterable[Event], window
              ) -> List[Tuple[float, float]]:
    """The stretches of ``window`` in which none of ``events`` ran."""
    lo, hi = window
    gaps, t = [], lo
    for a, b in clip(union(events), window):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def time_of(events: Iterable[Event], name: str) -> Tuple[float, int]:
    """Total ns and count of the events whose name contains ``name``."""
    hits = [e.dur for e in events if name in e.name]
    return sum(hits), len(hits)


def op_kind(name: str) -> str:
    """An op's name without its HLO text and instance number:
    ``%fusion.74 = bf16[...] fusion(...)`` -> ``fusion``."""
    return re.sub(r"\.\d+$", "", name.split(" = ")[0].lstrip("%"))


def top_ops(events: Iterable[Event], k: int = 10
            ) -> List[Tuple[str, float]]:
    """The ``k`` kinds of op that took most device time, in seconds."""
    tot: Dict[str, float] = {}
    for e in events:
        n = op_kind(e.name)
        tot[n] = tot.get(n, 0.0) + e.dur
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [(n, t / 1e9) for n, t in top]


# host spans, innermost first: an idle gap is charged to the first of
# these that covers its middle, else to the event loop (HTTP and SSE)
SPAN_ORDER = ("chipbench.plan", "chipbench.commit", "chipbench.execute",
              "chipbench.pump")


def attribute_gaps(gaps: Sequence[Tuple[float, float]],
                   spans: Sequence[Event]) -> List[Tuple[str, float]]:
    """Idle seconds by what the host was doing, largest first: the span
    covering each gap's middle (``plan``, ``commit``, ``execute`` = the
    step's host side around the device call, ``pump`` = fanning tokens
    out to streams), else ``event_loop``."""
    by_name: Dict[str, List[Event]] = {n: [] for n in SPAN_ORDER}
    for s in spans:
        if s.name in by_name:
            by_name[s.name].append(s)
    starts = {}
    for n, evs in by_name.items():
        evs.sort(key=lambda e: e.start)
        starts[n] = [e.start for e in evs]

    def covers(n: str, t: float) -> bool:
        # spans of one name never overlap: the last one starting at or
        # before t is the only candidate
        i = bisect.bisect_right(starts[n], t) - 1
        return i >= 0 and by_name[n][i].end > t

    tot: Dict[str, float] = {}
    for a, b in gaps:
        mid = (a + b) / 2
        label = next((n[len(SPAN_PREFIX):] for n in SPAN_ORDER
                      if covers(n, mid)), "event_loop")
        tot[label] = tot.get(label, 0.0) + (b - a)
    return [(n, t / 1e9) for n, t in
            sorted(tot.items(), key=lambda kv: -kv[1])]
