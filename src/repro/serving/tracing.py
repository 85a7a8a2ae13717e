"""Spans inside the serving path.

One :class:`Tracer` per :class:`~.engine.ServingEngine` (``engine.tracer``);
the scheduler and executor calls, :class:`~.frontend.AsyncFrontend` and
``launch/server.py``'s ``HttpFrontendServer`` all record into that one
object.  It is **off** by default: each span site then costs one test of
``tracer.enabled`` and nothing else (no allocation, no clock read, no
profiler annotation).  Turned on, each span

* is kept in memory as a :class:`Span` (name, start and end on the
  engine's injected ``clock``, the index of its parent span, the engine
  step number and, where there is one, the request id), in a bounded
  ring of :data:`CAPACITY` spans, oldest dropped first;
* is also a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``, so
  a profiler trace holds it on the device trace's clock, beside the
  device's operations.

Span names (``<layer>.<name>``) and the tree they form::

    engine.step                   one ServingEngine step
      scheduler.plan              Scheduler.plan
      executor.prepare            table delta upload, take_kv
      executor.dispatch           operand placement and the jitted call
      executor.wait               host blocked on the device's tokens
      executor.build              the execute call that built a bucket,
                                  up to the wait
      scheduler.commit            Scheduler.commit
    frontend.fanout               AsyncFrontend.pump after the step
    server.write                  one token's SSE write through drain()

``executor.build`` is known only once the jitted call has returned (the
jit cache grew), so it is recorded afterwards, with its ``(t_bucket, p_bucket)``,
and has no profiler annotation.  ``server.write`` crosses an ``await``:
it is a root span that never becomes a parent, and it carries the end
time of the commit that produced its token (``committed``; the engine
keeps the latest as ``ServingEngine.last_commit_end``).
"""

from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Callable, Deque, Dict, List, NamedTuple, Optional

import jax

__all__ = ["Tracer", "Span", "OFF", "PREFIX", "CAPACITY"]

PREFIX = "repro."

# spans kept in memory: ~4600 engine steps at ~14 spans a step
CAPACITY = 1 << 16

# what a span site enters when the tracer is off: one shared object
OFF = contextlib.nullcontext()


class Span(NamedTuple):
    """One finished span.  ``idx`` numbers spans in the order they began
    (it keeps counting when the ring drops old spans); ``parent`` is the
    ``idx`` of the enclosing span, -1 for a root."""
    idx: int
    name: str
    start: float
    end: float
    parent: int
    step: int
    req_id: Optional[int] = None
    attrs: Optional[Dict[str, Any]] = None


@dataclass
class _Open:
    idx: int
    name: str
    start: float
    parent: int
    step: int
    req_id: Optional[int]
    annotation: Any
    nest: bool
    end: float = 0.0


class Tracer:
    """Spans of one engine, kept in memory.

    ``clock`` is the engine's clock (``time.perf_counter`` in a server,
    a fake clock in tests).  Span sites test :attr:`enabled` first::

        with tr.span("scheduler.plan") if tr.enabled else OFF:
            plan = scheduler.plan()
    """

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self.enabled = False
        self.clock = clock
        self.spans: Deque[Span] = deque(maxlen=CAPACITY)
        self.step = 0                  # the engine's step number
        self._next = 0
        self._stack: List[_Open] = []

    def begin(self, name: str, *, req_id: Optional[int] = None,
              nest: bool = True) -> _Open:
        """Open span ``name`` under the innermost open span.  A span
        that crosses an ``await`` passes ``nest=False``: other code
        runs inside it, so it must not become their parent."""
        ann = jax.profiler.TraceAnnotation(PREFIX + name)
        ann.__enter__()
        op = _Open(self._next, name, self.clock(),
                   self._stack[-1].idx if self._stack else -1, self.step,
                   req_id, ann, nest)
        self._next += 1
        if nest:
            self._stack.append(op)
        return op

    def end(self, op: _Open, **attrs: Any) -> float:
        """Close ``op`` (and any span an exception left open inside it);
        returns its end time, also kept as ``op.end``."""
        t = op.end = self.clock()
        op.annotation.__exit__(None, None, None)
        if op.nest:
            while self._stack and self._stack.pop() is not op:
                pass
        self.spans.append(Span(op.idx, op.name, op.start, t, op.parent,
                               op.step, op.req_id, attrs or None))
        return t

    @contextlib.contextmanager
    def span(self, name: str, *, req_id: Optional[int] = None):
        """``begin``/``end`` around a block."""
        op = self.begin(name, req_id=req_id)
        try:
            yield op
        finally:
            self.end(op)

    def record(self, name: str, start: float, end: float,
               **attrs: Any) -> None:
        """Keep a span known only after it ended, under the innermost
        open span (no profiler annotation)."""
        self.spans.append(Span(
            self._next, name, start, end,
            self._stack[-1].idx if self._stack else -1, self.step, None,
            attrs or None))
        self._next += 1

    def records(self) -> List[dict]:
        """The spans in memory as plain dicts, in the order they ended."""
        return [s._asdict() for s in self.spans]
