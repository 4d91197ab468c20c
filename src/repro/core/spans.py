"""Spans and counters of the serving loop, kept in memory.

A ``SpanLog`` records named intervals on the engine's clock (ms, the clock
of ``BatchedEngine.events``), each with its parent, an optional request id
and a few integer counts.  While a span is open the log also holds a
``jax.profiler.TraceAnnotation`` of the same name, so a profiler trace
taken meanwhile carries a copy of every span on the device trace's own
clock: an idle gap on the device can be put down to the span the host was
in.

``BatchedEngine(spans=SpanLog())`` records one ``engine.tick`` span per
iteration of the drain loop, its steps as children in loop order, and the
tick's counters (see ``core/scheduler.py``).  The engine's default is
``spans=None``: every span site is then one ``is None`` test, nothing is
recorded and no annotation is built.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax


@dataclasses.dataclass
class Span:
    name: str
    start_ms: float
    end_ms: Optional[float] = None      # None while open
    parent: Optional[int] = None        # index of the parent in SpanLog.spans
    rid: Optional[int] = None
    counts: Dict[str, int] = dataclasses.field(default_factory=dict)


class SpanLog:
    """Spans in the order they opened (``spans``), on the bound clock."""

    def __init__(self):
        self.spans: List[Span] = []
        # (index in spans, its annotation), innermost last
        self._open: List[Tuple[int, Any]] = []
        self._clock = None

    def bind(self, clock) -> None:
        """Take times from ``clock`` (the engine binds its own)."""
        self._clock = clock

    def begin(self, name: str, rid: Optional[int] = None,
              **counts: int) -> None:
        """Open ``name`` inside the innermost open span."""
        parent = self._open[-1][0] if self._open else None
        span = Span(name, self._clock.now(), parent=parent, rid=rid,
                    counts=counts)
        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
        self._open.append((len(self.spans), ann))
        self.spans.append(span)

    def end(self) -> None:
        """Close the innermost open span."""
        i, ann = self._open.pop()
        ann.__exit__(None, None, None)
        self.spans[i].end_ms = self._clock.now()

    def mark(self, name: str, **counts: int) -> None:
        """A boundary inside the outermost open span: close what is open
        under it, then open ``name`` as its next child."""
        while len(self._open) > 1:
            self.end()
        self.begin(name, **counts)

    def count(self, **counts: int) -> None:
        """Add ``counts`` to the innermost open span's (a sum)."""
        have = self.spans[self._open[-1][0]].counts
        for k, v in counts.items():
            have[k] = have.get(k, 0) + v

    def finish(self, **counts: int) -> None:
        """Close every open span; ``counts`` go to the outermost."""
        while len(self._open) > 1:
            self.end()
        if self._open:
            self.count(**counts)
            self.end()
