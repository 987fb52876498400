"""Host spans of the serving loop, on the profiler's clock and in a ring.

``Spans.span(name, **counts)`` times one phase of ``InferenceEngine.serve``
(admission, a prefill, the decode dispatch, the read-back, ...).  It

* enters a ``jax.profiler.TraceAnnotation``, so that when a profiler runs
  the span lands in its trace on the same clock as the device operations;
  the trace label is the phase's name, plus ``.<turn>`` for a span that
  counts its ``turn`` (``serve.turn.12``), so labels stay free of counts;
* appends a :class:`Span` (name, ``time.perf_counter_ns`` start and end,
  the enclosing span's id, the counts) to a bounded ring, profiler on or
  off, so every run keeps a per-turn record of where the host's time went.

The body adds counts it learns on the way to ``.counts`` of the open
span, and marks its phases in order with ``.phase(name, **counts)``: each
phase is a child span that lasts until the next phase, or the end of its
parent.  ``Spans.turns`` runs a loop with one such span per turn, so a loop
body marks its phases without nesting.  With the profiler off a span costs
the annotation (about a microsecond) and two clock reads.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import time

import jax

CAPACITY = 1 << 16          # spans kept; a serving turn records about ten


@dataclasses.dataclass(frozen=True)
class Span:
    id: int
    name: str
    start_ns: int
    end_ns: int
    parent: int | None      # id of the enclosing span
    counts: dict

    @property
    def dur_ns(self) -> int:
        return self.end_ns - self.start_ns


class _Open:
    __slots__ = ("spans", "id", "name", "counts", "parent", "note", "start",
                 "_phase")

    def __init__(self, spans: "Spans", name: str, counts: dict):
        self.spans, self.name, self.counts = spans, name, counts
        self.id = next(spans._ids)
        label = f"{name}.{counts['turn']}" if "turn" in counts else name
        self.note = jax.profiler.TraceAnnotation(label)
        self._phase = None

    def __enter__(self) -> "_Open":
        stack = self.spans._stack
        self.parent = stack[-1] if stack else None
        stack.append(self.id)
        self.note.__enter__()
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        if self._phase is not None:
            self._phase.__exit__(*exc)
        end = time.perf_counter_ns()
        self.note.__exit__(*exc)
        self.spans._stack.pop()
        self.spans.ring.append(Span(self.id, self.name, self.start, end,
                                    self.parent, self.counts))
        return False

    def phase(self, name: str, **counts) -> dict:
        """End this span's current phase, if any, and begin the next as a
        child span; returns the new phase's counts."""
        if self._phase is not None:
            self._phase.__exit__(None, None, None)
        self._phase = self.spans.span(name, **counts).__enter__()
        return self._phase.counts


class Spans:
    """The last :data:`CAPACITY` spans of one engine, oldest first."""

    def __init__(self):
        self.ring: collections.deque[Span] = collections.deque(
            maxlen=CAPACITY)
        self._stack: list[int] = []
        self._ids = itertools.count()

    def span(self, name: str, **counts) -> _Open:
        return _Open(self, name, counts)

    def turns(self, more):
        """Loop while ``more()``: each turn ``k`` runs inside an open span
        ``serve.turn`` (counts ``turn=k``), which the loop yields."""
        k = 0
        while more():
            with self.span("serve.turn", turn=k) as turn:
                yield turn
            k += 1

    def __iter__(self):
        return iter(self.ring)


def self_ns(spans) -> dict[int, int]:
    """{span id: its duration less what its children cover}.  Children
    run inside their parent one after another (one thread), so their
    durations add up to what they cover."""
    out = {s.id: s.dur_ns for s in spans}
    for s in spans:
        if s.parent in out:
            out[s.parent] -= s.dur_ns
    return out
