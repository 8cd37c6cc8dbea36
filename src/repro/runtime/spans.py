"""Program spans: where the host's time goes, layer by layer.

``span(name, **attrs)`` marks one interval of work at a layer boundary:

    with span("serve.decode"):
        ...

It does two things, always:

* opens a ``jax.profiler.TraceAnnotation`` of the same name and
  attributes, so under a profiler trace the span lies on the host plane
  on the device trace's clock, and an idle gap of the device can be put
  down to the program span open around it;
* on exit appends ``Span(id, name, t0, t1, parent, attrs)`` on
  ``time.perf_counter`` to a bounded in-memory ring, ``parent`` being the
  id of the span open around it on the same thread.

Counts are small integer attributes of the span at the boundary where
they happen (``lanes=``, ``rid=``), set at opening or later with
``Open.set``; there is no second store.  A span costs a few
microseconds of host time, so the program opens a few per decode step
or admission, never one per lane or token.

No program span is named ``bench.*``: that prefix is the benchmark's own
spans around its calls into the program, which its trace reduction
reads.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import NamedTuple, Optional, Sequence

from jax.profiler import TraceAnnotation

#: spans the ring holds: a 51-s serving window opens about 20k
CAPACITY = 1 << 18
RESERVED = "bench."


class Span(NamedTuple):
    id: int
    name: str
    t0: float
    t1: float
    #: id of the span open around this one on its thread, or None
    parent: Optional[int]
    attrs: dict


class Open:
    """A span being recorded; ``set`` adds attributes before it closes."""

    __slots__ = ("_rec", "name", "attrs", "id", "parent", "_stack", "_t0",
                 "_ann")

    def __init__(self, rec: "Recorder", name: str, attrs: dict) -> None:
        self._rec, self.name, self.attrs = rec, name, attrs

    def __enter__(self) -> "Open":
        stack = self._stack = self._rec._stack()
        self.parent = stack[-1] if stack else None
        self.id = next(self._rec._ids)
        stack.append(self.id)
        self._ann = TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)
        self._ann.set_metadata(**attrs)

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self._stack.pop()
        rec = self._rec
        rec._ring.append((self.id, self.name, self._t0, t1, self.parent,
                          self.attrs))
        rec._closed += 1


class Recorder:
    """A bounded ring of closed spans; the oldest go first when full."""

    def __init__(self, capacity: int = CAPACITY) -> None:
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._ids = itertools.count()
        self._local = threading.local()
        self._closed = 0

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, **attrs) -> Open:
        if name.startswith(RESERVED):
            raise ValueError(f"span names {RESERVED}* are the benchmark's: "
                             f"{name!r}")
        return Open(self, name, attrs)

    def spans(self, since: float = float("-inf"),
              until: float = float("inf")) -> list[Span]:
        """The spans that overlap ``[since, until)``, clipped to it, in
        the order they closed."""
        return [Span(i, name, max(t0, since), min(t1, until), parent, attrs)
                for i, name, t0, t1, parent, attrs in list(self._ring)
                if t0 < until and t1 > since]

    @property
    def dropped(self) -> int:
        """Spans the ring has let go since it was made."""
        return self._closed - len(self._ring)


def self_time(spans: Sequence[Span]) -> dict[int, float]:
    """Each span's duration less the part of it that its children among
    ``spans`` cover, by span id."""
    kids: dict[int, list] = collections.defaultdict(list)
    for s in spans:
        if s.parent is not None:
            kids[s.parent].append((s.t0, s.t1))
    out = {}
    for s in spans:
        covered, end = 0.0, s.t0
        for a, b in sorted(kids.get(s.id, ())):
            a, b = max(a, end), min(b, s.t1)
            if b > a:
                covered += b - a
                end = b
        out[s.id] = s.t1 - s.t0 - covered
    return out


#: the process's recorder, which the program's spans go to
RECORDER = Recorder()
span = RECORDER.span
spans = RECORDER.spans
