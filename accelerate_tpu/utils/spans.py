"""Host spans (`docs/observability.md` "Host spans").

One always-on, bounded, in-memory ring of what the host was doing, fed by the
serving step (`serving/engine.py`, `serving/journal.py`) and the train loop's
loader (`data_loader.py`). A span is the plain tuple ``(name, start, end,
parent, attrs)``: ``start``/``end`` are ``time.perf_counter()`` seconds (the
clock of `StepTimings`, `serving.trace.Tracer` and the `RequestOutput` times);
``parent`` is the ``attrs["id"]`` of the step span it was opened inside (0
outside any), so the spans of one step share an identifier.

Every span also opens a ``jax.profiler.TraceAnnotation`` of the same name (a
flag test while no capture is active), so a person opening a profile sees the
spans the ring holds, on the profile's own clock. The ring itself is not laid
on a saved profile: that counts its ``start_ns`` from the start of the capture
and records that origin nowhere (PERF.md section 6, PR 26).

This module imports neither flax nor `accelerate_tpu.serving`.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Any

from jax.profiler import TraceAnnotation

Span = tuple  # (name, start, end, parent, attrs)

# a 51 s serving window at a 30 ms turn is 1,700 steps of about five spans
RING_SPANS = 1 << 15


class SpanRing:
    """A bounded, ordered record of finished spans: the oldest drop first and
    are counted in ``dropped`` (a reader that needs a whole window reports
    nothing once it is above 0). Appends come from the thread that runs the
    step loop; `deque.append` is atomic, so a reader on another thread sees a
    consistent prefix."""

    def __init__(self, maxlen: int = RING_SPANS):
        if maxlen < 1:
            raise ValueError(f"a span ring holds at least one span, got {maxlen}")
        self._spans: deque[Span] = deque(maxlen=int(maxlen))
        self.dropped = 0

    @property
    def maxlen(self) -> int:
        return self._spans.maxlen

    def __len__(self) -> int:
        return len(self._spans)

    def append(self, span: Span) -> None:
        if len(self._spans) == self._spans.maxlen:
            self.dropped += 1
        self._spans.append(span)

    def snapshot(self, name: str | None = None) -> list[Span]:
        """The spans held, in the order they ended; only ``name``'s if given."""
        spans = list(self._spans)
        return spans if name is None else [s for s in spans if s[0] == name]

    def clear(self) -> None:
        self._spans.clear()
        self.dropped = 0


RING = SpanRing()

_ids = itertools.count(1)  # step identifiers; never 0, which means "no step"
_seqs = itertools.count()
_open = threading.local()  # .step: id of the step span this thread is inside


def next_seq() -> int:
    """The process-wide dispatch sequence number: `serve.dispatch` and
    `serve.fetch` spans pair on it, and `serving.trace.Tracer.next_seq` hands
    out the same numbers, so an exported trace and the ring pair up."""
    return next(_seqs)


class span:
    """``with span("serve.dispatch", seq=7) as s:`` times the block once:
    ``s.start`` and ``s.end`` are the stamps the ring keeps, for the caller to
    fill its own sums from, and ``s.attrs`` the dict it keeps, open to
    additions until the block ends. ``is_step=True`` marks a step span: it takes
    an ``id``, and every span opened on this thread before it ends names that
    id as its ``parent``. `drop()` keeps the span out of the ring (a loader's
    probe that found the data exhausted)."""

    __slots__ = ("name", "parent", "attrs", "start", "end", "ring", "_ann")

    def __init__(self, name: str, is_step: bool = False,
                 ring: SpanRing | None = None, **attrs: Any):
        self.name, self.attrs = name, attrs
        self.ring = RING if ring is None else ring
        self.parent = 0
        self.start = self.end = 0.0
        if is_step:
            attrs["id"] = next(_ids)
        self._ann = TraceAnnotation(name)

    def drop(self) -> None:
        self.ring = None

    def __enter__(self) -> "span":
        self.parent = getattr(_open, "step", 0)
        if "id" in self.attrs:
            _open.step = self.attrs["id"]
        self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        self._ann.__exit__(*exc)
        if "id" in self.attrs:
            _open.step = self.parent
        if self.ring is not None:
            self.ring.append((self.name, self.start, self.end, self.parent, self.attrs))
