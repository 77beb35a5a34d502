"""Host spans (`docs/observability.md` "Host spans").

One always-on, bounded, in-memory ring of what the host was doing, fed by the
serving step (`serving/engine.py`, `serving/journal.py`), the train loop's
loader (`data_loader.py`) and the collector (`host.gc`, below). A span is the
plain tuple ``(name, start, end, parent, attrs)``: ``start``/``end`` are
``time.perf_counter()`` seconds (the clock of `StepTimings`,
`serving.trace.Tracer` and the `RequestOutput` times); ``parent`` is the
``attrs["id"]`` of the step span it was opened inside (0 outside any), so the
spans of one step share an identifier.

Every span also opens a ``jax.profiler.TraceAnnotation`` of the same name (a
flag test while no capture is active) that carries the span's ``step`` and
``seq`` attrs as its metadata, so a capture's host plane holds the spans the
ring holds on the device plane's clock. The ``serve.step`` annotations, matched
to the ring's step spans by ``step``, give the one constant that lays the whole
ring on that clock ("The clock" in the same document): `record`ed spans, which
open no annotation, included.

A `gc.callbacks` hook counts every collection of the process in `GC`, by
generation; a full (generation 2) collection is also a ``host.gc`` span in the
ring and on the profile, parented to the step it interrupted.

This module imports neither flax nor `accelerate_tpu.serving`.
"""

from __future__ import annotations

import gc
import itertools
import threading
import time
from collections import deque
from typing import Any

from jax.profiler import TraceAnnotation

Span = tuple  # (name, start, end, parent, attrs)

# The busiest cell keeps a whole window and its ramp: GPT-2's closed loop makes
# about 108 steps, 24 admits and 25 admitted requests a second over some 65 s.
# A step is five spans (step, admit, and its decode program's dispatch, fetch
# and deliver), an admit three more (dispatch, fetch, deliver) and a request
# one queue wait: 108 x 5 + 24 x 3 + 25 = 637 a second, 41 k in 65 s. A span's
# attrs hold atomic values only, so CPython leaves the dicts untracked and a
# full collection walks none of them.
RING_SPANS = 1 << 16


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
_MARKS = ("step", "seq")  # the attrs an annotation carries: what pairs it with the ring


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
        self._ann = TraceAnnotation(name, **{k: attrs[k] for k in _MARKS if k in attrs})

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


def record(name: str, start: float, end: float, ring: SpanRing | None = None,
           **attrs: Any) -> None:
    """Add a span timed elsewhere, after the fact (a request's wait in the
    queue, known only once a dispatch takes it): no annotation, and the step
    open on this thread as its parent."""
    (RING if ring is None else ring).append(
        (name, start, end, getattr(_open, "step", 0), attrs))


class GCTotals:
    """Collections since this module loaded and the seconds they took, by
    generation (0, 1, 2): at hundreds a second, the young generations are
    counted here and enter no ring."""

    __slots__ = ("collections", "seconds")

    def __init__(self):
        self.collections = [0, 0, 0]
        self.seconds = [0.0, 0.0, 0.0]


GC = GCTotals()
_collecting: list = [None, None]  # the collection under way: its start, its annotation


def _on_gc(phase: str, info: dict) -> None:
    """`gc.callbacks` hook; the interpreter runs one collection at a time."""
    generation = info["generation"]
    if phase == "start":
        ann = TraceAnnotation("host.gc", generation=generation) if generation == 2 else None
        if ann is not None:
            ann.__enter__()
        _collecting[:] = time.perf_counter(), ann
        return
    end = time.perf_counter()
    start, ann = _collecting
    _collecting[:] = None, None
    if start is None:  # installed while a collection was under way
        return
    GC.collections[generation] += 1
    GC.seconds[generation] += end - start
    if ann is not None:
        ann.__exit__(None, None, None)
        RING.append(("host.gc", start, end, getattr(_open, "step", 0),
                     {"generation": generation, "collected": info["collected"]}))


gc.callbacks.append(_on_gc)
