"""In-program tracing: named spans and integer counters.

Counters (:func:`count`) are always on: process-wide monotonic totals,
read as deltas (``host_model.probe_dispatch_count`` reads
``probe_dispatches``).  Spans (:func:`span`) are off by default.  Off, a
span costs one module-global check and returns a shared null context: it
reads no clock, allocates nothing and writes no profiler annotation.  On
(:func:`enable`), each span

* writes ``jax.profiler.TraceAnnotation("cachex:<name>")``, so a
  ``jax.profiler`` capture holds the program's spans on the same clock as
  the device's events, and
* keeps in memory, on ``time.perf_counter``, its name, parent, start and
  end (the first :data:`MAX_INTERVALS` of them; later ones are counted as
  dropped), and per name the count, total time and self time: the
  duration less the time covered by child spans.

Spans nest on one stack, so a span is never held open across a
``yield``: :func:`spanned` times the stretches of a generator's code
between its yields instead.  :func:`snapshot` returns the record.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator, List, Optional, Tuple

import jax

PREFIX = "cachex:"
MAX_INTERVALS = 100_000

_on = False
_null = contextlib.nullcontext()
_counters: Dict[str, int] = {}
_stack: List["_Span"] = []
# per name: [count, total seconds, self seconds]
_stats: Dict[str, List[float]] = {}
_intervals: List[Tuple[str, Optional[str], float, float]] = []
_dropped = 0


class _Span:
    __slots__ = ("name", "_ann", "_t0", "_child")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self._ann = jax.profiler.TraceAnnotation(PREFIX + self.name)
        self._ann.__enter__()
        self._child = 0.0
        _stack.append(self)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        global _dropped
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        if _stack and _stack[-1] is self:   # a reset() may have cleared it
            _stack.pop()
        dur = t1 - self._t0
        parent = _stack[-1] if _stack else None
        if parent is not None:
            parent._child += dur
        st = _stats.setdefault(self.name, [0, 0.0, 0.0])
        st[0] += 1
        st[1] += dur
        st[2] += dur - self._child
        if len(_intervals) < MAX_INTERVALS:
            _intervals.append((self.name, parent and parent.name,
                               self._t0, t1))
        else:
            _dropped += 1
        return False


def span(name: str, sub: str = ""):
    """A context manager timing ``name + sub`` while tracing is on; the
    shared null context while it is off.  ``sub`` keeps the name's
    concatenation off the disabled path (``span("op:", kind)``)."""
    if not _on:
        return _null
    return _Span(name + sub)


def spanned(gen, name: str) -> Iterator:
    """Re-yield ``gen``'s items unchanged, timing as span ``name`` each
    stretch of its code between two yields (and before the first and
    after the last); the generator's return value passes through."""
    try:
        with span(name):
            item = gen.send(None)
        while True:
            sent = yield item
            with span(name):
                item = gen.send(sent)
    except StopIteration as stop:
        return stop.value
    finally:
        gen.close()


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` (always on)."""
    _counters[name] = _counters.get(name, 0) + n


def counter(name: str) -> int:
    return _counters.get(name, 0)


def restore_counters(values: Dict[str, int]) -> None:
    """Set every counter back to ``values`` (a ``snapshot()["counters"]``):
    work done in between leaves no count."""
    _counters.clear()
    _counters.update(values)


def enable() -> None:
    global _on
    _on = True


def disable() -> None:
    """Stop recording spans; spans already open still close and record."""
    global _on
    _on = False


def reset() -> None:
    """Forget every recorded span.  Counters are totals and are kept."""
    global _dropped
    _stack.clear()
    _stats.clear()
    _intervals.clear()
    _dropped = 0


def snapshot() -> Dict:
    """``spans``: per name ``{"count", "total_s", "self_s"}``;
    ``intervals``: ``(name, parent name or None, start, end)`` on
    ``time.perf_counter``, in the order the spans closed; ``dropped``:
    spans past :data:`MAX_INTERVALS`; ``counters``: every counter."""
    return {
        "spans": {k: {"count": int(c), "total_s": t, "self_s": s}
                  for k, (c, t, s) in _stats.items()},
        "intervals": list(_intervals),
        "dropped": _dropped,
        "counters": dict(_counters),
    }
