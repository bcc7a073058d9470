"""Host spans of the program, kept in memory.

``with spans.span("data.batch", step=k) as s:`` records one span: its
name, its start and end on ``time.perf_counter_ns()``, the thread, its
id and the id of the span that encloses it on the same thread, the
training round ``step`` that the spans of one step share across
threads, and a few scalar attributes (``s.set(novel=3)`` adds one
before the span closes). A span given no step takes the step last given
on its thread, so an untagged span that follows a step-tagged one on a
worker thread belongs to the same step.

The newest records sit in a bounded deque; running aggregates by name
(count, total, max) cover every span since the last ``clear()``.
Nothing is written to disk: ``records()``, ``totals()`` and ``clear()``
read and reset them. Each span also enters
``jax.profiler.TraceAnnotation("repro.<name>")``, so while a profiler
trace runs the span sits on the device trace's own clock; with no trace
running the annotation costs about a microsecond. JAX is imported at
the first span, not with this module.

Recording is always on. The module-level functions use one recorder
per process; a ``Recorder`` of its own serves a caller (or a test) that
wants its records apart.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Dict, List, Optional

MAX_RECORDS = 65536

_trace_annotation = None


def _annotation(name: str, step: Optional[int], attrs: dict):
    global _trace_annotation
    if _trace_annotation is None:
        from jax import profiler

        _trace_annotation = profiler.TraceAnnotation
    if step is not None:
        attrs = dict(attrs, step=step)
    return _trace_annotation(f"repro.{name}", **attrs)


class Span:
    """One span: open inside its ``with`` block, a record after it."""

    __slots__ = ("name", "step", "attrs", "id", "parent", "thread",
                 "start_ns", "end_ns", "_recorder", "_annotation")

    def __init__(self, recorder: "Recorder", name: str,
                 step: Optional[int], attrs: dict):
        self.name = name
        self.step = step
        self.attrs = attrs
        self.id = self.parent = self.thread = None
        self.start_ns = self.end_ns = None
        self._recorder = recorder
        self._annotation = None

    def set(self, **attrs) -> None:
        """Add scalar attributes before the span closes."""
        self.attrs.update(attrs)

    @property
    def ms(self) -> float:
        return (self.end_ns - self.start_ns) / 1e6

    def __enter__(self) -> "Span":
        local = self._recorder._local
        stack = local.__dict__.setdefault("stack", [])
        if self.step is None:
            self.step = getattr(local, "step", None)
        else:
            local.step = self.step
        self.parent = stack[-1].id if stack else None
        self.id = next(self._recorder._ids)
        self.thread = threading.get_ident()
        stack.append(self)
        self._annotation = _annotation(self.name, self.step, self.attrs)
        self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.end_ns = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        self._annotation = None
        self._recorder._local.stack.remove(self)
        self._recorder._close(self)
        return False


class Recorder:
    """Records and per-name aggregates of the spans it opened."""

    def __init__(self, maxlen: int = MAX_RECORDS):
        self._records: collections.deque = collections.deque(maxlen=maxlen)
        self._agg: Dict[str, list] = {}   # name -> [count, total, max] ns
        self._lock = threading.Lock()
        self._local = threading.local()
        self._ids = itertools.count(1)

    def span(self, name: str, step: Optional[int] = None,
             **attrs) -> Span:
        return Span(self, name, step, attrs)

    def _close(self, s: Span) -> None:
        d = s.end_ns - s.start_ns
        with self._lock:
            self._records.append(s)
            agg = self._agg.setdefault(s.name, [0, 0, 0])
            agg[0] += 1
            agg[1] += d
            agg[2] = max(agg[2], d)

    def records(self, name: Optional[str] = None) -> List[Span]:
        """The newest closed spans, oldest first (of ``name`` alone if
        given)."""
        with self._lock:
            recs = list(self._records)
        return [r for r in recs if name is None or r.name == name]

    def totals(self) -> Dict[str, dict]:
        """Per name: count, total_s, mean_ms and max_ms of every span
        closed since the last ``clear()``."""
        with self._lock:
            agg = {k: list(v) for k, v in self._agg.items()}
        return {k: {"count": n, "total_s": t / 1e9, "mean_ms": t / n / 1e6,
                    "max_ms": m / 1e6}
                for k, (n, t, m) in sorted(agg.items())}

    def clear(self) -> None:
        with self._lock:
            self._records.clear()
            self._agg.clear()


_default = Recorder()
span = _default.span
records = _default.records
totals = _default.totals
clear = _default.clear
