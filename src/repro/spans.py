"""Host spans of the program (DESIGN.md §9).

    with spans.span("hi2.query", id=7):
        ...

A span always enters a ``jax.profiler.TraceAnnotation`` of its name, so
a profiler trace shows it on the host, on the device trace's clock.
Between :func:`record` and :func:`take` each span is also kept in
memory as a :class:`Span`, its parent the innermost span open on the
same thread when it began; :func:`take` hands the kept spans over in
the order they began and keeps recording.  Recording is off by
default, and then a span costs one ``TraceAnnotation``.  Take with no
span open: one open at a take is not handed over.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import NamedTuple, Optional

import jax


class Span(NamedTuple):
    name: str
    start_ns: int            # time.perf_counter_ns()
    end_ns: int
    parent: Optional[int]    # index of the parent in the taken list
    attrs: dict


_kept: Optional[list] = None
_open = threading.local()


def record() -> None:
    """Keep every span from now on (until :func:`stop`)."""
    global _kept
    if _kept is None:
        _kept = []


def stop() -> None:
    global _kept
    _kept = None


def recording() -> bool:
    return _kept is not None


def take() -> list:
    """The spans finished since recording began or the last take."""
    global _kept
    if _kept is None:
        return []
    done, _kept = _kept, []
    return [s for s in done if s is not None]


class span(contextlib.ContextDecorator):
    """``with span(name, **attrs)``, or ``@span(name)`` over a function:
    see the module docstring."""

    def __init__(self, name: str, **attrs):
        self.name, self.attrs = name, attrs
        self._annotation = jax.profiler.TraceAnnotation(name, **attrs)

    def _recreate_cm(self):         # a fresh span for each decorated call
        return span(self.name, **self.attrs)

    def __enter__(self):
        self._annotation.__enter__()
        self._kept = kept = _kept
        if kept is not None:
            stack = getattr(_open, "stack", None)
            if stack is None:
                stack = _open.stack = []
            # a span opened before the last take is no parent here
            self._parent = (stack[-1][1] if stack and stack[-1][0] is kept
                            else None)
            self._slot = len(kept)
            kept.append(None)
            stack.append((kept, self._slot))
            self._start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        kept = self._kept
        if kept is not None:
            end = time.perf_counter_ns()
            _open.stack.pop()
            kept[self._slot] = Span(self.name, self._start, end,
                                    self._parent, self.attrs)
        return self._annotation.__exit__(*exc)
