"""Named host spans and counters of the dispatch path.

Every timer of the dispatch path (staging, H2D, compile, the executable
call, the device wait, the D2H copy, folding, backpressure) is a
:func:`span`.  A span always measures its wall seconds and hands them back
to the caller, which fills :class:`~repro.core.analyzer.DispatchStats`
with them.  While a ``jax.profiler`` session records, a span also opens a
``TraceAnnotation`` of the same name (on the clock of the device ops, in
every profiler trace an operator takes) and adds ``(count, seconds)`` to a
process-wide table; :func:`count` adds to the same table.  With no
session recording the only cost is one ``TraceAnnotation.is_enabled()``
check.  So wrapping N calls in ``jax.profiler.trace(dir)`` and reading
:func:`traced_totals` afterwards gives those calls' totals.

Backend compiles are seen through one ``jax.monitoring`` listener: while a
session records they count as ``cxlsim.compile.backend``, and every thread
keeps a running total of its compile seconds (:func:`compile_seconds`) so a
dispatch can move a compile that happened inside its call out of its
compute time.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Tuple

import jax
from jax import monitoring

__all__ = [
    "BACKEND_COMPILE",
    "H2D_BUFFERS",
    "compile_seconds",
    "count",
    "reset",
    "span",
    "traced_totals",
]

BACKEND_COMPILE = "cxlsim.compile.backend"
# arrays each analyzer dispatch places on the device (one staged word plane)
H2D_BUFFERS = "cxlsim.h2d.buffers"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_recording = jax.profiler.TraceAnnotation.is_enabled
_lock = threading.Lock()
_table: Dict[str, Tuple[int, float]] = {}
_thread = threading.local()


def _add(name: str, n: int, seconds: float) -> None:
    with _lock:
        c, s = _table.get(name, (0, 0.0))
        _table[name] = (c + n, s + seconds)


class span:
    """One timed host span: ``with span("cxlsim.stage") as s: ...``, then
    ``s.seconds`` holds its wall time."""

    __slots__ = ("name", "seconds", "_t0", "_annotation")

    def __init__(self, name: str):
        self.name = name
        self.seconds = 0.0

    def __enter__(self) -> "span":
        self._annotation = None
        if _recording():
            self._annotation = jax.profiler.TraceAnnotation(self.name)
            self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
            _add(self.name, 1, self.seconds)


def count(name: str, n: int) -> None:
    """Add ``n`` to a counter while a profiler session records."""
    if _recording():
        _add(name, int(n), 0.0)


def traced_totals() -> Dict[str, Tuple[int, float]]:
    """A copy of the table: name -> (count, seconds) recorded so far."""
    with _lock:
        return dict(_table)


def reset() -> None:
    with _lock:
        _table.clear()


def compile_seconds() -> float:
    """Backend compile seconds seen on the calling thread so far."""
    return getattr(_thread, "compile_s", 0.0)


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event != _COMPILE_EVENT:
        return
    _thread.compile_s = compile_seconds() + duration
    if _recording():
        _add(BACKEND_COMPILE, 1, duration)


monitoring.register_event_duration_secs_listener(_on_duration)
