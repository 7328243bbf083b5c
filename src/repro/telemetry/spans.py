"""Phase spans: one name on two clocks.

``span(name)`` marks a phase of the serving stack's host work.  While a
profiler runs it opens a ``jax.profiler.TraceAnnotation`` of that name,
so the phase shows on the profiler's host plane beside the device ops it
dispatched; with no profiler running it makes none (an annotation would
record nothing).  Where a ``Recorder`` is open on the calling thread
(``recording``), the span also adds its ``time.perf_counter`` seconds,
one occurrence, and the XLA compile requests made while it was the
innermost open span to that recorder; the component that opened the
recorder reports the totals as fields of the bus event it already emits
(``admission_round``, ``serve_round``).

Recorders are per thread (a ``contextvars.ContextVar``), so the admission
solver thread and a serving thread each see only their own phases.  A
component opens one only when a bus is attached: with ``bus=None``
nothing here is allocated and no listener is registered.

Compile requests are counted from ``jax.monitoring``'s backend-compile
event, which also fires for programs loaded from the persistent compile
cache.  The listener is registered once per process, when the first
recorder is made.

Span names carry the ``era.`` prefix; the README's Observability section
lists them.
"""
from __future__ import annotations

import contextvars
import threading
import time
from contextlib import contextmanager
from typing import Dict, Iterator, List, Optional, Union

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_Annotation = jax.profiler.TraceAnnotation


class Recorder:
    """Totals of one round's spans on one thread: seconds, occurrences and
    compile requests per span name, the round's compile requests in all,
    and ``mark``ed offsets from the recorder's start."""

    __slots__ = ("t0", "seconds", "counts", "compiles", "total_compiles",
                 "marks", "_open")

    def __init__(self):
        self.t0 = time.perf_counter()
        self.seconds: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        self.compiles: Dict[str, int] = {}
        self.total_compiles = 0
        self.marks: Dict[str, List[float]] = {}
        self._open: List[str] = []

    def fields(self, **phases: str) -> Dict[str, Union[float, int]]:
        """Bus fields for ``field=span name`` pairs: ``<field>_s`` (the
        span's summed seconds, 0.0 if it never ran) and
        ``<field>_compiles``."""
        out = {}
        for field, name in phases.items():
            out[f"{field}_s"] = self.seconds.get(name, 0.0)
            out[f"{field}_compiles"] = self.compiles.get(name, 0)
        return out


_ACTIVE: contextvars.ContextVar[Optional[Recorder]] = \
    contextvars.ContextVar("repro_span_recorder", default=None)
_LISTENER_LOCK = threading.Lock()
_listening = False


def _on_duration(event: str, duration: float, **_) -> None:
    if event != COMPILE_EVENT:
        return
    rec = _ACTIVE.get()
    if rec is None:
        return
    rec.total_compiles += 1
    if rec._open:
        name = rec._open[-1]
        rec.compiles[name] = rec.compiles.get(name, 0) + 1


def _ensure_listener() -> None:
    global _listening
    with _LISTENER_LOCK:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _listening = True


@contextmanager
def recording(enabled: bool) -> Iterator[Optional[Recorder]]:
    """Open a ``Recorder`` for this thread's spans until the block exits;
    yields None, and makes nothing, when not ``enabled``."""
    if not enabled:
        yield None
        return
    _ensure_listener()
    rec = Recorder()
    token = _ACTIVE.set(rec)
    try:
        yield rec
    finally:
        _ACTIVE.reset(token)


def mark(name: str) -> None:
    """Record the seconds since the open recorder's start under ``name``
    (nothing without a recorder)."""
    rec = _ACTIVE.get()
    if rec is not None:
        rec.marks.setdefault(name, []).append(time.perf_counter() - rec.t0)


class span:
    """``with span(name, **args):`` — the profiler annotation ``name``
    (``args`` ride on it as trace metadata) and, under an open recorder,
    the phase's seconds, occurrence and compile requests."""

    __slots__ = ("_name", "_args", "_ann", "_rec", "_t0")

    def __init__(self, name: str, **args):
        self._name = name
        self._args = args

    def __enter__(self) -> "span":
        # an annotation records nothing while no profiler runs: make none
        ann = self._ann = (_Annotation(self._name, **self._args)
                           if _Annotation.is_enabled() else None)
        if ann is not None:
            ann.__enter__()
        rec = self._rec = _ACTIVE.get()
        if rec is not None:
            rec._open.append(self._name)
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        rec = self._rec
        if rec is not None:
            dt = time.perf_counter() - self._t0
            rec._open.pop()
            name = self._name
            rec.seconds[name] = rec.seconds.get(name, 0.0) + dt
            rec.counts[name] = rec.counts.get(name, 0) + 1
        if self._ann is not None:
            self._ann.__exit__(*exc)
