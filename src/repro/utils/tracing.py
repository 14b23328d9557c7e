"""The program's spans and counters.

``span(name)`` marks a stretch of host work twice: as a
``jax.profiler.TraceAnnotation``, so a profiler trace shows it beside the
device's operations, and as an entry ``(name, start_ns, dur_ns)`` of a
bounded in-process flight recorder that :func:`spans` reads back. Times are
``time.time_ns()``, the realtime clock the profiler stamps its host events
with, so recorder entries and a trace's events share one clock.

``count(name, delta)`` adds to a named counter; :func:`counters` takes a
snapshot and :func:`reset` zeroes.

Importing this module registers two ``jax.monitoring`` listeners. Every
executable JAX builds, or loads from its persistent compilation cache,
becomes a ``repro.compile`` recorder entry and a ``compiles`` count; every
load from that cache also counts a ``compile_cache_hits``.

Span and counter names the program uses:

  * ``repro.fit``, ``repro.fit_batch`` — a whole entry call;
  * ``repro.handoff`` (children ``repro.handoff.cast``, the host cast, and
    ``repro.handoff.put``, the host's part of the transfer) and the counter
    ``handoff_bytes`` — host data becoming a device array;
  * ``repro.dispatch`` — backend choice, executable lookup (tracing and
    compiling on a miss) and launch;
  * ``repro.ring.prepare`` — the ring's eager normalize and covariance;
  * ``repro.readback`` — the host waiting on results it converts;
  * ``repro.compile`` and the counters ``compiles``, ``compile_cache_hits``;
  * the counter ``score_pair_slots`` — the row pairs of the stage buffers a
    dense ``fit``/``fit_batch`` sweeps, once per data set
    (``utils.schedule.Schedule.pair_slots``); threshold fits add nothing;
  * the counters ``kernel_bypass`` and ``auto_downgrade``
    (``core.paralingam.dispatch_stats``).
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time

import jax

#: Entries the flight recorder keeps; the oldest drop off first.
CAPACITY = 16384

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_mu = threading.Lock()
_recorder: collections.deque = collections.deque(maxlen=CAPACITY)
_counters: dict[str, int] = {}


def record(name: str, start_ns: int, dur_ns: int) -> None:
    """Append one entry to the flight recorder."""
    with _mu:
        _recorder.append((name, start_ns, dur_ns))


@contextlib.contextmanager
def span(name: str):
    """Mark the body as the span ``name``: a profiler annotation and a
    flight-recorder entry, written when the body ends (raising or not)."""
    with jax.profiler.TraceAnnotation(name):
        start = time.time_ns()
        try:
            yield
        finally:
            record(name, start, time.time_ns() - start)


def spans(since_ns: int | None = None) -> list[tuple[str, int, int]]:
    """A copy of the recorder's entries in the order they ended (a child
    before its parent); only those starting at or after ``since_ns`` when
    given."""
    with _mu:
        out = list(_recorder)
    if since_ns is not None:
        out = [s for s in out if s[1] >= since_ns]
    return out


def count(name: str, delta: int = 1) -> None:
    """Add ``delta`` to the counter ``name``."""
    with _mu:
        _counters[name] = _counters.get(name, 0) + delta


def counters(*names: str) -> dict[str, int]:
    """A snapshot of the counters: every one counted so far, or just
    ``names`` (0 where never counted)."""
    with _mu:
        if not names:
            return dict(_counters)
        return {k: _counters.get(k, 0) for k in names}


def reset(*names: str) -> None:
    """Zero the counters ``names``; with none given, drop every counter and
    empty the flight recorder."""
    with _mu:
        if not names:
            _counters.clear()
            _recorder.clear()
        for k in names:
            _counters[k] = 0


def _on_time_span(event: str, start_s: float, end_s: float, **_) -> None:
    if event == COMPILE_EVENT:
        start = round(start_s * 1e9)
        record("repro.compile", start, round(end_s * 1e9) - start)
        count("compiles")


def _on_event(event: str, **_) -> None:
    if event == CACHE_HIT_EVENT:
        count("compile_cache_hits")


jax.monitoring.register_event_time_span_listener(_on_time_span)
jax.monitoring.register_event_listener(_on_event)
