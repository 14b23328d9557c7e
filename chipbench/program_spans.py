"""The program's own host spans on a trace's clock, for the metrics of the
host path.

The program keeps a flight recorder of host spans, ``(name, start_ns,
dur_ns)`` on the realtime clock (``repro.utils.tracing.spans()``); the
profiler stamps its events with the same clock. ``trace.load``'s form counts
from the profile session's start instead, an origin it does not keep. Each
benchmark call span (``chipbench.call``) holds exactly one entry call of the
program (``repro.fit`` or ``repro.fit_batch``), begun and ended within
microseconds of it, so the window's calls give that origin: every pair
bounds it, from below by the ends and from above by the starts, and it is
taken as the middle of the bounds all pairs leave.

Where the program keeps no recorder (a commit before it existed), or the
trace has no device plane, every reading here is None.
"""

from __future__ import annotations

from chipbench import trace

CALL = "chipbench.call"
ENTRY = ("repro.fit", "repro.fit_batch")
#: Spans of host work between device programs: the hand-off of the data,
#: dispatch (tracing, compiling, launching), the ring's eager set-up, the
#: wait on results, and compiles.
HOST_STEPS = ("repro.handoff", "repro.dispatch", "repro.ring.prepare",
              "repro.readback", "repro.compile")


def recorded() -> list | None:
    """The program's recorder entries, or None where it keeps none."""
    try:
        from repro.utils import tracing
    except ImportError:
        return None
    return tracing.spans()


def origin_ns(tr: dict, rec: list) -> int | None:
    """The realtime nanosecond at which ``tr``'s clock reads 0, from the
    window's call spans and the program's entry spans, the last of which
    belong to the window; None where they cannot be paired."""
    calls = [s for s in tr["host"] if s[0] == CALL]
    entries = sorted((s for s in rec if s[0] in ENTRY), key=lambda s: s[1])
    if not calls or len(entries) < len(calls):
        return None
    pairs = list(zip(calls, entries[-len(calls):]))
    lo = max(e[1] + e[2] - (c[1] + c[2]) for c, e in pairs)
    hi = min(e[1] - c[1] for c, e in pairs)
    return (lo + hi) // 2


def on_trace(tr: dict) -> list | None:
    """The recorder's entries as ``[name, start_ns, dur_ns]`` on ``tr``'s
    clock; None where no device ran or the program keeps no recorder."""
    if not tr["devices"]:
        return None
    rec = recorded()
    if rec is None:
        return None
    t0 = origin_ns(tr, rec)
    if t0 is None:
        return None
    return [[name, start - t0, dur] for name, start, dur in rec]


def span_intervals(spans, names, window) -> list[tuple[int, int]]:
    """Merged intervals of the spans named ``names``, clipped to
    ``window``."""
    return trace.union((max(s[1], window[0]), min(s[1] + s[2], window[1]))
                       for s in spans if s[0] in names)


def span_s(tr: dict, names, window=None) -> float | None:
    """Seconds under a span named in ``names`` inside ``window`` (the
    trace's window by default)."""
    spans = on_trace(tr)
    if spans is None:
        return None
    w = tr["window"] if window is None else window
    return trace.length(span_intervals(spans, names, w)) * 1e-9


def idle_under_s(tr: dict, names) -> float | None:
    """Seconds inside the window in which a device ran nothing and a span
    named in ``names`` was open, averaged over the devices."""
    spans = on_trace(tr)
    if spans is None:
        return None
    under = span_intervals(spans, names, tr["window"])

    def fn(ops, w):
        busy = trace.intervals(ops, w)
        return (trace.length(under)
                - trace.length(trace.intersect(under, busy))) * 1e-9
    return trace.per_device(tr, fn)


def count_in_window(tr: dict, name: str) -> int | None:
    """Spans named ``name`` that overlap the trace's window."""
    spans = on_trace(tr)
    if spans is None:
        return None
    w = tr["window"]
    return sum(s[0] == name and s[1] < w[1] and s[1] + s[2] > w[0]
               for s in spans)
