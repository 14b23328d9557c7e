"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reduces the profiler's ``.xplane.pb`` to a small form:

    {"window": [start_ns, end_ns],
     "devices": {"0": [[name, kind, start_ns, dur_ns], ...], ...},
     "host": [[name, start_ns, dur_ns], ...]}

with every operation the device ran (its ``XLA Ops`` line) classed as
``kernel`` (a Pallas/Mosaic custom call), ``collective`` (an exchange between
chips) or ``other``, and the benchmark's own host spans (names starting
``chipbench.``). The functions below read that form; the per-layer metric
files under ``metrics/`` call them, so every PR computes the same number the
same way. Times are in nanoseconds on the trace's one clock.
"""

from __future__ import annotations

import glob
import os

SPAN_PREFIX = "chipbench."
COLLECTIVES = ("all-reduce", "all-gather", "collective-permute", "all-to-all",
               "reduce-scatter", "collective-broadcast", "send", "recv")


def short_name(text: str) -> str:
    """The HLO instruction name of a trace event, which on a TPU is the
    instruction's whole text: ``"%fusion.12 = f32[...] fusion(...)"`` gives
    ``"fusion.12"``."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def op_group(name: str) -> str:
    """An instruction name without its numeric suffix: ``"fusion.12"`` and
    ``"fusion.7"`` are both ``"fusion"``."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def classify(text: str, stats: dict) -> str:
    """``kernel``, ``collective`` or ``other`` for one device operation,
    from its event text and statistics."""
    full = " ".join([text, str(stats.get("hlo_category", "")),
                     str(stats.get("long_name", ""))]).lower()
    if "tpu_custom_call" in full or "mosaic" in full:
        return "kernel"
    if op_group(short_name(text)).lower().startswith(COLLECTIVES):
        return "collective"
    return "other"


def load(logdir: str, window: tuple[int, int] | None = None) -> dict:
    """Reduce the one ``.xplane.pb`` under ``logdir``. ``window`` defaults
    to the span of the benchmark's host spans."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {logdir}, found {paths}")
    data = ProfileData.from_file(paths[0])
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    ops.append([short_name(e.name),
                                classify(e.name, dict(e.stats)),
                                int(e.start_ns), int(e.duration_ns)])
            if ops:
                devices[plane.name.rsplit(":", 1)[-1]] = sorted(
                    ops, key=lambda o: o[2])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns)])
    host.sort(key=lambda s: s[1])
    if window is None:
        if not host:
            raise RuntimeError("the trace holds none of the benchmark's spans")
        window = (host[0][1], max(s[1] + s[2] for s in host))
    return {"window": list(window), "devices": devices, "host": host}


def _clip(start: int, dur: int, window) -> tuple[int, int]:
    return max(start, window[0]), min(start + dur, window[1])


def union(intervals) -> list[tuple[int, int]]:
    """Merged ``(start, end)`` intervals, sorted."""
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals) -> int:
    return sum(b - a for a, b in intervals)


def intersect(xs, ys) -> list[tuple[int, int]]:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def intervals(ops, window, kinds=None) -> list[tuple[int, int]]:
    """Merged intervals of a device's operations of ``kinds`` (all when
    None), clipped to ``window``."""
    return union(_clip(o[2], o[3], window) for o in ops
                 if kinds is None or o[1] in kinds)


def window_s(tr: dict) -> float:
    return (tr["window"][1] - tr["window"][0]) * 1e-9


def per_device(tr: dict, fn) -> float | None:
    """``fn(ops, window)`` in seconds, averaged over the devices that ran
    anything; None where none did."""
    vals = [fn(ops, tr["window"]) for ops in tr["devices"].values()]
    return sum(vals) / len(vals) if vals else None


def busy_s(tr: dict) -> float | None:
    """Seconds in which some operation ran, per device."""
    return per_device(tr, lambda ops, w: length(intervals(ops, w)) * 1e-9)


def kind_s(tr: dict, kind: str) -> float | None:
    """Seconds in which an operation of ``kind`` ran, per device; None where
    no device ran one."""
    if not any(o[1] == kind for ops in tr["devices"].values() for o in ops):
        return None
    return per_device(tr, lambda ops, w:
                      length(intervals(ops, w, (kind,))) * 1e-9)


def other_s(tr: dict) -> float | None:
    """Seconds in which an ``other`` operation ran and no kernel or
    collective did, per device."""
    def fn(ops, w):
        other = intervals(ops, w, ("other",))
        rest = intervals(ops, w, ("kernel", "collective"))
        return (length(other) - length(intersect(other, rest))) * 1e-9
    return per_device(tr, fn)


def idle_share(tr: dict) -> float | None:
    """Share of the window in which a device ran nothing, averaged over
    devices."""
    busy = busy_s(tr)
    return None if busy is None else 1.0 - busy / window_s(tr)


def self_times(ops, window) -> list[tuple[str, int]]:
    """``(name, self ns)`` of each operation inside ``window``: its time
    less the time of the operations nested in it (a loop's body runs inside
    the loop's own event)."""
    out, stack = [], []  # stack of [end, index into out]
    for o in sorted(ops, key=lambda o: (o[2], -o[3])):
        a, b = _clip(o[2], o[3], window)
        if b <= a:
            continue
        while stack and stack[-1][0] <= a:
            stack.pop()
        if stack:
            parent = out[stack[-1][1]]
            parent[1] -= min(b, stack[-1][0]) - a
        out.append([o[0], b - a])
        stack.append([b, len(out) - 1])
    return [(name, ns) for name, ns in out]


def top_ops(tr: dict, k: int = 10) -> list[list]:
    """The ``k`` groups of device operations (instruction names without
    their numeric suffix) that took most self time, seconds averaged over
    the devices."""
    tot: dict[str, float] = {}
    for ops in tr["devices"].values():
        for name, ns in self_times(ops, tr["window"]):
            g = op_group(name)
            tot[g] = tot.get(g, 0.0) + ns * 1e-9
    nd = max(len(tr["devices"]), 1)
    return [[name, s / nd] for name, s in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(tr: dict, k: int = 10) -> list[list]:
    """The ``k`` longest gaps in which the first device ran nothing, each
    named by the innermost benchmark span around its middle (``idle`` where
    none is)."""
    if not tr["devices"]:
        return []
    dev = tr["devices"][sorted(tr["devices"], key=int)[0]]
    w = tr["window"]
    busy = intervals(dev, w)
    edges = [w[0]] + [x for iv in busy for x in iv] + [w[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:k]:
        mid = (a + b) // 2
        around = [s for s in tr["host"] if s[1] <= mid < s[1] + s[2]]
        name = min(around, key=lambda s: s[2])[0] if around else "idle"
        out.append([name, (b - a) * 1e-9])
    return out
