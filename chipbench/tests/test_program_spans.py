"""The per-layer metrics that read the program's own spans, on a constructed
trace with constructed recorder entries whose answers are known, and the
recorder's origin found on a trace recorded here."""

import glob
import os
import sys

import jax
import numpy as np
import pytest

from chipbench import program_spans, trace
from chipbench.run import load_reader
from chipbench.tests.conftest import ROOT

MS = 1_000_000  # ns
T0 = 1_790_000_000_000_000_000  # realtime ns at which the trace reads 0
READERS = ("host_handoff_s.fit", "host_handoff_s.batch", "host_idle_s.fit",
           "host_idle_s.batch", "window_compiles.fit",
           "window_compiles.batch", "setup_compile_s")


def constructed():
    """Window 0-100 ms, one benchmark call over 0-80. Device 0 busy 10-50
    and 60-70; device 1 busy 0-30."""
    return {
        "window": [0, 100 * MS],
        "devices": {
            "0": [["custom-call.3", "kernel", 10 * MS, 30 * MS],
                  ["fusion.7", "other", 35 * MS, 15 * MS],
                  ["all-reduce.1", "collective", 60 * MS, 10 * MS]],
            "1": [["custom-call.3", "kernel", 0, 20 * MS],
                  ["collective-permute-done", "collective", 20 * MS, 10 * MS]],
        },
        "host": [["chipbench.call", 0, 80 * MS],
                 ["chipbench.readback", 80 * MS, 20 * MS]],
    }


def recorder():
    """Entries on the realtime clock. Set-up: a warm fit and three compiles
    (two overlapping: 2.6 s of compiling). Window: the fit inside the call,
    its hand-off (0-8 ms), dispatch (8-12), a compile (52-58) under the
    readback (55-79), and a hand-off 95-110 that the window's end cuts."""
    ms = [("repro.compile", -5000, 2000), ("repro.compile", -2500, 500),
          ("repro.compile", -2200, 300), ("repro.fit", -1500, 1400),
          ("repro.handoff.cast", 0.01, 4.99), ("repro.handoff.put", 5, 3),
          ("repro.handoff", 0.01, 7.99), ("repro.dispatch", 8, 4),
          ("repro.compile", 52, 6), ("repro.readback", 55, 24),
          ("repro.fit", 0.01, 79.98), ("repro.handoff", 95, 15)]
    return [(name, T0 + round(a * MS), round(d * MS)) for name, a, d in ms]


@pytest.fixture
def program(monkeypatch):
    """The program's recorder holding ``recorder()``'s entries."""
    from repro.utils import tracing

    entries = recorder()
    monkeypatch.setattr(tracing, "spans", lambda since_ns=None: list(entries))
    return entries


def read(name, tr, datasets=2):
    ctx = {"datasets": datasets, "least_s": 1.0, "chips": 1}
    return load_reader(ROOT, name)(tr, ctx)


def test_origin_from_the_call_spans(program):
    assert program_spans.origin_ns(constructed(), program) == T0
    got = program_spans.on_trace(constructed())
    assert got[-1] == ["repro.handoff", 95 * MS, 15 * MS]


def test_readers_on_the_constructed_trace(program):
    tr = constructed()
    # hand-offs 0.01-8 ms and 95-100 (cut by the window), per data set
    assert read("host_handoff_s.fit", tr) == pytest.approx(0.012990 / 2)
    assert read("host_handoff_s.batch", tr) == pytest.approx(0.012990 / 2)
    # host steps 0.01-12, 52-79, 95-100. Device 0 idle 0-10, 50-60, 70-100:
    # 9.99 + 8 + 9 + 5 ms under steps. Device 1 idle 30-100: 27 + 5 ms.
    assert read("host_idle_s.fit", tr) == pytest.approx(0.031995 / 2)
    assert read("host_idle_s.batch", tr) == pytest.approx(0.031995 / 2)
    assert read("window_compiles.fit", tr) == 1
    assert read("window_compiles.batch", tr) == 1
    assert read("setup_compile_s", tr) == pytest.approx(2.6)


def test_host_idle_over_four_devices(program):
    tr = constructed()
    tr["devices"] = {str(k): [["fusion.1", "other", a * MS, (b - a) * MS]]
                     for k, (a, b) in enumerate([(0, 100), (0, 50),
                                                 (50, 100), (20, 30)])}
    # steps 0.01-12, 52-79, 95-100 (43.99 ms): idle under them 0, 32,
    # 11.99 and 43.99 ms
    assert read("host_idle_s.fit", tr, datasets=1) == pytest.approx(
        (0 + 0.032 + 0.01199 + 0.04399) / 4)
    assert read("host_handoff_s.fit", tr, datasets=1) == pytest.approx(0.01299)


def test_no_device_plane_reads_nothing(program):
    tr = constructed()
    tr["devices"] = {}
    assert all(read(m, tr) is None for m in READERS)


def test_a_program_without_a_recorder_reads_nothing(monkeypatch):
    import repro.utils

    monkeypatch.delattr(repro.utils, "tracing", raising=False)
    monkeypatch.setitem(sys.modules, "repro.utils.tracing", None)
    assert program_spans.recorded() is None
    assert all(read(m, constructed()) is None for m in READERS)


def test_unpaired_calls_read_nothing(monkeypatch):
    from repro.utils import tracing

    monkeypatch.setattr(tracing, "spans", lambda since_ns=None: [
        s for s in recorder() if s[0] != "repro.fit"])
    assert all(read(m, constructed()) is None for m in READERS)


def test_origin_on_a_recorded_trace(tmp_path):
    """On a trace recorded here, the origin found from the call spans is
    the session's start the trace itself records, within 1 ms."""
    import repro
    from repro.utils import tracing

    x = np.random.default_rng(0).laplace(size=(8, 300))
    cfg = repro.ParaLiNGAMConfig(min_bucket=8)
    repro.fit(x, cfg)
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(2):
        with jax.profiler.TraceAnnotation("chipbench.call"):
            repro.fit(x, cfg)
    jax.profiler.stop_trace()
    tr = trace.load(str(tmp_path))
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    start = next(dict(p.stats)["profile_start_time"] for p in data.planes
                 if p.name == "Task Environment")
    assert abs(program_spans.origin_ns(tr, tracing.spans()) - start) < MS
