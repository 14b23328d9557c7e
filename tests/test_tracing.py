"""The program's spans and counters (``repro.utils.tracing``): the flight
recorder, the counter store and the views over it, the compile listener,
the spans the entry points record, the clock they share with a profiler
trace, and the named scopes that leave the compiled program unchanged."""

import contextlib
import glob
import os
import re
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import paralingam
from repro.core.paralingam import ParaLiNGAMConfig, fit, fit_batch
from repro.utils import tracing

CFG = ParaLiNGAMConfig(min_bucket=8)


def _x(p, n, seed=0):
    return np.random.default_rng(seed).laplace(size=(p, n))


def _names(since_ns):
    return [s[0] for s in tracing.spans(since_ns) if s[0] != "repro.compile"]


# -- recorder ----------------------------------------------------------------


def test_recorder_is_bounded_oldest_first_out():
    tracing.reset()
    for i in range(tracing.CAPACITY + 10):
        tracing.record("r", i, 1)
    got = tracing.spans()
    assert len(got) == tracing.CAPACITY
    assert got[0] == ("r", 10, 1) and got[-1][1] == tracing.CAPACITY + 9
    assert [s[1] for s in tracing.spans(since_ns=tracing.CAPACITY + 5)] == [
        tracing.CAPACITY + k for k in range(5, 10)]
    tracing.reset()
    assert tracing.spans() == []


def test_nested_spans_end_child_first_inside_parent():
    t0 = time.time_ns()
    with tracing.span("outer"):
        with tracing.span("inner"):
            time.sleep(0.001)
    inner, outer = tracing.spans(t0)[-2:]
    assert (inner[0], outer[0]) == ("inner", "outer")
    assert outer[1] <= inner[1]
    assert inner[1] + inner[2] <= outer[1] + outer[2]
    assert inner[2] >= 1_000_000


def test_span_recorded_when_body_raises():
    t0 = time.time_ns()
    with pytest.raises(KeyError):
        with tracing.span("boom"):
            raise KeyError("x")
    assert _names(t0) == ["boom"]


def test_counters_exact_under_threads():
    tracing.reset("t.count")
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(
            target=lambda: [tracing.count("t.count") for _ in range(500)])
            for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
    finally:
        sys.setswitchinterval(old)
    assert all(not t.is_alive() for t in threads)
    assert tracing.counters("t.count") == {"t.count": 16 * 500}
    assert tracing.counters("t.never") == {"t.never": 0}
    tracing.count("t.count", 5)
    assert tracing.counters()["t.count"] == 16 * 500 + 5
    tracing.reset("t.count")
    assert tracing.counters("t.count") == {"t.count": 0}


def test_dispatch_stats_are_views_of_the_counters(fake_clock):
    from repro.serve.async_engine import AsyncLingamEngine
    from repro.serve.lingam_engine import LingamServeConfig

    paralingam.reset_dispatch_stats()
    zero = {"kernel_bypass": 0, "auto_downgrade": 0}
    assert dict(paralingam.dispatch_stats) == zero
    assert paralingam.dispatch_stats_snapshot() == zero
    paralingam._bump_stat("auto_downgrade", 3)
    assert paralingam.dispatch_stats["auto_downgrade"] == 3
    assert tracing.counters("auto_downgrade") == {"auto_downgrade": 3}
    assert paralingam.dispatch_stats_snapshot() == {"kernel_bypass": 0,
                                                    "auto_downgrade": 3}
    with pytest.raises(KeyError):
        paralingam.dispatch_stats["compiles"]
    eng = AsyncLingamEngine(CFG, LingamServeConfig(min_p_bucket=8,
                                                   min_n_bucket=64),
                            clock=fake_clock, start=False)
    st = eng.stats()
    assert st["auto_downgrade"] == 3 and st["kernel_bypass"] == 0
    assert {"compiles", "compile_cache_hits", "handoff_bytes"} <= set(st)
    eng.close()
    paralingam.reset_dispatch_stats()
    assert dict(paralingam.dispatch_stats) == zero


# -- compiles ----------------------------------------------------------------


def test_compiles_count_a_new_shape_once():
    f = jax.jit(lambda v: v * 2.0 + 1.0)
    before = tracing.counters("compiles", "compile_cache_hits")
    t0 = time.time_ns()
    f(np.ones((3, 17), np.float32)).block_until_ready()
    mid = tracing.counters("compiles")["compiles"]
    f(np.ones((3, 17), np.float32)).block_until_ready()
    after = tracing.counters("compiles", "compile_cache_hits")
    assert mid == before["compiles"] + 1
    assert after["compiles"] == mid
    assert after["compile_cache_hits"] == before["compile_cache_hits"]
    compiles = [s for s in tracing.spans(t0) if s[0] == "repro.compile"]
    assert len(compiles) == 1 and compiles[0][2] > 0


# -- the entry points' spans and hand-off bytes --------------------------------


def test_fit_spans_in_order_and_handoff_bytes():
    x = _x(8, 300)
    fit(x, CFG)  # warm
    tracing.reset("handoff_bytes")
    t0 = time.time_ns()
    fit(x, CFG)
    assert _names(t0) == [
        "repro.handoff.cast", "repro.handoff.put", "repro.handoff",
        "repro.dispatch", "repro.readback", "repro.readback", "repro.fit"]
    assert tracing.counters("handoff_bytes")["handoff_bytes"] == 8 * 300 * 4


def test_fit_batch_spans_in_order_and_handoff_bytes():
    xs = np.stack([_x(8, 300, s) for s in range(3)])
    fit_batch(xs, CFG)
    tracing.reset("handoff_bytes")
    t0 = time.time_ns()
    fit_batch(xs, CFG)
    assert _names(t0) == ["repro.handoff.cast", "repro.handoff.put",
                          "repro.handoff", "repro.dispatch",
                          "repro.fit_batch"]
    assert tracing.counters("handoff_bytes")["handoff_bytes"] == 3 * 8 * 300 * 4


def test_device_input_records_no_handoff():
    xd = jax.numpy.asarray(_x(8, 300), jax.numpy.float32)
    fit(xd, CFG)
    tracing.reset("handoff_bytes")
    t0 = time.time_ns()
    fit(xd, CFG)
    assert "repro.handoff" not in _names(t0)
    assert tracing.counters("handoff_bytes")["handoff_bytes"] == 0


def test_compiled_fit_batch_records_its_spans():
    xs = np.stack([_x(8, 128, s) for s in range(2)])
    exe = paralingam.aot_fit_batch(2, 8, 128, CFG, padded=False)
    tracing.reset("handoff_bytes")
    t0 = time.time_ns()
    exe(xs)
    assert _names(t0) == ["repro.handoff.cast", "repro.handoff.put",
                          "repro.handoff", "repro.dispatch",
                          "repro.fit_batch"]
    assert tracing.counters("handoff_bytes")["handoff_bytes"] == 2 * 8 * 128 * 4


def test_ring_fit_hands_off_twice():
    """The ring path converts and transfers ``x`` once for the order and
    once for phase 2, with the eager set-up between (any device count: one
    device is a ring of one)."""
    x = _x(16, 256)
    cfg = ParaLiNGAMConfig(order_backend="ring", min_bucket=8)
    fit(x, cfg)
    tracing.reset("handoff_bytes")
    t0 = time.time_ns()
    fit(x, cfg)
    hand = ["repro.handoff.cast", "repro.handoff.put", "repro.handoff"]
    assert _names(t0) == hand + [
        "repro.ring.prepare", "repro.dispatch", "repro.readback"] + hand + [
        "repro.dispatch", "repro.readback", "repro.fit"]
    assert tracing.counters("handoff_bytes")["handoff_bytes"] == 2 * 16 * 256 * 4


# -- one clock with the profiler ---------------------------------------------


def test_recorder_shares_the_profiler_clock(tmp_path):
    """A recorder entry and the same span's event in the profiler's trace
    start within 1 ms of each other. The trace counts from the session's
    start, ``profile_start_time`` of its ``Task Environment`` plane, on the
    realtime clock."""
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        with tracing.span("repro.x"):
            time.sleep(0.002)
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    t0 = next(dict(p.stats)["profile_start_time"] for p in data.planes
              if p.name == "Task Environment")
    events = sorted(t0 + int(e.start_ns) for p in data.planes
                    for line in p.lines for e in line.events
                    if e.name == "repro.x")
    recorded = [s[1] for s in tracing.spans() if s[0] == "repro.x"][-3:]
    assert len(events) == 3
    for a, b in zip(events, recorded):
        assert abs(a - b) < 1_000_000


# -- named scopes --------------------------------------------------------------

SCOPES = ("normalize", "covariance", "order_", "find_root", "update",
          "compact", "adjacency")
DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _strip_debug(text: str) -> str:
    """Compiled HLO text without its debug information: each
    ``metadata={...}`` and the source tables it points into."""
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    out, skip = [], False
    for line in text.splitlines():
        if line in DEBUG_TABLES:
            skip = True
        elif skip and not line.strip():
            skip = False
        elif not skip:
            out.append(line)
    return "\n".join(out)


def _compiled_fit(x) -> str:
    """The one-dispatch fit's compiled text, traced afresh."""
    cfg = ParaLiNGAMConfig(order_backend="scan", min_bucket=8)
    _, _, args = paralingam._pipeline_call(x, cfg, adjacency=True,
                                           batched=False)
    fn = paralingam._pipeline_fn.__wrapped__(
        False, None, adjacency=True, threshold=False, block_j=cfg.block_j,
        backend="xla", min_bucket=cfg.min_bucket, chunk=cfg.chunk,
        max_rounds=cfg.max_rounds, prune_below=0.0)
    return fn.lower(*args).compile().as_text()


def test_named_scopes_change_only_metadata(monkeypatch):
    x = _x(40, 300)
    scoped = _compiled_fit(x)
    names = re.findall(r'op_name="([^"]*)"', scoped)
    for scope in SCOPES:
        assert any(scope in n for n in names), scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _compiled_fit(x)
    assert not any(f"{s}/" in plain for s in ("find_root", "adjacency"))
    assert scoped != plain
    assert _strip_debug(scoped) == _strip_debug(plain)


def test_ring_named_scopes():
    from repro.dist import ring_order

    mesh, _, _, axis = ring_order._canonical_mesh(None, 256)
    run = ring_order._make_ring_order_fn.__wrapped__(mesh, axis, 16, 256, 8)
    x = jax.numpy.asarray(_x(16, 256), jax.numpy.float32)
    c = jax.numpy.eye(16, dtype=jax.numpy.float32)
    with jax.set_mesh(mesh):
        text = run.lower(x, c).as_text(debug_info=True)
    scopes = {part for loc in re.findall(r'loc\("([^"]*)"', text)
              for part in loc.split("/")}
    assert {"order_16", "find_root", "update", "compact"} <= scopes


def test_strip_debug_keeps_the_program():
    text = ('HloModule m\n\nFileNames\n1 "a.py"\n\nENTRY %e {\n'
            '  %a = f32[] add(%x, %y), metadata={op_name="s/add" '
            'stack_frame_id=3}\n}\n')
    assert _strip_debug(text) == "HloModule m\n\nENTRY %e {\n  %a = f32[] add(%x, %y)\n}"

