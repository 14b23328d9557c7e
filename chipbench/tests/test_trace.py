"""The reduction from a trace to the per-layer numbers, on a constructed
trace whose answers are known, and on a trace recorded here."""

import jax
import jax.numpy as jnp
import pytest

from chipbench import trace
from chipbench.run import load_reader

MS = 1_000_000  # ns


def constructed():
    """Window 0-100 ms. Device 0: kernel 10-40, fusion 35-50 (5 ms under
    the kernel), all-reduce 60-70; idle 0-10, 50-60, 70-100. Device 1:
    kernel 0-20, collective-permute-done 20-30, idle 30-100. Host spans:
    a call over 0-80, a readback over 80-100."""
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


def test_busy_and_idle():
    tr = constructed()
    assert trace.window_s(tr) == pytest.approx(0.1)
    # device 0 busy 10-50 and 60-70 = 50 ms; device 1 busy 0-30 = 30 ms
    assert trace.busy_s(tr) == pytest.approx(0.040)
    assert trace.idle_share(tr) == pytest.approx(0.6)


def test_kinds():
    tr = constructed()
    assert trace.kind_s(tr, "kernel") == pytest.approx((0.030 + 0.020) / 2)
    assert trace.kind_s(tr, "collective") == pytest.approx(0.010)
    # device 0: fusion 35-50 less its 5 ms under the kernel; device 1: none
    assert trace.other_s(tr) == pytest.approx((0.010 + 0.0) / 2)


def test_kind_absent_reads_nothing():
    tr = constructed()
    for ops in tr["devices"].values():
        ops[:] = [o for o in ops if o[1] != "collective"]
    assert trace.kind_s(tr, "collective") is None
    tr["devices"] = {}
    assert trace.busy_s(tr) is None and trace.idle_share(tr) is None


def test_window_clips():
    tr = constructed()
    tr["window"] = [20 * MS, 40 * MS]
    assert trace.busy_s(tr) == pytest.approx((0.020 + 0.010) / 2)


def test_idle_gaps_named_by_host_span():
    gaps = trace.idle_gaps(constructed())
    assert gaps[0] == ["chipbench.readback", pytest.approx(0.030)]
    assert sorted(g[1] for g in gaps[1:]) == [pytest.approx(0.010)] * 2
    assert {g[0] for g in gaps} == {"chipbench.call", "chipbench.readback"}


def test_top_ops():
    top = trace.top_ops(constructed())
    # device 0's fusion starts inside the kernel, so it counts as nested:
    # the kernel keeps (25 + 20) / 2 ms of self time
    assert top[0] == ["custom-call", pytest.approx(0.0225)]
    assert len(top) == 4


def test_self_times_nested():
    """A loop event holding two kernel calls keeps only its own time."""
    ops = [["while.3", "other", 0, 100], ["custom-call.1", "kernel", 10, 30],
           ["fusion.2", "other", 50, 20], ["copy.9", "other", 120, 5]]
    assert trace.self_times(ops, (0, 1000)) == [
        ("while.3", 50), ("custom-call.1", 30), ("fusion.2", 20), ("copy.9", 5)]


def test_names():
    text = ('%fused_score_vector.66 = f32[128,1,8]{2,1,0} custom-call(s32[8128]'
            ' %a), custom_call_target="tpu_custom_call"')
    assert trace.short_name(text) == "fused_score_vector.66"
    assert trace.op_group("fused_score_vector.66") == "fused_score_vector"
    assert trace.op_group("copy-start") == "copy-start"
    assert trace.classify(text, {}) == "kernel"


@pytest.mark.parametrize("name,stats,kind", [
    ("custom-call.12", {"hlo_category": "custom-call",
                        "long_name": "custom-call(...) custom_call_target=\"tpu_custom_call\""},
     "kernel"),
    ("_fused_tri_kernel", {"hlo_category": "mosaic"}, "kernel"),
    ("all-reduce.3", {}, "collective"),
    ("%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %x), to_apply=%add", {},
     "collective"),
    ("%collective-permute-start.1 = (f32[8,128]) collective-permute-start(%y)",
     {}, "collective"),
    ("collective-permute-start.1", {}, "collective"),
    ("all-gather-done", {}, "collective"),
    ("fusion.44", {"hlo_category": "loop fusion"}, "other"),
    ("while.2", {}, "other"),
])
def test_classify(name, stats, kind):
    assert trace.classify(name, stats) == kind


def test_unions():
    assert trace.union([(5, 9), (0, 3), (2, 4), (9, 9)]) == [(0, 4), (5, 9)]
    assert trace.intersect([(0, 4), (5, 9)], [(3, 6)]) == [(3, 4), (5, 6)]


def test_metric_files_read_the_constructed_trace():
    tr = constructed()
    ctx = {"datasets": 2, "least_s": 0.005, "chips": 1}
    from chipbench.tests.conftest import ROOT

    def read(name):
        return load_reader(ROOT, name)(tr, ctx)

    assert read("device_idle_share.fit") == pytest.approx(60.0)
    assert read("score_kernel_s.batch") == pytest.approx(0.0125)
    assert read("score_kernel_roofline.fit") == pytest.approx(40.0)
    assert read("other_device_s.fit") == pytest.approx(0.0025)
    assert read("collective_s.fit") == pytest.approx(0.005)


def test_recorded_trace(tmp_path):
    """A trace recorded here holds the benchmark's host spans; a CPU trace
    has no device plane, so every device reading is None, not 0."""
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("chipbench.call"):
        f(x).block_until_ready()
    with jax.profiler.TraceAnnotation("chipbench.readback"):
        pass
    jax.profiler.stop_trace()
    tr = trace.load(str(tmp_path))
    assert [s[0] for s in tr["host"]] == ["chipbench.call", "chipbench.readback"]
    assert tr["window"][1] > tr["window"][0]
    assert trace.busy_s(tr) is None
    assert load_reader_none(tr)


def load_reader_none(tr):
    from chipbench.tests.conftest import ROOT

    ctx = {"datasets": 1, "least_s": 1.0, "chips": 1}
    return all(load_reader(ROOT, m)(tr, ctx) is None for m in
               ("device_idle_share.fit", "score_kernel_s.fit",
                "score_kernel_roofline.fit", "other_device_s.fit",
                "collective_s.fit"))
